"""
JAX-discipline checks — the invariants no Python type checker sees.

The fleet's perf and correctness story hinges on discipline the general
checks (checks.py) cannot express: PR 2's two headline defects — a
jitted closure re-traced on every ``fit`` call, and a ``split(key, n)``
layout that silently changed every sweep variant's RNG stream with the
sweep width — are *JAX* bugs, not Python bugs. Avoidable recompiles and
host round-trips are the dominant tax on small-model fleets (PAPERS.md:
"A Learned Performance Model for TPUs"; the ML-fleet-goodput line of
work), so these checks enforce mechanically what PR 2 re-discovered by
hand:

- ``retrace-risk``       jax.jit applied to a local closure/lambda whose
                         handle never escapes the enclosing scope — a
                         fresh wrapper (and a fresh trace cache) per call
                         of the enclosing function. The exact shape fixed
                         for ``_keep_better`` in PR 2.
- ``host-sync``          device->host synchronization primitives inside
                         a ``for``/``while`` body of a hot module
                         (parallel/, models/core.py): ``.item()``,
                         ``jax.device_get``, ``block_until_ready``, and
                         ``float()/int()``/``np.asarray`` applied to
                         values produced by a jitted handle. Each one
                         stalls the asynchronous dispatch pipeline
                         once per iteration.
- ``prng-reuse``         a key name passed to two or more consuming
                         calls without an intervening ``split``/
                         ``fold_in`` rebinding — correlated streams.
- ``prng-split-width``   ``split(key, <non-constant>)`` whose result is
                         then indexed per variant: threefry lays keys
                         out by the TOTAL count, so variant i's stream
                         changes with the width (the PR 2 sweep bug).
- ``traced-branch``      Python ``if``/``while`` on a value derived from
                         a jitted function's (non-static) parameters —
                         raises TracerBoolConversionError under jit.
- ``donation-safety``    a binding read again after being passed at a
                         donated argnum of a jitted call: XLA may have
                         reused the buffer (CPU declines donation, so
                         the bug only fires on accelerators).

All checks are purely syntactic (AST + source, no imports), so they run
on any file — tests and benchmarks included — and transfer verbatim to
any JAX training or inference stack.
"""

import ast
import re
import typing

from gordo_tpu.analysis.checks import _own_scope_nodes

# --------------------------------------------------------------------------
# shared: recognizing jax.jit spellings and scopes
# --------------------------------------------------------------------------

#: functions through which a device value reaches the host *on purpose*,
#: with its cost accounted (fleet.py's host_fetch is the counted sync
#: point the sync-budget telemetry and tests watch)
SANCTIONED_SYNC_FUNCTIONS = frozenset({"host_fetch"})

#: modules tagged hot: host-sync findings only fire here (engine.py maps
#: paths onto this; the check itself is path-agnostic). This used to be
#: an accreted per-PR list of subsystems (parallel, server, lifecycle,
#: ledger, programs, router, streaming, ...) that every new-subsystem PR
#: had to remember to extend — and the list only ever grew toward "all
#: of it". Now it IS all of it: every package module is hot by default,
#: and a module where an unaccounted device sync is genuinely fine says
#: so locally with an inline suppression (the sanctioned ``host_fetch``
#: path already exists for syncs that should be counted instead of
#: hidden). tests/ and benchmarks/ stay cold: their paths never contain
#: the package-directory segment.
HOT_PATH_PATTERNS = ("gordo_tpu/",)


def _jit_names(tree: ast.Module) -> typing.Set[str]:
    """Local spellings of jax.jit: 'jit' (or an alias) when imported
    from jax; the ``jax.jit`` attribute form is matched structurally."""
    names: typing.Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "jit":
                    names.add(alias.asname or alias.name)
    return names


def _is_jit_func(node: ast.AST, jit_names: typing.Set[str]) -> bool:
    """Is this expression (a Call's func / a decorator) jax.jit?"""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return isinstance(node.value, ast.Name) and node.value.id == "jax"
    return isinstance(node, ast.Name) and node.id in jit_names


def _is_jit_call(node: ast.AST, jit_names: typing.Set[str]) -> bool:
    return isinstance(node, ast.Call) and _is_jit_func(node.func, jit_names)


def _scope_functions(tree: ast.Module):
    yield from (
        node
        for node in ast.walk(tree)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
    )


def _param_names(fn: ast.AST) -> typing.Set[str]:
    args = fn.args
    names = {
        a.arg
        for a in (*args.posonlyargs, *args.args, *args.kwonlyargs)
    }
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    return names


def _bound_names(fn: ast.AST) -> typing.Set[str]:
    """Every name bound inside ``fn``'s own scope: params, stores,
    nested def/class names, comprehension targets (their scopes leak
    nothing, but being conservative here only *reduces* findings)."""
    bound = _param_names(fn)
    for node in _own_scope_nodes(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, (ast.Store, ast.Del)):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            bound.add(node.name)
        elif isinstance(node, ast.arg):
            bound.add(node.arg)
    return bound


def _callee_tail(node: ast.AST) -> typing.Optional[str]:
    """The last name segment of a call target: ``a.b.c(...)`` -> 'c',
    ``f(...)`` -> 'f', anything else -> None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


# --------------------------------------------------------------------------
# retrace-risk
# --------------------------------------------------------------------------


def _free_variables(target: ast.AST, enclosing_locals: typing.Set[str]) -> typing.Set[str]:
    """Names the closure/lambda ``target`` reads from the ENCLOSING
    function scope (not its own bindings, not module/builtin names)."""
    bound = _bound_names(target)
    free: typing.Set[str] = set()
    for node in _own_scope_nodes(target):
        if (
            isinstance(node, ast.Name)
            and isinstance(node.ctx, ast.Load)
            and node.id not in bound
            and node.id in enclosing_locals
        ):
            free.add(node.id)
    return free


def check_retrace_risk(tree: ast.Module) -> typing.List[str]:
    """
    ``jax.jit`` applied to a locally-defined function or lambda inside a
    function body, where the jitted handle never escapes the scope (it
    is only ever *called*, or is called in the same expression): every
    invocation of the enclosing function builds a FRESH wrapper with a
    fresh trace cache, so the closure re-traces (and recompiles) per
    call — the exact shape PR 2 fixed by hoisting ``_keep_better`` to a
    module-level ``@jax.jit``.

    Deliberate near-misses are NOT flagged:

    - the handle escapes (returned, stored on ``self`` or in a
      container, passed to another call) — that is the instance-cache
      idiom (``self._step_fn = jax.jit(...)``,
      ``self._epoch_fn_cache[key] = fn``);
    - the closure reads variables from the enclosing scope — it cannot
      be hoisted without a redesign, and per-call retrace may be the
      intended trade (the solo trainer's per-fit ``train_epoch``).
    """
    jit_names = _jit_names(tree)
    problems: typing.List[str] = []
    for fn in _scope_functions(tree):
        own = _own_scope_nodes(fn)
        local_defs = {
            n.name: n
            for n in own
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        enclosing_locals = _bound_names(fn)

        def jit_target(call: ast.Call):
            """The function object being jitted: first positional arg or
            ``fun=`` kwarg; unwraps ``jax.vmap(...)``-style wrappers."""
            arg = call.args[0] if call.args else next(
                (kw.value for kw in call.keywords if kw.arg == "fun"), None
            )
            while isinstance(arg, ast.Call) and arg.args:
                arg = arg.args[0]  # jax.jit(jax.vmap(one)) -> one
            return arg

        def closure_name(call: ast.Call) -> typing.Optional[str]:
            """Name of the local closure/lambda being jitted, or None
            when the target is not a hoistable local closure."""
            arg = jit_target(call)
            if isinstance(arg, ast.Lambda):
                free = _free_variables(arg, enclosing_locals)
                return "<lambda>" if not free else None
            if isinstance(arg, ast.Name) and arg.id in local_defs:
                free = _free_variables(local_defs[arg.id], enclosing_locals - {arg.id})
                return arg.id if not free else None
            return None

        # map: local name -> the jit call bound to it (simple Assign only)
        bound_jits: typing.Dict[str, ast.Call] = {}
        for node in own:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and _is_jit_call(node.value, jit_names)
            ):
                bound_jits[node.targets[0].id] = node.value

        # (1) jit-and-call in one expression: always a per-call retrace
        for node in own:
            if (
                isinstance(node, ast.Call)
                and _is_jit_call(node.func, jit_names)
            ):
                name = closure_name(node.func) or "the traced function"
                problems.append(
                    f"line {node.lineno}: jax.jit({name})(...) builds and "
                    f"discards a fresh jitted wrapper on every call of "
                    f"{fn.name!r} — hoist to module level or cache the "
                    f"handle"
                )

        # (2) handle bound to a local name used ONLY as a call target
        for name, call in bound_jits.items():
            target = closure_name(call)
            if target is None:
                continue
            escapes = False
            uses = 0
            for node in own:
                if not (
                    isinstance(node, ast.Name)
                    and node.id == name
                    and isinstance(node.ctx, ast.Load)
                ):
                    continue
                uses += 1
            # a use is benign only as the func of a Call; find those
            call_uses = sum(
                1
                for node in own
                if isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == name
            )
            if uses > call_uses:
                escapes = True  # returned / stored / passed on: cached
            if not escapes:
                problems.append(
                    f"line {call.lineno}: jax.jit({target}) is rebuilt on "
                    f"every call of {fn.name!r} and its handle {name!r} "
                    f"never escapes — each call re-traces the closure "
                    f"(the PR-2 _keep_better shape); hoist to a "
                    f"module-level @jax.jit or cache on the instance"
                )
    return problems


# --------------------------------------------------------------------------
# host-sync
# --------------------------------------------------------------------------

_NP_CONVERTERS = frozenset({"asarray", "array"})
_SYNC_BUILTINS = frozenset({"float", "int", "bool"})


def _loop_bodies(tree: ast.Module):
    """Every For/While node anywhere (module or function scope), with
    nested function/lambda bodies excluded from the loop's own nodes
    (code defined in a loop runs elsewhere)."""
    for node in ast.walk(tree):
        if not isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            continue
        own: typing.List[ast.AST] = []
        stack: typing.List[ast.AST] = [*node.body, *node.orelse]
        while stack:
            child = stack.pop()
            own.append(child)
            if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            stack.extend(ast.iter_child_nodes(child))
        yield node, own


def _jitted_handles(tree: ast.Module) -> typing.Set[str]:
    """Names bound (anywhere) to the result of a jax.jit call — calls
    through them produce device values whose host conversion is a sync."""
    jit_names = _jit_names(tree)
    handles: typing.Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _is_jit_call(node.value, jit_names):
            for target in node.targets:
                if isinstance(target, ast.Name):
                    handles.add(target.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_func(d, jit_names) for d in node.decorator_list):
                handles.add(node.name)
    return handles


def _device_tainted_names(tree: ast.Module, handles: typing.Set[str]) -> typing.Set[str]:
    """Names assigned from a call to a jitted handle (incl. tuple
    unpacking): ``params, opt_state, loss = train_epoch_jit(...)``."""
    tainted: typing.Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id in handles
        ):
            continue
        for target in node.targets:
            elts = target.elts if isinstance(target, (ast.Tuple, ast.List)) else [target]
            for elt in elts:
                if isinstance(elt, ast.Name):
                    tainted.add(elt.id)
    return tainted


def check_host_sync(tree: ast.Module) -> typing.List[str]:
    """
    Device->host synchronization inside a ``for``/``while`` body: each
    occurrence stalls the async dispatch pipeline once PER ITERATION —
    over a DCN link that is the whole epoch budget
    (docs/performance.md, "Device-resident multi-epoch training"). Only
    enforced on hot modules (``HOT_PATH_PATTERNS``; the engine applies
    the path filter). Flagged inside loop bodies:

    - ``x.item()``, ``x.block_until_ready()``,
      ``jax.block_until_ready(...)``, ``jax.device_get(...)``
    - ``float(x)`` / ``int(x)`` / ``bool(x)`` and
      ``np.asarray(x)`` / ``np.array(x)`` where ``x`` is a value
      produced by a jitted handle (directly, or a name assigned from
      one) — host conversions of host data are free and are not
      flagged.

    ``host_fetch(...)`` is the sanctioned, telemetry-counted sync point
    and is never flagged; neither are conversions of its result
    (``np.asarray(host_fetch(x))`` pays one accounted sync, not two).
    """
    jit_names = _jit_names(tree)
    handles = _jitted_handles(tree)
    tainted = _device_tainted_names(tree, handles)
    problems: typing.List[str] = []
    seen: typing.Set[int] = set()

    def from_device(arg: ast.AST) -> bool:
        if isinstance(arg, ast.Name):
            return arg.id in tainted
        if isinstance(arg, ast.Call):
            return (
                isinstance(arg.func, ast.Name) and arg.func.id in handles
            )
        return False

    for _loop, own in _loop_bodies(tree):
        for node in own:
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            func = node.func
            tail = _callee_tail(func)
            if tail in SANCTIONED_SYNC_FUNCTIONS:
                continue
            finding = None
            if isinstance(func, ast.Attribute) and func.attr == "item" and not node.args:
                finding = f"'{ast.unparse(func.value)}.item()'"
            elif isinstance(func, ast.Attribute) and func.attr == "block_until_ready":
                finding = f"'{ast.unparse(func)}(...)'"
            elif (
                # jax.block_until_ready is caught by the attr test above;
                # only device_get needs the jax-qualified form
                isinstance(func, ast.Attribute)
                and func.attr == "device_get"
                and isinstance(func.value, ast.Name)
                and func.value.id == "jax"
            ):
                finding = f"'jax.{func.attr}(...)'"
            elif (
                isinstance(func, ast.Name)
                and func.id in _SYNC_BUILTINS
                and len(node.args) == 1
                and from_device(node.args[0])
            ):
                finding = (
                    f"'{func.id}({ast.unparse(node.args[0])})' on a "
                    f"jitted-handle result"
                )
            elif (
                isinstance(func, ast.Attribute)
                and func.attr in _NP_CONVERTERS
                and isinstance(func.value, ast.Name)
                and func.value.id in ("np", "numpy")
                and node.args
                and from_device(node.args[0])
            ):
                finding = (
                    f"'{ast.unparse(func)}({ast.unparse(node.args[0])})' "
                    f"on a jitted-handle result"
                )
            if finding:
                seen.add(id(node))
                problems.append(
                    f"line {node.lineno}: {finding} synchronizes "
                    f"device->host once per loop iteration — batch the "
                    f"fetch after the loop (or route it through "
                    f"host_fetch outside the hot loop); per-iteration "
                    f"syncs stall the asynchronous dispatch pipeline"
                )
    return problems


# --------------------------------------------------------------------------
# prng-reuse
# --------------------------------------------------------------------------

KEY_NAME_RE = re.compile(r"(^|_)(key|keys|rng|rngs|prng)$")

#: call targets that derive or repackage keys rather than consuming
#: randomness: passing a key here does NOT burn its stream
_NON_CONSUMING_TAILS = frozenset(
    {
        "split",
        "fold_in",
        "PRNGKey",
        "key",  # jax.random.key (new-style key construction)
        "asarray",
        "array",
        "device_put",
        "broadcast_to",
        "copy",
        "len",
        "host_fetch",
        "device_get",
        "block_until_ready",
        "append",
        "stack",
        "concatenate",
        "reshape",
    }
)


_DERIVATION_NAMES = frozenset({"split", "fold_in", "PRNGKey"})


_RANDOM_BASES = frozenset({"random", "jrandom", "jr"})


def _derivation_marker(node: ast.AST) -> bool:
    """Is this name/attribute a PRNG derivation function? ``PRNGKey`` in
    any spelling; ``split``/``fold_in`` as bare names (from-imports) or
    hanging off a ``random``-ish base (``jax.random.split``,
    ``jrandom.fold_in``) — NOT ``str.split`` (``uri.split(':')``,
    whose base is an arbitrary expression)."""
    if isinstance(node, ast.Name):
        return node.id in _DERIVATION_NAMES
    if not isinstance(node, ast.Attribute):
        return False
    if node.attr == "PRNGKey":
        return True
    if node.attr not in ("split", "fold_in"):
        return False
    base = node.value
    tail = base.attr if isinstance(base, ast.Attribute) else (
        base.id if isinstance(base, ast.Name) else None
    )
    return tail in _RANDOM_BASES


def _call_is_true_derivation(call: ast.Call) -> bool:
    """A call whose target chain mentions a PRNG derivation anywhere
    (incl. ``jax.vmap(lambda k: fold_in(k, e))(keys)``): it DERIVES key
    streams. The anchor for key-variable discovery."""
    return any(_derivation_marker(node) for node in ast.walk(call.func))


def _call_is_derivation(call: ast.Call) -> bool:
    """Calls that do not CONSUME the key they are given: derivations,
    plus pure repackaging (asarray/device_put/...)."""
    if _call_is_true_derivation(call):
        return True
    return _callee_tail(call.func) in _NON_CONSUMING_TAILS


def _key_names_in_scope(fn: ast.AST) -> typing.Set[str]:
    """
    PRNG-key variables in this scope. A name qualifies only when it
    provably touches the PRNG machinery here:

    - it is assigned from a PRNGKey/split/fold_in derivation, or
    - it is passed directly to one, and its name says key
      (``key``/``keys``/``rng``/``*_key``...).

    Name alone is NOT enough: ``for key, value in d.items()`` is a dict
    key, not a PRNG key, and must never be flagged.
    """
    named = {n for n in _param_names(fn) if KEY_NAME_RE.search(n)}
    own = _own_scope_nodes(fn)
    for node in own:
        if (
            isinstance(node, ast.Name)
            and isinstance(node.ctx, ast.Store)
            and KEY_NAME_RE.search(node.id)
        ):
            named.add(node.id)
    names: typing.Set[str] = set()
    for node in own:
        if not isinstance(node, ast.Call):
            continue
        if not _call_is_true_derivation(node):
            continue
        # names fed INTO the derivation are keys (if plausibly named)
        for arg in [*node.args, *[kw.value for kw in node.keywords]]:
            if isinstance(arg, ast.Name) and arg.id in named:
                names.add(arg.id)
    for node in own:
        if not (
            isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Call)
            and _call_is_true_derivation(node.value)
        ):
            continue
        # names assigned FROM a derivation are keys, whatever the name
        for target in node.targets:
            elts = (
                target.elts
                if isinstance(target, (ast.Tuple, ast.List))
                else [target]
            )
            for elt in elts:
                if isinstance(elt, ast.Name):
                    names.add(elt.id)
    return names


def check_prng_key_reuse(tree: ast.Module) -> typing.List[str]:
    """
    A PRNG key passed to >= 2 consuming calls without an intervening
    ``split``/``fold_in`` rebinding: both consumers draw the SAME
    stream, so their "independent" randomness is bit-identical — the
    silent-correlation class of bug. A consumption inside a loop with no
    per-iteration rebinding counts as multi-use (every iteration draws
    the same stream). ``split``/``fold_in``/``PRNGKey`` calls and pure
    repackaging (``asarray``, ``device_put``, ``broadcast_to``, ...) do
    not consume.
    """
    problems: typing.List[str] = []

    for fn in _scope_functions(tree):
        keys = _key_names_in_scope(fn)
        if not keys:
            continue
        flagged: typing.Set[str] = set()
        consumed: typing.Dict[str, int] = {}

        def consumptions(call: ast.Call) -> typing.Set[str]:
            """Key names consumed by this call (direct args only)."""
            if _call_is_derivation(call):
                return set()
            out: typing.Set[str] = set()
            for arg in [*call.args, *[kw.value for kw in call.keywords]]:
                if isinstance(arg, ast.Name) and arg.id in keys:
                    out.add(arg.id)
            return out

        def expr_nodes(root: typing.Optional[ast.AST]):
            """Nodes of one expression, nested scopes excluded."""
            stack = [root] if root is not None else []
            while stack:
                node = stack.pop()
                if isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                ):
                    continue
                yield node
                stack.extend(ast.iter_child_nodes(node))

        def rebinds(root: typing.Optional[ast.AST]) -> typing.Set[str]:
            return {
                node.id
                for node in expr_nodes(root)
                if isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Store)
                and node.id in keys
            }

        def process_exprs(*exprs: typing.Optional[ast.AST]):
            for expr in exprs:
                for node in expr_nodes(expr):
                    if not isinstance(node, ast.Call):
                        continue
                    for name in consumptions(node):
                        count = consumed.get(name, 0) + 1
                        consumed[name] = count
                        if count >= 2 and name not in flagged:
                            flagged.add(name)
                            problems.append(
                                f"line {node.lineno}: key {name!r} "
                                f"already consumed (see earlier use) and "
                                f"is consumed again without an "
                                f"intervening split/fold_in — both "
                                f"consumers draw the same stream"
                            )

        def visit_block(stmts: typing.Sequence[ast.stmt]):
            for stmt in stmts:
                if isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
                ):
                    continue  # nested scope, analyzed on its own
                if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                    body = [*stmt.body, *stmt.orelse]
                    head = stmt.iter if hasattr(stmt, "iter") else stmt.test
                    process_exprs(head)
                    body_rebinds: typing.Set[str] = set()
                    if hasattr(stmt, "target"):
                        body_rebinds |= rebinds(stmt.target)
                    for s in body:
                        body_rebinds |= rebinds(s)
                    # a key consumed in the loop but never rebound in it
                    # draws the SAME stream every iteration
                    for s in body:
                        for node in expr_nodes(s):
                            if isinstance(node, ast.Call):
                                for name in consumptions(node):
                                    if (
                                        name not in body_rebinds
                                        and name not in flagged
                                    ):
                                        flagged.add(name)
                                        problems.append(
                                            f"line {node.lineno}: key "
                                            f"{name!r} is consumed every "
                                            f"loop iteration without a "
                                            f"split/fold_in rebinding — "
                                            f"each iteration draws the "
                                            f"same stream"
                                        )
                    visit_block(body)
                    continue
                if isinstance(stmt, ast.If):
                    # only ONE branch executes: count each against the
                    # pre-branch state and keep the per-key maximum, so
                    # `epoch_fn(keys, ...)` in both arms is one
                    # consumption, not two
                    process_exprs(stmt.test)
                    before = dict(consumed)
                    visit_block(stmt.body)
                    after_body = dict(consumed)
                    consumed.clear()
                    consumed.update(before)
                    visit_block(stmt.orelse)
                    for name in set(after_body) | set(consumed):
                        consumed[name] = max(
                            after_body.get(name, 0), consumed.get(name, 0)
                        )
                    continue
                if isinstance(stmt, ast.Try):
                    visit_block(stmt.body)
                    for handler in stmt.handlers:
                        visit_block(handler.body)
                    visit_block(stmt.orelse)
                    visit_block(stmt.finalbody)
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    process_exprs(*[item.context_expr for item in stmt.items])
                    visit_block(stmt.body)
                    continue
                # simple statement: consumptions, then rebind resets
                process_exprs(stmt)
                for name in rebinds(stmt):
                    consumed[name] = 0

        visit_block(fn.body)
    return problems


# --------------------------------------------------------------------------
# prng-split-width
# --------------------------------------------------------------------------


def _is_split_call(node: ast.Call) -> bool:
    tail = _callee_tail(node.func)
    return tail == "split"


def _width_arg(node: ast.Call) -> typing.Optional[ast.AST]:
    if len(node.args) >= 2:
        return node.args[1]
    for kw in node.keywords:
        if kw.arg == "num":
            return kw.value
    return None


def check_prng_split_width(tree: ast.Module) -> typing.List[str]:
    """
    ``split(key, <non-constant width>)`` whose result is then INDEXED:
    threefry's split lays keys out by the TOTAL count, so element i of
    the result changes whenever the width does — per-variant streams
    silently depend on how many variants ride along (the PR 2 sweep bug:
    variant 0's init/shuffle stream changed with the sweep width; the
    fix shares the width-independent solo key). A non-constant split
    used WHOLESALE (vmapped over, returned as the fleet's key block) is
    fine and is not flagged — only indexing into it pins stream i to the
    width.
    """
    problems: typing.List[str] = []
    for fn in [*_scope_functions(tree), tree]:
        own = (
            _own_scope_nodes(fn)
            if not isinstance(fn, ast.Module)
            else [
                n
                for n in ast.walk(fn)
                if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            ]
        )
        if isinstance(fn, ast.Module):
            # module scope: everything not inside a function
            in_function: typing.Set[int] = set()
            for f in _scope_functions(tree):
                for n in ast.walk(f):
                    in_function.add(id(n))
            own = [n for n in own if id(n) not in in_function]

        # names bound to a non-constant-width split in this scope,
        # mapped to the width EXPRESSION (not the line number: baseline
        # matches must survive unrelated line shifts)
        wide_names: typing.Dict[str, str] = {}
        for node in own:
            if not (isinstance(node, ast.Call) and _is_split_call(node)):
                continue
            width = _width_arg(node)
            if width is None or isinstance(width, ast.Constant):
                continue
            wide_names_here = False
            # direct indexing: split(key, n)[i]
            for parent in own:
                if (
                    isinstance(parent, ast.Subscript)
                    and parent.value is node
                ):
                    problems.append(
                        f"line {parent.lineno}: indexing into "
                        f"split(key, {ast.unparse(width)}) pins stream "
                        f"{ast.unparse(parent.slice)} to the split WIDTH "
                        f"— threefry lays keys out by the total count, "
                        f"so this stream changes when "
                        f"{ast.unparse(width)} does (the PR-2 sweep "
                        f"bug); derive it width-independently "
                        f"(fold_in, or the solo key)"
                    )
                    wide_names_here = True
            if not wide_names_here:
                # bound to a name? remember it for indexing elsewhere
                for candidate in own:
                    if (
                        isinstance(candidate, ast.Assign)
                        and candidate.value is node
                        and len(candidate.targets) == 1
                        and isinstance(candidate.targets[0], ast.Name)
                    ):
                        wide_names[candidate.targets[0].id] = ast.unparse(width)
        for node in own:
            if (
                isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name)
                and node.value.id in wide_names
                and isinstance(node.ctx, ast.Load)
                and not isinstance(node.slice, ast.Slice)
            ):
                problems.append(
                    f"line {node.lineno}: indexing "
                    f"{node.value.id!r} (split with non-constant width "
                    f"{wide_names[node.value.id]}) pins the selected "
                    f"stream to the split width — it changes whenever "
                    f"the variant count does (the PR-2 sweep bug); "
                    f"derive per-variant keys with fold_in or share "
                    f"the width-independent solo key"
                )
    return problems


# --------------------------------------------------------------------------
# traced-branch
# --------------------------------------------------------------------------

_STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size"})
_STATIC_CALLS = frozenset({"len", "isinstance", "getattr", "hasattr", "type"})


def _static_arg_names(fn: ast.AST, jit_call: typing.Optional[ast.Call]) -> typing.Set[str]:
    """Parameters declared static via static_argnums/static_argnames on
    the decorator or the jit call — they are Python values under the
    trace and branching on them is fine."""
    static: typing.Set[str] = set()
    params = [
        a.arg
        for a in (*fn.args.posonlyargs, *fn.args.args, *fn.args.kwonlyargs)
    ]

    def harvest(call: ast.Call):
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for node in ast.walk(kw.value):
                    if isinstance(node, ast.Constant) and isinstance(
                        node.value, str
                    ):
                        static.add(node.value)
            elif kw.arg == "static_argnums":
                for node in ast.walk(kw.value):
                    if isinstance(node, ast.Constant) and isinstance(
                        node.value, int
                    ):
                        if 0 <= node.value < len(params):
                            static.add(params[node.value])

    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            harvest(dec)
    if jit_call is not None:
        harvest(jit_call)
    return static


def check_traced_branching(tree: ast.Module) -> typing.List[str]:
    """
    Python ``if``/``while`` on a value derived from a jitted function's
    (non-static) parameters, inside the traced scope: the branch
    condition is a tracer, and ``bool(tracer)`` raises
    TracerBoolConversionError at trace time — or, if the value is
    concrete only by accident, silently bakes one trace-time path into
    the compiled program. Static escapes are recognized and skipped:
    ``x is None`` / ``isinstance`` tests, and values reached through
    ``.shape``/``.ndim``/``.dtype``/``len()`` (trace-time constants).
    Heuristic by design; route data-dependent branches through
    ``jax.numpy.where``/``lax.cond``/``lax.while_loop``.
    """
    jit_names = _jit_names(tree)
    problems: typing.List[str] = []

    # jitted functions: decorated defs + local defs passed to jax.jit
    jitted: typing.List[typing.Tuple[ast.AST, typing.Optional[ast.Call]]] = []
    defs_by_name: typing.Dict[str, typing.List[ast.AST]] = {}
    for fn in _scope_functions(tree):
        defs_by_name.setdefault(fn.name, []).append(fn)
        for dec in fn.decorator_list:
            if _is_jit_func(dec, jit_names) or (
                isinstance(dec, ast.Call)
                and (
                    _is_jit_func(dec.func, jit_names)
                    or (
                        _callee_tail(dec.func) == "partial"
                        and dec.args
                        and _is_jit_func(dec.args[0], jit_names)
                    )
                )
            ):
                jitted.append((fn, dec if isinstance(dec, ast.Call) else None))
    for node in ast.walk(tree):
        if not _is_jit_call(node, jit_names):
            continue
        arg = node.args[0] if node.args else None
        if isinstance(arg, ast.Name):
            for fn in defs_by_name.get(arg.id, []):
                jitted.append((fn, node))

    seen_fns: typing.Set[int] = set()
    for fn, jit_call in jitted:
        if id(fn) in seen_fns:
            continue
        seen_fns.add(id(fn))
        static = _static_arg_names(fn, jit_call)
        tainted = _param_names(fn) - static
        own = _own_scope_nodes(fn)

        def expr_tainted(node: ast.AST) -> bool:
            if isinstance(node, ast.Attribute):
                if node.attr in _STATIC_ATTRS:
                    return False
                return expr_tainted(node.value)
            if isinstance(node, ast.Call):
                if _callee_tail(node.func) in _STATIC_CALLS:
                    return False
                return any(
                    expr_tainted(a)
                    for a in [
                        node.func,
                        *node.args,
                        *[kw.value for kw in node.keywords],
                    ]
                )
            if isinstance(node, ast.Compare):
                # `x is None` / `x is not None` are trace-time static
                if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                    return False
                return any(
                    expr_tainted(n) for n in [node.left, *node.comparators]
                )
            if isinstance(node, ast.Name):
                return isinstance(node.ctx, ast.Load) and node.id in tainted
            return any(expr_tainted(c) for c in ast.iter_child_nodes(node))

        # one level of propagation: plain assignments from tainted exprs
        for node in own:
            if isinstance(node, ast.Assign) and expr_tainted(node.value):
                for target in node.targets:
                    elts = (
                        target.elts
                        if isinstance(target, (ast.Tuple, ast.List))
                        else [target]
                    )
                    for elt in elts:
                        if isinstance(elt, ast.Name):
                            tainted.add(elt.id)

        for node in own:
            if not isinstance(node, (ast.If, ast.While)):
                continue
            if expr_tainted(node.test):
                kind = "if" if isinstance(node, ast.If) else "while"
                problems.append(
                    f"line {node.lineno}: `{kind} "
                    f"{ast.unparse(node.test)}:` branches on a value "
                    f"derived from {fn.name!r}'s traced parameters — "
                    f"under jax.jit this raises at trace time (or bakes "
                    f"in one path); use jnp.where / lax.cond / "
                    f"lax.while_loop"
                )
    return problems


# --------------------------------------------------------------------------
# donation-safety: reading a buffer after passing it at a donated argnum
# --------------------------------------------------------------------------


def _donated_handles(tree: ast.Module) -> typing.Dict[str, typing.FrozenSet[int]]:
    """Names bound to donating jitted callables, mapped to their donated
    positional indices: ``f = jax.jit(g, donate_argnums=(0, 1))``
    assignments and ``@partial(jax.jit, donate_argnums=...)`` /
    ``@jax.jit(...)``-style decorated defs. Only literal int argnums are
    harvested — dynamic specs are invisible to a syntactic pass."""
    jit_names = _jit_names(tree)

    def donated_positions(call: ast.Call) -> typing.FrozenSet[int]:
        pos: typing.Set[int] = set()
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                for node in ast.walk(kw.value):
                    if isinstance(node, ast.Constant) and isinstance(
                        node.value, int
                    ):
                        pos.add(node.value)
        return frozenset(pos)

    handles: typing.Dict[str, typing.FrozenSet[int]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _is_jit_call(node.value, jit_names):
            pos = donated_positions(node.value)
            if pos:
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        handles[target.id] = pos
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and (
                    _is_jit_func(dec.func, jit_names)
                    or (
                        _callee_tail(dec.func) == "partial"
                        and dec.args
                        and _is_jit_func(dec.args[0], jit_names)
                    )
                ):
                    pos = donated_positions(dec)
                    if pos:
                        handles[node.name] = pos
    return handles


def check_donation_safety(tree: ast.Module) -> typing.List[str]:
    """
    A binding read again after being passed at a donated argnum of a
    jitted call: ``donate_argnums`` hands the buffer to XLA, which may
    reuse its memory for the output — on TPU the later read returns
    garbage or raises (on CPU donation is declined, which is why the bug
    survives local testing). Per scope, straight-line: a plain-name
    positional argument at a donated index, loaded again after the call
    with no intervening rebinding, is flagged. Names rebound by the
    call's own statement (``params, opt = step(params, opt)`` — the
    canonical donation shape) are clean, as are calls through ``*args``
    (positions are invisible) and non-Name arguments (fresh temporaries
    by construction).
    """
    handles = _donated_handles(tree)
    if not handles:
        return []
    problems: typing.List[str] = []
    for scope in (tree, *_scope_functions(tree)):
        own = _own_scope_nodes(scope)
        calls = [
            n
            for n in own
            if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Name)
            and n.func.id in handles
        ]
        if not calls:
            continue
        stores: typing.Dict[str, typing.List[int]] = {}
        loads: typing.Dict[str, typing.List[int]] = {}
        for node in own:
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, (ast.Store, ast.Del)):
                    stores.setdefault(node.id, []).append(node.lineno)
                elif isinstance(node.ctx, ast.Load):
                    loads.setdefault(node.id, []).append(node.lineno)
        assign_stmts = [
            n
            for n in own
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))
        ]
        for call in calls:
            if any(isinstance(a, ast.Starred) for a in call.args):
                continue  # positions are invisible through *args
            # names rebound by the statement containing this call count
            # as rebound AT the call — the canonical consume-and-replace
            rebound_here: typing.Set[str] = set()
            for stmt in assign_stmts:
                if not any(n is call for n in ast.walk(stmt)):
                    continue
                targets = (
                    stmt.targets
                    if isinstance(stmt, ast.Assign)
                    else [stmt.target]
                )
                for target in targets:
                    elts = (
                        target.elts
                        if isinstance(target, (ast.Tuple, ast.List))
                        else [target]
                    )
                    for elt in elts:
                        if isinstance(elt, ast.Name):
                            rebound_here.add(elt.id)
            call_end = getattr(call, "end_lineno", call.lineno) or call.lineno
            for idx in sorted(handles[call.func.id]):
                if idx >= len(call.args):
                    continue
                arg = call.args[idx]
                if not isinstance(arg, ast.Name) or arg.id in rebound_here:
                    continue
                name = arg.id
                later_stores = [
                    ln for ln in stores.get(name, []) if ln > call_end
                ]
                next_store = min(later_stores) if later_stores else None
                for load_line in sorted(loads.get(name, [])):
                    if load_line <= call_end:
                        continue
                    if next_store is not None and load_line > next_store:
                        break  # rebound before this read: fresh buffer
                    problems.append(
                        f"line {load_line}: `{name}` is read after being "
                        f"passed at donated argument {idx} of "
                        f"`{call.func.id}` — the donated buffer may "
                        f"already be reused by XLA (CPU declines "
                        f"donation, so this only fails on accelerators); "
                        f"rebind the name from the call's result or pass "
                        f"a fresh array"
                    )
                    break
    return problems
