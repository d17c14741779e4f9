"""
Core static checks — the stand-in for the reference's mypy/pyflakes
pytest plugins (reference pytest.ini:8-9, mypy.ini; neither tool exists in
this image, and nothing may be installed). Grown from the vendored test
helper (``tests/static_analysis.py``, now a re-export shim over this
package) into the ``gordo_tpu.analysis`` subsystem: these checks run both
package-wide from tests/test_static.py and on demand via
``gordo-tpu lint`` (see ``gordo_tpu/analysis/engine.py`` for the runner
and ``gordo_tpu/analysis/jax_checks.py`` for the JAX-discipline family).

Nine general checks with near-zero false-positive rates:

1. unused imports           (pyflakes' highest-value diagnostic)
2. module-attribute typos   (``module.atr`` that cannot resolve)
3. call-signature mismatch  (wrong arity / unknown kwarg on calls whose
                             target resolves statically — the slice of
                             mypy's checking that needs no annotations)
4. module shadowing         (a plain ``import X`` coexisting with another
                             binding of ``X`` — ``from X import X``, a
                             def/class — makes every ``X.attr`` ambiguous;
                             the exact class of the round-2 ``copy`` bug)
5. annotated-attribute typos (``param.atr`` where ``param`` is annotated
                             with a statically-resolvable class and the
                             attribute exists neither on the class nor as
                             a ``self.atr`` assignment in its methods —
                             the annotation-driven slice of mypy)
6. return-annotation drift  (a bare ``return`` in a function annotated
                             ``-> X`` for non-Optional X, or ``return v``
                             in one annotated ``-> None``)
7. self-attribute reads     (``self.atr`` reads against the class's known
                             surface, incl. AugAssign reads)
8. self-method-call binding (``self.method(...)`` arity/kwargs against
                             the class's own or inherited signature)
9. annotated-receiver calls (``param.method(...)`` where ``param`` is
                             annotated with vouched class(es): the call
                             must bind to the class's method signature —
                             the cross-module signature-drift net)
"""

import ast
import builtins
import importlib
import inspect
import re
import sys
import textwrap
import types
import typing


def parse(path) -> ast.Module:
    with open(path) as fh:
        return ast.parse(fh.read(), filename=str(path))


# --------------------------------------------------------------------------
# 1. unused imports
# --------------------------------------------------------------------------


def _imported_names(tree: ast.Module):
    """(local name, node lineno) for every import binding in the module."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                yield name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name == "*":
                    continue
                yield (alias.asname or alias.name), node.lineno


def check_unused_imports(tree: ast.Module, source: str) -> typing.List[str]:
    """
    Imports whose bound name never appears again in the source. The "appears
    again" test is whole-word matching (including inside strings), which
    forgives __all__ re-exports, doctests and quoted annotations — so a hit
    here is a genuinely dead import.
    """
    problems = []
    for name, lineno in _imported_names(tree):
        if name.startswith("_"):
            continue  # conventional "import for side effects/re-export"
        uses = len(re.findall(rf"\b{re.escape(name)}\b", source))
        # one whole-word occurrence is the import statement itself
        if uses <= 1:
            problems.append(f"line {lineno}: unused import {name!r}")
    return problems


# --------------------------------------------------------------------------
# 2 + 3. attribute/call checking against the *imported* module
# --------------------------------------------------------------------------

_SKIP_SIGNATURE = (types.BuiltinFunctionType, types.BuiltinMethodType, type(print))


def _resolve(node: ast.AST, namespace: dict):
    """Resolve Name/Attribute chains against the live module namespace."""
    if isinstance(node, ast.Name):
        return namespace.get(node.id, _UNRESOLVED)
    if isinstance(node, ast.Attribute):
        base = _resolve(node.value, namespace)
        if base is _UNRESOLVED:
            return _UNRESOLVED
        try:
            return getattr(base, node.attr, _UNRESOLVED)
        except Exception:
            return _UNRESOLVED
    return _UNRESOLVED


class _Unresolved:
    pass


_UNRESOLVED = _Unresolved()


def _locally_rebound_names(tree: ast.Module) -> typing.Set[str]:
    """
    Every name that is ever a *store* target or parameter anywhere in the
    module. Resolution against the module namespace must skip these: a
    local `json = ...` or `def f(json)` shadows the imported module, and
    vouching for the module-level object there would be a false positive.
    """
    rebound: typing.Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and isinstance(node.ctx, (ast.Store, ast.Del)):
            rebound.add(node.id)
        elif isinstance(node, ast.arg):
            rebound.add(node.arg)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            rebound.add(node.name)
        elif isinstance(node, ast.Global) or isinstance(node, ast.Nonlocal):
            rebound.update(node.names)
    return rebound


def check_module_attributes(tree: ast.Module, module) -> typing.List[str]:
    """``some_module.attr`` expressions whose attr does not exist."""
    namespace = vars(module)
    rebound = _locally_rebound_names(tree)
    problems = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)):
            continue
        if node.value.id in rebound:
            continue  # shadowed somewhere; can't vouch for what it refers to
        base = namespace.get(node.value.id, _UNRESOLVED)
        # only vouch for real modules: object attributes may be dynamic
        if not isinstance(base, types.ModuleType):
            continue
        if hasattr(base, node.attr):
            continue
        # lazily-imported submodules resolve via import, not getattr
        try:
            importlib.import_module(f"{base.__name__}.{node.attr}")
        except Exception:
            problems.append(
                f"line {node.lineno}: module {base.__name__!r} has no "
                f"attribute {node.attr!r}"
            )
    return problems


# --------------------------------------------------------------------------
# 4. module shadowing
# --------------------------------------------------------------------------


def check_module_shadowing(tree: ast.Module) -> typing.List[str]:
    """
    A plain ``import X`` whose bound name is ALSO bound by a from-import,
    def, or class at module scope. Whichever binding executes last
    wins silently, so every ``X.attr`` in the module is ambiguous — and the
    attribute checker above must *skip* such names rather than vouch for
    them, which is exactly how ``import copy`` + ``from copy import copy``
    slipped through in round 2 (``copy.copy(spec)`` then called the stdlib
    *function*). Plain assignments are deliberately not flagged: the
    ``try: import foo / except ImportError: foo = None`` optional-dependency
    gate is a legitimate rebinding of the same conceptual slot.
    """
    def module_scope(root: ast.Module):
        """Statements executed in MODULE scope only: the body plus the
        bodies of top-level if/try/with blocks — never function or class
        bodies, which bind in their own scope (a ``def copy(self)`` method
        does not shadow a module-level ``import copy``)."""
        stack = list(root.body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.If, ast.Try, ast.With, ast.For, ast.While)):
                for field in ("body", "orelse", "finalbody", "handlers"):
                    for child in getattr(node, field, []):
                        if isinstance(child, ast.ExceptHandler):
                            stack.extend(child.body)
                        else:
                            stack.append(child)

    plain: typing.Dict[str, int] = {}
    for node in module_scope(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                plain.setdefault(name, node.lineno)
    if not plain:
        return []
    problems = []
    shadowed: typing.Set[str] = set()
    for node in module_scope(tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                name = alias.asname or alias.name
                if name in plain:
                    shadowed.add(name)
                    problems.append(
                        f"line {node.lineno}: 'from ... import {name}' shadows "
                        f"'import {name}' (line {plain[name]})"
                    )
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            if node.name in plain:
                shadowed.add(node.name)
                problems.append(
                    f"line {node.lineno}: definition of {node.name!r} shadows "
                    f"'import {node.name}' (line {plain[node.name]})"
                )
    # use sites: every attribute access through a shadowed module name is
    # reported too, so the finding points at the code that will misbehave
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id in shadowed
        ):
            problems.append(
                f"line {node.lineno}: attribute access "
                f"'{node.value.id}.{node.attr}' goes through a shadowed "
                f"module name"
            )
    return problems


# --------------------------------------------------------------------------
# 5. annotation-driven attribute checking (the mypy slice)
# --------------------------------------------------------------------------

_ATTR_CACHE: typing.Dict[type, typing.Optional[typing.Set[str]]] = {}


#: attrs seen ONLY as AugAssign targets per class (see _known_attrs)
_AUG_ONLY_CANDIDATES: typing.Dict[type, typing.Set[str]] = {}


def _known_attrs(cls: type) -> typing.Optional[typing.Set[str]]:
    """
    The statically-knowable attribute surface of ``cls``: everything on the
    class (dir), declared annotations, plus every ``self.X = ...`` target
    found in the class's own source. Returns None — "can't vouch" — for
    classes with dynamic attribute hooks or unreadable source.
    """
    if cls in _ATTR_CACHE:
        return _ATTR_CACHE[cls]
    result: typing.Optional[typing.Set[str]]
    # only a PYTHON-level hook makes the surface dynamic; C slots
    # (tuple.__getattribute__ etc.) are ordinary attribute lookup
    if any(
        isinstance(vars(base).get(hook), types.FunctionType)
        for base in cls.__mro__
        for hook in ("__getattr__", "__getattribute__")
        if base is not object
    ):
        result = None
    else:
        names = set(dir(cls))
        for base in cls.__mro__:
            names.update(getattr(base, "__annotations__", {}))
            if base is object:
                continue
            try:
                base_tree = ast.parse(textwrap.dedent(inspect.getsource(base)))
            except TypeError:
                # C-implemented base (tuple, Exception, ...): no Python
                # source means no `self.x = ...` sites to miss — dir()
                # already covers it, keep going
                continue
            except (OSError, SyntaxError, IndentationError):
                # Python base whose source we cannot read: it may assign
                # instance attributes we cannot see — can't vouch
                result = None
                break
            dynamic = False
            # AugAssign targets are Store-ctx but READ first at runtime
            # (self.x += 1 on an undefined x raises): they do not define
            # the surface on their own — check_self_attributes treats a
            # name ONLY ever aug-assigned as undefined
            aug_targets = {
                id(node.target)
                for node in ast.walk(base_tree)
                if isinstance(node, ast.AugAssign)
            }
            for node in ast.walk(base_tree):
                if (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.ctx, ast.Store)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                ):
                    if id(node) in aug_targets:
                        _AUG_ONLY_CANDIDATES.setdefault(cls, set()).add(
                            node.attr
                        )
                    else:
                        names.add(node.attr)
                elif (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "setattr"
                    and node.args
                    and isinstance(node.args[0], ast.Name)
                    and node.args[0].id == "self"
                ):
                    # setattr(self, <name>, ...): a constant name is just
                    # another attribute; a computed one makes the surface
                    # dynamic — can't vouch for the class at all
                    if len(node.args) > 1 and isinstance(
                        node.args[1], ast.Constant
                    ) and isinstance(node.args[1].value, str):
                        names.add(node.args[1].value)
                    else:
                        dynamic = True
                        break
            if dynamic:
                result = None
                break
        else:
            result = names
    _ATTR_CACHE[cls] = result
    return result


def _annotation_classes(node: ast.AST, namespace: dict) -> typing.List[type]:
    """
    Resolve an annotation expression to the plain classes it names.
    ``Optional[X]``/``Union[X, Y]`` yield their non-None members;
    ``List[X]`` yields ``list``. Unresolvable pieces yield nothing.
    """
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        try:
            node = ast.parse(node.value, mode="eval").body
        except SyntaxError:
            return []
    if isinstance(node, (ast.Name, ast.Attribute)):
        target = _resolve(node, namespace)
        if isinstance(target, type):
            return [target]
        return []
    if isinstance(node, ast.Subscript):
        base = _resolve(node.value, namespace)
        if base in (typing.Optional, typing.Union):
            members = (
                node.slice.elts if isinstance(node.slice, ast.Tuple) else [node.slice]
            )
            out: typing.List[type] = []
            for member in members:
                if isinstance(member, ast.Constant) and member.value is None:
                    continue
                out.extend(_annotation_classes(member, namespace))
            return out
        origin = typing.get_origin(base)
        if isinstance(origin, type):
            return [origin]
        if isinstance(base, type):
            return [base]
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitOr):  # X | None
        return _annotation_classes(node.left, namespace) + _annotation_classes(
            node.right, namespace
        )
    return []


# Nominal typing only applies where the annotations are authoritative: this
# package and the (typeshed-typed) stdlib. Third-party science libs
# (sklearn, pandas, jax, ...) ship no stubs — real mypy treats their classes
# as Any, and annotating a duck-typed estimator parameter as BaseEstimator
# is idiom, not a contract. `typing` specials (Any, ...) are never vouched.
_NOMINAL_ROOTS = set(sys.stdlib_module_names) | {"gordo_tpu"}


def _nominally_typed(cls: type) -> bool:
    module_name = getattr(cls, "__module__", "") or ""
    if module_name == "typing" or cls is object:
        return False
    return module_name.split(".")[0] in _NOMINAL_ROOTS


def check_annotated_attributes(tree: ast.Module, module) -> typing.List[str]:
    """
    For every function parameter annotated with resolvable class(es):
    attribute reads through that parameter must exist on at least one of
    the classes (their known surface per ``_known_attrs``). Parameters
    rebound inside the function are skipped.
    """
    namespace = dict(vars(builtins))
    namespace.update(vars(module))
    problems = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        args = fn.args
        annotated: typing.Dict[str, typing.List[type]] = {}
        for arg in [*args.posonlyargs, *args.args, *args.kwonlyargs]:
            if arg.annotation is None:
                continue
            classes = _annotation_classes(arg.annotation, namespace)
            if not classes:
                continue
            # every named class must be one we can vouch for, else skip
            if not all(
                _nominally_typed(cls) and _known_attrs(cls) is not None
                for cls in classes
            ):
                continue
            annotated[arg.arg] = classes
        if not annotated:
            continue
        # own-scope nodes only: a nested def/lambda is its own scope (its
        # params may shadow ours) and is visited as its own FunctionDef by
        # the outer walk
        own_nodes = _own_scope_nodes(fn)
        rebound = {
            n.id
            for n in own_nodes
            if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store, ast.Del))
        }
        for node in own_nodes:
            if not (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and isinstance(node.ctx, ast.Load)
            ):
                continue
            param = node.value.id
            if param not in annotated or param in rebound:
                continue
            surfaces = [_known_attrs(cls) for cls in annotated[param]]
            if any(surface is None or node.attr in surface for surface in surfaces):
                continue
            owners = ", ".join(cls.__name__ for cls in annotated[param])
            problems.append(
                f"line {node.lineno}: {param}.{node.attr} — no attribute "
                f"{node.attr!r} on annotated type {owners}"
            )
    return problems


# --------------------------------------------------------------------------
# 6. return-annotation drift
# --------------------------------------------------------------------------


def _is_nonelike_annotation(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return node.value is None
    if isinstance(node, ast.Attribute):  # typing.Any / t.Any spelling
        return node.attr in ("Any", "object")
    return isinstance(node, ast.Name) and node.id in ("None", "Any", "object")


def _permits_bare_return(node: ast.AST, namespace: typing.Optional[dict] = None) -> bool:
    """Optional[...] / ``X | None`` / None / Any annotations allow ``return``."""
    if _is_nonelike_annotation(node):
        return True
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        try:
            parsed = ast.parse(node.value, mode="eval").body
        except SyntaxError:
            return True
        return _permits_bare_return(parsed, namespace)
    if isinstance(node, ast.Subscript):
        head = node.value
        head_name = head.attr if isinstance(head, ast.Attribute) else (
            head.id if isinstance(head, ast.Name) else None
        )
        # resolve aliases (``from typing import Optional as Opt``) through
        # the live namespace when we have one; fall back to literal names
        if namespace is not None:
            target = _resolve(head, namespace)
            if target is typing.Optional:
                head_name = "Optional"
            elif target is typing.Union:
                head_name = "Union"
        if head_name == "Optional":
            return True
        if head_name == "Union":
            members = (
                node.slice.elts if isinstance(node.slice, ast.Tuple) else [node.slice]
            )
            return any(_permits_bare_return(m, namespace) for m in members)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitOr):
        return _permits_bare_return(node.left, namespace) or _permits_bare_return(
            node.right, namespace
        )
    return False


def _declares_none(node: ast.AST) -> bool:
    """Annotations that literally promise None (quoted form included)."""
    if isinstance(node, ast.Constant):
        if node.value is None:
            return True
        if isinstance(node.value, str):
            try:
                return _declares_none(ast.parse(node.value, mode="eval").body)
            except SyntaxError:
                return False
        return False
    return isinstance(node, ast.Name) and node.id == "None"


def check_return_annotations(tree: ast.Module, module=None) -> typing.List[str]:
    """
    ``return`` (no value) inside ``def f(...) -> X`` for a concrete
    non-Optional X, and ``return value`` inside ``-> None`` — both are
    annotation/behavior drift mypy would flag. Generators are exempt
    (their annotation describes the generator object, not ``return``).
    With ``module`` given, Optional/Union aliases resolve through its
    namespace.
    """
    namespace = None
    if module is not None:
        namespace = dict(vars(builtins))
        namespace.update(vars(module))
    problems = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.returns is None:
            continue
        own_nodes = _own_scope_nodes(fn)
        if any(isinstance(node, (ast.Yield, ast.YieldFrom)) for node in own_nodes):
            continue
        declares_none = _declares_none(fn.returns)
        allows_bare = _permits_bare_return(fn.returns, namespace)
        for node in own_nodes:
            if not isinstance(node, ast.Return):
                continue
            if node.value is None or (
                isinstance(node.value, ast.Constant) and node.value.value is None
            ):
                if not allows_bare:
                    problems.append(
                        f"line {node.lineno}: bare return in function "
                        f"{fn.name!r} annotated -> "
                        f"{ast.unparse(fn.returns)}"
                    )
            elif declares_none:
                problems.append(
                    f"line {node.lineno}: function {fn.name!r} annotated "
                    f"-> None returns a value"
                )
    return problems


def _own_scope_nodes(fn: ast.AST) -> typing.List[ast.AST]:
    """All AST nodes in ``fn``'s body excluding nested function/lambda bodies."""
    out: typing.List[ast.AST] = []
    stack: typing.List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        out.append(node)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))
    return out


def _bindable(callee) -> typing.Optional[inspect.Signature]:
    if isinstance(callee, _SKIP_SIGNATURE):
        return None
    if isinstance(callee, type):
        if callee.__init__ is object.__init__ and callee.__new__ is object.__new__:
            return None
        try:
            return inspect.signature(callee)
        except (ValueError, TypeError):
            return None
    if callable(callee):
        try:
            return inspect.signature(callee)
        except (ValueError, TypeError):
            return None
    return None


def check_call_signatures(tree: ast.Module, module) -> typing.List[str]:
    """
    Statically-resolvable calls must bind: right arity, known keywords.
    Calls with *args/**kwargs splats, or whose target can't be resolved
    to a concrete callable in the module's namespace, are skipped.
    """
    namespace = dict(vars(builtins))
    namespace.update(vars(module))
    rebound = _locally_rebound_names(tree)
    problems = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if any(isinstance(a, ast.Starred) for a in node.args):
            continue
        if any(kw.arg is None for kw in node.keywords):  # **splat
            continue
        # skip anything rooted in a shadowed/rebound name
        root = node.func
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name) and root.id in rebound:
            continue
        callee = _resolve(node.func, namespace)
        if callee is _UNRESOLVED:
            continue
        signature = _bindable(callee)
        if signature is None:
            continue
        try:
            signature.bind(
                *[None] * len(node.args),
                **{kw.arg: None for kw in node.keywords},
            )
        except TypeError as exc:
            name = ast.unparse(node.func)
            problems.append(f"line {node.lineno}: call to {name}(): {exc}")
    return problems


def _rebinds_self(fn: ast.AST) -> bool:
    args = fn.args
    return any(
        a.arg == "self"
        for a in (
            *args.posonlyargs, *args.args, *args.kwonlyargs,
            *([args.vararg] if args.vararg else []),
            *([args.kwarg] if args.kwarg else []),
        )
    )


def _method_scope_nodes(cls_node: ast.ClassDef) -> typing.List[ast.AST]:
    """Nodes where ``self`` is THIS class's instance: method bodies, minus
    nested ClassDefs and minus nested functions/lambdas that rebind
    ``self`` (a callback's ``self`` is some other object's)."""
    out: typing.List[ast.AST] = []
    stack: typing.List[ast.AST] = list(ast.iter_child_nodes(cls_node))
    while stack:
        node = stack.pop()
        if isinstance(node, ast.ClassDef):
            continue
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ) and _rebinds_self(node) and node not in cls_node.body:
            continue  # a callback with its own self
        out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def check_self_attributes(tree: ast.Module, module) -> typing.List[str]:
    """
    ``self.attr`` READS inside a module-scope class must name an
    attribute on the class's statically-knowable surface (class dir +
    annotations + every ``self.X = ...`` in its own and its bases'
    source) — the typo'd-state-read slice of mypy. Stores are exempt
    (they DEFINE the surface), as are dynamic-surface classes.
    """
    namespace = vars(module)
    problems: typing.List[str] = []
    for cls_node in tree.body:
        if not isinstance(cls_node, ast.ClassDef):
            continue
        cls = namespace.get(cls_node.name)
        if not isinstance(cls, type):
            continue
        known = _known_attrs(cls)
        if known is None:
            continue
        for node in _method_scope_nodes(cls_node):
            is_read = (
                isinstance(node, ast.Attribute)
                and isinstance(node.ctx, ast.Load)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            )
            if isinstance(node, ast.AugAssign) and isinstance(
                node.target, ast.Attribute
            ):
                # self.x += 1 READS x before writing: an undefined x
                # raises at runtime even though the ctx is Store
                target = node.target
                is_read = (
                    isinstance(target.value, ast.Name)
                    and target.value.id == "self"
                )
                node = target
            if is_read and node.attr not in known:
                aug_only = node.attr in _AUG_ONLY_CANDIDATES.get(cls, set())
                detail = (
                    " (only ever aug-assigned: self.X += ... reads X "
                    "before writing)" if aug_only else ""
                )
                problems.append(
                    f"line {node.lineno}: self.{node.attr} is not on "
                    f"{cls_node.name}'s attribute surface{detail}"
                )
    return problems


def _splatted(node: ast.Call) -> bool:
    """Calls with positional or keyword splats cannot be bound statically."""
    return any(isinstance(a, ast.Starred) for a in node.args) or any(
        kw.arg is None for kw in node.keywords
    )


def _bind_probe(signature: inspect.Signature, node: ast.Call, implicit: int = 0):
    """Bind a call node's arg shape (values as None) against a signature;
    returns the TypeError on mismatch, else None. ``implicit`` prepends
    that many positional slots (an unbound method's ``self``)."""
    try:
        signature.bind(
            *[None] * (implicit + len(node.args)),
            **{kw.arg: None for kw in node.keywords},
        )
    except TypeError as exc:
        return exc
    return None


def _method_bind_error(cls: type, name: str, node: ast.Call):
    """Resolve ``cls.name`` as a statically-bindable method and bind the
    call node's arg shape against it: returns the TypeError on mismatch,
    None when it binds, and ``_UNRESOLVED`` when the attribute is missing
    or not a plain static/class/instance method (property, descriptor,
    callable object, C-accelerated signature)."""
    try:
        raw = inspect.getattr_static(cls, name)
    except AttributeError:
        return _UNRESOLVED
    if isinstance(raw, staticmethod):
        target, implicit = raw.__func__, 0
    elif isinstance(raw, classmethod):
        target, implicit = getattr(cls, name), 0  # cls pre-bound
    elif inspect.isfunction(raw):
        target, implicit = raw, 1  # self
    else:
        return _UNRESOLVED
    try:
        signature = inspect.signature(target)
    except (ValueError, TypeError):
        return _UNRESOLVED
    return _bind_probe(signature, node, implicit)


def check_self_method_calls(tree: ast.Module, module) -> typing.List[str]:
    """
    ``self.method(...)`` calls inside a MODULE-SCOPE class body must bind
    to that class's own (or inherited) method signature — the
    signature-drift class of bug the module-level call check cannot see
    because the receiver is an instance. Conservative: skips splats,
    dynamic-surface classes (``__getattr__`` hooks), properties,
    non-function class attributes, function-local classes (their names
    need not resolve at module scope), and any subtree where a nested
    function or lambda REBINDS ``self`` (a callback's ``self`` is some
    other object's).
    """
    namespace = vars(module)
    problems: typing.List[str] = []

    for cls_node in tree.body:  # module scope only: names resolve reliably
        if not isinstance(cls_node, ast.ClassDef):
            continue
        cls = namespace.get(cls_node.name)
        if not isinstance(cls, type) or _known_attrs(cls) is None:
            continue
        for node in _method_scope_nodes(cls_node):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "self"
            ):
                continue
            if _splatted(node):
                continue
            name = node.func.attr
            error = _method_bind_error(cls, name, node)
            if error is not None and error is not _UNRESOLVED:
                problems.append(f"line {node.lineno}: self.{name}(): {error}")
    return problems


# --------------------------------------------------------------------------
# 10. metric-registration discipline (observability registry call sites)
# --------------------------------------------------------------------------

#: the observability registry's factory methods — every call site
#: registering a metric goes through one of these
METRIC_FACTORY_METHODS = frozenset({"counter", "gauge", "histogram"})

#: The documented label vocabulary (docs/observability.md — keep in
#: sync). Label NAMES outside this set are flagged: an undocumented
#: label is usually a high-cardinality one (a raw path or machine name)
#: about to blow up the time-series count.
ALLOWED_METRIC_LABELS = frozenset(
    {
        "path", "phase", "endpoint", "method", "outcome", "windowed",
        "kind", "status",
        # replica ids are a config-bounded handful per deployment (the
        # router's shard manifest names them all), not a cardinality risk
        "replica",
        # knob names are bounded by the knob registry
        # (gordo_tpu/tuning/knobs.py), a fixed compile-time set
        "knob",
        # transfer accounting (parallel/transfer.py): plane is one of
        # build/train/stream, mode is prefetched/direct — both fixed
        # three-or-fewer-value vocabularies
        "plane", "mode",
        # chaos injection sites are bounded by the _KNOWN_SITES
        # frozenset (robustness/faults.py), a fixed compile-time set
        "site",
    }
)

METRIC_NAME_RE = re.compile(r"^gordo_[a-z][a-z0-9_]*$")


def check_metric_registrations(tree: ast.Module) -> typing.List[str]:
    """
    Every ``<registry>.counter/gauge/histogram("name", ..., labelnames)``
    registration must use a LITERAL ``gordo_``-prefixed metric name
    (counters additionally ending ``_total``, Prometheus convention) and
    a literal label-name tuple drawn from the documented bounded set —
    so no call site can smuggle raw paths or machine names in as labels,
    and the bridged /metrics namespace stays collision-free.
    """
    problems = []
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in METRIC_FACTORY_METHODS
        ):
            continue
        name_node = node.args[0] if node.args else None
        if name_node is None:
            name_node = next(
                (kw.value for kw in node.keywords if kw.arg == "name"), None
            )
        if not (
            isinstance(name_node, ast.Constant)
            and isinstance(name_node.value, str)
        ):
            # not a statically-vouchable registration (or a different
            # library's same-named method) — out of scope
            continue
        name = name_node.value
        if not METRIC_NAME_RE.match(name):
            problems.append(
                f"line {node.lineno}: metric {name!r} must match "
                f"'gordo_<lower_snake>'"
            )
        elif node.func.attr == "counter" and not name.endswith("_total"):
            problems.append(
                f"line {node.lineno}: counter {name!r} must end '_total'"
            )
        labels_node = node.args[2] if len(node.args) > 2 else None
        if labels_node is None:
            labels_node = next(
                (kw.value for kw in node.keywords if kw.arg == "labelnames"),
                None,
            )
        if labels_node is None:
            continue  # unlabeled metric
        if not isinstance(labels_node, (ast.Tuple, ast.List)):
            problems.append(
                f"line {node.lineno}: metric {name!r} labelnames must be a "
                f"literal tuple/list (got {ast.unparse(labels_node)})"
            )
            continue
        for element in labels_node.elts:
            if not (
                isinstance(element, ast.Constant)
                and isinstance(element.value, str)
            ):
                problems.append(
                    f"line {node.lineno}: metric {name!r} has a non-literal "
                    f"label name"
                )
            elif element.value not in ALLOWED_METRIC_LABELS:
                problems.append(
                    f"line {node.lineno}: metric {name!r} label "
                    f"{element.value!r} is not in the documented label set "
                    f"{sorted(ALLOWED_METRIC_LABELS)}"
                )
    return problems


def collect_metric_names(tree: ast.Module) -> typing.Set[str]:
    """
    Every LITERAL metric name registered through the observability
    registry's factory methods in this module — the same call sites
    ``check_metric_registrations`` disciplines. Used by the catalogue
    sync check (tests/test_static.py): a metric registered in code but
    absent from docs/observability.md's catalogue is a doc drift, the
    failure mode that would otherwise let new telemetry (e.g. a fit's
    dispatch/sync metrics) ship undocumented.
    """
    names: typing.Set[str] = set()
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in METRIC_FACTORY_METHODS
        ):
            continue
        name_node = node.args[0] if node.args else None
        if name_node is None:
            name_node = next(
                (kw.value for kw in node.keywords if kw.arg == "name"), None
            )
        if (
            isinstance(name_node, ast.Constant)
            and isinstance(name_node.value, str)
            and METRIC_NAME_RE.match(name_node.value)
        ):
            names.add(name_node.value)
    return names


#: The event-log emission surface (observability/events.py): the
#: module-level helper plus the EventEmitter method it wraps.
EVENT_EMIT_FUNCTIONS = frozenset({"emit_event"})
EVENT_EMIT_METHODS = frozenset({"emit"})


def collect_event_names(tree: ast.Module) -> typing.Set[str]:
    """
    Every LITERAL event type emitted through the observability event log
    in this module: ``emit_event("<name>", ...)`` calls and
    ``<emitter>.emit("<name>", ...)`` method calls. The docs-catalogue
    sync sibling of :func:`collect_metric_names` — an event type emitted
    in code but absent from docs/observability.md's event schema is doc
    drift (metrics were already enforced; events were not, so e.g. a new
    lifecycle event could ship with no documented fields).
    """
    names: typing.Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        is_emit = (
            isinstance(node.func, ast.Name)
            and node.func.id in EVENT_EMIT_FUNCTIONS
        ) or (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in EVENT_EMIT_METHODS
        )
        if not is_emit:
            continue
        name_node = node.args[0] if node.args else None
        if name_node is None:
            name_node = next(
                (kw.value for kw in node.keywords if kw.arg == "event"), None
            )
        if isinstance(name_node, ast.Constant) and isinstance(
            name_node.value, str
        ):
            names.add(name_node.value)
    return names


#: the span-opening surface (observability/tracing.py): context-managed —
#: a span opened any other way is never closed, so never persisted
SPAN_OPEN_FUNCTIONS = frozenset({"start_span"})
#: completed-span recorders: they persist a finished span immediately,
#: no context manager involved (record_phase is the server's
#: Server-Timing phase hook, which forwards into record_span)
SPAN_RECORD_FUNCTIONS = frozenset({"record_span", "record_phase"})
#: the trace-correlation field names ONLY trace_fields() may spell out
TRACE_STAMP_KEYS = frozenset({"trace_id", "span_id"})


def collect_span_names(tree: ast.Module) -> typing.Set[str]:
    """
    Every LITERAL span name this module opens (``start_span``) or
    records (``record_span`` / ``record_phase``) — the docs-catalogue
    sync sibling of :func:`collect_metric_names` /
    :func:`collect_event_names`: a span name emitted in code but absent
    from docs/observability.md's span catalogue is doc drift.
    """
    openers = SPAN_OPEN_FUNCTIONS | SPAN_RECORD_FUNCTIONS
    names: typing.Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        is_span = (
            isinstance(node.func, ast.Name) and node.func.id in openers
        ) or (
            isinstance(node.func, ast.Attribute) and node.func.attr in openers
        )
        if not is_span:
            continue
        name_node = node.args[0] if node.args else None
        if name_node is None:
            name_node = next(
                (kw.value for kw in node.keywords if kw.arg == "name"), None
            )
        if isinstance(name_node, ast.Constant) and isinstance(
            name_node.value, str
        ):
            names.add(name_node.value)
    return names


#: the chaos-site vocabulary's one spelling (robustness/faults.py)
FAULT_SITES_CONSTANT = "_KNOWN_SITES"


def collect_fault_sites(tree: ast.Module) -> typing.Set[str]:
    """
    The literal chaos-site names bound to ``_KNOWN_SITES`` in this
    module (robustness/faults.py's ``frozenset({...})``) — the
    docs-catalogue sync sibling of :func:`collect_metric_names` /
    :func:`collect_event_names` / :func:`collect_span_names` applied to
    fault injection: a site ``parse_spec`` accepts but
    docs/robustness.md's chaos table doesn't list is a seam no chaos
    run will ever discover.
    """
    sites: typing.Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == FAULT_SITES_CONSTANT
            for t in node.targets
        ):
            continue
        for constant in ast.walk(node.value):
            if isinstance(constant, ast.Constant) and isinstance(
                constant.value, str
            ):
                sites.add(constant.value)
    return sites


def check_span_discipline(tree: ast.Module) -> typing.List[str]:
    """
    Tracing hygiene (docs/observability.md "Distributed tracing"):

    - ``start_span(...)`` must be the context expression of a ``with``
      statement (or handed to an ``ExitStack.enter_context``). A span
      opened any other way is a LEAK: it is never ended, never
      persisted, and — had the contextvar been set — would re-parent
      every later span in the thread.
    - event emissions must not hand-stamp ``trace_id=`` / ``span_id=``
      keywords: ``emit_event`` stamps the ambient span itself, and
      cross-thread sites go through ``**trace_fields(span)`` so the
      correlation fields keep one spelling everywhere.
    """
    managed: typing.Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                managed.add(id(item.context_expr))
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "enter_context"
        ):
            for arg in node.args:
                managed.add(id(arg))

    problems: typing.List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        opens_span = (
            isinstance(func, ast.Name) and func.id in SPAN_OPEN_FUNCTIONS
        ) or (
            isinstance(func, ast.Attribute)
            and func.attr in SPAN_OPEN_FUNCTIONS
        )
        if opens_span and id(node) not in managed:
            problems.append(
                f"line {node.lineno}: start_span(...) outside a "
                "with-statement — the span is never ended or persisted "
                "(leak risk)"
            )
            continue
        emits_event = (
            isinstance(func, ast.Name) and func.id in EVENT_EMIT_FUNCTIONS
        ) or (
            isinstance(func, ast.Attribute)
            and func.attr in EVENT_EMIT_METHODS
        )
        if emits_event:
            stamped = sorted(
                kw.arg
                for kw in node.keywords
                if kw.arg in TRACE_STAMP_KEYS
            )
            if stamped:
                problems.append(
                    f"line {node.lineno}: event emission hand-stamps "
                    f"{', '.join(stamped)} — stamp trace context via "
                    "**trace_fields(span) (or rely on the ambient span)"
                )
    return problems


def check_annotated_param_method_calls(tree: ast.Module, module) -> typing.List[str]:
    """
    ``param.method(...)`` calls where ``param`` is annotated with vouched
    class(es) must bind to the class's method signature — the
    cross-module signature-drift net for the receiver-typed calls that
    ``check_call_signatures`` (module-scope callables) and
    ``check_self_method_calls`` (``self`` receivers) cannot see. Same
    conservatism as the attribute check: only nominally-typed classes
    with a known surface, params never rebound in scope, no splats;
    with a Union annotation, binding on ANY member passes.
    """
    namespace = dict(vars(builtins))
    namespace.update(vars(module))
    problems: typing.List[str] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        args = fn.args
        annotated: typing.Dict[str, typing.List[type]] = {}
        for arg in [*args.posonlyargs, *args.args, *args.kwonlyargs]:
            if arg.annotation is None:
                continue
            classes = _annotation_classes(arg.annotation, namespace)
            if not classes:
                continue
            if not all(
                _nominally_typed(cls) and _known_attrs(cls) is not None
                for cls in classes
            ):
                continue
            annotated[arg.arg] = classes
        if not annotated:
            continue
        own_nodes = _own_scope_nodes(fn)
        rebound = {
            n.id
            for n in own_nodes
            if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store, ast.Del))
        }
        for node in own_nodes:
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
            ):
                continue
            param = node.func.value.id
            if param not in annotated or param in rebound or _splatted(node):
                continue
            name = node.func.attr
            errors: typing.List[TypeError] = []
            for cls in annotated[param]:
                error = _method_bind_error(cls, name, node)
                if error is None or error is _UNRESOLVED:
                    # binds on this member, or isn't statically bindable
                    # (existence is check_annotated_attributes' concern;
                    # a miss on one Union member may hit on another)
                    errors = []
                    break
                errors.append(error)
            if errors:
                owners = ", ".join(cls.__name__ for cls in annotated[param])
                problems.append(
                    f"line {node.lineno}: {param}.{name}() "
                    f"[{param}: {owners}]: {errors[0]}"
                )
    return problems
