"""
Static-health checks — the stand-in for the reference's mypy/pyflakes
pytest plugins (reference pytest.ini:8-9; neither tool is available in this
image). Every module must byte-compile and import cleanly, and the analyzer
(gordo_tpu.analysis, re-exported through the tests/static_analysis.py shim)
checks unused imports, module-attribute typos and call-signature mismatches
across the whole package — plus, parametrized at the end of this file, the
JAX-discipline family (retrace/host-sync/PRNG/traced-branch) so a lint
regression fails tier-1 the same way a broken signature does.
"""

import compileall
import importlib
from pathlib import Path

import pytest

import gordo_tpu

from tests.utils import package_module_names

from static_analysis import (
    check_annotated_attributes,
    check_call_signatures,
    check_module_attributes,
    check_module_shadowing,
    check_return_annotations,
    check_unused_imports,
    parse,
)

PACKAGE_ROOT = Path(gordo_tpu.__file__).parent

# The ONLY third-party modules allowed to be missing from the image; a
# ModuleNotFoundError for anything else is a typo'd import, not an
# optional-dependency gate.
OPTIONAL_THIRD_PARTY = {"influxdb", "psycopg2", "peewee", "mlflow", "azureml"}


def _iter_module_names():
    # filesystem-derived (tests/utils.py): no imports during collection
    yield from package_module_names()


def test_every_module_imports():
    failures = {}
    for name in _iter_module_names():
        try:
            importlib.import_module(name)
        except ModuleNotFoundError as exc:
            root = (exc.name or "").split(".")[0]
            if root not in OPTIONAL_THIRD_PARTY:
                failures[name] = repr(exc)
        except Exception as exc:  # noqa: BLE001 — collecting all failures
            failures[name] = repr(exc)
    assert not failures, f"modules failed to import: {failures}"


def _importable_modules():
    for name in _iter_module_names():
        try:
            yield name, importlib.import_module(name)
        except Exception:  # noqa: BLE001
            continue  # ANY import failure is test_every_module_imports' job


def test_no_unused_imports():
    problems = {}
    for name, module in _importable_modules():
        path = module.__file__
        if path.endswith("__init__.py"):
            continue  # package surfaces import purely to re-export
        with open(path) as fh:
            source = fh.read()
        found = check_unused_imports(parse(path), source)
        if found:
            problems[name] = found
    assert not problems, f"unused imports: {problems}"


def test_module_attributes_resolve():
    problems = {}
    for name, module in _importable_modules():
        found = check_module_attributes(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"unresolvable module attributes: {problems}"


def test_call_signatures_bind():
    problems = {}
    for name, module in _importable_modules():
        found = check_call_signatures(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"mis-bound calls: {problems}"


def test_no_module_shadowing():
    problems = {}
    for name, module in _importable_modules():
        found = check_module_shadowing(parse(module.__file__))
        if found:
            problems[name] = found
    assert not problems, f"shadowed module imports: {problems}"


def test_annotated_attributes_resolve():
    """The annotation-driven mypy slice: ``param.attr`` must exist on the
    class the parameter is annotated with (reference runs real mypy via
    pytest.ini:8-9; this is the equivalent gate for the typed surface)."""
    problems = {}
    for name, module in _importable_modules():
        found = check_annotated_attributes(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"attribute typos on annotated parameters: {problems}"


def test_return_annotations_consistent():
    problems = {}
    for name, module in _importable_modules():
        found = check_return_annotations(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"return-annotation drift: {problems}"


def test_annotated_attribute_check_catches_typo():
    """The typed-attribute check must catch a misspelled attribute on an
    annotated parameter, including instance attributes assigned in
    __init__ — and must NOT flag real ones."""
    import ast as _ast
    import types as _types

    source = (
        "def good(m: Probe):\n"
        "    return m.field + m.derived\n"
        "def bad(m: Probe):\n"
        "    return m.feild\n"
    )

    class Probe:
        def __init__(self):
            self.field = 1

        @property
        def derived(self):
            return self.field * 2

    fake = _types.ModuleType("fake")
    fake.Probe = Probe
    # the checker only vouches for nominally-typed (project/stdlib) classes;
    # let it vouch for this test module's Probe for the duration
    from static_analysis import _NOMINAL_ROOTS

    root = Probe.__module__.split(".")[0]
    _NOMINAL_ROOTS.add(root)
    try:
        found = check_annotated_attributes(_ast.parse(source), fake)
    finally:
        _NOMINAL_ROOTS.discard(root)
    assert len(found) == 1 and "m.feild" in found[0], found


def test_annotated_attribute_check_respects_nested_scopes():
    """A nested def/lambda parameter shadowing an annotated outer
    parameter is its own scope — accesses inside it must not be checked
    against the outer annotation."""
    import ast as _ast
    import types as _types

    source = (
        "def outer(m: Probe):\n"
        "    def inner(m):\n"
        "        return m.whatever\n"
        "    take = lambda m: m.anything\n"
        "    return inner, take, m.field\n"
    )

    class Probe:
        def __init__(self):
            self.field = 1

    fake = _types.ModuleType("fake")
    fake.Probe = Probe
    from static_analysis import _NOMINAL_ROOTS

    root = Probe.__module__.split(".")[0]
    _NOMINAL_ROOTS.add(root)
    try:
        assert check_annotated_attributes(_ast.parse(source), fake) == []
    finally:
        _NOMINAL_ROOTS.discard(root)


def test_annotated_attribute_check_covers_c_based_classes():
    """NamedTuples and other classes with C-implemented bases stay
    vouchable: getsource failing on `tuple` must not blind the check."""
    from gordo_tpu.data.sensor_tag import SensorTag

    from static_analysis import _known_attrs

    attrs = _known_attrs(SensorTag)
    assert attrs is not None and "name" in attrs and "asset" in attrs


def test_return_annotation_check_resolves_aliases():
    import ast as _ast
    import types as _types
    import typing as _typing

    fake = _types.ModuleType("fake")
    fake.Opt = _typing.Optional
    source = (
        "from typing import Optional as Opt\n"
        "def fine() -> Opt[int]:\n"
        "    return\n"
        "def bad_quoted() -> 'None':\n"
        "    return 3\n"
    )
    found = check_return_annotations(_ast.parse(source), fake)
    assert len(found) == 1 and "bad_quoted" in found[0], found


class _DynamicKnobs:
    """A class assigning knobs via a setattr loop (as TimeSeriesDataset
    did before its knobs became explicit assignments)."""

    def __init__(self, **knobs):
        for key, value in knobs.items():
            setattr(self, key, value)


def test_annotated_attribute_check_skips_dynamic_setattr_classes():
    """A class whose __init__ assigns knobs via a setattr loop has a
    dynamic surface — the checker must not vouch for it rather than
    false-flag the loop-assigned attributes."""
    from static_analysis import _known_attrs

    assert _known_attrs(_DynamicKnobs) is None


def test_annotated_attribute_check_vouches_for_explicit_assignments():
    """TimeSeriesDataset's knobs are explicit ``self.X = ...`` statements;
    the checker can and should vouch for its full surface now."""
    import gordo_tpu.data.datasets as d

    from static_analysis import _known_attrs

    known = _known_attrs(d.TimeSeriesDataset)
    assert known is not None
    assert {"resolution", "row_filter", "interpolation_limit"} <= known


def test_return_annotation_check_allows_attribute_form_any():
    import ast as _ast

    source = (
        "import typing\n"
        "def fine_any() -> typing.Any:\n"
        "    return\n"
        "def fine_any_value() -> typing.Any:\n"
        "    return 3\n"
    )
    assert check_return_annotations(_ast.parse(source)) == []


def test_return_annotation_check_catches_drift():
    import ast as _ast

    source = (
        "import typing\n"
        "def bad_bare() -> bool:\n"
        "    return\n"
        "def bad_value() -> None:\n"
        "    return 3\n"
        "def fine_optional() -> typing.Optional[int]:\n"
        "    return\n"
        "def fine_generator() -> int:\n"
        "    yield 1\n"
        "    return\n"
    )
    found = check_return_annotations(_ast.parse(source))
    assert len(found) == 2, found
    assert any("bad_bare" in p for p in found), found
    assert any("bad_value" in p for p in found), found


def test_shadowing_check_catches_round2_copy_bug():
    """The analyzer must flag the exact bug that broke round 2:
    ``import copy`` + ``from copy import copy`` + ``copy.copy(x)`` — the
    attribute call silently hits the stdlib *function*, not the module."""
    import ast

    source = (
        "import copy\n"
        "from copy import copy\n"
        "def f(x):\n"
        "    return copy.copy(x)\n"
    )
    found = check_module_shadowing(ast.parse(source))
    assert any("shadows 'import copy'" in p for p in found), found
    assert any("copy.copy" in p for p in found), found


def test_metric_registrations_disciplined():
    """Every observability-registry metric registration in the package
    must carry the gordo_ prefix and draw its label names from the
    documented bounded set (docs/observability.md) — raw paths or
    machine names as labels would blow up the series cardinality."""
    from static_analysis import check_metric_registrations

    problems = {}
    for name, module in _importable_modules():
        found = check_metric_registrations(parse(module.__file__))
        if found:
            problems[name] = found
    assert not problems, f"undisciplined metric registrations: {problems}"


def test_metric_names_documented():
    """Every literal metric the package registers through the
    observability registry must appear in docs/observability.md's
    catalogue — registering telemetry nobody can find is how internal
    numbers go unread."""
    from static_analysis import collect_metric_names

    registered: set = set()
    for name, module in _importable_modules():
        registered |= collect_metric_names(parse(module.__file__))
    assert registered, "no metric registrations found — collector broken?"
    docs = (
        Path(gordo_tpu.__file__).parent.parent / "docs" / "observability.md"
    ).read_text()
    undocumented = sorted(m for m in registered if m not in docs)
    assert not undocumented, (
        f"metrics registered in code but missing from "
        f"docs/observability.md: {undocumented}"
    )


def test_metric_registration_check_catches_violations():
    import ast as _ast

    from static_analysis import check_metric_registrations

    source = (
        "def instrument(reg, machine_name):\n"
        "    reg.counter('gordo_good_total', 'd', ('path',)).inc(path='x')\n"
        "    reg.counter('bad_prefix_total', 'd')\n"
        "    reg.counter('gordo_missing_suffix', 'd')\n"
        "    reg.gauge('gordo_ok_gauge', 'd', ('machine',))\n"
        "    reg.histogram('gordo_h_seconds', 'd', labelnames=(machine_name,))\n"
        "    reg.histogram('gordo_h2_seconds', 'd', machine_name)\n"
    )
    found = check_metric_registrations(_ast.parse(source))
    assert len(found) == 5, found
    assert any("bad_prefix_total" in p and "gordo_" in p for p in found)
    assert any("gordo_missing_suffix" in p and "_total" in p for p in found)
    assert any("'machine'" in p and "documented label set" in p for p in found)
    assert any("non-literal label name" in p for p in found)
    assert any("literal tuple/list" in p for p in found)


def test_metric_registration_check_skips_foreign_counters():
    """A call to some other object's .counter() with a non-literal first
    arg is out of scope — the check only vouches for literal names."""
    import ast as _ast

    from static_analysis import check_metric_registrations

    source = (
        "def other(obj, key):\n"
        "    return obj.counter(key) + obj.gauge(12)\n"
    )
    assert check_metric_registrations(_ast.parse(source)) == []


def test_package_byte_compiles():
    assert compileall.compile_dir(
        str(PACKAGE_ROOT), quiet=2, force=False
    ), "byte-compilation failed"


def test_no_module_shadows_stdlib():
    """Top-level module names must not shadow common stdlib modules."""
    import sys

    stdlib = set(sys.stdlib_module_names)
    ours = {
        p.stem
        for p in PACKAGE_ROOT.iterdir()
        if not p.name.startswith("_") and (p.is_dir() or p.suffix == ".py")
    }
    # these would break `import logging`-style absolute imports if run
    # from inside the package directory; keep the namespace clean
    dangerous = ours & stdlib - {"data"}  # 'data' is not a stdlib module
    assert not dangerous, f"package dirs shadow stdlib modules: {dangerous}"


def test_self_method_calls_bind():
    """Instance-method call sites (self.method(...)) must match their own
    class's signatures — the drift class the module-level check can't see
    (a round-4 signature change to FleetTrainer._validation_masks was
    caught only at runtime by a stale caller; this closes that gap)."""
    from static_analysis import check_self_method_calls

    problems = {}
    for name, module in _importable_modules():
        found = check_self_method_calls(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"mis-bound self-method calls: {problems}"


def test_self_method_check_catches_drift():
    import ast as _ast
    import types as _types

    from static_analysis import check_self_method_calls

    source = (
        "class Thing:\n"
        "    def helper(self, a, b):\n"
        "        return a + b\n"
        "    def run(self):\n"
        "        return self.helper(1, 2, 3)\n"
        "    def ok(self):\n"
        "        return self.helper(1, b=2)\n"
    )
    module = _types.ModuleType("fake_drift")
    exec(source, module.__dict__)
    found = check_self_method_calls(_ast.parse(source), module)
    assert len(found) == 1 and "self.helper()" in found[0], found


def test_self_method_check_scopes_nested_classes():
    """A nested class's self.method() calls bind against the NESTED
    class, never the enclosing one (ast.walk would otherwise attribute
    them to the outer class)."""
    import ast as _ast
    import types as _types

    from static_analysis import check_self_method_calls

    source = (
        "class Outer:\n"
        "    def run(self):\n"
        "        return 1\n"
        "    class Inner:\n"
        "        def run(self, x):\n"
        "            return x\n"
        "        def go(self):\n"
        "            return self.run(1)\n"
    )
    module = _types.ModuleType("fake_nested")
    exec(source, module.__dict__)
    # Inner.run(self, x) makes self.run(1) valid; binding it against
    # Outer.run(self) would false-flag 'too many positional arguments'
    assert check_self_method_calls(_ast.parse(source), module) == []


def test_self_method_check_skips_function_local_classes():
    """A function-local class must not bind against a same-named
    module-level class (names only resolve reliably at module scope)."""
    import ast as _ast
    import types as _types

    from static_analysis import check_self_method_calls

    source = (
        "class Cfg:\n"
        "    def load(self, path):\n"
        "        return path\n"
        "def factory():\n"
        "    class Cfg:\n"
        "        def load(self):\n"
        "            return 1\n"
        "        def go(self):\n"
        "            return self.load()\n"
        "    return Cfg\n"
    )
    module = _types.ModuleType("fake_local_cls")
    exec(source, module.__dict__)
    assert check_self_method_calls(_ast.parse(source), module) == []


def test_self_method_check_skips_callbacks_rebinding_self():
    """A nested function whose own parameter is named ``self`` is some
    other object's receiver — its calls must not bind against the
    enclosing class."""
    import ast as _ast
    import types as _types

    from static_analysis import check_self_method_calls

    source = (
        "class Widget:\n"
        "    def draw(self, a, b):\n"
        "        return a + b\n"
        "    def wire(self):\n"
        "        def on_event(self):\n"
        "            return self.draw(1, 2, 3)\n"
        "        take = lambda self: self.draw(1, 2, 3, 4)\n"
        "        return on_event, take, self.draw(1, 2)\n"
    )
    module = _types.ModuleType("fake_callback")
    exec(source, module.__dict__)
    assert check_self_method_calls(_ast.parse(source), module) == []


def test_self_attributes_resolve():
    """self.attr READS across the package must name real attribute
    surface — the typo'd-state-read slice of mypy."""
    from static_analysis import check_self_attributes

    problems = {}
    for name, module in _importable_modules():
        found = check_self_attributes(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"typo'd self-attribute reads: {problems}"


class _Gauge:
    """Real class (readable source) backing the typo-check fixture —
    exec'd classes have no source for _known_attrs to harvest."""

    def __init__(self):
        self.level = 1

    def read(self):
        return self.level


def test_self_attribute_check_catches_typo():
    import ast as _ast
    import types as _types

    from static_analysis import check_self_attributes

    # the ANALYZED source carries the typo; the runtime surface comes
    # from the real _Gauge class above
    source = (
        "class Gauge:\n"
        "    def read(self):\n"
        "        return self.level + self.levl\n"
    )
    module = _types.ModuleType("fake_attr_typo")
    module.Gauge = _Gauge
    found = check_self_attributes(_ast.parse(source), module)
    assert len(found) == 1 and "self.levl" in found[0], found


class _Tally:
    """Fixture for the AugAssign read check: counter is plainly defined,
    and a typo'd aug-assign must read as undefined."""

    def __init__(self):
        self.counter = 0

    def bump(self):
        self.counter += 1
        return self.counter


def test_self_attribute_check_catches_augassign_typo():
    """self.countr += 1 is a READ of an undefined attribute (runtime
    AttributeError) even though its AST ctx is Store — and the typo'd
    name must not be harvested into the class surface either."""
    import ast as _ast
    import types as _types

    from static_analysis import check_self_attributes

    source = (
        "class Tally:\n"
        "    def bump(self):\n"
        "        self.countr += 1\n"
        "        return self.countr\n"
    )
    module = _types.ModuleType("fake_aug_typo")
    module.Tally = _Tally
    found = check_self_attributes(_ast.parse(source), module)
    assert len(found) == 2 and all("self.countr" in f for f in found), found


def test_self_attribute_check_allows_defined_augassign():
    import ast as _ast
    import types as _types

    from static_analysis import check_self_attributes

    source = (
        "class Tally:\n"
        "    def bump(self):\n"
        "        self.counter += 1\n"
        "        return self.counter\n"
    )
    module = _types.ModuleType("fake_aug_ok")
    module.Tally = _Tally
    assert check_self_attributes(_ast.parse(source), module) == []


def test_annotated_param_method_calls_bind():
    from static_analysis import check_annotated_param_method_calls

    problems = {}
    for name, module in _importable_modules():
        found = check_annotated_param_method_calls(parse(module.__file__), module)
        if found:
            problems[name] = found
    assert not problems, f"mis-bound annotated-receiver calls: {problems}"


def test_annotated_param_method_call_check_catches_drift():
    """The cross-module signature-drift net: a call through an annotated
    parameter with the wrong arity / unknown kwarg must be flagged, while
    valid calls, Union fallbacks, rebinding, and splats are skipped."""
    import ast as ast_mod

    from static_analysis import check_annotated_param_method_calls

    src = (
        "import typing\n"
        "def bad_kwarg(m: Probe):\n"
        "    m.ping(1, nope=2)\n"
        "def bad_arity(m: Probe):\n"
        "    m.ping(1, 2, 3)\n"
        "def fine(m: Probe):\n"
        "    m.ping(1, flag=True)\n"
        "def fine_static(m: Probe):\n"
        "    m.of(1)\n"
        "def skipped_rebound(m: Probe):\n"
        "    m = object()\n"
        "    m.ping(1, 2, 3)\n"
        "def skipped_splat(m: Probe, a):\n"
        "    m.ping(*a)\n"
        "def skipped_union_other_member(m: 'typing.Union[Probe, dict]'):\n"
        "    m.update(1, 2, 3)\n"
    )

    class Probe:
        def ping(self, value, flag=False):
            return value

        @staticmethod
        def of(value):
            return value

    import types as types_mod
    import typing

    fake = types_mod.ModuleType("fake_param_calls")
    fake.Probe = Probe
    fake.typing = typing
    Probe.__module__ = "gordo_tpu.fake"  # nominally typed

    found = check_annotated_param_method_calls(ast_mod.parse(src), fake)
    assert len(found) == 2, found
    assert any("bad" in f or "nope" in f for f in found)
    assert all("line 3" in f or "line 5" in f for f in found)


def test_event_names_documented():
    """Every literal event type the package emits through the
    observability event log must appear in docs/observability.md's event
    schema — the sibling of test_metric_names_documented (metrics were
    enforced since PR 2; events were not, so a new lifecycle event could
    ship with undocumented fields)."""
    from static_analysis import collect_event_names

    emitted: set = set()
    for name, module in _importable_modules():
        if name == "gordo_tpu.observability.events":
            continue  # the emitter itself, not an emission site
        emitted |= collect_event_names(parse(module.__file__))
    assert emitted, "no event emissions found — collector broken?"
    docs = (
        Path(gordo_tpu.__file__).parent.parent / "docs" / "observability.md"
    ).read_text()
    undocumented = sorted(e for e in emitted if f"`{e}`" not in docs)
    assert not undocumented, (
        f"event types emitted in code but missing from "
        f"docs/observability.md: {undocumented}"
    )


def test_event_name_collector_reads_both_surfaces():
    import ast as _ast

    from static_analysis import collect_event_names

    source = (
        "def f(emitter, dynamic):\n"
        "    emit_event('build_started', n=1)\n"
        "    emitter.emit('epoch', epoch=0)\n"
        "    emit_event(dynamic)\n"  # non-literal: out of scope
        "    emit_event(event='early_stop')\n"
    )
    names = collect_event_names(_ast.parse(source))
    assert names == {"build_started", "epoch", "early_stop"}


def test_span_names_documented():
    """Every literal span name the package opens (start_span) or records
    (record_span/record_phase) must appear in docs/observability.md's
    span catalogue — the tracing sibling of the metric/event sync
    gates: an attribution surface nobody can look up is how slow-phase
    investigations go back to external re-measurement."""
    from static_analysis import collect_span_names

    opened: set = set()
    for name, module in _importable_modules():
        opened |= collect_span_names(parse(module.__file__))
    assert opened, "no span names found — collector broken?"
    docs = (
        Path(gordo_tpu.__file__).parent.parent / "docs" / "observability.md"
    ).read_text()
    undocumented = sorted(s for s in opened if f"`{s}`" not in docs)
    assert not undocumented, (
        f"span names opened in code but missing from "
        f"docs/observability.md: {undocumented}"
    )


def test_span_name_collector_reads_open_and_record_surfaces():
    import ast as _ast

    from static_analysis import collect_span_names

    source = (
        "def f(tracing, ctx, dynamic):\n"
        "    with start_span('client.request', machine='m'):\n"
        "        pass\n"
        "    with tracing.start_span('server.request'):\n"
        "        pass\n"
        "    tracing.record_span('predict', 0.1)\n"
        "    ctx.record_phase('model_load', 0.1)\n"
        "    tracing.record_span(dynamic, 0.1)\n"  # non-literal: out of scope
    )
    names = collect_span_names(_ast.parse(source))
    assert names == {
        "client.request",
        "server.request",
        "predict",
        "model_load",
    }


def test_knobs_documented():
    """Every knob in the registry must appear in docs/performance.md's
    knob catalogue — the docs half of the knob-discipline gate
    (docs/tuning.md): the lint check guarantees no GORDO_* read exists
    outside the registry, and this guarantees no registry knob is
    missing from the operator-facing table."""
    from gordo_tpu.tuning.knobs import KNOBS

    docs = (
        Path(gordo_tpu.__file__).parent.parent / "docs" / "performance.md"
    ).read_text()
    undocumented = sorted(
        k.name
        for k in KNOBS
        if f"`{k.name}`" not in docs or k.env_var not in docs
    )
    assert not undocumented, (
        f"knobs registered in gordo_tpu/tuning/knobs.py but missing from "
        f"docs/performance.md's knob catalogue: {undocumented}"
    )


def test_knob_registry_well_formed():
    """Registry invariants the rest of the gate leans on: canonical
    names and env vars are unique, every default that is not None sits
    inside its own domain, and no env var is classified on BOTH sides
    of the knob / non-knob line."""
    from gordo_tpu.tuning.knobs import KNOBS, NON_KNOB_ENV_VARS

    names = [k.name for k in KNOBS]
    assert len(names) == len(set(names)), "duplicate knob names"
    env_vars = [k.env_var for k in KNOBS]
    assert len(env_vars) == len(set(env_vars)), "duplicate knob env vars"
    both = set(env_vars) & NON_KNOB_ENV_VARS
    assert not both, f"env vars classified as knob AND non-knob: {both}"
    bad_defaults = [
        k.name
        for k in KNOBS
        if k.default is not None and not k.domain.contains(k.default)
    ]
    assert not bad_defaults, (
        f"knob defaults outside their own domain: {bad_defaults}"
    )


# --------------------------------------------------------------------------
# the JAX- and concurrency-discipline families, package-wide (the
# tier-1 lint gate)
# --------------------------------------------------------------------------

_LINT_ROOT = Path(gordo_tpu.__file__).parent.parent


@pytest.mark.parametrize(
    "check_name",
    [
        "retrace-risk",
        "host-sync",
        "prng-reuse",
        "prng-split-width",
        "traced-branch",
        "donation-safety",
        "span-discipline",
        "knob-discipline",
        "blocking-under-lock",
        "lock-order",
        "unguarded-shared-state",
        "thread-leak",
        "lock-held-across-yield",
    ],
)
def test_jax_discipline_package_wide(check_name):
    """gordo_tpu + tests + benchmarks lint clean for every JAX and
    concurrency check — the mechanical enforcement of what PR 2 (jitted
    closures, PRNG streams) and PR 6 (event I/O under the queue lock)
    fixed by hand. Intentional violations carry inline
    `# lint: disable=` suppressions next to the comment justifying
    them; there is nothing in the baseline."""
    from gordo_tpu.analysis import lint_paths

    targets = [
        _LINT_ROOT / "gordo_tpu",
        _LINT_ROOT / "tests",
        _LINT_ROOT / "benchmarks",
    ]
    result = lint_paths([p for p in targets if p.exists()], select=[check_name])
    rendered = "\n".join(f.render() for f in result.findings)
    assert not result.findings, (
        f"[{check_name}] lint regressions (fix them, suppress with a "
        f"justifying comment, or baseline with a justification):\n{rendered}"
    )


def test_fault_sites_documented():
    """Every chaos site ``parse_spec`` accepts (the ``_KNOWN_SITES``
    vocabulary in robustness/faults.py) must appear in
    docs/robustness.md — a seam the chaos catalogue doesn't list is a
    seam no game day will ever arm."""
    from static_analysis import collect_fault_sites

    sites: set = set()
    for name, module in _importable_modules():
        sites |= collect_fault_sites(parse(module.__file__))
    assert sites, "no _KNOWN_SITES literal found — collector broken?"
    from gordo_tpu.robustness import faults

    assert sites == set(faults._KNOWN_SITES)
    docs = (
        Path(gordo_tpu.__file__).parent.parent / "docs" / "robustness.md"
    ).read_text()
    undocumented = sorted(s for s in sites if f"`{s}" not in docs)
    assert not undocumented, (
        f"fault sites accepted by parse_spec but missing from "
        f"docs/robustness.md: {undocumented}"
    )


def test_fault_site_collector_reads_literal_frozenset():
    import ast as _ast

    from static_analysis import collect_fault_sites

    source = (
        "_KNOWN_SITES = frozenset({'fetch', 'train'})\n"
        "OTHER = frozenset({'not-a-site'})\n"
    )
    assert collect_fault_sites(_ast.parse(source)) == {"fetch", "train"}
    assert collect_fault_sites(_ast.parse("x = 1\n")) == set()
