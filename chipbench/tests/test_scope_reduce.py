"""The reduction from a profiler trace to device time by scope name and idle
time by span, on a small trace recorded on a TPU v5e from the tree that names
its scopes (``testdata/tiny_lstm_scoped.xplane.pb.gz``: one traced ``fit``
call of lstm50.fit's tiny preset, PR 24) and on hand-made intervals."""

import io
import json
from pathlib import Path

import pytest

from chipbench import loading, scope_reduce, trace_reduce

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
SCOPED = TESTDATA / "tiny_lstm_scoped.xplane.pb.gz"
UNSCOPED = TESTDATA / "tiny_lstm.xplane.pb.gz"  # PR 23's: a program without scopes
TABLE = loading.read_json(loading.HERE / "scopes.json")


@pytest.fixture(scope="module")
def reduced():
    return scope_reduce.reduce(SCOPED, table=TABLE)


def test_decoder_reads_what_profile_data_reads():
    """The wire-format decoder and ``jax.profiler.ProfileData`` see the same
    device operations, name for name, at the same times."""
    planes = scope_reduce.read_planes(SCOPED)
    old = trace_reduce.load(SCOPED, ("fit_call",))
    device = planes["/device:TPU:0"]
    mine = [
        (device["metadata"][ident]["name"], start, end)
        for ident, start, end in device["lines"][trace_reduce.OPS_LINE]
    ]
    theirs = old["devices"]["/device:TPU:0"]["ops"]
    assert len(mine) == len(theirs) > 1000
    assert [name for name, _, _ in mine] == [name for name, _, _ in theirs]
    assert max(abs(a[1] - b[1]) + abs(a[2] - b[2]) for a, b in zip(mine, theirs)) < 1e-8
    # and the path is a stat of the METADATA, which ProfileData does not show
    paths = {meta.get("tf_op", "") for meta in device["metadata"].values()}
    assert any("fleet.gather" in path for path in paths)


def test_scope_sums_add_up_to_the_programs_self_time(reduced):
    """Scope sums + what no scope holds = the epoch program's self time, which
    is what ``trace_reduce`` reads for the program's runs on the device."""
    old = trace_reduce.load(SCOPED, ("fit_call",))
    lo, hi = trace_reduce.window_of(old["spans"], "fit_call")
    assert reduced["window"] == pytest.approx((lo, hi), abs=1e-9)
    program = sum(
        seconds for name, seconds in trace_reduce.program_seconds(old, lo, hi).items()
        if "machine_epoch" in name
    )
    total = reduced["program_self_s"]
    # self time leaves out only the gaps between a program's operations
    assert 0.9 * program < total <= program
    covered = sum(reduced["scopes"].values())
    left = sum(seconds for _, _, seconds in reduced["unscoped"])
    assert covered + left == pytest.approx(total, rel=1e-9)
    assert sum(reduced["by_path"].values()) == pytest.approx(total, rel=1e-9)
    coverage = covered / total
    assert coverage > 0.9
    # what is left carries no scope of the table in its path
    for _, path, _ in reduced["unscoped"]:
        assert scope_reduce.scope_of(path, TABLE) is None


def test_every_scope_is_found_and_the_scans_split(reduced):
    assert set(reduced["scopes"]) == {scope["name"] for scope in TABLE["scopes"]}
    forward, backward = reduced["scopes"]["scan.forward"], reduced["scopes"]["scan.backward"]
    assert forward > 0 and backward > 0
    # the scans are taken out of fleet.loss_grad, not counted twice
    scan_paths = sum(s for p, s in reduced["by_path"].items() if "/scan/" in p)
    assert forward + backward == pytest.approx(scan_paths, rel=1e-9)
    assert all("fleet.loss_grad" in p for p in reduced["by_path"] if "/scan/" in p)


def test_idle_seconds_by_span_add_up_to_the_windows_idle(reduced):
    old = trace_reduce.load(SCOPED, ("fit_call",))
    lo, hi = reduced["window"]
    # ProfileData hands out nanoseconds, the file holds picoseconds: over
    # 20,000 operations the two unions differ by under a microsecond
    idle = (hi - lo) - trace_reduce.device_busy(old, lo, hi)
    assert reduced["idle_s"] == pytest.approx(idle, abs=5e-6)
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(
        reduced["idle_s"], abs=1e-12
    )
    names = [name for name, _, _ in reduced["spans"]]
    # the alias is renamed: the dispatch span lies on the timeline as train-dispatch
    assert names.count("train.dispatch") == 3 and "train-dispatch" not in names
    assert [n for n in names if n != "train.dispatch"] == [
        "train.fit", "train.prepare", "train.first_sync", "train.collect", "train.report",
    ]
    assert set(reduced["idle_by_span"]) <= set(names) | {"no_span"}
    # nearly all of a tiny fit's idle time lies under a phase, not the root
    under_phases = sum(
        s for n, s in reduced["idle_by_span"].items() if n not in ("no_span", "train.fit")
    )
    assert under_phases / idle > 0.9


def test_a_program_without_scopes_reads_as_nothing():
    """The parent's program names no scope: the reduction finds the epoch
    program, no scope, and no span, and the readers return nothing."""
    result = scope_reduce.reduce(UNSCOPED, table=TABLE)
    assert result["scopes"] == {} and result["spans"] == []
    assert result["program_self_s"] > 0
    assert set(result["idle_by_span"]) == {"no_span"}
    ctx = {"scope_reduce": result, "traced": {"calls": [{}], "epochs_per_call": 3}}
    assert scope_reduce.scope_ms_per_epoch(ctx, "fleet.gather") is None
    coverage = loading.metric_reader("layer_metrics", "scope_coverage.fit")
    assert coverage(ctx) is None
    # and a run without a trace at all
    assert scope_reduce.for_run({"trace": None}) is None


def test_readers_on_the_recorded_trace(reduced):
    ctx = {"scope_reduce": reduced, "traced": {"calls": [{}], "epochs_per_call": 3}}
    read = lambda name: loading.metric_reader("layer_metrics", name)(ctx)  # noqa: E731
    scopes = reduced["scopes"]
    assert read("gather_ms.fit") == pytest.approx(1000 * scopes["fleet.gather"] / 3)
    assert read("optimizer_ms.fit") == pytest.approx(
        1000 * (scopes["fleet.optimizer"] + scopes["fleet.guard"]) / 3
    )
    assert read("scan_forward_ms.fit") == pytest.approx(1000 * scopes["scan.forward"] / 3)
    assert read("scan_backward_ms.fit") == pytest.approx(1000 * scopes["scan.backward"] / 3)
    assert 90 < read("scope_coverage.fit") <= 100


def test_program_counter_readers():
    calls = [
        {"telemetry": {"prepare_s": p, "collect_s": c, "report_s": 0.001}}
        for p, c in ((0.010, 1.0), (0.030, 3.0), (0.020, 2.0))
    ]
    ctx = {"window": {"calls": calls}}
    assert loading.metric_reader("layer_metrics", "fit_prepare_ms.fit")(ctx) == pytest.approx(20.0)
    assert loading.metric_reader("layer_metrics", "fit_collect_ms.fit")(ctx) == pytest.approx(2001.0)
    # the parent's trainer books neither
    parent = {"window": {"calls": [{"telemetry": {"n_dispatches": 3}}]}}
    assert loading.metric_reader("layer_metrics", "fit_prepare_ms.fit")(parent) is None
    assert loading.metric_reader("layer_metrics", "fit_collect_ms.fit")(parent) is None


def test_self_seconds_nest_as_top_ops_does():
    events = [
        ("while", 1.0, 5.0), ("a", 1.0, 2.0), ("b", 2.5, 4.5), ("c", 7.0, 9.0),
    ]
    assert scope_reduce.self_seconds(events, 0.0, 10.0) == {
        "while": pytest.approx(1.0), "a": pytest.approx(1.0),
        "b": pytest.approx(2.0), "c": pytest.approx(2.0),
    }
    # an operation that only touches the window counts whole, as there
    assert scope_reduce.self_seconds(events, 8.0, 10.0) == {"c": pytest.approx(2.0)}


def test_scope_of_takes_the_first_entry_that_fits():
    base = "jit(machine_epoch)/vmap(fleet.step)/while/body/closed_call/"
    assert scope_reduce.scope_of(base + "fleet.gather/gather", TABLE) == "fleet.gather"
    forward = base + "fleet.loss_grad/jvp(LSTMNet)/FusedLSTMLayer_2/scan/while/body/mul"
    backward = base + "fleet.loss_grad/transpose(jvp(LSTMNet))/FusedLSTMLayer_2/scan/while"
    assert scope_reduce.scope_of(forward, TABLE) == "scan.forward"
    assert scope_reduce.scope_of(backward, TABLE) == "scan.backward"
    assert scope_reduce.scope_of(base + "fleet.loss_grad/jvp(LSTMNet)/Dense_0/dot_general", TABLE) == "fleet.loss_grad"
    assert scope_reduce.scope_of(base.rstrip("/"), TABLE) == "fleet.step"
    assert scope_reduce.scope_of("jit(machine_epoch)/vmap(fleet.order)/sort", TABLE) == "fleet.order"
    assert scope_reduce.scope_of("", TABLE) is None


def test_idle_goes_to_the_innermost_span_piece_by_piece():
    busy = [(1.0, 5.0), (7.0, 9.0)]
    spans = [("train.fit", 0.5, 10.0), ("train.prepare", 0.5, 0.9), ("train.collect", 6.0, 9.5)]
    idle = scope_reduce.idle_by_span(busy, spans, 0.0, 10.0)
    assert idle == {
        "no_span": pytest.approx(0.5),          # 0-0.5
        "train.prepare": pytest.approx(0.4),    # 0.5-0.9
        "train.fit": pytest.approx(0.1 + 1.0 + 0.5),  # 0.9-1, 5-6, 9.5-10
        "train.collect": pytest.approx(1.0 + 0.5),    # 6-7, 9-9.5
    }
    assert sum(idle.values()) == pytest.approx(4.0)


def test_tables_name_every_scope_and_the_share_under_phases(reduced):
    out = io.StringIO()
    scope_reduce.log_tables(reduced, TABLE, out=out)
    text = out.getvalue()
    for scope in TABLE["scopes"]:
        assert f"scope {scope['name']}" in text
    assert "idle under a span other than the root train.fit" in text
    assert json.dumps(TABLE)  # the table is plain data
