"""The reduction from a profiler trace to numbers, on a small trace recorded
on a TPU v5e (``testdata/tiny_lstm.xplane.pb.gz``: one traced ``fit`` call of
lstm50.fit's tiny preset, PR 23) and on hand-made intervals."""

from pathlib import Path

import pytest

from chipbench import trace_reduce

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "tiny_lstm.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(RECORDED, ("fit_call",))


def hand_made():
    """One device: a loop op of 4 s enclosing two children, then a lone op;
    gaps of 1 s (under span a), 2 s (under the inner span b) and 1 s."""
    ops = [
        ("%while.1 = f32[2]{0} while(...)", 1.0, 5.0),
        ("%fusion.1 = f32[8,128]{1,0} fusion(...), kind=kLoop, calls=%c", 1.0, 2.0),
        ("%fusion.2 = f32[8,128]{1,0} fusion(...), kind=kOutput, calls=%d", 2.5, 4.5),
        ("%copy.3 = f32[4]{0} copy(...)", 7.0, 9.0),
    ]
    modules = [("jit_machine_epoch(1)", 1.0, 5.0), ("jit_other(2)", 7.0, 9.0)]
    spans = [("a", 0.0, 10.0), ("b", 5.0, 7.0)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "spans": spans}


def test_union_merges_and_clips():
    merged = trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 6)], lo=0.5, hi=5.5)
    assert merged == [(0.5, 3), (5, 5.5)]


def test_busy_idle_and_programs_on_hand_made_trace():
    trace = hand_made()
    assert trace_reduce.window_of(trace["spans"], "a") == (0.0, 10.0)
    assert trace_reduce.device_busy(trace, 0.0, 10.0) == pytest.approx(6.0)
    # clipped to a window that cuts the loop op
    assert trace_reduce.device_busy(trace, 4.0, 8.0) == pytest.approx(2.0)
    programs = trace_reduce.program_seconds(trace, 0.0, 10.0)
    assert programs == {"jit_machine_epoch(1)": 4.0, "jit_other(2)": 2.0}


def test_top_ops_count_self_time():
    ranked = dict(trace_reduce.top_ops(hand_made(), 0.0, 10.0))
    assert ranked["fusion.2 f32[8,128] kOutput"] == pytest.approx(2.0)
    assert ranked["copy.3 f32[4]"] == pytest.approx(2.0)
    assert ranked["fusion.1 f32[8,128] kLoop"] == pytest.approx(1.0)
    # the loop keeps only what its children leave: 4 - 1 - 2
    assert ranked["while.1 f32[2]"] == pytest.approx(1.0)


def test_idle_gaps_go_to_the_span_that_covers_them():
    gaps = dict(trace_reduce.idle_gaps(hand_made(), 0.0, 10.0))
    # 0-1 and 9-10 lie under a alone; 5-7 under both, and the inner wins
    assert gaps == {"a": pytest.approx(2.0), "b": pytest.approx(2.0)}


def test_recorded_trace_has_device_and_span(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    device = recorded["devices"]["/device:TPU:0"]
    assert len(device["ops"]) > 1000 and len(device["modules"]) > 10
    assert [name for name, _, _ in recorded["spans"]] == ["fit_call"]


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    lo, hi = trace_reduce.window_of(recorded["spans"], "fit_call")
    assert hi - lo == pytest.approx(0.060743, abs=1e-6)
    busy = trace_reduce.device_busy(recorded, lo, hi)
    assert busy == pytest.approx(0.0019684, abs=1e-6)
    assert 0.96 < 1 - busy / (hi - lo) < 0.97  # a tiny model leaves the chip idle
    programs = trace_reduce.program_seconds(recorded, lo, hi)
    epoch = {n: s for n, s in programs.items() if "machine_epoch" in n}
    assert len(epoch) == 1
    # three epochs of the tiny preset, nearly all of the busy time
    assert sum(epoch.values()) == pytest.approx(0.0019471, abs=1e-6)
    assert sum(epoch.values()) <= busy
    ops = trace_reduce.top_ops(recorded, lo, hi)
    assert len(ops) == 10 and ops[0][0] == "fusion.347 bf16[768,6] kCustom"
    assert sum(s for _, s in ops) <= busy
    gaps = trace_reduce.idle_gaps(recorded, lo, hi)
    assert gaps[0][0] == "fit_call"
    assert gaps[0][1] == pytest.approx((hi - lo) - busy, abs=1e-6)


def test_short_op_name():
    line = ("%fusion.345 = bf16[262144,50]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,16384,50]"
            "{2,1,0} %get-tuple-element.7159), kind=kCustom, calls=%fused_computation.1")
    assert trace_reduce.short_op_name(line) == "fusion.345 bf16[262144,50] kCustom"
    assert trace_reduce.short_op_name("no equals sign " * 10) == ("no equals sign " * 10)[:80]
