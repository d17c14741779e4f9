"""
bench.py's no-fallback contract: the headline is a TPU metric, so a run
that finds no chip fails — it never retries on the CPU, never prints a
value, and never rates a device it has no cited peak for.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_tpu_attempt_retries_once_then_exits_nonzero(monkeypatch, capsys):
    """A failed chip attempt gets exactly ONE bounded retry, then the run
    exits non-zero with nothing on stdout: no CPU result, no null-valued
    headline line."""
    calls = []

    def fake_run_child(n_ts, epochs, timeout_s):
        calls.append(timeout_s)
        return None

    monkeypatch.setattr(bench, "run_child", fake_run_child)
    monkeypatch.setattr(bench, "bench_torch_cpu", lambda: 2000.0)
    monkeypatch.setattr(bench, "remaining", lambda: 1400.0)
    with pytest.raises(SystemExit) as exit_info:
        bench.main()

    assert exit_info.value.code not in (0, None)
    assert len(calls) == 2, calls
    # the retry is tighter than the first attempt
    assert calls[1] <= 300.0 < calls[0]
    assert capsys.readouterr().out.strip() == ""


def test_child_without_tpu_exits_nonzero(capsys):
    """The child refuses any platform but ``tpu`` (the suite runs on the
    CPU backend): non-zero exit, no result line."""
    with pytest.raises(SystemExit) as exit_info:
        bench.child_main(64, 1)
    assert exit_info.value.code not in (0, None)
    assert "'cpu'" in str(exit_info.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_compute_mfu_unknown_device_is_an_error():
    """A device_kind without a cited peak raises instead of returning a
    null utilization; the one cited kind computes."""
    with pytest.raises(KeyError, match="no cited bf16 peak"):
        bench.compute_mfu(1000.0, "cpu")
    mfu = bench.compute_mfu(1000.0, "TPU v5 lite")
    assert 0.0 < mfu < 1.0
