"""
Phase-ledger time attribution tests (docs/observability.md "Time
attribution"): the closed phase vocabulary must account for real served
requests' wall time host-vs-device, the disabled path must be a strict
no-op (call-count pinned, like tracing and fault injection), the opt-in
wall sampler must start/stop cleanly and attribute samples to ledger
phases, and every downstream surface — rollup signals, SLO specs, the
telemetry summary, Chrome-trace export, the cost-seam report — must
read the same ``gordo_phase_seconds`` accounting.
"""

import json
import threading
import time

import numpy as np
import pytest

from gordo_tpu.observability import attribution, sampling
from gordo_tpu.observability.attribution import (
    DEVICE_PHASES,
    HOST_PHASES,
    LEDGER_ENV_VAR,
    NOOP_LEDGER,
    PHASES,
    PLANES,
    PhaseLedger,
    ledger_for,
    measure_overhead,
    phase_attribution_block,
    phase_totals,
    record_current,
    split_host_device,
)

from tests.conftest import GORDO_PROJECT, GORDO_SINGLE_TARGET, SENSORS


# -- the closed vocabulary -------------------------------------------------


def test_phase_vocabulary_is_closed_and_partitioned():
    """Every phase is host or device, never both; the planes are the
    documented four."""
    assert set(PHASES) == HOST_PHASES | DEVICE_PHASES
    assert not (HOST_PHASES & DEVICE_PHASES)
    assert PLANES == ("server", "stream", "router")


def test_phases_documented():
    """The vocabulary is a public contract: every phase name and both
    control signals must appear in docs/observability.md."""
    from pathlib import Path

    import gordo_tpu

    docs = (
        Path(gordo_tpu.__file__).parent.parent / "docs" / "observability.md"
    ).read_text()
    missing = [p for p in PHASES if f"``{p}``" not in docs and f"`{p}`" not in docs]
    assert not missing, f"phases missing from docs/observability.md: {missing}"
    for needle in ("gordo_phase_seconds", "host_fraction", "device_fraction"):
        assert needle in docs


# -- strict no-op discipline (the house rule) ------------------------------


def test_disabled_ledger_is_the_noop_singleton(monkeypatch):
    monkeypatch.setenv(LEDGER_ENV_VAR, "0")
    assert ledger_for("server") is NOOP_LEDGER
    assert ledger_for("stream") is NOOP_LEDGER
    # off-spellings
    for off in ("false", "off", "FALSE"):
        monkeypatch.setenv(LEDGER_ENV_VAR, off)
        assert ledger_for("server") is NOOP_LEDGER
    monkeypatch.delenv(LEDGER_ENV_VAR)
    assert isinstance(ledger_for("server"), PhaseLedger)


def test_disabled_path_call_counts_pinned(monkeypatch):
    """GORDO_PHASE_LEDGER=0: creating a ledger is ONE env lookup and a
    bracket is zero clock reads, zero dict writes — the whole point of
    shipping the ledger always-on is that turning it off buys nothing."""
    monkeypatch.setenv(LEDGER_ENV_VAR, "0")
    ledger = ledger_for("server")

    clock_reads = []
    real_perf_counter = time.perf_counter
    monkeypatch.setattr(
        attribution.time,
        "perf_counter",
        lambda: clock_reads.append(1) or real_perf_counter(),
    )
    with ledger.phase("parse"):
        pass
    with ledger.activate():
        assert record_current("device", 1.0) is False
    ledger.add("transform", 1.0)
    assert ledger.finish() == {}
    assert clock_reads == [], "disabled bracket must not touch the clock"
    assert ledger.phases == {}
    # the reusable no-op context manager: no per-bracket allocation
    assert ledger.phase("parse") is ledger.phase("serialize")


def test_sampler_hook_is_one_global_read_when_inactive(monkeypatch):
    """GORDO_PROFILE_HZ unset: an ENABLED ledger bracket must never call
    into the sampling phase map — the hook is the single module-global
    ``_ACTIVE`` read."""
    monkeypatch.delenv(sampling.PROFILE_HZ_ENV_VAR, raising=False)
    assert sampling.maybe_start_from_env() is None
    assert not sampling.profiler_active()

    def _bomb(*a, **k):  # pragma: no cover - the assertion IS the test
        raise AssertionError("sampling map touched while profiler inactive")

    monkeypatch.setattr(sampling, "set_phase", _bomb)
    monkeypatch.setattr(sampling, "clear_phase", _bomb)
    ledger = PhaseLedger("server")
    with ledger.phase("parse"):
        pass
    assert "parse" in ledger.phases


# -- accounting ------------------------------------------------------------


def test_phase_sum_approximates_wall():
    """Bracketing a workload's seams must account for (nearly) all of
    its wall time — the coverage arithmetic finish() reports."""
    ledger = PhaseLedger("server")
    t0 = time.perf_counter()
    with ledger.phase("parse"):
        time.sleep(0.01)
    with ledger.phase("transform"):
        time.sleep(0.02)
    with ledger.phase("device"):
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    summary = ledger.finish(wall_s=wall)
    assert set(summary["phases"]) == {"parse", "transform", "device"}
    total = summary["host_s"] + summary["device_s"]
    assert total == pytest.approx(sum(ledger.phases.values()))
    assert summary["coverage"] > 0.9
    assert summary["coverage"] <= 1.0
    assert summary["host_fraction"] + summary["device_fraction"] == pytest.approx(1.0)
    # host/device partition follows the vocabulary
    assert summary["device_s"] == pytest.approx(ledger.phases["device"])


def test_nested_brackets_and_add_accumulate():
    ledger = PhaseLedger("stream")
    with ledger.phase("transform"):
        with ledger.phase("transfer"):
            pass
    ledger.add("transform", 0.5)
    ledger.add("transform", 0.25)
    assert ledger.phases["transform"] >= 0.75
    assert "transfer" in ledger.phases


def test_record_current_lands_on_innermost_sink():
    outer, inner = PhaseLedger("server"), PhaseLedger("stream")
    assert record_current("queue", 1.0) is False  # no sink: no-op
    with outer.activate():
        assert record_current("queue", 1.0) is True
        with inner.activate():
            assert record_current("transfer", 2.0) is True
        assert record_current("device", 3.0) is True
    assert outer.phases == {"queue": 1.0, "device": 3.0}
    assert inner.phases == {"transfer": 2.0}


def test_record_current_is_thread_local():
    """A worker thread without its own activation must NOT inherit the
    spawning thread's sink — thread-locality is the double-count guard
    for pool fan-outs (the router brackets the pool wait caller-side;
    the per-call brackets run on pool threads)."""
    ledger = PhaseLedger("router")
    results = []
    with ledger.activate():
        worker = threading.Thread(
            target=lambda: results.append(record_current("device", 1.0))
        )
        worker.start()
        worker.join()
    assert results == [False]
    assert ledger.phases == {}


def test_finish_stamps_span_attributes():
    class FakeSpan:
        recording = True

        def __init__(self):
            self.attrs = {}

        def set_attribute(self, key, value):
            self.attrs[key] = value

    ledger = PhaseLedger("server")
    ledger.add("parse", 0.25)
    ledger.add("device", 0.75)
    span = FakeSpan()
    summary = ledger.finish(span=span, wall_s=1.0)
    assert span.attrs["phase_parse_ms"] == 250.0
    assert span.attrs["phase_device_ms"] == 750.0
    assert span.attrs["host_fraction"] == 0.25
    assert span.attrs["device_fraction"] == 0.75
    assert span.attrs["ledger_coverage"] == 1.0
    assert summary["wall_s"] == 1.0


def test_finish_observes_gordo_phase_seconds():
    before = phase_totals().get(("router", "serialize"), {"count": 0, "sum": 0.0})
    ledger = PhaseLedger("router")
    ledger.add("serialize", 0.125)
    ledger.finish()
    after = phase_totals()[("router", "serialize")]
    assert after["count"] == before["count"] + 1
    assert after["sum"] == pytest.approx(before["sum"] + 0.125)


def test_split_host_device_and_block_shape():
    totals = {
        ("server", "parse"): {"count": 2, "sum": 1.0},
        ("server", "device"): {"count": 2, "sum": 3.0},
        ("stream", "transfer"): {"count": 1, "sum": 1.0},
    }
    split = split_host_device(totals)
    assert split["host_s"] == 1.0
    assert split["device_s"] == 4.0
    assert split["host_fraction"] == 0.2
    assert split["device_fraction"] == 0.8
    block = phase_attribution_block(
        snapshot={
            "gordo_phase_seconds": {
                "series": [
                    {
                        "labels": {"plane": "server", "phase": "parse"},
                        "count": 2,
                        "sum": 1.0,
                    },
                    {
                        "labels": {"plane": "server", "phase": "device"},
                        "count": 2,
                        "sum": 3.0,
                    },
                ]
            }
        }
    )
    assert block["phases"]["server/parse"] == {"count": 2, "sum_s": 1.0}
    assert block["host_fraction"] == 0.25
    # empty snapshot: fractions are None, not a ZeroDivisionError
    empty = phase_attribution_block(snapshot={})
    assert empty["host_fraction"] is None


def test_measure_overhead_reports_both_regimes(monkeypatch):
    monkeypatch.setenv(LEDGER_ENV_VAR, "1")
    result = measure_overhead(samples=200)
    assert set(result) == {
        "samples",
        "disabled_ns_per_phase",
        "enabled_ns_per_phase",
    }
    assert result["disabled_ns_per_phase"] > 0
    assert result["enabled_ns_per_phase"] > 0
    # the mutated env var is restored
    assert attribution.os.environ[LEDGER_ENV_VAR] == "1"


# -- the wall sampler ------------------------------------------------------


def test_sampler_start_stop_and_phase_attribution():
    """Start/stop is clean (no leaked _ACTIVE, no stale phase map), and
    a sampled thread inside a ledger bracket is attributed to its
    (plane, phase) while a bare thread lands in unattributed."""
    sampler = sampling.WallSampler(hz=50)
    release = threading.Event()
    inside = threading.Event()

    def bracketed():
        ledger = PhaseLedger("server")
        with ledger.phase("transform"):
            inside.set()
            release.wait(timeout=10)

    worker = threading.Thread(target=bracketed)
    sampler.start()
    try:
        assert sampling.profiler_active()
        worker.start()
        assert inside.wait(timeout=10)
        for _ in range(5):
            sampler.sample_once()
    finally:
        release.set()
        worker.join()
        sampler.stop()
    assert not sampling.profiler_active()
    assert sampling._PHASES == {}
    report = sampler.report()
    assert report["profile_version"] == sampling.PROFILE_VERSION
    assert report["n_samples"] >= 5
    assert report["per_phase"].get("server/transform", 0) >= 1
    assert sampling.UNATTRIBUTED in report["per_phase"]
    # the bracketed worker's leaf module is this test module
    modules = report["modules_by_phase"]["server/transform"]
    assert any("threading" in m or "test_attribution" in m for m in modules)
    # folded stacks render as `stack count` lines, hottest first
    lines = sampling.folded_lines(report)
    assert lines and all(" " in line for line in lines)
    counts = [int(line.rsplit(" ", 1)[1]) for line in lines]
    assert counts == sorted(counts, reverse=True)
    # stop is idempotent
    sampler.stop()


def test_sampler_flush_and_env_start(tmp_path, monkeypatch):
    out = tmp_path / "profile.json"
    monkeypatch.setenv(sampling.PROFILE_HZ_ENV_VAR, "200")
    monkeypatch.setenv(sampling.PROFILE_OUT_ENV_VAR, str(out))
    monkeypatch.setattr(sampling, "_SAMPLER", None)
    sampler = sampling.maybe_start_from_env()
    try:
        assert sampler is not None
        assert sampling.maybe_start_from_env() is sampler  # idempotent
        assert sampling.active_sampler() is sampler
        sampler.sample_once()
    finally:
        sampler.stop()
        sampler.flush()
        monkeypatch.setattr(sampling, "_SAMPLER", None)
    payload = json.loads(out.read_text())
    assert payload["profile_version"] == sampling.PROFILE_VERSION
    assert payload["hz"] == 200.0
    assert "phase_seconds" in payload


def test_env_start_rejects_garbage(monkeypatch):
    monkeypatch.setattr(sampling, "_SAMPLER", None)
    monkeypatch.setenv(sampling.PROFILE_HZ_ENV_VAR, "not-a-rate")
    assert sampling.maybe_start_from_env() is None
    monkeypatch.setenv(sampling.PROFILE_HZ_ENV_VAR, "0")
    assert sampling.maybe_start_from_env() is None
    assert not sampling.profiler_active()


# -- downstream surfaces ---------------------------------------------------


def _phase_metric(series):
    return {
        "gordo_phase_seconds": {
            "type": "histogram",
            "description": "d",
            "labelnames": ["plane", "phase"],
            "series": series,
        }
    }


def _phase_series(plane, phase, count, total):
    return {
        "labels": {"plane": plane, "phase": phase},
        "count": count,
        "sum": total,
        "buckets": {"+Inf": count},
    }


def test_rollup_host_device_fraction_signals():
    from gordo_tpu.observability.rollup import compute_signals

    previous = {
        "metrics": _phase_metric(
            [
                _phase_series("server", "transform", 10, 1.0),
                _phase_series("server", "device", 10, 1.0),
            ]
        )
    }
    current = {
        "metrics": _phase_metric(
            [
                _phase_series("server", "transform", 20, 4.0),
                _phase_series("server", "device", 20, 2.0),
            ]
        )
    }
    signals = compute_signals(current, previous)
    # window: transform +3s (host), device +1s → host 3/4
    assert signals["host_fraction"] == pytest.approx(0.75)
    assert signals["device_fraction"] == pytest.approx(0.25)
    # no ledger data → None, not 0 (absence is not a healthy signal)
    empty = compute_signals({"metrics": {}})
    assert empty["host_fraction"] is None
    assert empty["device_fraction"] is None


def test_slo_spec_accepts_host_fraction_objective():
    from gordo_tpu.observability.slo import KNOWN_SIGNALS, parse_slo_spec

    assert "host_fraction" in KNOWN_SIGNALS
    assert "device_fraction" in KNOWN_SIGNALS
    spec = parse_slo_spec(
        {
            "objectives": [
                {
                    "signal": "host_fraction",
                    "threshold": 0.85,
                    "window_s": 3600,
                    "budget": 0.1,
                }
            ]
        },
        name="host-seam",
    )
    assert spec.objectives[0].signal == "host_fraction"


def test_example_slo_spec_carries_host_seam_objective():
    import yaml

    from gordo_tpu.observability.slo import parse_slo_spec

    with open("examples/slo_serving.yaml") as fh:
        spec = parse_slo_spec(yaml.safe_load(fh), name="serving")
    assert any(o.signal == "host_fraction" for o in spec.objectives)


def test_summarize_phases_section(tmp_path):
    """telemetry summarize v4: persisted plane rollups carrying
    gordo_phase_seconds surface as the summary's phases section."""
    from gordo_tpu.observability.report import (
        SUMMARY_SCHEMA_VERSION,
        summarize_directory,
        summary_payload,
    )

    assert SUMMARY_SCHEMA_VERSION == 4
    line = {
        "ts": "2026-01-01T00:00:00+00:00",
        "snapshot_version": 1,
        "members": {},
        "metrics": _phase_metric(
            [
                _phase_series("server", "serialize", 10, 3.0),
                _phase_series("server", "device", 10, 1.0),
            ]
        ),
    }
    (tmp_path / "plane.jsonl").write_text(json.dumps(line) + "\n")
    payload = summary_payload(tmp_path)
    phases = payload["phases"]
    assert phases["phases"]["server/serialize"] == {"count": 10, "sum_s": 3.0}
    assert phases["host_fraction"] == pytest.approx(0.75)
    text = summarize_directory(tmp_path)
    assert "Time attribution" in text
    assert "server/serialize" in text
    # no ledger data → no phases section at all
    empty = tmp_path / "empty"
    empty.mkdir()
    assert summary_payload(empty)["phases"] == {}


def test_chrome_trace_phase_tracks():
    """Phase spans land on the dedicated host/device tracks with their
    thread_name metadata; ordinary spans keep per-trace synthetic tids."""
    from gordo_tpu.observability.tracing import spans_to_chrome_trace

    base = {
        "trace_id": "t1",
        "span_id": "s",
        "start_unix_ms": 1000.0,
        "pid": 42,
    }
    records = [
        {**base, "name": "server.request", "span_id": "s1", "duration_ms": 10.0},
        {**base, "name": "serialize", "span_id": "s2", "duration_ms": 4.0},
        {**base, "name": "device", "span_id": "s3", "duration_ms": 2.0},
    ]
    doc = spans_to_chrome_trace(records)
    by_name = {
        e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"
    }
    assert by_name["serialize"]["tid"] == 1_000_000
    assert by_name["device"]["tid"] == 1_000_001
    assert by_name["serialize"]["cat"] == "gordo-phase"
    assert by_name["server.request"]["tid"] not in (1_000_000, 1_000_001)
    labels = {
        (e["pid"], e["tid"]): e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert labels[(42, 1_000_000)] == "host phases"
    assert labels[(42, 1_000_001)] == "device phases"


def test_profile_report_names_the_cost_seam():
    """The merged report ranks phases by ledger seconds and names each
    host phase's hottest modules — the transform seam reads as pandas,
    not as an anonymous host blob."""
    from gordo_tpu.cli.profile import render_report

    payload = {
        "profile_version": 1,
        "hz": 97.0,
        "n_samples": 100,
        "duration_s": 2.0,
        "per_phase": {
            "server/transform": 60,
            "server/device": 30,
            "-/unattributed": 10,
        },
        "modules_by_phase": {
            "server/transform": {"pandas.core.frame": 40, "numpy": 20},
            "server/device": {"jaxlib.xla_client": 30},
        },
        "folded": {"a:f;b:g": 3},
        "phase_seconds": {
            "server/transform": {"count": 10, "sum": 6.0},
            "server/device": {"count": 10, "sum": 4.0},
        },
    }
    text = render_report(payload, top=2)
    assert "server/transform" in text
    assert "pandas.core.frame: 40" in text
    # ledger table ranks transform (6s) above device (4s)
    assert text.index("server/transform") < text.index("server/device")
    assert "host 6.000s (60.0%)" in text
    # device phases never get a module ranking (samples there are the
    # host thread blocked on the sync point, not device cost)
    assert "jaxlib.xla_client" not in text


def test_profile_cli_rejects_non_profile_json(tmp_path):
    import click
    from gordo_tpu.cli.profile import _load_profile

    bogus = tmp_path / "not_a_profile.json"
    bogus.write_text("{}")
    with pytest.raises(click.ClickException):
        _load_profile(str(bogus))


# -- the served plane, end to end ------------------------------------------


@pytest.fixture
def batched_app_client(model_collection_env):
    from werkzeug.test import Client

    from gordo_tpu.server import build_app
    from gordo_tpu.server import utils as server_utils

    server_utils.clear_caches()
    return Client(build_app({"BATCH_WAIT_MS": 2.0}))


def _timing_map(response) -> dict:
    out = {}
    for part in (response.headers.get("Server-Timing") or "").split(","):
        part = part.strip()
        if ";dur=" in part:
            name, _, dur = part.partition(";dur=")
            out[name] = float(dur)
    return out


def test_batched_and_streamed_requests_account_their_wall(
    batched_app_client,
):
    """Mixed serving: a BATCHED fleet POST and a STREAMED update must
    both leave ledger phases covering (nearly) all of their measured
    wall time — the always-on accounting acceptance, exercised through
    the real app against the real trained artifact."""
    rng = np.random.default_rng(3)
    rows = rng.random((20, len(SENSORS))).tolist()

    before = phase_totals()
    resp = batched_app_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/prediction/fleet",
        json={"machines": {GORDO_SINGLE_TARGET: {c: r for c, r in zip(SENSORS, np.asarray(rows).T.tolist())}}},
    )
    assert resp.status_code == 200, resp.get_data()
    timings = _timing_map(resp)
    ledger_ms = sum(timings.get(p, 0.0) for p in PHASES)
    assert timings["total"] > 0
    # batched path: queue + the drainer's collected dispatch phases
    assert timings.get("queue", 0.0) > 0
    assert ledger_ms / timings["total"] > 0.7
    after = phase_totals()
    server_counts = sum(
        state["count"]
        for (plane, _), state in after.items()
        if plane == "server"
    ) - sum(
        state["count"]
        for (plane, _), state in before.items()
        if plane == "server"
    )
    assert server_counts >= 4  # parse/queue/postprocess/serialize at least

    # streamed update: the stream-plane ledger nests inside the server
    # request's and both account
    resp = batched_app_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/stream/open",
        json={"machines": [GORDO_SINGLE_TARGET]},
    )
    assert resp.status_code == 201, resp.get_data()
    sid = json.loads(resp.get_data())["session"]
    resp = batched_app_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/stream/{sid}/update",
        json={
            "updates": {
                GORDO_SINGLE_TARGET: {"rows": rows, "seq": 0}
            }
        },
    )
    assert resp.status_code == 200, resp.get_data()
    timings = _timing_map(resp)
    ledger_ms = sum(timings.get(p, 0.0) for p in PHASES)
    assert ledger_ms / timings["total"] > 0.7
    stream_totals = phase_totals()
    assert any(
        plane == "stream" and state["count"] > 0
        for (plane, _), state in stream_totals.items()
        for state in [state]
    )


def test_bench_attribution_artifact_shape():
    """The committed bench artifact carries the acceptance evidence:
    per-arm ledger coverage with a >=0.95 median, the host/device
    split, and the overhead numbers."""
    with open("benchmarks/results_attribution_cpu_r20.json") as fh:
        doc = json.load(fh)
    assert doc["bench"] == "attribution"
    for arm in ("single", "fleet"):
        coverage = doc[arm]["ledger_coverage"]
        assert coverage["p50"] >= 0.95, (arm, coverage)
    assert doc["phase_attribution"]["host_fraction"] is not None
    assert doc["ledger_overhead"]["disabled_ns_per_phase"] < 10_000
    assert "top_modules_by_phase" in doc["sampler"]
