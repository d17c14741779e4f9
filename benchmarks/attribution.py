"""
Phase-ledger attribution bench (``make bench-attribution``;
docs/observability.md "Time attribution").

Self-serves a real server (the load_test harness), drives the
single-machine and batched fleet endpoints closed-loop with the wall
profiler sampling in-process, and measures what the always-on phase
ledger actually delivers:

- **coverage**: per request, the ledger phases' share of the request's
  own ``Server-Timing: total`` wall (the >=95% accounting claim,
  checked request-by-request off the wire, not from an average);
- **phase_attribution**: the ``gordo_phase_seconds`` host/device split
  for the whole run (the block consolidate.py folds into
  trajectory.json as ``host_fraction``);
- **ledger_overhead**: per-bracket cost, disabled vs enabled — the
  always-on claim as a number, next to ``tracing_overhead``;
- **sampler**: the wall profiler's per-phase sample counts and each
  host phase's hottest modules — the cost-seam report's raw material.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/attribution.py --duration 8 \\
        --output benchmarks/results_attribution_cpu_r20.json
"""

import argparse
import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()

from benchmarks.load_test import self_serve  # noqa: E402
from benchmarks.server_latency import summarize_ms  # noqa: E402

_TIMING_RE = re.compile(r"([\w-]+);dur=([0-9.eE+-]+)")


def _coverage_of(server_timing: str, phases) -> float:
    """Ledger-phase share of the request's total wall, parsed from one
    Server-Timing header (durs are milliseconds; the legacy
    request_walltime_s entry is skipped by unit)."""
    durs = {
        name: float(value)
        for name, value in _TIMING_RE.findall(server_timing or "")
        if name != "request_walltime_s"
    }
    total = durs.get("total")
    if not total:
        return 0.0
    return sum(durs.get(p, 0.0) for p in phases) / total


def _drive(url: str, body: bytes, duration: float, users: int, phases):
    """Closed-loop drive; returns (latencies_ms, coverages, errors)."""
    latencies: list = []
    coverages: list = []
    errors: list = []

    def worker(stop_at: float):
        while time.perf_counter() < stop_at:
            request = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"}
            )
            start = time.perf_counter()
            try:
                with urllib.request.urlopen(request, timeout=30) as resp:
                    resp.read()
                    timing = resp.headers.get("Server-Timing", "")
            except Exception as exc:  # noqa: BLE001 - recorded
                errors.append(str(exc))
                continue
            latencies.append((time.perf_counter() - start) * 1000.0)
            coverages.append(_coverage_of(timing, phases))

    stop_at = time.perf_counter() + duration
    threads = [
        threading.Thread(target=worker, args=(stop_at,)) for _ in range(users)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return latencies, coverages, errors


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--project", default="proj")
    parser.add_argument("--machines", type=int, default=4)
    parser.add_argument("--samples", type=int, default=100)
    parser.add_argument("--users", type=int, default=4)
    parser.add_argument("--duration", type=float, default=8.0)
    parser.add_argument("--port", type=int, default=5617)
    parser.add_argument("--batch-wait-ms", type=float, default=5.0)
    parser.add_argument(
        "--profile-hz",
        type=float,
        default=97.0,
        help="In-process wall-profiler rate (odd rate: avoids aliasing "
        "with millisecond-periodic work).",
    )
    parser.add_argument("--output", default=None)
    args = parser.parse_args()

    import numpy as np

    from gordo_tpu.observability import attribution, sampling
    from gordo_tpu.observability.tracing import measure_overhead

    sampler = sampling.WallSampler(args.profile_hz)
    sampler.start()

    out = {
        "bench_schema_version": 1,
        "bench": "attribution",
        "platform": os.environ.get("JAX_PLATFORMS", "default"),
        "n_machines": args.machines,
        "samples": args.samples,
        "users": args.users,
        "duration_s": args.duration,
        "batch_wait_ms": args.batch_wait_ms,
        "profile_hz": args.profile_hz,
    }
    with tempfile.TemporaryDirectory() as tmp:
        base_url = self_serve(
            tmp,
            args.port,
            n_machines=args.machines,
            model="hourglass",
            batch_wait_ms=args.batch_wait_ms,
        )
        rows = np.random.default_rng(0).random((args.samples, 4)).tolist()
        names = [f"bench-m{i}" for i in range(args.machines)]
        arms = {
            "single": (
                f"{base_url}/gordo/v0/{args.project}/{names[0]}/prediction",
                json.dumps({"X": rows}).encode(),
            ),
            "fleet": (
                f"{base_url}/gordo/v0/{args.project}/prediction/fleet",
                json.dumps({"machines": {n: rows for n in names}}).encode(),
            ),
        }
        for arm_name, (url, body) in arms.items():
            # warmup: the first request pays model load + compile
            urllib.request.urlopen(
                urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"},
                ),
                timeout=120,
            ).read()
            latencies, coverages, errors = _drive(
                url, body, args.duration, args.users, attribution.PHASES
            )
            coverages.sort()
            out[arm_name] = {
                "requests": len(latencies),
                "errors": len(errors),
                **(summarize_ms(latencies) if latencies else {}),
                "ledger_coverage": {
                    "min": round(coverages[0], 4) if coverages else None,
                    "p50": (
                        round(coverages[len(coverages) // 2], 4)
                        if coverages
                        else None
                    ),
                    "mean": (
                        round(sum(coverages) / len(coverages), 4)
                        if coverages
                        else None
                    ),
                },
            }

    sampler.stop()
    profile = sampler.report()
    out["phase_attribution"] = attribution.phase_attribution_block()
    out["ledger_overhead"] = attribution.measure_overhead(samples=2000)
    out["tracing_overhead"] = measure_overhead(samples=1000)
    out["sampler"] = {
        "n_samples": profile["n_samples"],
        "per_phase": profile["per_phase"],
        # each HOST phase's hottest modules: the cost-seam ranking —
        # the transform seam should name pandas/sklearn/numpy here
        "top_modules_by_phase": {
            key: dict(
                sorted(mods.items(), key=lambda kv: -kv[1])[:5]
            )
            for key, mods in profile["modules_by_phase"].items()
            if key.rpartition("/")[2] not in attribution.DEVICE_PHASES
        },
    }
    print(json.dumps(out, indent=2))
    if args.output:
        with open(args.output, "w") as fh:
            json.dump(out, fh, indent=2)
            fh.write("\n")
        print(f"wrote {args.output}", file=sys.stderr)


if __name__ == "__main__":
    main()
