"""
Streaming scoring load bench (docs/serving.md "Streaming scoring").

N concurrent streams push k-row updates against a real HTTP server
(windowed LSTM anomaly machines), each stream a closed loop through the
REAL client publisher (`client/streaming.py` — reconnects, Retry-After
honoring and all). Per arm we report per-update p50/p99 and sustained
updates/s; N is swept (``--streams 1,4,16``). ``--mixed-rps`` overlays
the existing open-loop one-shot POST load (`load_test.open_loop`) on
the same server, so the numbers show streams and POSTs coexisting in
one batcher — and the one-shot arm's latency IS the comparison the
device-resident window wins against: an update scores k new rows
without re-shipping (or re-scoring) the accumulated window a one-shot
POST must carry.

Usage::

    python benchmarks/stream_load.py --streams 1,4,16 --duration 10 \\
        --update-rows 5 --window-rows 256 --mixed-rps 2 \\
        --output benchmarks/results_stream_cpu_r12.json
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()

from benchmarks.load_test import open_loop, self_serve  # noqa: E402
from benchmarks.server_latency import summarize_ms  # noqa: E402


def one_stream(
    base_url: str,
    project: str,
    machine: str,
    stop_at: float,
    update_rows: int,
    latencies_ms: list,
    errors: list,
    counters: dict,
):
    """One closed-loop stream: open, push updates until the deadline,
    close. Uses the real publisher, so sheds/resumes are absorbed the
    way a production stream would absorb them."""
    import numpy as np
    import requests

    from gordo_tpu.client.streaming import StreamPublisher

    rng = np.random.default_rng(hash(machine) % (2**32))
    publisher = StreamPublisher(
        session=requests.Session(),
        server_endpoint=f"{base_url}/gordo/v0/{project}",
        machines=[machine],
        n_retries=3,
    )
    try:
        with publisher as stream:
            while time.perf_counter() < stop_at:
                rows = rng.random((update_rows, 4))
                start = time.perf_counter()
                try:
                    stream.send(rows)
                except Exception as exc:  # noqa: BLE001 - recorded
                    errors.append(str(exc))
                    continue
                latencies_ms.append((time.perf_counter() - start) * 1000.0)
    except Exception as exc:  # noqa: BLE001 - open failed terminally
        errors.append(str(exc))
    counters["reconnects"] = counters.get("reconnects", 0) + publisher.reconnects
    counters["sheds"] = counters.get("sheds", 0) + publisher.sheds_honored


def run_stream_arm(
    base_url: str,
    project: str,
    machines: list,
    n_streams: int,
    duration: float,
    update_rows: int,
    window_rows: int,
    mixed_rps: float,
) -> dict:
    """One sweep arm: ``n_streams`` concurrent streams (+ optional
    open-loop one-shot POST load of full ``window_rows`` windows)."""
    import numpy as np

    latencies: list = []
    errors: list = []
    counters: dict = {}
    stop_at = time.perf_counter() + duration
    threads = [
        threading.Thread(
            target=one_stream,
            args=(
                base_url,
                project,
                machines[i % len(machines)],
                stop_at,
                update_rows,
                latencies,
                errors,
                counters,
            ),
        )
        for i in range(n_streams)
    ]

    mixed_result = {}
    mixed_thread = None
    if mixed_rps > 0:
        rng = np.random.default_rng(0)
        body = json.dumps(
            {
                "machines": {
                    machines[0]: rng.random((window_rows, 4)).tolist()
                }
            }
        ).encode()
        url = f"{base_url}/gordo/v0/{project}/prediction/fleet"

        def run_mixed():
            lat, errs, sheds, partials, elapsed = open_loop(
                url, body, mixed_rps, duration, seed=1
            )
            mixed_result.update(
                latency=summarize_ms(lat) if lat else None,
                errors=len(errs),
                sheds=len(sheds),
                achieved_rps=round(len(lat) / elapsed, 2) if lat else 0.0,
            )

        mixed_thread = threading.Thread(target=run_mixed)

    started = time.perf_counter()
    for thread in threads:
        thread.start()
    if mixed_thread is not None:
        mixed_thread.start()
    for thread in threads:
        thread.join()
    if mixed_thread is not None:
        mixed_thread.join()
    elapsed = time.perf_counter() - started
    arm = {
        "n_streams": n_streams,
        "updates_total": len(latencies),
        "updates_per_s": round(len(latencies) / elapsed, 2),
        "rows_per_s": round(len(latencies) * update_rows / elapsed, 2),
        "update_latency": summarize_ms(latencies) if latencies else None,
        "errors": len(errors),
        "reconnects": counters.get("reconnects", 0),
        "sheds_honored": counters.get("sheds", 0),
    }
    if mixed_result:
        arm["mixed_one_shot"] = mixed_result
    return arm


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--project", default="proj")
    parser.add_argument("--machines", type=int, default=4)
    parser.add_argument("--model", default="lstm", choices=["lstm", "hourglass"])
    parser.add_argument(
        "--streams",
        default="1,4,16",
        help="Comma-separated sweep of concurrent stream counts.",
    )
    parser.add_argument("--duration", type=float, default=10.0)
    parser.add_argument(
        "--update-rows", type=int, default=5,
        help="Rows per stream update (the O(update) unit).",
    )
    parser.add_argument(
        "--window-rows", type=int, default=256,
        help="Rows per one-shot POST in the mixed load — the window a "
        "non-streaming monitor would re-ship per score.",
    )
    parser.add_argument(
        "--mixed-rps", type=float, default=2.0,
        help="Open-loop one-shot POST load overlaid on each arm "
        "(0 disables).",
    )
    parser.add_argument("--port", type=int, default=5613)
    parser.add_argument("--batch-wait-ms", type=float, default=5.0)
    parser.add_argument("--output", default=None)
    parser.add_argument(
        "--slo",
        default=None,
        help="SLO spec (YAML/JSON, docs/observability.md) evaluated "
        "against the sweep's measured signals (worst arm p99, "
        "aggregate resume/error rates); the result JSON gains an "
        "'slo' block with pass/fail + per-objective burn rates.",
    )
    args = parser.parse_args()

    sweep = [int(n) for n in str(args.streams).split(",") if n.strip()]
    results = {
        "bench_schema_version": 1,
        "bench": "stream_load",
        "model": args.model,
        "n_machines": args.machines,
        "update_rows": args.update_rows,
        "window_rows": args.window_rows,
        "duration_s": args.duration,
        "mixed_rps": args.mixed_rps,
        "platform": os.environ.get("JAX_PLATFORMS", "default"),
        "arms": [],
    }
    with tempfile.TemporaryDirectory() as tmp:
        base_url = self_serve(
            tmp,
            args.port,
            n_machines=args.machines,
            model=args.model,
            batch_wait_ms=args.batch_wait_ms,
        )
        machines = [f"bench-m{i}" for i in range(args.machines)]
        # warm the dispatch programs so arm 1 isn't a compile bench
        run_stream_arm(
            base_url, args.project, machines, 1, 2.0,
            args.update_rows, args.window_rows, 0.0,
        )
        for n_streams in sweep:
            arm = run_stream_arm(
                base_url,
                args.project,
                machines,
                n_streams,
                args.duration,
                args.update_rows,
                args.window_rows,
                args.mixed_rps,
            )
            results["arms"].append(arm)
            print(json.dumps(arm))

    # the server ran in-process: the phase ledger's stream/server-plane
    # accounting for the whole sweep reads off the shared registry
    from gordo_tpu.observability.attribution import phase_attribution_block

    results["phase_attribution"] = phase_attribution_block()

    # the headline: per-update latency vs re-shipping the whole window
    per_update = [
        arm["update_latency"]["p99_ms"]
        for arm in results["arms"]
        if arm["update_latency"]
    ]
    one_shot = [
        arm["mixed_one_shot"]["latency"]["p99_ms"]
        for arm in results["arms"]
        if arm.get("mixed_one_shot", {}).get("latency")
    ]
    if per_update and one_shot:
        results["p99_per_update_vs_one_shot"] = {
            "stream_update_p99_ms": min(per_update),
            "one_shot_window_p99_ms": min(one_shot),
            "speedup": round(min(one_shot) / max(min(per_update), 1e-9), 2),
        }
    if args.slo:
        # the sweep's worst numbers, so the gate holds at the highest
        # concurrency tried — the plane signal names the spec uses are
        # the same ones the rollup computes (docs/observability.md)
        from gordo_tpu.observability.slo import evaluate_values, load_slo_spec

        spec = load_slo_spec(args.slo)
        arms = results["arms"]
        updates = sum(a["updates_total"] for a in arms)
        errors = sum(a["errors"] for a in arms)
        reconnects = sum(a["reconnects"] for a in arms)
        p99s = [
            a["update_latency"]["p99_ms"]
            for a in arms
            if a.get("update_latency")
        ]
        signals = {
            "predict_p99_ms": max(p99s) if p99s else None,
            "stream_resume_rate": (
                round(reconnects / updates, 4) if updates else None
            ),
            "unstructured_error_rate": (
                round(errors / (updates + errors), 4)
                if updates + errors
                else None
            ),
        }
        results["slo"] = evaluate_values(spec, signals).to_dict()
    print(json.dumps(results, indent=2))
    if args.output:
        with open(args.output, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"wrote {args.output}")


if __name__ == "__main__":
    main()
