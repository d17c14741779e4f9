"""
Lifecycle-cycle benchmark (docs/lifecycle.md): what continuous
operation actually buys and costs.

Measures, on one JSON line (the bench-output contract):

1. **Refit-subset rate vs full rebuild** — build an N-machine anomaly
   fleet (the baseline a naive "models went stale" response pays), then
   drift K machines (the ``drift:shift`` chaos seam) and run one
   ``lifecycle tick``: the warm-start refit rebuilds only the drifted
   subset, and the models/hour of subset-refit vs full-rebuild is the
   headline ratio.
2. **Serving p99 interference** — serve the collection in-process (the
   one-device deployment shape: handler threads + refit sharing a chip)
   and drive Poisson open-loop traffic (``load_test.open_loop``) twice:
   once quiescent, once with a tick running concurrently. The p99
   delta is the cost of refitting in the serving process — the number
   that decides whether refits need their own replica.

CPU-runnable end to end (JAX_PLATFORMS=cpu); on TPU the same script
measures the real contention.
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

from benchmarks.load_test import open_loop  # noqa: E402

SENSORS = [f"tag-{i}" for i in range(4)]


def _machine(name, epochs):
    from gordo_tpu.machine import Machine

    return Machine(
        name=name,
        project_name="lifecycle-bench",
        model={
            "gordo_tpu.models.anomaly.DiffBasedAnomalyDetector": {
                "base_estimator": {
                    "sklearn.pipeline.Pipeline": {
                        "steps": [
                            "sklearn.preprocessing.MinMaxScaler",
                            {
                                "gordo_tpu.models.AutoEncoder": {
                                    "kind": "feedforward_hourglass",
                                    "epochs": epochs,
                                    "batch_size": 32,
                                }
                            },
                        ]
                    }
                }
            }
        },
        dataset={
            "type": "RandomDataset",
            "train_start_date": "2019-01-01T00:00:00+00:00",
            "train_end_date": "2019-01-02T00:00:00+00:00",
            "tags": SENSORS,
            "target_tag_list": SENSORS,
            "asset": "gra",
        },
    )


def build_collection(models_dir, n_machines, epochs):
    """Full fleet build into <models_dir>/<rev> + latest symlink;
    returns (wall_s, revision)."""
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    revision = str(int(time.time() * 1000))
    start = time.perf_counter()
    FleetModelBuilder(
        [_machine(f"bench-m{i}", epochs) for i in range(n_machines)],
        fetch_backoff=lambda attempt: 0.0,
    ).build(output_dir_base=os.path.join(models_dir, revision))
    wall = time.perf_counter() - start
    os.symlink(revision, os.path.join(models_dir, "latest"))
    return wall, revision


def run_tick(models_dir, drifted):
    """One lifecycle cycle with the given machines drifted; returns
    (wall_s, TickResult)."""
    from gordo_tpu.lifecycle import LifecycleConfig, LifecycleManager
    from gordo_tpu.robustness import faults

    os.environ["GORDO_FAULT_INJECT"] = ";".join(
        f"drift:shift:{name}" for name in drifted
    )
    faults.reset()
    try:
        manager = LifecycleManager(
            os.path.join(models_dir, "latest"),
            # explicit criteria: noise models hover near ratio 1 by
            # construction; the injected shift scores ~30x threshold
            config=LifecycleConfig(ratio_threshold=2.0,
                                   exceedance_threshold=0.9),
        )
        start = time.perf_counter()
        result = manager.tick()
        return time.perf_counter() - start, result
    finally:
        os.environ.pop("GORDO_FAULT_INJECT", None)
        faults.reset()


def serve(models_dir, port):
    """The collection behind a threaded in-process server (the
    load_test self-serve shape, pointed at the latest symlink)."""
    from werkzeug.serving import make_server

    from gordo_tpu.server import build_app

    os.environ["MODEL_COLLECTION_DIR"] = os.path.join(models_dir, "latest")
    server = make_server("127.0.0.1", port, build_app(), threaded=True)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return f"http://127.0.0.1:{port}"


def _p(latencies, q):
    if not latencies:
        return None
    ordered = sorted(latencies)
    return round(ordered[min(len(ordered) - 1, int(q * len(ordered)))], 2)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--machines", type=int, default=8)
    parser.add_argument(
        "--drifted", type=int, default=2,
        help="Machines the chaos seam drifts (the refit subset size)",
    )
    parser.add_argument("--epochs", type=int, default=4)
    parser.add_argument("--rps", type=float, default=20.0)
    parser.add_argument("--duration", type=float, default=10.0)
    parser.add_argument("--port", type=int, default=5598)
    parser.add_argument("--samples", type=int, default=100)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--skip-serving", action="store_true",
        help="Only the refit-vs-rebuild rates (no interference phase)",
    )
    args = parser.parse_args()
    if not 0 < args.drifted <= args.machines:
        parser.error("--drifted must be in [1, --machines]")

    tmp = tempfile.mkdtemp(prefix="lifecycle-bench-")
    models_dir = os.path.join(tmp, "models")
    os.makedirs(models_dir)

    full_wall, base_revision = build_collection(
        models_dir, args.machines, args.epochs
    )
    drifted = [f"bench-m{i}" for i in range(args.drifted)]
    refit_wall, result = run_tick(models_dir, drifted)
    assert result.drifted == sorted(drifted), (
        f"expected {sorted(drifted)} to drift, got {result.drifted}"
    )

    out = {
        "bench_schema_version": 1,
        "bench": "lifecycle_cycle",
        "n_machines": args.machines,
        "n_drifted": args.drifted,
        "epochs": args.epochs,
        "base_revision": base_revision,
        "full_build_wall_s": round(full_wall, 2),
        "full_build_models_per_hour": round(args.machines / full_wall * 3600, 1),
        "refit_tick_wall_s": round(refit_wall, 2),
        # the tick's rate over the machines it actually rebuilt — the
        # comparable models/hour for "keep the fleet fresh"
        "refit_models_per_hour": round(args.drifted / refit_wall * 3600, 1),
        "refit_speedup_vs_full_rebuild": round(full_wall / refit_wall, 2),
        "promoted": result.promoted,
        "revision": result.revision,
    }

    if not args.skip_serving:
        import numpy as np
        import pandas as pd

        base_url = serve(models_dir, args.port)
        machine = f"bench-m{args.machines - 1}"  # never drifted: stable URL
        url = (
            f"{base_url}/gordo/v0/lifecycle-bench/{machine}/anomaly/prediction"
        )
        index = pd.date_range(
            "2019-01-01", periods=args.samples, freq="10min", tz="UTC"
        )
        frame = pd.DataFrame(
            np.random.default_rng(args.seed).random(
                (args.samples, len(SENSORS))
            ),
            columns=SENSORS,
            index=index,
        )
        from gordo_tpu.server import utils as server_utils

        body = json.dumps(
            {
                "X": server_utils.dataframe_to_dict(frame),
                "y": server_utils.dataframe_to_dict(frame),
            }
        ).encode()

        # warm the serving path, then the quiescent baseline
        open_loop(url, body, rps=5.0, duration=2.0, seed=args.seed)
        quiet, quiet_err, _, _, quiet_elapsed = open_loop(
            url, body, rps=args.rps, duration=args.duration, seed=args.seed
        )

        # the same offered load while a tick refits IN-PROCESS
        tick_done = {}

        def background_tick():
            wall, tick = run_tick(models_dir, drifted)
            tick_done.update(wall_s=wall, revision=tick.revision)

        refit_thread = threading.Thread(target=background_tick)
        refit_thread.start()
        busy, busy_err, _, _, busy_elapsed = open_loop(
            url, body, rps=args.rps, duration=args.duration,
            seed=args.seed + 1,
        )
        refit_thread.join()

        out["serving"] = {
            "rps_offered": args.rps,
            "quiescent": {
                "p50_ms": _p(quiet, 0.50),
                "p99_ms": _p(quiet, 0.99),
                "achieved_rps": round(len(quiet) / quiet_elapsed, 1),
                "errors": len(quiet_err),
            },
            "during_refit": {
                "p50_ms": _p(busy, 0.50),
                "p99_ms": _p(busy, 0.99),
                "achieved_rps": round(len(busy) / busy_elapsed, 1),
                "errors": len(busy_err),
                "refit_wall_s": round(tick_done.get("wall_s", 0.0), 2),
                "refit_revision": tick_done.get("revision"),
            },
        }
        p99_quiet, p99_busy = _p(quiet, 0.99), _p(busy, 0.99)
        if p99_quiet and p99_busy:
            out["serving"]["p99_interference_ratio"] = round(
                p99_busy / p99_quiet, 2
            )

    print(json.dumps(out))


if __name__ == "__main__":
    main()
