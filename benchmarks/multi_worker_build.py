"""
Multi-worker ledger-build benchmark (docs/robustness.md "Multi-worker
builds"): what sharding a fleet build across N worker processes buys,
and what a worker death costs.

Measures, on one JSON line (the bench-output contract):

1. **Models/hour at 1/2/4 workers** — the same B-bucket fleet built
   through ``build-fleet --workers N``: each worker is its own JAX
   process claiming buckets off the shared ledger, so the scaling
   headroom is (buckets ÷ workers) × per-process compile overlap.
2. **Goodput retained under a mid-run kill** — the N-worker build
   re-run with ``worker:die:train@worker:0``: worker 0 is SIGKILL'd
   mid-train, its unit is lease-stolen and rebuilt, and the headline is
   killed-run models/hour as a fraction of the clean N-worker run (the
   "recoverable interruptions dominate fleet goodput" number from the
   ML-goodput paper, PAPERS.md arXiv:2502.06982).

CPU only (JAX_PLATFORMS=cpu): N local workers cannot share one chip,
and ``build-fleet --workers N>1`` refuses to start anywhere else.
Worker counts that exceed the host (or the bucket count) just shard
shallower.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.robustness import faults  # noqa: E402

SENSORS = [["Tag 1", None], ["Tag 2", None], ["Tag 3", None]]


def _config(name: str, epochs: int) -> dict:
    return {
        "name": name,
        "project_name": "mw-bench",
        "model": {
            "gordo_tpu.models.AutoEncoder": {
                "kind": "feedforward_hourglass",
                "epochs": epochs,
                "batch_size": 32,
            }
        },
        "dataset": {
            "type": "RandomDataset",
            "train_start_date": "2017-12-25 06:00:00Z",
            "train_end_date": "2017-12-27 06:00:00Z",
            "tags": SENSORS,
        },
    }


def _fleet_configs(n_machines: int, n_buckets: int) -> list:
    """``n_buckets`` distinct epoch counts so the ledger has that many
    units to shard; machines round-robin across them."""
    return [
        _config(f"mw-m-{i:03d}", epochs=1 + (i % n_buckets))
        for i in range(n_machines)
    ]


def _run_build(
    configs: list,
    workers: int,
    *,
    lease_ttl: float,
    kill_worker: bool = False,
) -> dict:
    out_dir = tempfile.mkdtemp(prefix=f"mw-bench-{workers}w-")
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in (faults.FAULT_INJECT_ENV_VAR, faults.WORKER_ID_ENV_VAR)
    }
    if kill_worker:
        env[faults.FAULT_INJECT_ENV_VAR] = "worker:die:train@worker:0"
    argv = [
        sys.executable, "-m", "gordo_tpu.cli", "build-fleet",
        json.dumps(configs), out_dir,
        "--workers", str(workers), "--lease-ttl", str(lease_ttl),
    ]
    start = time.monotonic()
    proc = subprocess.run(argv, env=env, capture_output=True, text=True)
    wall = time.monotonic() - start
    if proc.returncode != 0:
        raise RuntimeError(
            f"build-fleet --workers {workers} failed "
            f"(rc {proc.returncode}):\n{proc.stderr[-3000:]}"
        )
    with open(os.path.join(out_dir, "build_report.json")) as fh:
        report = json.load(fh)
    ledger = {}
    telemetry_path = os.path.join(out_dir, "telemetry_report.json")
    if os.path.exists(telemetry_path):
        with open(telemetry_path) as fh:
            ledger = json.load(fh).get("ledger") or {}
    shutil.rmtree(out_dir, ignore_errors=True)
    n_built = int(report.get("n_built") or 0)
    return {
        "workers": workers,
        "killed_worker": bool(kill_worker),
        "wall_s": round(wall, 3),
        "n_built": n_built,
        "n_failed": int(report.get("n_failed") or 0),
        "models_per_hour": round(n_built / wall * 3600, 2) if wall else None,
        "steals": ledger.get("steals"),
        "attempts_total": ledger.get("attempts_total"),
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--machines", type=int, default=12)
    parser.add_argument("--buckets", type=int, default=4)
    parser.add_argument(
        "--worker-counts", default="1,2,4",
        help="Comma-separated worker counts to sweep",
    )
    parser.add_argument(
        "--lease-ttl", type=float, default=10.0,
        help="Lease TTL for the ledger runs (the steal latency after a kill)",
    )
    parser.add_argument(
        "--skip-kill", action="store_true",
        help="Skip the worker-killed goodput run",
    )
    args = parser.parse_args()

    configs = _fleet_configs(args.machines, args.buckets)
    counts = [int(c) for c in args.worker_counts.split(",") if c.strip()]
    runs = [
        _run_build(configs, workers, lease_ttl=args.lease_ttl)
        for workers in counts
    ]

    kill_run = None
    goodput_retained = None
    if not args.skip_kill:
        kill_workers = max(c for c in counts)
        clean = next(r for r in runs if r["workers"] == kill_workers)
        kill_run = _run_build(
            configs, kill_workers, lease_ttl=args.lease_ttl, kill_worker=True
        )
        if clean["models_per_hour"] and kill_run["models_per_hour"]:
            goodput_retained = round(
                kill_run["models_per_hour"] / clean["models_per_hour"], 4
            )

    out = {
        "bench_schema_version": 1,
        "bench": "multi_worker_build",
        "n_machines": args.machines,
        "n_buckets": args.buckets,
        "lease_ttl_s": args.lease_ttl,
        "runs": runs,
        "kill_run": kill_run,
        "goodput_retained_after_kill": goodput_retained,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
