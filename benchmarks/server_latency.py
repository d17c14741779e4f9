"""
Server endpoint latency harness (reference shape:
benchmarks/test_ml_server.py:21-41 — 100 samples x 4 tags, repeated
rounds against prediction and anomaly endpoints), extended with the fleet
endpoint.

Prints one JSON object: per-endpoint mean/p50/p95 milliseconds.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()


ESTIMATOR_BLOCKS = {
    "hourglass": """
          gordo_tpu.models.AutoEncoder:
            kind: feedforward_hourglass
            epochs: 1""",
    # windowed serving edition: on-device window gather + chunked predict
    "lstm": """
          gordo_tpu.models.LSTMAutoEncoder:
            kind: lstm_model
            lookback_window: 16
            encoding_dim: [16]
            encoding_func: [tanh]
            decoding_dim: [16]
            decoding_func: [tanh]
            fused: true
            epochs: 1""",
}


def build_collection(
    n_machines: int,
    tmp: str,
    model: str = "hourglass",
    precision: str = "float32",
) -> str:
    """Build a servable collection of random-data machines under ``tmp``.

    ``precision`` != "float32" routes through the fleet builder (the
    only path with a calibration pass), so the collection carries a
    ``build_report.json`` with per-machine precision decisions and the
    served models' ``precision_`` stamps — what the load test's
    precision arm reads back.
    """
    from gordo_tpu import serializer
    from gordo_tpu.builder import local_build

    machine_tpl = """
  - name: bench-m{i}
    dataset:
      type: RandomDataset
      tags: [tag-0, tag-1, tag-2, tag-3]
      target_tag_list: [tag-0, tag-1, tag-2, tag-3]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      asset: gra
    model:
      gordo_tpu.models.anomaly.DiffBasedAnomalyDetector:
        base_estimator:{block}
"""
    config = "machines:" + "".join(
        machine_tpl.format(i=i, block=ESTIMATOR_BLOCKS[model])
        for i in range(n_machines)
    )
    collection = os.path.join(tmp, "proj", "models", "rev1")
    if precision != "float32":
        import yaml

        from gordo_tpu.builder.fleet_build import FleetModelBuilder
        from gordo_tpu.workflow.config_elements.normalized_config import (
            NormalizedConfig,
        )

        machines = NormalizedConfig(
            yaml.safe_load(config), project_name="proj"
        ).machines
        FleetModelBuilder(machines, precision=precision).build(collection)
        return collection
    for fitted, machine in local_build(config):
        serializer.dump(
            fitted, os.path.join(collection, machine.name), metadata=machine.to_dict()
        )
    return collection


_BUILD_CHILD_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks.server_latency import build_collection
collection = build_collection({n_machines}, {tmp!r}, {model!r})
n_programs = None
if {export_programs}:
    from gordo_tpu.programs import export_serving_programs
    n_programs = export_serving_programs(collection)["n_programs"]
print(json.dumps({{"collection": collection, "n_programs": n_programs}}))
"""


def build_collection_in_child(
    n_machines: int,
    tmp: str,
    model: str = "hourglass",
    export_programs: bool = False,
    env: "dict | None" = None,
) -> dict:
    """
    :func:`build_collection` in a child process that EXITS before the
    caller starts anything else. An accelerator belongs to one process at
    a time: a parent that built in-process has initialized JAX and holds
    the chip, and a server it then spawns on the same chip fails or
    hangs. Returns ``{collection, n_programs}``.
    """
    script = _BUILD_CHILD_SCRIPT.format(
        repo=REPO_ROOT,
        n_machines=int(n_machines),
        tmp=tmp,
        model=model,
        export_programs=bool(export_programs),
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        stdout=subprocess.PIPE,
        text=True,
        check=True,
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def summarize_ms(times):
    """mean/p50/p95/p99 summary of a list of millisecond latencies."""
    ordered = sorted(times)
    return {
        "mean_ms": round(statistics.mean(ordered), 3),
        "p50_ms": round(statistics.median(ordered), 3),
        "p95_ms": round(ordered[max(0, int(0.95 * len(ordered)) - 1)], 3),
        "p99_ms": round(ordered[max(0, int(0.99 * len(ordered)) - 1)], 3),
    }


def timed_posts(client, url, body, rounds):
    times = []
    for _ in range(rounds):
        start = time.perf_counter()
        resp = client.post(url, json=body)
        times.append((time.perf_counter() - start) * 1000)
        assert resp.status_code == 200, resp.get_data()
    return {**summarize_ms(times), "rounds": rounds}


def live_throughput(
    collection: str,
    workers: int,
    threads: int,
    body: dict,
    n_requests: int = 120,
    parallel: int = 12,
) -> dict:
    """
    Requests/sec against a real pre-forked server at the given
    workers/threads setting — the load test demonstrating that the
    runner's knobs change concurrency (see server/runner.py).
    """
    import signal
    import socket
    import threading

    import requests as http

    probe = socket.create_server(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()

    # the normal entry point, on whatever platform the environment names:
    # the child is never forced onto the CPU behind the caller's back
    env = dict(os.environ, MODEL_COLLECTION_DIR=collection)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "gordo_tpu.cli", "run-server",
            "--host", "127.0.0.1", "--port", str(port),
            "--workers", str(workers), "--threads", str(threads),
            "--log-level", "warning",
        ],
        cwd=REPO_ROOT,
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    url = f"http://127.0.0.1:{port}/gordo/v0/proj/bench-m0/prediction"
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            try:
                # generous timeout: the first request pays model load + jit
                if http.post(url, json=body, timeout=120).status_code == 200:
                    break
            except http.RequestException:
                pass
            time.sleep(0.3)
        else:
            raise RuntimeError("live server never came up")

        # parallel warmup burst so EVERY forked worker pays its model
        # load + jit compile before the timed phase (sequential warmup
        # would only reliably warm one of them)
        warm_done = threading.Semaphore(0)

        def warm():
            try:
                http.post(url, json=body, timeout=120)
            finally:
                warm_done.release()

        n_warm = 4 * max(workers, 1) * 2
        for _ in range(n_warm):
            threading.Thread(target=warm, daemon=True).start()
        for _ in range(n_warm):
            warm_done.acquire()

        pids, errors = set(), []
        done = threading.Semaphore(0)
        per_thread = n_requests // parallel

        def fire():
            try:
                for _ in range(per_thread):
                    resp = http.post(url, json=body, timeout=60)
                    assert resp.status_code == 200
                    pids.add(resp.headers.get("X-Gordo-Server-Pid"))
            except Exception as exc:  # surfaced below
                errors.append(repr(exc))
            finally:
                done.release()

        start = time.perf_counter()
        for _ in range(parallel):
            threading.Thread(target=fire, daemon=True).start()
        for _ in range(parallel):
            done.acquire()
        elapsed = time.perf_counter() - start
        assert not errors, errors[:3]
        return {
            "workers": workers,
            "threads": threads,
            "requests": per_thread * parallel,
            "requests_per_s": round(per_thread * parallel / elapsed, 2),
            "serving_pids": len(pids),
        }
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rounds", type=int, default=100)
    parser.add_argument("--samples", type=int, default=100)
    parser.add_argument("--fleet-machines", type=int, default=8)
    parser.add_argument(
        "--concurrency",
        action="store_true",
        help="Also load-test a live pre-forked server at several "
        "workers/threads settings.",
    )
    args = parser.parse_args()

    import numpy as np
    import pandas as pd
    from werkzeug.test import Client

    with tempfile.TemporaryDirectory() as tmp:
        # build in a child that exits, and run the live-server arm BEFORE
        # this process initializes JAX: each live server needs the device
        # for itself (one process per chip)
        collection = build_collection_in_child(args.fleet_machines, tmp)[
            "collection"
        ]
        os.environ["MODEL_COLLECTION_DIR"] = collection

        rng = np.random.default_rng(0)
        index = pd.date_range(
            "2019-01-01", periods=args.samples, freq="10min", tz="UTC"
        )
        frame = pd.DataFrame(
            rng.random((args.samples, 4)),
            columns=[f"tag-{i}" for i in range(4)],
            index=index,
        )

        from gordo_tpu.server.utils import dataframe_to_dict

        X = dataframe_to_dict(frame)
        results = {"bench_schema_version": 1, "bench": "server_latency"}

        if args.concurrency:
            arms = [(1, 1), (1, 8)]
            if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
                # run-server refuses >1 local worker off the CPU (N
                # processes cannot share one chip)
                arms.append((2, 8))
            results["live_concurrency"] = [
                live_throughput(collection, workers, threads, {"X": X})
                for workers, threads in arms
            ]

        from gordo_tpu.server import build_app

        client = Client(build_app())
        base_url = "/gordo/v0/proj"
        # warmup (first request pays model load + jit compile)
        client.post(f"{base_url}/bench-m0/prediction", json={"X": X})
        results["prediction"] = timed_posts(
            client, f"{base_url}/bench-m0/prediction", {"X": X}, args.rounds
        )
        client.post(
            f"{base_url}/bench-m0/anomaly/prediction", json={"X": X, "y": X}
        )
        results["anomaly_prediction"] = timed_posts(
            client,
            f"{base_url}/bench-m0/anomaly/prediction",
            {"X": X, "y": X},
            args.rounds,
        )
        fleet_body = {
            "machines": {f"bench-m{i}": X for i in range(args.fleet_machines)}
        }
        client.post(f"{base_url}/prediction/fleet", json=fleet_body)
        fleet = timed_posts(
            client, f"{base_url}/prediction/fleet", fleet_body, args.rounds
        )
        fleet["machines_per_request"] = args.fleet_machines
        fleet["ms_per_machine"] = round(
            fleet["mean_ms"] / args.fleet_machines, 3
        )
        results["fleet_prediction"] = fleet

        print(json.dumps(results))


if __name__ == "__main__":
    main()
