"""
Concurrent-user serving load test (reference analogue:
benchmarks/load_test/load_test.py, which drives Locust against a deployed
cluster). This is dependency-free: N worker threads hammer the prediction
endpoint of a running server for a fixed duration and report RPS and
latency percentiles as one JSON object.

Target a deployed server:

    python benchmarks/load_test.py --base-url http://host:5555 \\
        --project proj --machine m0 --users 8 --duration 30

or self-serve a temporary in-process server on random-data artifacts:

    python benchmarks/load_test.py --self-serve --users 4 --duration 10

Two arrival modes:

- closed-loop (default): ``--users`` workers send back-to-back — each
  worker waits for its response before the next request, so offered
  load self-throttles to the server's capacity and queueing collapse is
  INVISIBLE (latency grows, arrival rate falls, the queue never melts).
- ``--open-loop``: Poisson arrivals at ``--rps`` regardless of how the
  server is doing — the millions-of-users shape. This is the mode that
  can see what dynamic batching (docs/serving.md#dynamic-batching)
  fixes: batch sizes converging above 1, queue-wait bounded by the SLO
  cap, and admission control shedding (503 + Retry-After) instead of
  unbounded queue melt. Reports p50/p99 latency, achieved vs offered
  throughput, mean dispatch batch size, and shed rate.

    python benchmarks/load_test.py --self-serve --open-loop --rps 80 \\
        --duration 20 --fleet 2 --batch-wait-ms 10 --queue-limit 32

Sharded serving plane (docs/serving.md): ``--replicas 1,2,4`` runs the
open-loop arm against an in-process router + N shard replicas per count
and reports aggregate goodput (machine-scores/s) + p99 per replica
count; ``--kill-replica-at S`` additionally SIGKILL-shapes one replica
(its server stops accepting) S seconds into a final run at the highest
count, reporting ``goodput_retained`` vs the same-count healthy arm —
the PR-8 crash-tolerance number, now for serving:

    python benchmarks/load_test.py --self-serve --open-loop --rps 40 \\
        --duration 12 --fleet 6 --replicas 1,2,4 --kill-replica-at 5
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()


def self_serve(
    tmp: str,
    port: int,
    n_machines: int = 1,
    model: str = "hourglass",
    batch_wait_ms: float = 0.0,
    queue_limit: int = 64,
    precision: str = "float32",
) -> str:
    """Train machine(s) on random data and serve them; returns base URL."""
    from werkzeug.serving import make_server

    from benchmarks.server_latency import build_collection
    from gordo_tpu.server import build_app

    collection = build_collection(n_machines, tmp, model, precision=precision)
    os.environ["MODEL_COLLECTION_DIR"] = collection
    app = build_app(
        {"BATCH_WAIT_MS": batch_wait_ms, "BATCH_QUEUE_LIMIT": queue_limit}
    )
    server = make_server("127.0.0.1", port, app, threaded=True)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return f"http://127.0.0.1:{port}"


def serve_sharded_plane(
    collection: str,
    base_port: int,
    n_replicas: int,
    batch_wait_ms: float = 0.0,
    queue_limit: int = 64,
):
    """
    One in-process sharded serving plane: N shard replicas (each a full
    GordoApp with its slice of the shard manifest) + a router fronting
    them, every one on its own localhost port. Returns
    (router_url, replica_servers, router_app) — shutting down a replica
    server is the bench's SIGKILL shape (connections refuse, the router
    ejects and fails the shard over).
    """
    from werkzeug.serving import make_server

    from gordo_tpu.router.app import build_router_app
    from gordo_tpu.server import build_app
    from gordo_tpu.server.catalog import write_shard_manifest

    os.environ["MODEL_COLLECTION_DIR"] = collection
    replica_ids = [f"r{i}" for i in range(n_replicas)]
    manifest = write_shard_manifest(
        os.path.join(
            os.path.dirname(collection), f"shard_manifest_{n_replicas}.json"
        ),
        replica_ids,
    )
    servers = {}
    replica_urls = {}
    for i, rid in enumerate(replica_ids):
        app = build_app(
            {
                "SHARD_MANIFEST": manifest,
                "REPLICA_ID": rid,
                "BATCH_WAIT_MS": batch_wait_ms,
                "BATCH_QUEUE_LIMIT": queue_limit,
            }
        )
        server = make_server("127.0.0.1", base_port + 1 + i, app, threaded=True)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        servers[rid] = server
        replica_urls[rid] = f"http://127.0.0.1:{base_port + 1 + i}"
    router = build_router_app(
        {
            "REPLICAS": replica_urls,
            "PROBE_INTERVAL_S": 0.25,
            "BACKOFF_SCALE": 0.05,  # sub-second ejection windows
            "MAX_INFLIGHT": 256,
        }
    )
    router_server = make_server("127.0.0.1", base_port, router, threaded=True)
    threading.Thread(target=router_server.serve_forever, daemon=True).start()
    servers["__router__"] = router_server
    return f"http://127.0.0.1:{base_port}", servers, router


def worker(url: str, body: bytes, stop_at: float, latencies, errors):
    while time.perf_counter() < stop_at:
        request = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"}
        )
        start = time.perf_counter()
        try:
            with urllib.request.urlopen(request, timeout=30) as resp:
                resp.read()
        except urllib.error.HTTPError as err:
            errors.append(err.code)
            continue
        except Exception:
            errors.append("exception")
            continue
        latencies.append((time.perf_counter() - start) * 1000)


def open_loop(url: str, body: bytes, rps: float, duration: float, seed: int):
    """
    Poisson arrivals at target ``rps`` for ``duration`` seconds, one
    thread per in-flight request (arrivals never wait for responses).
    Returns (latencies_ms, errors, sheds, partials, elapsed_s) — a shed
    is a 503 carrying Retry-After (admission control, server or
    router); a partial is a structured 409 naming per-machine
    casualties (the sharded plane's failover-window shape); other
    failures are errors. ``elapsed_s`` runs from the first arrival to
    the LAST COMPLETION (not the thread-join return): achieved-
    throughput math must not be diluted by one straggler's urlopen
    timeout.
    """
    import random

    rng = random.Random(seed)
    latencies: list = []
    errors: list = []
    sheds: list = []
    partials: list = []
    done_at: list = []

    def one_request():
        request = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"}
        )
        start = time.perf_counter()
        try:
            try:
                with urllib.request.urlopen(request, timeout=60) as resp:
                    resp.read()
            except urllib.error.HTTPError as err:
                detail = err.read()
                retry_after = err.headers.get("Retry-After")
                if err.code == 503 and retry_after is not None:
                    sheds.append(float(retry_after))
                elif err.code == 409:
                    try:
                        named = json.loads(detail).get("unavailable") or {}
                    except ValueError:
                        named = {}
                    partials.append(len(named))
                else:
                    errors.append(err.code)
                return
            except Exception:
                errors.append("exception")
                return
            latencies.append((time.perf_counter() - start) * 1000)
        finally:
            done_at.append(time.perf_counter())

    threads = []
    start = time.perf_counter()
    next_arrival = start
    while next_arrival - start < duration:
        next_arrival += rng.expovariate(rps)
        delay = next_arrival - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        thread = threading.Thread(target=one_request)
        thread.start()
        threads.append(thread)
    for thread in threads:
        thread.join()
    elapsed = (max(done_at) if done_at else time.perf_counter()) - start
    return latencies, errors, sheds, partials, elapsed


def run_sharded_bench(args, tmp: str) -> dict:
    """
    The ``--replicas`` arms: per replica count, an open-loop run against
    a fresh in-process plane (router + N shard replicas), reporting
    aggregate goodput (machine-scores/s) and latency percentiles; then
    (``--kill-replica-at``) one more run at the highest count with a
    replica killed mid-run, reporting ``goodput_retained`` vs the
    same-count healthy arm.
    """
    import numpy as np

    from benchmarks.server_latency import build_collection, summarize_ms
    from gordo_tpu.router.ring import HashRing

    counts = sorted({int(x) for x in str(args.replicas).split(",") if x})
    fleet = max(1, args.fleet)
    names = [f"bench-m{i}" for i in range(fleet)]
    collection = build_collection(fleet, tmp, args.model)
    rows = np.random.default_rng(0).random(
        (args.samples, args.features)
    ).tolist()
    body = json.dumps({"machines": {n: rows for n in names}}).encode()
    path = f"/gordo/v0/{args.project}/prediction/fleet"

    def run_plane(n_replicas, kill_at=0.0):
        port = run_plane.next_port
        run_plane.next_port += n_replicas + 2
        url, servers, router = serve_sharded_plane(
            collection,
            port,
            n_replicas,
            batch_wait_ms=args.batch_wait_ms,
            queue_limit=args.queue_limit,
        )
        target = url + path
        urllib.request.urlopen(
            urllib.request.Request(
                target, data=body, headers={"Content-Type": "application/json"}
            ),
            timeout=120,
        ).read()
        victim = None
        killer = None
        if kill_at > 0:
            ring = HashRing([f"r{i}" for i in range(n_replicas)])
            partition = ring.partition(names)
            # kill the replica owning the most machines: the worst case
            victim = max(partition, key=lambda r: len(partition[r]))

            def kill():
                servers[victim].shutdown()
                servers[victim].server_close()

            killer = threading.Timer(kill_at, kill)
            killer.start()
        try:
            latencies, errors, sheds, partials, elapsed = open_loop(
                target, body, args.rps, args.duration, args.seed
            )
        finally:
            if killer is not None:
                killer.join()
            router.close()
            for name, server in servers.items():
                if name != victim:
                    server.shutdown()
                    server.server_close()
        goodput = fleet * len(latencies) / elapsed if elapsed else 0.0
        arm = {
            "replicas": n_replicas,
            "requests": len(latencies),
            "errors": len(errors),
            "sheds": len(sheds),
            "partials": len(partials),
            "machines_named_in_partials": sum(partials),
            "achieved_rps": round(len(latencies) / elapsed, 1) if elapsed else 0,
            "goodput_machine_scores_per_s": round(goodput, 1),
            **summarize_ms(latencies),
        }
        if victim is not None:
            arm["killed_replica"] = victim
            arm["killed_at_s"] = kill_at
        return arm, goodput

    run_plane.next_port = args.port
    arms = []
    goodput_by_count = {}
    for n in counts:
        arm, goodput = run_plane(n)
        arms.append(arm)
        goodput_by_count[n] = goodput
    kill_run = None
    if args.kill_replica_at > 0 and max(counts) >= 2:
        kill_run, kill_goodput = run_plane(
            max(counts), kill_at=args.kill_replica_at
        )
        healthy = goodput_by_count[max(counts)]
        kill_run["goodput_retained"] = (
            round(kill_goodput / healthy, 3) if healthy else 0.0
        )
    return {
        "bench_schema_version": 1,
        "mode": "sharded-open-loop",
        "offered_rps": args.rps,
        "duration_s": args.duration,
        "fleet_size": fleet,
        "model": args.model,
        "batch_wait_ms": args.batch_wait_ms,
        "queue_limit": args.queue_limit,
        "arms": arms,
        "kill_run": kill_run,
    }


def batching_registry_stats():
    """
    Dispatch batch size / queue wait / shed counts from the in-process
    observability registry — meaningful only under --self-serve, where
    the bench and the server share a process (against --base-url the
    numbers live in the REMOTE server's /metrics).
    """
    from gordo_tpu.observability import get_registry

    snap = get_registry().snapshot()

    def first_series(name):
        series = (snap.get(name) or {}).get("series") or []
        return series[0] if series else None

    out = {}
    requests = first_series("gordo_serve_batch_requests")
    if requests and requests["count"]:
        out["dispatches"] = requests["count"]
        out["mean_batch_size"] = round(requests["sum"] / requests["count"], 2)
    wait = first_series("gordo_serve_batch_queue_wait_seconds")
    if wait and wait["count"]:
        out["queue_wait_mean_ms"] = round(wait["sum"] / wait["count"] * 1000, 3)
    shed = first_series("gordo_serve_batch_shed_total")
    if shed:
        out["sheds"] = shed["value"]
    return out


def stamp_slo(out: dict, slo_path: str) -> None:
    """
    Evaluate the SLO spec at slo_path against this run's measured
    signals and stamp the report into out["slo"]. The bench's own
    numbers map onto the plane control signals (docs/observability.md):
    p99_ms -> predict_p99_ms, shed_rate -> shed_rate, and the raw error
    fraction -> unstructured_error_rate. Objectives over signals the
    bench cannot measure evaluate with zero samples (never exhausted).
    """
    from gordo_tpu.observability.slo import evaluate_values, load_slo_spec

    spec = load_slo_spec(slo_path)
    attempts = (out.get("requests") or 0) + (out.get("errors") or 0)
    signals = {
        "predict_p99_ms": out.get("p99_ms"),
        "shed_rate": out.get("shed_rate"),
        "unstructured_error_rate": (
            round((out.get("errors") or 0) / attempts, 4) if attempts else None
        ),
    }
    report = evaluate_values(spec, signals)
    out["slo"] = report.to_dict()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--base-url", default=None)
    parser.add_argument("--project", default="proj")
    parser.add_argument("--machine", default="bench-m0")
    parser.add_argument("--users", type=int, default=4)
    parser.add_argument("--duration", type=float, default=15.0)
    parser.add_argument("--samples", type=int, default=100)
    parser.add_argument(
        "--features",
        type=int,
        default=4,
        help="Feature width of the request payload; must match the target "
        "model's tag count (self-serve models have 4)",
    )
    parser.add_argument("--self-serve", action="store_true")
    parser.add_argument("--port", type=int, default=5599)
    parser.add_argument(
        "--open-loop",
        action="store_true",
        help="Poisson arrivals at --rps instead of closed-loop --users "
        "workers: offered load does not self-throttle, so queueing "
        "collapse (and the batching/shedding that prevents it) is "
        "actually visible",
    )
    parser.add_argument(
        "--rps",
        type=float,
        default=50.0,
        help="Open-loop target arrival rate (requests/second)",
    )
    parser.add_argument(
        "--seed",
        type=int,
        default=0,
        help="Open-loop arrival-process seed (reproducible schedules)",
    )
    parser.add_argument(
        "--batch-wait-ms",
        type=float,
        default=0.0,
        help="Self-serve server's dynamic-batching SLO cap "
        "(docs/serving.md); 0 = batching disabled",
    )
    parser.add_argument(
        "--queue-limit",
        type=int,
        default=64,
        help="Self-serve server's batching admission-control bound",
    )
    def _non_negative(value):
        n = int(value)
        if n < 0:
            raise argparse.ArgumentTypeError("--fleet must be >= 0")
        return n

    parser.add_argument(
        "--fleet",
        type=_non_negative,
        default=0,
        metavar="N",
        help="Drive the batched fleet endpoint with N machines per request "
        "instead of the single-machine endpoint (self-serve builds N "
        "machines named bench-m0..bench-m<N-1>)",
    )
    parser.add_argument(
        "--fleet-machines",
        default=None,
        metavar="NAME,NAME,...",
        help="Comma-separated machine names for fleet mode against a real "
        "--base-url deployment (default: the self-serve bench-m<i> names)",
    )
    parser.add_argument(
        "--model",
        choices=["hourglass", "lstm"],
        default="hourglass",
        help="Self-serve estimator family (lstm exercises the windowed "
        "serving path: on-device window gather + chunked predict)",
    )
    parser.add_argument(
        "--replicas",
        default=None,
        metavar="N[,N...]",
        help="Sharded serving plane (docs/serving.md): run the open-loop "
        "fleet arm against an in-process router + N shard replicas for "
        "each count (e.g. 1,2,4), reporting aggregate goodput + p99 per "
        "count. Implies --self-serve --open-loop --fleet.",
    )
    parser.add_argument(
        "--kill-replica-at",
        type=float,
        default=0.0,
        metavar="S",
        help="With --replicas: one more run at the highest count where "
        "the busiest replica stops accepting S seconds in; reports "
        "goodput_retained vs the healthy same-count arm.",
    )
    parser.add_argument(
        "--precision",
        choices=["float32", "bf16", "auto"],
        default="float32",
        help="Self-serve build precision: bf16/auto route the build "
        "through the fleet builder's calibration pass, and the output "
        "gains per-machine precision decisions + the worst served MAE "
        "delta the calibration measured (docs/performance.md). The "
        "request wire format stays float32 either way — the cast is "
        "in-program.",
    )
    parser.add_argument(
        "--output",
        default=None,
        help="Also write the result JSON to this path.",
    )
    parser.add_argument(
        "--slo",
        default=None,
        help="SLO spec (YAML/JSON, docs/observability.md) evaluated "
        "against this run's measured signals; the result JSON gains an "
        "'slo' block with pass/fail + per-objective burn rates, and "
        "consolidate folds it into trajectory.json.",
    )
    args = parser.parse_args()

    import numpy as np

    tmp_ctx = tempfile.TemporaryDirectory()

    if args.replicas:
        if not args.fleet:
            parser.error("--replicas requires --fleet N")
        out = run_sharded_bench(args, tmp_ctx.name)
        if args.slo:
            stamp_slo(out, args.slo)
        payload = json.dumps(out, indent=2)
        print(payload)
        if args.output:
            with open(args.output, "w") as fh:
                fh.write(payload + "\n")
        return
    base_url = args.base_url
    served_locally = False
    if base_url is None:
        if not args.self_serve:
            parser.error("--base-url or --self-serve required")
        base_url = self_serve(
            tmp_ctx.name,
            args.port,
            max(1, args.fleet),
            args.model,
            batch_wait_ms=args.batch_wait_ms,
            queue_limit=args.queue_limit,
            precision=args.precision,
        )
        served_locally = True

    rows = np.random.default_rng(0).random((args.samples, args.features)).tolist()
    if args.fleet:
        names = (
            args.fleet_machines.split(",")
            if args.fleet_machines
            else [f"bench-m{i}" for i in range(args.fleet)]
        )
        body = json.dumps({"machines": {name: rows for name in names}}).encode()
        url = f"{base_url}/gordo/v0/{args.project}/prediction/fleet"
    else:
        body = json.dumps({"X": rows}).encode()
        url = f"{base_url}/gordo/v0/{args.project}/{args.machine}/prediction"

    # warmup: first request pays model load + compile
    try:
        urllib.request.urlopen(
            urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"}
            ),
            timeout=120,
        ).read()
    except urllib.error.HTTPError as err:
        detail = err.read().decode(errors="replace")[:300]
        hint = (
            "--project/--fleet-machines"
            if args.fleet
            else "--project/--machine"
        )
        sys.exit(
            f"warmup request failed with HTTP {err.code}: {detail}\n"
            f"(check {hint}, and that --features matches the "
            f"model's tag count)"
        )
    except urllib.error.URLError as err:
        sys.exit(f"cannot reach {url}: {err.reason}")

    sheds: list = []
    partials: list = []
    start = time.perf_counter()
    if args.open_loop:
        latencies, errors, sheds, partials, elapsed = open_loop(
            url, body, args.rps, args.duration, args.seed
        )
    else:
        latencies = []
        errors = []
        stop_at = time.perf_counter() + args.duration
        threads = [
            threading.Thread(
                target=worker, args=(url, body, stop_at, latencies, errors)
            )
            for _ in range(args.users)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start

    from benchmarks.server_latency import summarize_ms
    from gordo_tpu.observability import attribution
    from gordo_tpu.observability.tracing import measure_overhead

    summary = summarize_ms(latencies) if latencies else {}
    out = {
        "bench_schema_version": 1,
        "mode": "open" if args.open_loop else "closed",
        **(
            {"offered_rps": args.rps}
            if args.open_loop
            else {"users": args.users}
        ),
        # only self-serve knows what it built; against a --base-url
        # deployment the family is whatever is deployed there
        **({"model": args.model} if served_locally else {}),
        "duration_s": round(elapsed, 1),
        "requests": len(latencies),
        "errors": len(errors),
        "rps": round(len(latencies) / elapsed, 1),
        **summary,
        # span-machinery cost per enter/exit in each regime (disabled /
        # sampled-out / recording), so the tracing-sampling default is
        # justified against the request latencies above by a number
        "tracing_overhead": measure_overhead(samples=1000),
        # the phase ledger's per-bracket cost in each regime (disabled /
        # enabled), justified the same way
        "ledger_overhead": attribution.measure_overhead(samples=1000),
    }
    if args.open_loop:
        attempts = len(latencies) + len(errors) + len(sheds) + len(partials)
        out["sheds"] = len(sheds)
        out["shed_rate"] = round(len(sheds) / attempts, 4) if attempts else 0.0
        # structured 409s (named per-machine casualties: build-report
        # 409s, or the router's transient failover-window partials) —
        # reported in their own bucket, not silently dropped and not
        # conflated with raw errors
        out["partials"] = len(partials)
        if sheds:
            out["shed_retry_after_s_max"] = max(sheds)
    # each request scores --samples timesteps per machine: the serving
    # analogue of the trainer's sensor-timesteps/s throughput axis
    out["sensor_timesteps_per_s"] = (
        round(args.samples * max(1, args.fleet) * len(latencies) / elapsed, 1)
        if elapsed
        else 0.0
    )
    # host->device bytes one machine's scoring update moves: the wire
    # batch stays float32 even under bf16 (the cast is in-program), so
    # this number is precision-invariant — bf16 halves the RESIDENT
    # param bytes instead, a device-side (TPU HBM) saving
    out["bytes_transferred_per_update"] = args.samples * args.features * 4
    if served_locally:
        out["batch_wait_ms"] = args.batch_wait_ms
        out["queue_limit"] = args.queue_limit
        # the server runs in-process: its dispatch batch sizes and queue
        # waits are readable straight off the shared registry
        out.update(batching_registry_stats())
        # ...and so is the phase ledger: where this run's request wall
        # time went, by plane/phase, with the host/device split
        out["phase_attribution"] = attribution.phase_attribution_block()
        out["precision"] = args.precision
        if args.precision != "float32":
            # the fleet builder persisted its calibration decisions next
            # to the artifacts; report them beside the latencies so one
            # JSON carries both the speed and the accuracy cost
            report_path = os.path.join(
                os.environ["MODEL_COLLECTION_DIR"], "build_report.json"
            )
            with open(report_path) as fh:
                machines = (
                    json.load(fh).get("precision") or {}
                ).get("machines") or {}
            deltas = [
                r["mae_delta"]
                for r in machines.values()
                if r.get("mae_delta") is not None
            ]
            out["n_machines_bf16"] = sum(
                1 for r in machines.values() if r.get("precision") == "bf16"
            )
            out["n_machines_float32_fallback"] = sum(
                1 for r in machines.values() if r.get("precision") == "float32"
            )
            out["worst_machine_mae_delta"] = (
                float(f"{max(deltas):.3g}") if deltas else None
            )
    if args.fleet:
        # each request scores --fleet machines; the comparable per-machine
        # rate against the single-machine mode
        out["fleet_size"] = args.fleet
        out["machine_scores_per_s"] = round(
            args.fleet * len(latencies) / elapsed, 1
        )
    if args.slo:
        stamp_slo(out, args.slo)
    print(json.dumps(out))
    if args.output:
        with open(args.output, "w") as fh:
            fh.write(json.dumps(out, indent=2) + "\n")


if __name__ == "__main__":
    main()
