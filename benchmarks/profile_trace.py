"""
Profiler-trace evidence for the roofline/MFU claims (VERDICT r3 item 4):
capture a ``jax.profiler`` trace of one WARM headline-bench epoch (the
bench.py LSTM-AE) or one warm fleet-bucket epoch, and summarize it —
device busy fraction, dispatch gaps, top ops by self time — from the
Chrome-trace JSON the profiler writes alongside the xplane protobuf.

The summary turns "single-model MFU is dispatch/latency-bound, the
fleet axis is how you fill the MXU" from an analytic argument into a
measured one. Run on the chip:

    python benchmarks/profile_trace.py --target bench
    python benchmarks/profile_trace.py --target fleet --machines 64

Prints one JSON object; pass --keep-trace to keep the raw trace dir for
TensorBoard/Perfetto.
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()


def self_times(evs) -> dict:
    """Per-op SELF time on one thread lane: each event's duration minus
    the durations of events nested inside it (same-lane children) — a
    parent op must not double-count its children."""
    ordered = sorted(evs, key=lambda e: (e["ts"], -e["dur"]))
    totals: dict = {}
    stack: list = []  # (end_ts, name, accumulator index)
    accum: list = []
    for ev in ordered:
        start, end = ev["ts"], ev["ts"] + ev["dur"]
        while stack and start >= stack[-1][0]:
            _end, name, idx = stack.pop()
            totals[name] = totals.get(name, 0.0) + accum[idx]
        if stack:
            accum[stack[-1][2]] -= ev["dur"]  # charge child to the parent
        accum.append(ev["dur"])
        stack.append((end, ev["name"], len(accum) - 1))
    while stack:
        _end, name, idx = stack.pop()
        totals[name] = totals.get(name, 0.0) + accum[idx]
    return totals


def summarize_chrome_trace(trace_dir: str, top_n: int = 10) -> dict:
    """
    Parse the profiler's ``*.trace.json.gz`` into lane-level busy/gap
    numbers. Device lanes are thread lanes whose process is a device
    (``/device:...``) — on those, the union of op intervals over the
    traced wall span is the busy fraction, and 1 - busy is dispatch gap
    + host time the device spent idle.
    """
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
    with gzip.open(sorted(paths)[-1], "rt") as fh:
        events = json.load(fh).get("traceEvents", [])

    process_names: dict = {}
    thread_names: dict = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            process_names[ev.get("pid")] = ev.get("args", {}).get("name", "")
        elif ev.get("name") == "thread_name":
            thread_names[(ev.get("pid"), ev.get("tid"))] = (
                ev.get("args", {}).get("name", "")
            )

    complete = [ev for ev in events if ev.get("ph") == "X" and "dur" in ev]
    if not complete:
        raise ValueError("trace holds no complete events")
    t0 = min(ev["ts"] for ev in complete)
    t1 = max(ev["ts"] + ev["dur"] for ev in complete)
    span_us = max(t1 - t0, 1)

    def busy_union(evs) -> float:
        spans = sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in evs)
        total, cur_start, cur_end = 0.0, None, None
        for start, end in spans:
            if cur_end is None or start > cur_end:
                if cur_end is not None:
                    total += cur_end - cur_start
                cur_start, cur_end = start, end
            else:
                cur_end = max(cur_end, end)
        if cur_end is not None:
            total += cur_end - cur_start
        return total

    lanes = {}
    for ev in complete:
        pid, tid = ev.get("pid"), ev.get("tid")
        pname = process_names.get(pid, "")
        tname = thread_names.get((pid, tid), "")
        # device execution lanes, keyed narrowly: a device PROCESS
        # ("/device:TPU:0", whose threads are the XLA op streams) or, on
        # the CPU backend, the PjRt executor thread pools specifically —
        # NOT any thread that merely mentions XLA (host-side launch
        # threads would inflate the busy fraction)
        is_device = pname.startswith("/device:") or tname.startswith(
            ("tf_XLAPjRt", "tf_XLAEigen", "XLA Ops")
        )
        lanes.setdefault((pid, tid, is_device, pname, tname), []).append(ev)

    op_totals: dict = {}
    device_lanes = []
    for (pid, tid, is_device, pname, tname), evs in lanes.items():
        if not is_device:
            continue
        busy = busy_union(evs)
        device_lanes.append(
            {
                "process": pname,
                "thread": tname[:60],
                "busy_us": round(busy, 1),
                "busy_fraction": round(busy / span_us, 4),
                "events": len(evs),
            }
        )
        for name, self_us in self_times(evs).items():
            op_totals[name] = op_totals.get(name, 0.0) + self_us
    top_ops = sorted(op_totals.items(), key=lambda kv: -kv[1])[:top_n]
    return {
        "span_us": round(span_us, 1),
        "device_lanes": sorted(
            device_lanes, key=lambda d: -d["busy_us"]
        ),
        "top_device_ops_us": [
            {"name": name[:120], "total_us": round(us, 1)} for name, us in top_ops
        ],
    }


def trace_bench_epoch(trace_dir: str, n_timesteps: int) -> dict:
    """One WARM epoch of the bench.py LSTM-AE workload under the tracer."""
    import numpy as np

    import bench as bench_mod
    import jax

    from gordo_tpu.models.factories.lstm import lstm_model
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    rng = np.random.default_rng(0)
    X = rng.standard_normal((n_timesteps, bench_mod.N_SENSORS)).astype("float32")
    data = StackedData.from_ragged([X], [X.copy()])
    spec = lstm_model(
        n_features=bench_mod.N_SENSORS,
        lookback_window=bench_mod.LOOKBACK,
        encoding_dim=bench_mod.ENC,
        encoding_func=("tanh",) * len(bench_mod.ENC),
        decoding_dim=bench_mod.DEC,
        decoding_func=("tanh",) * len(bench_mod.DEC),
        dtype="bfloat16" if on_tpu else "float32",
        fused=True,
        time_unroll=int(os.environ.get("BENCH_TIME_UNROLL", "1")),
        schedule=os.environ.get(
            "BENCH_SCHEDULE", "layer" if on_tpu else "stacked"
        ),
    )
    trainer = FleetTrainer(spec, lookahead=0, donate=True)
    keys = trainer.machine_keys(1)
    params, _ = trainer.fit(data, keys, epochs=1, batch_size=bench_mod.BATCH)  # warm
    with jax.profiler.trace(trace_dir):
        params, _ = trainer.fit(
            data, keys, epochs=1, batch_size=bench_mod.BATCH, params=params
        )
        jax.block_until_ready(params)
    return {"device_kind": dev.device_kind, "platform": dev.platform}


def trace_fleet_epoch(trace_dir: str, machines: int, rows: int) -> dict:
    """One WARM fleet-bucket epoch (hourglass AE fleet) under the tracer."""
    import numpy as np

    import jax

    from gordo_tpu.models.core import solo_init_key
    from gordo_tpu.models.factories.feedforward import feedforward_hourglass
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    Xs = [rng.random((rows, 4)).astype("float32") for _ in range(machines)]
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    trainer = FleetTrainer(feedforward_hourglass(n_features=4))
    keys = np.stack([np.asarray(solo_init_key(0))] * machines)
    params, _ = trainer.fit(data, keys, epochs=1, batch_size=32)  # warm
    with jax.profiler.trace(trace_dir):
        params, _ = trainer.fit(data, keys, epochs=1, batch_size=32, params=params)
        jax.block_until_ready(params)
    return {"device_kind": dev.device_kind, "platform": dev.platform}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--target", choices=["bench", "fleet"], default="bench")
    parser.add_argument("--timesteps", type=int, default=4096)
    parser.add_argument("--machines", type=int, default=64)
    parser.add_argument("--rows", type=int, default=288)
    parser.add_argument("--keep-trace", action="store_true")
    args = parser.parse_args()

    trace_dir = tempfile.mkdtemp(prefix=f"gordo_trace_{args.target}_")
    if args.target == "bench":
        meta = trace_bench_epoch(trace_dir, args.timesteps)
    else:
        meta = trace_fleet_epoch(trace_dir, args.machines, args.rows)
    summary = summarize_chrome_trace(trace_dir)
    summary.update(meta)
    summary["target"] = args.target
    if args.keep_trace:
        summary["trace_dir"] = trace_dir
        print(f"trace kept at {trace_dir}", file=sys.stderr)
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
