"""
Cold-start benchmark: time-to-first-prediction for a FRESHLY EXEC'D
server process, cold trace vs AOT executable cache
(docs/performance.md "AOT executable cache").

Process model: this parent never initializes JAX. The build runs in a
child that exits, then each measured server is its own fresh process —
an accelerator belongs to one process at a time, so a parent that built
in-process would hold the chip its servers need.

The paper's regime — thousands of tiny models — makes XLA compile time
the dominant cost of every fresh serving process: the goodput lost is
time the device is reserved but doing no model work (PAPERS.md
arXiv:2502.06982). This harness measures exactly that interval, end to
end: ``exec`` of a new Python interpreter → the first 200 from the
fleet prediction endpoint, with ``GORDO_SERVER_PRELOAD`` on so the
measured path is the production one (preload behind the readiness
probe, then the first real request).

Two arms over the SAME built collection:

- ``cold_trace``: ``GORDO_AOT_CACHE=false`` — the server re-traces and
  re-compiles every serving program (the pre-AOT world).
- ``aot_cache``: ``GORDO_AOT_CACHE=true`` — the preload maps the
  build-time serialized executables in; the first request executes a
  deserialized program.

Both arms also record the first response body, and the emitted JSON
carries ``predictions_identical`` — the AOT-loaded and freshly-traced
programs must agree bit-for-bit (also pinned by
tests/test_programs.py).

Two numbers per arm: the end-to-end wall (exec → first 200 — what an
operator sees; noisy with process startup) and the first request's
server-side ``predict`` phase from Server-Timing — exactly where
trace+compile vs deserialize lands, with the startup noise both arms
share subtracted out. CI strictness pins the latter.

Usage::

    python benchmarks/cold_start.py --machines 6 --repeats 2
    make bench-cold-start

Emits one JSON object (the usual bench shape) on stdout.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SERVER_SCRIPT = """
from werkzeug.serving import make_server
from gordo_tpu.server import build_app
app = build_app()
server = make_server("127.0.0.1", {port}, app, threaded=True)
server.serve_forever()
"""


def first_prediction_seconds(
    collection: str,
    port: int,
    body: bytes,
    url: str,
    aot: bool,
    xla_cache_dir: str,
    timeout_s: float = 600.0,
):
    """
    Exec a fresh server process against ``collection`` and poll the
    fleet endpoint until the first 200; returns (seconds from exec to
    that response, response body bytes, the response's server-side
    ``predict`` phase in seconds). The child's persistent XLA compile
    cache is placed (``JAX_COMPILATION_CACHE_DIR``) in a per-RUN directory
    so the cold arm cannot warm itself across repeats into an AOT-cache
    lookalike.
    """
    env = dict(os.environ)
    env.update(
        MODEL_COLLECTION_DIR=collection,
        GORDO_SERVER_PRELOAD="true",
        GORDO_AOT_CACHE="true" if aot else "false",
        JAX_COMPILATION_CACHE_DIR=xla_cache_dir,
    )
    script = _SERVER_SCRIPT.format(port=port)
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        deadline = t0 + timeout_s
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server process died with rc={proc.returncode}"
                )
            if time.perf_counter() > deadline:
                raise TimeoutError("no first prediction within budget")
            request = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"}
            )
            try:
                with urllib.request.urlopen(request, timeout=30) as resp:
                    payload = resp.read()
                    timing = resp.headers.get("Server-Timing") or ""
                break
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.05)
        return time.perf_counter() - t0, payload, _predict_phase_s(timing)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def _predict_phase_s(server_timing: str):
    """
    The first request's server-side ``predict`` phase, from the
    Server-Timing header — where trace+compile (cold) vs
    deserialized-execute (AOT) lands, with none of the process-startup
    noise (imports, model unpickling) that is identical across arms.
    This is the low-variance number the CI strictness gate pins.
    """
    for entry in server_timing.split(","):
        name, _, params = entry.strip().partition(";")
        if name.strip() == "predict" and params.strip().startswith("dur="):
            try:
                return float(params.strip()[4:]) / 1000.0
            except ValueError:
                return None
    return None


def main() -> dict:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--machines", type=int, default=6)
    parser.add_argument(
        "--model", default="hourglass", help="hourglass or lstm"
    )
    parser.add_argument("--samples", type=int, default=100)
    parser.add_argument(
        "--repeats", type=int, default=1,
        help="Fresh server processes per arm; the best (min) time is "
        "reported per arm, mean alongside.",
    )
    parser.add_argument("--port", type=int, default=5577)
    parser.add_argument(
        "--collection-dir", default=None,
        help="Serve THIS built collection instead of building a "
        "temporary one (its .programs dir must exist for the AOT arm).",
    )
    parser.add_argument("--json-out", default=None)
    args = parser.parse_args()

    import numpy as np

    tmp_ctx = tempfile.TemporaryDirectory(prefix="gordo_cold_start_")
    tmp = tmp_ctx.name
    if args.collection_dir is None:
        # the build child may use its own compile cache freely — only
        # the measured server arms get segregated cache dirs below
        from benchmarks.server_latency import build_collection_in_child

        built = build_collection_in_child(
            args.machines, tmp, args.model, export_programs=True,
            env=dict(
                os.environ,
                JAX_COMPILATION_CACHE_DIR=os.path.join(tmp, "xla_build"),
            ),
        )
        collection = built["collection"]
    else:
        collection = args.collection_dir
        built = {}
    # the world the measured servers ran in, as the collection's own
    # manifest records it (this process never asks JAX)
    with open(os.path.join(collection, ".programs", "manifest.json")) as fh:
        manifest = json.load(fh)

    names = sorted(
        n for n in os.listdir(collection)
        if not n.startswith(".")
        and os.path.isdir(os.path.join(collection, n))
    )
    rows = np.random.default_rng(0).random((args.samples, 4)).tolist()
    body = json.dumps({"machines": {name: rows for name in names}}).encode()
    url = f"http://127.0.0.1:{args.port}/gordo/v0/proj/prediction/fleet"

    arms = {}
    payloads = {}
    for arm, aot in (("cold_trace", False), ("aot_cache", True)):
        times = []
        phases = []
        for repeat in range(max(1, args.repeats)):
            seconds, payload, phase_s = first_prediction_seconds(
                collection,
                args.port,
                body,
                url,
                aot=aot,
                # per (arm, repeat): a truly cold XLA world every run
                xla_cache_dir=os.path.join(tmp, f"xla_{arm}_{repeat}"),
            )
            times.append(seconds)
            if phase_s is not None:
                phases.append(phase_s)
            # the bit-identity comparand is the prediction DATA — the
            # response's time-seconds field differs every run by nature
            payloads[arm] = json.loads(payload).get("data")
            print(
                f"# {arm} repeat {repeat}: first prediction in "
                f"{seconds:.3f}s (request predict phase "
                f"{phase_s if phase_s is None else round(phase_s, 4)}s)",
                file=sys.stderr,
            )
        arms[arm] = {
            "best_s": round(min(times), 4),
            "mean_s": round(sum(times) / len(times), 4),
            "times_s": [round(t, 4) for t in times],
            # the low-noise per-arm number: the first request's
            # server-side predict phase (compile-or-deserialize +
            # execute), immune to the process-startup noise both arms
            # share — the CI strictness gate pins on this
            "first_predict_s": round(min(phases), 4) if phases else None,
        }

    result = {
        "bench_schema_version": 1,
        "benchmark": "cold_start",
        "platform": manifest["backend"],
        "device_kind": manifest["device_kind"],
        "n_machines": len(names),
        "model": args.model,
        "samples": args.samples,
        "preload": True,
        "cold_trace_s": arms["cold_trace"]["best_s"],
        "aot_cache_s": arms["aot_cache"]["best_s"],
        "speedup": round(
            arms["cold_trace"]["best_s"] / arms["aot_cache"]["best_s"], 3
        )
        if arms["aot_cache"]["best_s"] > 0
        else None,
        "saved_s": round(
            arms["cold_trace"]["best_s"] - arms["aot_cache"]["best_s"], 4
        ),
        "cold_trace_first_predict_s": arms["cold_trace"]["first_predict_s"],
        "aot_cache_first_predict_s": arms["aot_cache"]["first_predict_s"],
        "first_predict_speedup": round(
            arms["cold_trace"]["first_predict_s"]
            / arms["aot_cache"]["first_predict_s"],
            3,
        )
        if arms["cold_trace"]["first_predict_s"]
        and arms["aot_cache"]["first_predict_s"]
        else None,
        "predictions_identical": payloads.get("cold_trace")
        == payloads.get("aot_cache"),
        "n_programs_exported": built.get("n_programs"),
        "arms": arms,
    }
    line = json.dumps(result)
    print(line)
    if args.json_out:
        with open(args.json_out, "w") as fh:
            fh.write(line + "\n")
    tmp_ctx.cleanup()
    return result


if __name__ == "__main__":
    main()
