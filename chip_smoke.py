"""
chip_smoke.py — the standing proof that the main path starts on the chip.

Drives build-fleet -> run-server end to end through the entry points a user
calls, at the full width of the flagship configuration (the paper's 50-tag
LSTM plant: 50 tags, lookback 64, encoder (128, 64), decoder (64, 128),
batch 512, 16,384 timesteps per machine, DiffBasedAnomalyDetector wrapper,
8 machines in one bucket, 3 epochs), on data RandomDataset makes from
``--seed``. No network, no git. Phases, each failing the run if it fails:

1. device  a process that imports JAX sees platform ``tpu``
2. build   ``python -m gordo_tpu.cli build-fleet`` (+ one identical
           re-compile to show the persistent compile cache hitting)
3. serve   ``python -m gordo_tpu.cli run-server`` on that output, preload
           on: fleet POSTs, a single-machine anomaly POST, a stream session
4. kernel  a flash-attention Transformer trains through FleetTrainer with
           the Mosaic kernel in the compiled step, and matches dense
5. parity  one built machine scored in float32 on the host CPU agrees with
           what the server answered from the chip

Process model: the chip belongs to one process at a time, so THIS process
stays off JAX and runs every phase as a child, one after the other, each
exited before the next starts; it acts only as HTTP client and reads the
device triple from what the children print. ``run_child`` refuses to spawn
once ``jax`` is in ``sys.modules`` — a parent that imported JAX and then
spawned would pass every CPU rehearsal and hang on real hardware.

    python chip_smoke.py              # one chip, full width (the driver's run)
    python chip_smoke.py --chips 4    # ONLY the fleet-mesh build vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --size tiny   # the CPU rehearsal

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
on any failure ``"ok": false`` and a non-zero exit code. On any platform
but ``tpu`` the remaining phases still run (that is the rehearsal) and the
verdict is still a failure.
"""

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PROJECT = "smoke"
#: request rows: one 256-row AOT bucket for the one-shot POST, and stream
#: updates whose window (lookback - 1 resident rows + new rows) stays in
#: the 128-row bucket — every dispatch shape is one the build exported
POST_ROWS = 256
STREAM_CHUNKS = (128, 64, 64)

SIZES = {
    # the flagship configuration (BASELINE.json configs 2-3)
    "full": dict(
        n_machines=8, n_tags=50, lookback=64, enc=(128, 64), dec=(64, 128),
        batch=512, n_timesteps=16384, epochs=3,
        # phase 4: a width that fills lanes
        k_d_model=256, k_heads=4, k_lookback=1024, k_batch=8, k_features=16,
    ),
    # the CPU rehearsal and tests/test_chip_smoke.py only
    "tiny": dict(
        n_machines=8, n_tags=6, lookback=8, enc=(16, 8), dec=(8, 16),
        batch=64, n_timesteps=512, epochs=3,
        k_d_model=32, k_heads=2, k_lookback=32, k_batch=4, k_features=4,
    ),
}

# Stated float32 tolerances (max abs difference; model outputs live on the
# MinMax-scaled [0, 1] sensor range, the transformer's on unit-normal data).
#: stream updates vs the one-shot POST of the same rows: bit-identical on
#: the CPU (tests/test_streaming.py); on the chip the two dispatch shapes
#: are different compiled programs, so a float32 rounding margin
STREAM_ATOL = 1e-5
#: flash vs dense attention on the same params: on the chip XLA's default
#: float32 matmul is a single bf16 pass while the Mosaic kernel multiplies
#: in float32, so the bound is bf16-sized (5.6e-3 observed on a v5e at
#: outputs up to 2.9, PR 21)
FLASH_DENSE_ATOL = 5e-2
#: server (chip) vs the float32 host-CPU forward pass of the same artifact:
#: bf16-pass matmuls through 4 LSTM layers x 64 steps (2.2e-3 observed on
#: a v5e, PR 21)
PARITY_ATOL = 2e-2
#: 4-chip mesh vs one device: the same program, sharded — per-machine
#: arithmetic is unchanged, only the partitioning differs
MESH_LOSS_RTOL = 1e-3
MESH_PARAM_ATOL = 1e-3


class PhaseFailed(Exception):
    """A phase's check did not hold; the message says which, in words."""


def say(msg: str = "") -> None:
    print(msg, flush=True)


def check(condition, message: str) -> None:
    if not condition:
        raise PhaseFailed(message)


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

_live_children = []


def _kill_group(proc) -> None:
    """Stop a child and everything it started (its own process group)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=15)
    if proc in _live_children:
        _live_children.remove(proc)


def start_child(argv, log_path, env=None):
    """Spawn one child, stdout+stderr to ``log_path``. The process model's
    guard lives here: a parent that has JAX loaded may hold the chip."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "chip_smoke's parent process imported jax: it may hold the chip, "
            "and a child that needs it would fail or hang"
        )
    child_env = dict(os.environ if env is None else env)
    # children import the repo from the script's directory, wherever the
    # parent was started from
    child_env["PYTHONPATH"] = HERE + os.pathsep + child_env.get("PYTHONPATH", "")
    child_env.setdefault("TPU_STDERR_LOG_LEVEL", "3")
    log = open(log_path, "w")
    try:
        proc = subprocess.Popen(
            argv, cwd=HERE, env=child_env, stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
    finally:
        log.close()
    _live_children.append(proc)
    return proc


def run_child(name, argv, log_path, timeout_s, env=None) -> str:
    """Run one child to its end; returns its output. A non-zero exit or a
    timeout fails the phase, with the end of the child's output shown."""
    t0 = time.perf_counter()
    proc = start_child(argv, log_path, env=env)
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        raise PhaseFailed(
            f"{name} did not finish within {timeout_s:.0f}s "
            f"(killed)\n{_tail(log_path)}"
        )
    finally:
        _kill_group(proc)
    say(f"  [{name}] child exited rc={proc.returncode} "
        f"in {time.perf_counter() - t0:.1f}s (log: {log_path})")
    if proc.returncode != 0:
        raise PhaseFailed(
            f"{name} exited with code {proc.returncode}\n{_tail(log_path)}"
        )
    with open(log_path, errors="replace") as fh:
        return fh.read()


def _tail(path, n=25) -> str:
    try:
        with open(path, errors="replace") as fh:
            lines = fh.read().splitlines()
    except OSError:
        return "    (no output)"
    return "\n".join("    | " + line[:300] for line in lines[-n:])


def child_result(output: str) -> dict:
    """The ``RESULT {json}`` line a ``--phase`` child ends with."""
    for line in reversed(output.splitlines()):
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise PhaseFailed("the child printed no RESULT line")


def self_phase(phase, args, extra=()):
    return [
        sys.executable, os.path.join(HERE, "chip_smoke.py"), "--phase", phase,
        "--size", args.size, "--seed", str(args.seed), "--out", args.out,
        *extra,
    ]


# --------------------------------------------------------------------------
# the machines config
# --------------------------------------------------------------------------


def machine_names(size):
    return [f"plant-m{i}" for i in range(size["n_machines"])]


def machines_config(size, seed: int) -> list:
    """The flagship plant as build-fleet's MACHINES-CONFIG. RandomDataset
    derives each tag's series from the tag NAME, so the seed and the
    machine index go into the names: machines differ, runs repeat."""
    import datetime

    start = datetime.datetime(2019, 1, 1, tzinfo=datetime.timezone.utc)
    end = start + datetime.timedelta(minutes=10 * size["n_timesteps"])
    machines = []
    for i, name in enumerate(machine_names(size)):
        tags = [f"s{seed}-m{i}-tag-{j}" for j in range(size["n_tags"])]
        machines.append({
            "name": name,
            "project_name": PROJECT,
            "dataset": {
                "type": "RandomDataset",
                "tags": tags,
                "target_tag_list": tags,
                "train_start_date": start.isoformat(),
                "train_end_date": end.isoformat(),
                "resolution": "10T",
                # RandomDataset scatters 100-300 points per tag over the
                # window; interpolate across them however long it is
                # (the default 8H leaves no row where all 50 tags have
                # a value over 114 days)
                "interpolation_limit": "30D",
                "asset": "gra",
            },
            "model": {
                "gordo_tpu.models.anomaly.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {
                                    "gordo_tpu.models.LSTMAutoEncoder": {
                                        "kind": "lstm_model",
                                        "lookback_window": size["lookback"],
                                        "encoding_dim": list(size["enc"]),
                                        "encoding_func": ["tanh"] * len(size["enc"]),
                                        "decoding_dim": list(size["dec"]),
                                        "decoding_func": ["tanh"] * len(size["dec"]),
                                        "fused": True,
                                        "batch_size": size["batch"],
                                        "epochs": size["epochs"],
                                    }
                                },
                            ]
                        }
                    }
                }
            },
        })
    return machines


def request_rows(size, seed: int) -> dict:
    """The rows every serving check sends: machine -> (POST_ROWS, n_tags)
    nested lists in [0, 1), from the seed."""
    import random

    rng = random.Random(seed)
    return {
        name: [
            [rng.random() for _ in range(size["n_tags"])]
            for _ in range(POST_ROWS)
        ]
        for name in machine_names(size)
    }


# --------------------------------------------------------------------------
# phase 1: device
# --------------------------------------------------------------------------


def child_device(args) -> None:
    from importlib import metadata

    import jax

    from gordo_tpu.programs.cache import hbm_headroom

    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    versions = {}
    for dist in ("jax", "jaxlib", "libtpu", "flax"):
        try:
            versions[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            versions[dist] = None
    print("RESULT " + json.dumps({
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
        "versions": versions,
        "memory_stats_keys": sorted(stats),
        "bytes_limit": stats.get("bytes_limit"),
        "hbm_headroom": hbm_headroom(),
    }), flush=True)


def phase_device(args, ctx) -> None:
    out = run_child(
        "device", self_phase("device", args),
        os.path.join(args.out, "device.log"), 180,
    )
    info = child_result(out)
    ctx["device"] = {k: info[k] for k in ("platform", "kind", "count")}
    ctx["memory_stats"] = bool(info["memory_stats_keys"])
    say(f"  platform={info['platform']} device_kind={info['kind']!r} "
        f"count={info['count']}")
    say("  versions: " + " ".join(
        f"{k}={v}" for k, v in info["versions"].items()))
    say(f"  memory_stats keys: {info['memory_stats_keys'] or 'none'}")
    say(f"  bytes_limit={info['bytes_limit']} "
        f"hbm_headroom()={info['hbm_headroom']}")
    check(
        info["platform"] == "tpu",
        f"JAX's platform is {info['platform']!r} "
        f"({info['kind']}), not 'tpu': nothing below ran on the chip",
    )
    check(info["count"] == args.chips,
          f"expected {args.chips} device(s), JAX reports {info['count']}")
    check(info["bytes_limit"],
          "device.memory_stats() reports no bytes_limit on the chip")
    check(
        isinstance(info["hbm_headroom"], float)
        and 0.0 < info["hbm_headroom"] <= 1.0,
        f"hbm_headroom() returned {info['hbm_headroom']!r}, not a fraction",
    )


# --------------------------------------------------------------------------
# phase 2: build
# --------------------------------------------------------------------------


def _load_json(path):
    with open(path) as fh:
        return json.load(fh)


def _machine_losses(collection, name):
    meta = _load_json(os.path.join(collection, name, "metadata.json"))
    model = meta["metadata"]["build_metadata"]["model"]
    return [float(v) for v in model["model_meta"]["history"]["loss"]]


def phase_build(args, ctx) -> None:
    size = SIZES[args.size]
    collection = os.path.join(args.out, PROJECT, "models", "rev-smoke")
    shutil.rmtree(os.path.join(args.out, PROJECT), ignore_errors=True)
    config_path = os.path.join(args.out, "machines.json")
    with open(config_path, "w") as fh:
        json.dump(machines_config(size, args.seed), fh)
    events = os.path.join(args.out, "build_events.jsonl")
    if os.path.exists(events):
        os.remove(events)
    run_child(
        "build-fleet",
        [sys.executable, "-m", "gordo_tpu.cli", "build-fleet",
         "--machines-from", config_path],
        os.path.join(args.out, "build.log"), 700,
        env=dict(os.environ, OUTPUT_DIR=collection,
                 GORDO_TPU_EVENT_LOG=events),
    )
    ctx["collection"] = collection

    names = machine_names(size)
    for name in names:
        for artifact in ("model.pkl", "metadata.json"):
            check(os.path.exists(os.path.join(collection, name, artifact)),
                  f"{name} has no {artifact}")
    report = _load_json(os.path.join(collection, "build_report.json"))
    check(
        report["n_built"] == len(names) and not report["failed"]
        and not report["quarantined"],
        f"build_report.json names casualties: built {report['n_built']} of "
        f"{len(names)}, failed {report['failed']}, "
        f"quarantined {report['quarantined']}",
    )
    for name in names:
        losses = _machine_losses(collection, name)
        check(all(math.isfinite(v) for v in losses),
              f"{name}: non-finite training loss {losses}")
        check(losses[-1] < losses[0],
              f"{name}: training loss did not fall: {losses}")
    first = _machine_losses(collection, names[0])
    say(f"  {len(names)} artifacts, no casualties; losses finite and fell "
        f"(e.g. {names[0]}: {first[0]:.5f} -> {first[-1]:.5f})")

    manifest = _load_json(
        os.path.join(collection, ".programs", "manifest.json"))
    device = ctx.get("device") or {}
    say(f"  .programs/manifest.json: backend={manifest['backend']} "
        f"device_kind={manifest['device_kind']!r} "
        f"programs={len(manifest['programs'])}")
    check(
        manifest["backend"] == device.get("platform")
        and manifest["device_kind"] == device.get("kind"),
        f"the AOT manifest says {manifest['backend']}/"
        f"{manifest['device_kind']!r} but the device phase saw "
        f"{device.get('platform')}/{device.get('kind')!r}",
    )
    check(manifest["programs"], "the build exported no AOT serving programs")
    ctx["n_aot_programs"] = len(manifest["programs"])

    telemetry = _load_json(os.path.join(collection, "telemetry_report.json"))
    memory = telemetry["device_memory"]
    say(f"  telemetry memory watermarks: available={memory['available']} "
        f"peak_bytes_in_use={memory['peak_bytes_in_use']} "
        f"bytes_in_use={memory['bytes_in_use']}")
    # the watermarks must say what the device says: a backend that reports
    # memory stats (the chip; phase 1 requires it there) must not come out
    # of the build as null
    reports_memory = bool(ctx.get("memory_stats"))
    check(
        bool(memory["available"]) == reports_memory
        and (memory["peak_bytes_in_use"] is not None) == reports_memory,
        "telemetry_report.json's memory watermarks disagree with "
        f"device.memory_stats(): {memory['available']=}, "
        f"{memory['peak_bytes_in_use']=}",
    )
    bucket = telemetry["buckets"][0]
    fit = bucket["fit"]
    say("  wall split (information, not a metric): "
        f"build {telemetry['wall_time_s']:.1f}s = cv "
        f"{bucket['cv_duration_s']:.1f}s + fit {bucket['fit_duration_s']:.1f}s"
        f" + rest; final fit: first epoch incl. compile "
        f"{fit['first_epoch_s']:.2f}s (compile ~{fit['compile_time_s']:.2f}s)"
        f", steady epoch {fit['steady_state_epoch_s']:.3f}s, "
        f"{fit['n_dispatches']} dispatches / {fit['n_host_syncs']} host syncs")
    cache = telemetry["compile_cache"]
    say(f"  compile cache during build: {cache['start_bytes']} -> "
        f"{cache['end_bytes']} bytes")

    # one identical compile again, in a fresh process: `programs compile`
    # re-exports the same serving programs (on a copy, so the store the
    # server maps in stays the build's own). JAX_LOG_COMPILES raises the
    # persistent cache's hit line to WARNING. Whether it hits is printed,
    # not judged: a program that compiles under enable_compile_cache's
    # 0.5 s persistence threshold (the tiny size) is never written. What
    # is judged is that both processes resolved ONE cache directory.
    copy = os.path.join(args.out, "recompile", "models", "rev-smoke")
    shutil.rmtree(os.path.join(args.out, "recompile"), ignore_errors=True)
    shutil.copytree(collection, copy)
    recompile_events = os.path.join(args.out, "recompile_events.jsonl")
    if os.path.exists(recompile_events):
        os.remove(recompile_events)
    out = run_child(
        "programs-compile",
        [sys.executable, "-m", "gordo_tpu.cli", "programs", "compile", copy],
        os.path.join(args.out, "recompile.log"), 400,
        env=dict(os.environ, JAX_LOG_COMPILES="1",
                 GORDO_TPU_EVENT_LOG=recompile_events),
    )
    hits = out.count("Persistent compilation cache hit")
    directories = [
        [e["directory"] for e in _read_events(path)
         if e["event"] == "compile_cache_enabled"]
        for path in (events, recompile_events)
    ]
    say(f"  second identical compile: {hits} persistent-cache hit(s) while "
        f"re-exporting {ctx['n_aot_programs']} program(s) -> "
        f"{'HIT' if hits >= ctx['n_aot_programs'] else 'MISS'}; cache at "
        f"{directories[0][-1] if directories[0] else None}"
        + (" (on the CPU backend exports compile past the cache: "
           "programs.aot.fresh_compile)"
           if device.get("platform") == "cpu" else ""))
    check(
        directories[0] and directories[1]
        and set(directories[0]) == set(directories[1])
        and len(set(directories[0])) == 1,
        f"the build and the re-compile did not share one compile cache "
        f"directory: {directories}",
    )


# --------------------------------------------------------------------------
# phase 3: serve
# --------------------------------------------------------------------------


def _http(url, body=None, timeout=300):
    data = None if body is None else json.dumps(body).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(request, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, {"error": exc.read()[:400].decode(errors="replace")}, {}


def _frame_rows(block) -> list:
    """A response frame block ({column: {row: value}}) as rows x columns
    nested lists, rows in index order."""
    columns = list(block)
    index = sorted(block[columns[0]], key=int)
    return [[block[c][i] for c in columns] for i in index]


def _max_abs_diff(a, b) -> float:
    check(len(a) == len(b) and len(a) > 0,
          f"row counts differ: {len(a)} vs {len(b)}")
    return max(
        abs(x - y) for row_a, row_b in zip(a, b) for x, y in zip(row_a, row_b)
    )


def _all_finite(rows) -> bool:
    return all(
        v is not None and math.isfinite(v) for row in rows for v in row)


def _read_events(path) -> list:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def phase_serve(args, ctx) -> None:
    check("collection" in ctx, "blocked: the build phase left no collection")
    size = SIZES[args.size]
    names = machine_names(size)
    rows = request_rows(size, args.seed)
    events_path = os.path.join(args.out, "serve_events.jsonl")
    if os.path.exists(events_path):
        os.remove(events_path)
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    server = start_child(
        [sys.executable, "-m", "gordo_tpu.cli", "run-server",
         "--host", "127.0.0.1", "--port", str(port), "--log-level", "warning"],
        os.path.join(args.out, "serve.log"),
        env=dict(os.environ, MODEL_COLLECTION_DIR=ctx["collection"],
                 GORDO_SERVER_PRELOAD="true",
                 GORDO_TPU_EVENT_LOG=events_path),
    )
    base = f"http://127.0.0.1:{port}/gordo/v0/{PROJECT}"
    try:
        t0 = time.perf_counter()
        while True:
            check(server.poll() is None,
                  f"run-server exited with code {server.returncode} before "
                  f"answering\n{_tail(os.path.join(args.out, 'serve.log'))}")
            check(time.perf_counter() - t0 < 400,
                  "run-server did not answer within 400s\n"
                  + _tail(os.path.join(args.out, "serve.log")))
            try:
                status, listing, _ = _http(base + "/models", timeout=5)
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
                continue
            if status == 200:
                break
            time.sleep(0.5)
        say(f"  run-server up with preload in {time.perf_counter() - t0:.1f}s"
            f"; serves {len(listing['models'])} machines")
        check(sorted(listing["models"]) == sorted(names),
              f"/models lists {listing['models']}")

        # a few fleet POSTs: 8 machines x 256 rows
        outputs = None
        for i in range(3):
            status, body, headers = _http(
                base + "/prediction/fleet", {"machines": rows})
            check(status == 200, f"fleet POST {i} answered {status}: {body}")
            outputs = {
                name: _frame_rows(body["data"][name]["model-output"])
                for name in names
            }
            for name in names:
                check(
                    len(outputs[name]) == POST_ROWS - size["lookback"] + 1
                    and len(outputs[name][0]) == size["n_tags"]
                    and _all_finite(outputs[name]),
                    f"fleet POST {i}: {name}'s model-output is not "
                    f"{POST_ROWS - size['lookback'] + 1} finite rows x "
                    f"{size['n_tags']}",
                )
            say(f"  fleet POST {i}: 200, {len(names)} x "
                f"{len(outputs[names[0]])} x {size['n_tags']} finite; "
                f"Server-Timing {headers.get('Server-Timing', '')}")
        ctx["served_output"] = outputs[names[0]]

        # one single-machine anomaly POST
        status, body, _ = _http(
            base + f"/{names[0]}/anomaly/prediction",
            {"X": rows[names[0]], "y": rows[names[0]]})
        check(status == 200, f"anomaly POST answered {status}: {body}")
        total = _frame_rows(body["data"]["total-anomaly-scaled"])
        check(len(total) == POST_ROWS - size["lookback"] + 1
              and _all_finite(total),
              "the anomaly POST's total-anomaly-scaled is not finite")
        say(f"  anomaly POST {names[0]}: 200, {len(total)} finite "
            f"total-anomaly-scaled scores (max {max(max(r) for r in total):.3f})")

        # one stream session, three updates, every machine: the
        # concatenated scores must equal the one-shot POST of the same rows
        status, opened, _ = _http(base + "/stream/open", {"machines": names})
        check(status == 201, f"stream open answered {status}: {opened}")
        sid = opened["session"]
        streamed = {name: [] for name in names}
        seq = {name: 0 for name in names}
        offset = 0
        for k in STREAM_CHUNKS:
            updates = {
                name: {"rows": rows[name][offset:offset + k], "seq": seq[name]}
                for name in names
            }
            status, body, _ = _http(
                base + f"/stream/{sid}/update", {"updates": updates})
            check(status == 200, f"stream update answered {status}: {body}")
            for name in names:
                result = body["scores"][name]
                streamed[name].extend(result["rows"])
                seq[name] = result["seq"]
            offset += k
        status, body, _ = _http(base + f"/stream/{sid}/close", {})
        check(status == 200, f"stream close answered {status}: {body}")
        worst = max(
            _max_abs_diff(streamed[name], outputs[name]) for name in names)
        say(f"  stream open/3 updates/close: {len(streamed[names[0]])} scores"
            f" per machine; vs the one-shot POST max |diff| = {worst:.3g} "
            f"(bit-identical: {worst == 0.0}; tolerance {STREAM_ATOL})")
        check(all(_all_finite(streamed[name]) for name in names),
              "stream scores are not finite")
        check(worst <= STREAM_ATOL,
              f"stream scores differ from the one-shot POST by {worst:.3g} "
              f"> {STREAM_ATOL}")
    finally:
        _kill_group(server)

    events = _read_events(events_path)
    aot_hits = [e for e in events if e["event"] == "program_cache_hit"
                and e.get("outcome") == "aot"]
    fallbacks = [e for e in events if e["event"] == "program_cache_fallback"]
    say(f"  event log: {len(aot_hits)} program_cache_hit outcome=aot, "
        f"{len(fallbacks)} program_cache_fallback")
    check(len(aot_hits) == ctx["n_aot_programs"],
          f"expected {ctx['n_aot_programs']} preloaded AOT executables, the "
          f"event log shows {len(aot_hits)}")
    check(not fallbacks,
          "the store built on this device did not serve from it: "
          + "; ".join(f"{e.get('outcome')} {e.get('key')}" for e in fallbacks))


# --------------------------------------------------------------------------
# phase 4: kernel
# --------------------------------------------------------------------------


def child_kernel(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gordo_tpu.models.factories.transformer import transformer_model
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

    size = SIZES[args.size]
    lookback, batch, n_features = (
        size["k_lookback"], size["k_batch"], size["k_features"])
    n_machines, steps, epochs = 2, 3, 2
    n = lookback + batch * steps - 1  # `steps` full batches of windows

    def spec(attention_impl):
        return transformer_model(
            n_features=n_features, lookback_window=lookback,
            d_model=size["k_d_model"], n_heads=size["k_heads"], n_layers=2,
            dropout=0.0, attention_impl=attention_impl,
        )

    rng = np.random.default_rng(args.seed)
    Xs = [rng.standard_normal((n, n_features)).astype("float32")
          for _ in range(n_machines)]
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    trainer = FleetTrainer(spec("flash"), lookahead=0)
    keys = trainer.machine_keys(n_machines, seed=args.seed)
    params, losses = trainer.fit(
        data, keys, epochs=epochs, batch_size=batch, shuffle=True)
    losses = np.asarray(losses)

    # the compiled step itself: the same epoch program fit dispatched
    _, valid = trainer._fit_facts(data.sample_weight)
    epoch_fn = trainer._epoch_fn(
        data.n_timesteps, batch, True,
        sample_cap=max(1, int(np.max(valid))),
        quarantine=True,
    )
    text = epoch_fn.lower(
        params, trainer.init_opt_state(params), keys, data.X, data.y,
        data.sample_weight, jnp.ones(n_machines, dtype=bool),
    ).compile().as_text()

    flash_out = np.asarray(trainer.predict(params, data.X))
    dense_out = np.asarray(
        FleetTrainer(spec("dense"), lookahead=0).predict(params, data.X))
    print("RESULT " + json.dumps({
        "platform": jax.devices()[0].platform,
        "losses": losses.tolist(),
        "n_custom_calls": text.count("tpu_custom_call"),
        "output_shape": list(flash_out.shape),
        "finite": bool(np.isfinite(flash_out).all()
                       and np.isfinite(losses).all()),
        "flash_dense_max_abs_diff": float(np.abs(flash_out - dense_out).max()),
        "dense_max_abs": float(np.abs(dense_out).max()),
    }), flush=True)


def phase_kernel(args, ctx) -> None:
    size = SIZES[args.size]
    out = run_child(
        "kernel", self_phase("kernel", args),
        os.path.join(args.out, "kernel.log"), 500,
    )
    info = child_result(out)
    say(f"  2-layer TransformerNet attention_impl=flash d_model="
        f"{size['k_d_model']} heads={size['k_heads']} lookback="
        f"{size['k_lookback']}: {len(info['losses'])} epochs x 3 steps "
        f"through FleetTrainer, losses {info['losses']}")
    say(f"  compiled epoch program: {info['n_custom_calls']} tpu_custom_call;"
        f" flash vs dense on the same params: max |diff| = "
        f"{info['flash_dense_max_abs_diff']:.3g} (outputs up to "
        f"{info['dense_max_abs']:.3g}; tolerance {FLASH_DENSE_ATOL})")
    check(info["finite"], "flash-attention training produced non-finite values")
    check(info["flash_dense_max_abs_diff"] <= FLASH_DENSE_ATOL,
          f"flash and dense attention differ by "
          f"{info['flash_dense_max_abs_diff']:.3g} > {FLASH_DENSE_ATOL}")
    check(info["n_custom_calls"] > 0,
          "the compiled step's text has no tpu_custom_call: the Pallas "
          f"kernel ran in the interpreter (platform {info['platform']!r}), "
          "not as a Mosaic kernel")


# --------------------------------------------------------------------------
# phase 5: parity
# --------------------------------------------------------------------------


def child_parity(args) -> None:
    """Runs with JAX_PLATFORMS=cpu: the artifact loaded back through the
    serializer and scored in float32 on the host CPU backend."""
    import jax
    import numpy as np

    from gordo_tpu import serializer

    size = SIZES[args.size]
    name = machine_names(size)[0]
    collection = os.path.join(args.out, PROJECT, "models", "rev-smoke")
    model = serializer.load(os.path.join(collection, name))
    X = np.asarray(request_rows(size, args.seed)[name], dtype="float32")
    # the anomaly detector's base estimator IS the served model-output
    output = np.asarray(model.base_estimator.predict(X), dtype="float32")
    print("RESULT " + json.dumps({
        "platform": jax.devices()[0].platform,
        "output": output.tolist(),
    }), flush=True)


def phase_parity(args, ctx) -> None:
    check("served_output" in ctx,
          "blocked: the serve phase left no model-output to compare")
    out = run_child(
        "parity", self_phase("parity", args),
        os.path.join(args.out, "parity.log"), 300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    info = child_result(out)
    check(info["platform"] == "cpu",
          f"the reference ran on {info['platform']!r}, not the host CPU")
    diff = _max_abs_diff(info["output"], ctx["served_output"])
    say(f"  {machine_names(SIZES[args.size])[0]} through the serializer, "
        f"float32 on the host CPU: {len(info['output'])} rows; vs the "
        f"server's model-output max |diff| = {diff:.3g} "
        f"(tolerance {PARITY_ATOL})")
    check(diff <= PARITY_ATOL,
          f"the server's answer is {diff:.3g} from the float32 reference "
          f"> {PARITY_ATOL}")


# --------------------------------------------------------------------------
# --chips 4: the fleet mesh against one device
# --------------------------------------------------------------------------


def child_mesh(args) -> None:
    import jax
    import numpy as np

    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import FleetModelBuilder
    from gordo_tpu.machine import Machine
    from gordo_tpu.parallel.fleet import FleetTrainer
    from gordo_tpu.utils import enable_compile_cache

    enable_compile_cache()
    size = SIZES[args.size]
    devices = jax.devices()
    observed = {"n_devices_spanned": [], "bytes_in_use": None}

    # observation only: the builder unstacks the fleet's params to the
    # host before it returns, so the sharding is read where it exists
    real_fit = FleetTrainer.fit

    def watched_fit(self, *fit_args, **fit_kwargs):
        params, losses = real_fit(self, *fit_args, **fit_kwargs)
        leaf = jax.tree.leaves(params)[0]
        observed["n_devices_spanned"].append(len(leaf.sharding.device_set))
        observed["bytes_in_use"] = [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        return params, losses

    FleetTrainer.fit = watched_fit

    def build(tag, **builder_kwargs):
        machines = []
        for config in machines_config(size, args.seed):
            machine = Machine.from_config(config, project_name=PROJECT)
            machine.model = serializer.into_definition(
                serializer.from_definition(machine.model))
            machines.append(machine)
        observed["n_devices_spanned"].clear()
        t0 = time.perf_counter()
        built = FleetModelBuilder(machines, **builder_kwargs).build(
            output_dir_base=os.path.join(args.out, f"mesh-{tag}"))
        wall = time.perf_counter() - t0
        per_machine = {}
        for model, machine in built:
            estimator = model.base_estimator.steps[-1][1]
            leaves = jax.tree.leaves(estimator.params_)
            per_machine[machine.name] = {
                "final_loss": float(estimator.history_["loss"][-1]),
                "params": [np.asarray(leaf, dtype="float64") for leaf in leaves],
            }
        return {
            "wall_s": wall,
            "spanned": sorted(set(observed["n_devices_spanned"])),
            "bytes_in_use": observed["bytes_in_use"],
            "machines": per_machine,
        }

    sharded = build("sharded", auto_mesh=True)
    single = build("single")
    worst_loss = worst_param = 0.0
    for name, one in single["machines"].items():
        many = sharded["machines"][name]
        worst_loss = max(
            worst_loss,
            abs(many["final_loss"] - one["final_loss"])
            / max(abs(one["final_loss"]), 1e-12),
        )
        for a, b in zip(many["params"], one["params"]):
            worst_param = max(worst_param, float(np.abs(a - b).max()))
    print("RESULT " + json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "sharded_spanned": sharded["spanned"],
        "single_spanned": single["spanned"],
        "sharded_bytes_in_use": sharded["bytes_in_use"],
        "single_bytes_in_use": single["bytes_in_use"],
        "sharded_wall_s": sharded["wall_s"],
        "single_wall_s": single["wall_s"],
        "final_losses_sharded": {
            k: v["final_loss"] for k, v in sharded["machines"].items()},
        "final_losses_single": {
            k: v["final_loss"] for k, v in single["machines"].items()},
        "params_checksum_sharded": {
            k: float(sum(np.abs(p).sum() for p in v["params"]))
            for k, v in sharded["machines"].items()},
        "params_checksum_single": {
            k: float(sum(np.abs(p).sum() for p in v["params"]))
            for k, v in single["machines"].items()},
        "worst_loss_rel_diff": worst_loss,
        "worst_param_abs_diff": worst_param,
    }), flush=True)


def phase_mesh(args, ctx) -> None:
    out = run_child(
        "mesh", self_phase("mesh", args),
        os.path.join(args.out, "mesh.log"), 1100,
    )
    info = child_result(out)
    ctx["device"] = {k: info[k] for k in ("platform", "kind", "count")}
    say(f"  platform={info['platform']} device_kind={info['kind']!r} "
        f"count={info['count']}")
    say(f"  sharded build ({info['sharded_wall_s']:.1f}s): stacked params "
        f"span {info['sharded_spanned']} device(s); per-device bytes_in_use "
        f"after the fit: {info['sharded_bytes_in_use']}")
    say(f"  one-device build ({info['single_wall_s']:.1f}s): stacked params "
        f"span {info['single_spanned']} device(s); per-device bytes_in_use "
        f"after the fit: {info['single_bytes_in_use']}")
    for name in sorted(info["final_losses_single"]):
        say(f"    {name}: final loss sharded "
            f"{info['final_losses_sharded'][name]:.6f} / single "
            f"{info['final_losses_single'][name]:.6f}; params checksum "
            f"{info['params_checksum_sharded'][name]:.4f} / "
            f"{info['params_checksum_single'][name]:.4f}")
    say(f"  worst final-loss relative diff {info['worst_loss_rel_diff']:.3g} "
        f"(tolerance {MESH_LOSS_RTOL}); worst param abs diff "
        f"{info['worst_param_abs_diff']:.3g} (tolerance {MESH_PARAM_ATOL})")
    check(info["sharded_spanned"] == [4],
          f"the sharded build's stacked params span "
          f"{info['sharded_spanned']} devices, not 4")
    check(info["single_spanned"] == [1],
          f"the one-device build spans {info['single_spanned']} devices")
    check(info["worst_loss_rel_diff"] <= MESH_LOSS_RTOL,
          "sharded and one-device final losses differ")
    check(info["worst_param_abs_diff"] <= MESH_PARAM_ATOL,
          "sharded and one-device params differ")
    say("  comparison: sharding spans 4 devices, losses and params within "
        "tolerance")
    check(info["count"] == 4, f"JAX reports {info['count']} devices, not 4")
    check(info["platform"] == "tpu",
          f"JAX's platform is {info['platform']!r} ({info['kind']}), not "
          "'tpu': the comparison above did not run on chips")


# --------------------------------------------------------------------------
# the parent
# --------------------------------------------------------------------------

CHILD_PHASES = {
    "device": child_device,
    "kernel": child_kernel,
    "parity": child_parity,
    "mesh": child_mesh,
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--size", choices=sorted(SIZES), default="full",
        help="'tiny' is for the CPU rehearsal and the test only")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run ONLY the fleet-mesh build against the one-device build")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--out", default=os.path.join(HERE, "chip_smoke_out"),
        help="working directory (artifacts, logs)")
    parser.add_argument("--phase", choices=sorted(CHILD_PHASES),
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    args.out = os.path.abspath(args.out)
    if args.phase:
        CHILD_PHASES[args.phase](args)
        return 0

    os.makedirs(args.out, exist_ok=True)
    phases = (
        [("mesh", phase_mesh)] if args.chips == 4 else
        [("device", phase_device), ("build", phase_build),
         ("serve", phase_serve), ("kernel", phase_kernel),
         ("parity", phase_parity)]
    )
    ctx: dict = {}
    verdicts = {}
    t_start = time.perf_counter()
    try:
        for i, (name, phase) in enumerate(phases, 1):
            say(f"== phase {i} {name}")
            t0 = time.perf_counter()
            try:
                phase(args, ctx)
                verdicts[name] = "PASS"
            except PhaseFailed as exc:
                verdicts[name] = "FAIL"
                say(f"  FAIL: {exc}")
            except Exception:  # noqa: BLE001 - a broken phase fails the run
                verdicts[name] = "FAIL"
                say("  FAIL: the phase raised\n" + traceback.format_exc())
            say(f"== phase {i} {name}: {verdicts[name]} "
                f"({time.perf_counter() - t0:.1f}s)")
    finally:
        for proc in list(_live_children):
            _kill_group(proc)
    ok = all(v == "PASS" for v in verdicts.values())
    say("summary: " + " ".join(f"{k}={v}" for k, v in verdicts.items())
        + f" total {time.perf_counter() - t_start:.1f}s")
    device = ctx.get("device") or {
        "platform": None, "kind": None, "count": 0}
    say(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
