"""
Calibration sweeps: when a fleet has NO recorded telemetry corpus yet,
``gordo-tpu tune calibrate`` measures one — a ``--batch-wait-ms`` sweep
against an in-process server under open-loop Poisson load
(``benchmarks/load_test.py``'s ``--open-loop`` machinery, used as a
library).

The sweep result is written as an ordinary corpus file
(``results_calibration.json``, stamped ``bench_schema_version``) so the
corpus reader ingests it like any recorded telemetry — calibration is
just a way of growing a corpus, not a separate code path into the cost
model.
"""

import logging
import sys
import typing
from datetime import datetime, timezone
from pathlib import Path

from gordo_tpu.utils.atomic import atomic_write_json

logger = logging.getLogger(__name__)

BENCH_SCHEMA_VERSION = 1
CALIBRATION_FILENAME = "results_calibration.json"


class CalibrationUnavailable(RuntimeError):
    """The benchmarks/ directory (the sweep machinery lives there, next
    to the repo) is not importable in this deployment."""


def _bench_module(name: str):
    """Import ``benchmarks.<name>`` from the repo checkout (benchmarks/
    sits beside the gordo_tpu package, not inside it)."""
    import gordo_tpu

    repo_root = str(Path(gordo_tpu.__file__).parent.parent)
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    try:
        import importlib

        return importlib.import_module(f"benchmarks.{name}")
    except ImportError as exc:
        raise CalibrationUnavailable(
            f"benchmarks/{name}.py is not importable ({exc}); calibration "
            f"needs the repo checkout's benchmarks/ directory"
        )


def batch_wait_calibration(
    waits_ms: typing.Sequence[float],
    rps: float = 20.0,
    duration: float = 5.0,
    n_machines: int = 2,
    queue_limit: int = 64,
    port: int = 5617,
    model: str = "hourglass",
) -> typing.List[dict]:
    """
    One open-loop arm per ``--batch-wait-ms`` candidate against an
    in-process server over a shared throwaway collection. Each arm
    records request p50/p99 plus the batching registry's queue-wait and
    batch-size HISTOGRAMS — the evidence rows `tune plan` shows — with
    the registry reset between arms so histograms do not bleed across.
    """
    import json as _json
    import os
    import tempfile
    import threading

    from werkzeug.serving import make_server

    from gordo_tpu.observability import get_registry

    load_test = _bench_module("load_test")
    server_latency = _bench_module("server_latency")
    from gordo_tpu.server import build_app

    arms: typing.List[dict] = []
    previous_collection = os.environ.get("MODEL_COLLECTION_DIR")
    try:
        with tempfile.TemporaryDirectory(prefix="gordo-tune-calibrate-") as tmp:
            collection = server_latency.build_collection(n_machines, tmp, model)
            os.environ["MODEL_COLLECTION_DIR"] = collection
            machines = sorted(os.listdir(collection))
            # the fleet route's JSON shape: one frame (tag -> column) per
            # machine under a "machines" mapping
            rows = [[0.1, 0.2, 0.3, 0.4]] * 8
            frame = {
                f"tag-{i}": [row[i] for row in rows] for i in range(len(rows[0]))
            }
            body = _json.dumps(
                {"machines": {name: frame for name in machines}}
            ).encode()
            url_path = "/gordo/v0/proj/prediction/fleet"
            for index, wait_ms in enumerate(waits_ms):
                get_registry().reset()
                app = build_app(
                    {
                        "BATCH_WAIT_MS": float(wait_ms),
                        "BATCH_QUEUE_LIMIT": queue_limit,
                    }
                )
                server = make_server(
                    "127.0.0.1", port + index, app, threaded=True
                )
                threading.Thread(
                    target=server.serve_forever, daemon=True
                ).start()
                try:
                    latencies, errors, sheds, partials, elapsed = (
                        load_test.open_loop(
                            f"http://127.0.0.1:{port + index}{url_path}",
                            body,
                            rps=rps,
                            duration=duration,
                            seed=7,
                        )
                    )
                finally:
                    server.shutdown()
                snap = get_registry().snapshot()
                arm = {
                    "batch_wait_ms": float(wait_ms),
                    "queue_limit": queue_limit,
                    "requests": len(latencies),
                    "errors": len(errors),
                    "sheds": len(sheds),
                    "partials": len(partials),
                    "achieved_rps": (
                        round(len(latencies) / elapsed, 2) if elapsed else 0.0
                    ),
                }
                if latencies:
                    ordered = sorted(latencies)
                    arm["p50_ms"] = round(ordered[len(ordered) // 2], 3)
                    arm["p99_ms"] = round(
                        ordered[min(len(ordered) - 1, int(len(ordered) * 0.99))],
                        3,
                    )
                # raw histograms ride along: the corpus reader derives
                # queue_wait_p99_ms / mean_batch_size from these
                for metric in (
                    "gordo_serve_batch_queue_wait_seconds",
                    "gordo_serve_batch_requests",
                ):
                    if metric in snap:
                        arm[metric] = snap[metric]
                arms.append(arm)
    finally:
        # the sweep serves a throwaway collection through the env var;
        # the caller's value (or its absence) must survive the sweep
        if previous_collection is None:
            os.environ.pop("MODEL_COLLECTION_DIR", None)
        else:
            os.environ["MODEL_COLLECTION_DIR"] = previous_collection
    return arms


def run_calibration(
    output_dir: typing.Union[str, Path],
    batch_wait_sweep: typing.Sequence[float],
    rps: float = 20.0,
    duration: float = 5.0,
) -> typing.Tuple[Path, dict]:
    """Run the sweep and publish ``results_calibration.json`` under
    ``output_dir``; returns (path, payload)."""
    payload: dict = {
        "bench_schema_version": BENCH_SCHEMA_VERSION,
        "kind": "tune_calibration",
        "generated": datetime.now(timezone.utc).isoformat(),
        "batch_wait_sweep": batch_wait_calibration(
            batch_wait_sweep, rps=rps, duration=duration
        ),
    }
    path = Path(output_dir) / CALIBRATION_FILENAME
    atomic_write_json(path, payload, indent=2, sort_keys=True)
    logger.info("Calibration written to %s", path)
    return path, payload
