"""
Pre-fork server runner tests (the reference tunes gunicorn with
--workers/--threads/--worker-connections, gordo/server/server.py:230-294;
this stack must provably honor the same knobs natively).
"""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest
import requests

from gordo_tpu.server.runner import ConcurrencyGate, ServerRunner


class _Recorder:
    """WSGI app that sleeps and records how many requests run at once."""

    def __init__(self, hold_s=0.15):
        self.hold_s = hold_s
        self.active = 0
        self.max_active = 0
        self._lock = threading.Lock()

    def __call__(self, environ, start_response):
        with self._lock:
            self.active += 1
            self.max_active = max(self.max_active, self.active)
        time.sleep(self.hold_s)
        with self._lock:
            self.active -= 1
        start_response("200 OK", [("Content-Type", "text/plain")])
        return [b"ok"]


def _serve_and_fire(runner: ServerRunner, n_requests: int) -> None:
    """Serve ``runner`` in a thread and hit it with parallel requests."""
    sock = socket.create_server(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    server = runner.build_server(fd=sock.fileno())
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        workers = [
            threading.Thread(
                target=lambda: requests.get(
                    f"http://127.0.0.1:{port}/", timeout=10
                )
            )
            for _ in range(n_requests)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
    finally:
        server.shutdown()
        sock.close()


def test_threads_bound_concurrent_handling():
    app = _Recorder()
    runner = ServerRunner(lambda: app, "127.0.0.1", 0, workers=1, threads=2)
    _serve_and_fire(runner, n_requests=8)
    assert app.max_active <= 2
    # sanity: the gate allowed some parallelism, it didn't serialize
    assert app.max_active == 2


def test_worker_connections_bound_acceptance():
    app = _Recorder()
    runner = ServerRunner(
        lambda: app, "127.0.0.1", 0, workers=1, threads=None, worker_connections=1
    )
    _serve_and_fire(runner, n_requests=4)
    assert app.max_active == 1


def test_unbounded_without_limits():
    app = _Recorder()
    runner = ServerRunner(lambda: app, "127.0.0.1", 0, workers=1, threads=None)
    _serve_and_fire(runner, n_requests=6)
    assert app.max_active > 2


def test_concurrency_gate_releases_on_app_error():
    def exploding(environ, start_response):
        raise RuntimeError("boom")

    gate = ConcurrencyGate(exploding, 1)
    for _ in range(3):  # a leaked slot would deadlock the second call
        with pytest.raises(RuntimeError):
            gate({}, lambda *a: None)
    assert gate._slots.acquire(blocking=False)
    gate._slots.release()


_MULTIWORKER_SCRIPT = """
from gordo_tpu.server.app import run_server
run_server("127.0.0.1", {port}, workers=2, log_level="warning", threads=4)
"""


def test_prefork_workers_share_socket(tmp_path):
    """workers=2 provably changes the process model: two pids serve."""
    collection = tmp_path / "proj" / "models" / "rev-1"
    collection.mkdir(parents=True)
    probe = socket.create_server(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()

    env = dict(os.environ)
    env["MODEL_COLLECTION_DIR"] = str(collection)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", _MULTIWORKER_SCRIPT.format(port=port)],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        url = f"http://127.0.0.1:{port}/gordo/v0/proj/models"
        pids = set()
        deadline = time.time() + 60
        while time.time() < deadline and len(pids) < 2:
            try:
                response = requests.get(url, timeout=5)
            except requests.ConnectionError:
                time.sleep(0.3)
                continue
            assert response.status_code == 200
            pids.add(response.headers.get("X-Gordo-Server-Pid"))
        assert len(pids) >= 2, f"expected >=2 serving pids, saw {pids}"
        assert str(proc.pid) not in pids  # parent supervises, workers serve

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=20) is not None
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
