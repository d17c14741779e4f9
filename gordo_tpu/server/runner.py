"""
Production serving runner.

The reference runs its model server under gunicorn with worker/thread
tuning (gordo/server/server.py:230-294: gthread workers, --threads,
--worker-connections). gunicorn is not available in this stack, so the
same knobs are honored natively:

- ``workers``  — pre-forked processes sharing ONE listening socket (the
  parent binds, children inherit the fd, the kernel load-balances
  accepts). The parent supervises and restarts crashed workers.
- ``threads``  — per-worker bound on concurrently *handled* requests,
  enforced by a semaphore gate around the WSGI app.
- ``worker_connections`` — per-worker bound on simultaneously *accepted*
  connections (handled + queued-behind-the-gate).

One worker short-circuits the fork and serves in-process: a single
process keeps a single device context hot. Off the CPU that is the rule,
not a default: the chip is exclusive to one process, so ``run-server
--workers N>1`` is refused at start unless ``JAX_PLATFORMS=cpu`` —
scale-out on TPU is by replica, not by local workers.

Interplay with dynamic batching (docs/serving.md#dynamic-batching):
batching is per-process — each worker owns its own request queues and
drainer. Handler threads BLOCK on their batch futures, so ``threads``
must stay comfortably above the batching ``--queue-limit``; a too-small
thread gate serializes requests before they can ever coalesce, capping
the achievable batch size at the gate width.
"""

import logging
import os
import signal
import socket
import threading
import typing

from werkzeug.serving import ThreadedWSGIServer
from werkzeug.wsgi import ClosingIterator

logger = logging.getLogger(__name__)

# give up on a worker that keeps dying instead of fork-looping forever
MAX_RESTARTS_PER_WORKER = 5


class ConcurrencyGate:
    """
    WSGI middleware admitting at most ``limit`` requests into the wrapped
    app at once. The slot is held until the response iterable is closed,
    not just until the app callable returns, so streamed responses count
    for their whole lifetime.
    """

    def __init__(self, app, limit: int):
        self.app = app
        self.limit = limit
        self._slots = threading.BoundedSemaphore(limit)

    def __call__(self, environ, start_response):
        self._slots.acquire()
        release = _OnceReleaser(self._slots)
        try:
            iterable = self.app(environ, start_response)
        except BaseException:
            release()
            raise
        return ClosingIterator(iterable, release)


class _OnceReleaser:
    """Release a semaphore exactly once no matter how often invoked."""

    def __init__(self, semaphore):
        self._semaphore = semaphore
        self._done = threading.Lock()

    def __call__(self):
        if self._done.acquire(blocking=False):
            self._semaphore.release()


class BoundedThreadedWSGIServer(ThreadedWSGIServer):
    """ThreadedWSGIServer with a cap on simultaneous accepted connections."""

    def __init__(self, *args, max_connections: typing.Optional[int] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self._connection_gate = (
            threading.BoundedSemaphore(max_connections) if max_connections else None
        )

    def process_request(self, request, client_address):
        if self._connection_gate is not None:
            self._connection_gate.acquire()
        try:
            super().process_request(request, client_address)
        except BaseException:
            if self._connection_gate is not None:
                self._connection_gate.release()
            raise

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            if self._connection_gate is not None:
                self._connection_gate.release()


class ServerRunner:
    """
    Supervise ``workers`` pre-forked WSGI workers on one listening socket.

    ``app_factory`` is called *inside each worker* (after fork), so
    per-process state — device contexts, model caches, prometheus
    registries — is never shared across forks.
    """

    def __init__(
        self,
        app_factory: typing.Callable[[], typing.Any],
        host: str,
        port: int,
        workers: int = 1,
        threads: typing.Optional[int] = None,
        worker_connections: typing.Optional[int] = None,
    ):
        self.app_factory = app_factory
        self.host = host
        self.port = port
        self.workers = max(1, int(workers))
        self.threads = int(threads) if threads else None
        self.worker_connections = (
            int(worker_connections) if worker_connections else None
        )
        self._stopping = False

    # --- worker side ------------------------------------------------------

    def build_server(self, fd: typing.Optional[int] = None) -> BoundedThreadedWSGIServer:
        """The configured per-worker WSGI server (shared-fd aware)."""
        app = self.app_factory()
        if self.threads:
            app = ConcurrencyGate(app, self.threads)
        return BoundedThreadedWSGIServer(
            self.host,
            self.port,
            app,
            fd=fd,
            max_connections=self.worker_connections,
        )

    def _worker_main(self, fd: int):
        # restore default signal dispositions: the worker must die on the
        # parent's TERM rather than run the supervisor's handler
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.signal(signal.SIGINT, signal.SIG_DFL)
        try:
            self.build_server(fd).serve_forever()
        except BaseException:
            logger.exception("worker %d crashed", os.getpid())
            os._exit(1)
        os._exit(0)

    # --- supervisor side --------------------------------------------------

    def _open_socket(self) -> socket.socket:
        sock = socket.create_server(
            (self.host, self.port), backlog=2048, reuse_port=False
        )
        sock.set_inheritable(True)
        return sock

    def _spawn(self, fd: int) -> int:
        pid = os.fork()
        if pid == 0:
            self._worker_main(fd)  # never returns
        logger.info("spawned worker %d", pid)
        return pid

    def serve_forever(self):
        sock = self._open_socket()
        logger.info(
            "serving on %s:%d with %d worker(s), threads=%s, worker_connections=%s",
            self.host,
            self.port,
            self.workers,
            self.threads,
            self.worker_connections,
        )
        if self.workers == 1:
            # in-process: the normal TPU-serving shape (single device context)
            server = self.build_server(fd=sock.fileno())
            try:
                server.serve_forever()
            finally:
                sock.close()
            return

        fd = sock.fileno()
        alive: typing.Set[int] = set()
        restarts = 0

        def _shutdown(signum, frame):
            self._stopping = True
            for pid in alive:
                try:
                    os.kill(pid, signal.SIGTERM)
                except ProcessLookupError:
                    pass

        previous = {
            sig: signal.signal(sig, _shutdown)
            for sig in (signal.SIGTERM, signal.SIGINT)
        }
        try:
            for _ in range(self.workers):
                alive.add(self._spawn(fd))
            while alive:
                try:
                    pid, status = os.wait()
                except ChildProcessError:
                    break
                except KeyboardInterrupt:
                    _shutdown(signal.SIGINT, None)
                    continue
                alive.discard(pid)
                if self._stopping:
                    continue
                logger.warning("worker %d exited with status %d", pid, status)
                if restarts < MAX_RESTARTS_PER_WORKER * self.workers:
                    restarts += 1
                    alive.add(self._spawn(fd))
                else:
                    logger.error("restart budget exhausted; shutting down")
                    _shutdown(signal.SIGTERM, None)
        finally:
            for sig, handler in previous.items():
                signal.signal(sig, handler)
            sock.close()
