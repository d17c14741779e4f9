"""
The operator's profiler switch — the TPU-native analogue of the
reference's lightweight timing surface (SURVEY.md §5: Server-Timing
headers and metadata-embedded durations, which this package also keeps).

``maybe_trace`` wraps a region in a ``jax.profiler`` trace when profiling
is enabled, producing TensorBoard-loadable dumps (XLA op timelines, HBM
usage) under ``<dir>/<name>-<timestamp>/``. Enable per-process with the
``GORDO_TPU_PROFILE_DIR`` env var or per-call with an explicit directory.
It only starts and stops the profiler: the program's phases reach the
timeline through :func:`gordo_tpu.observability.tracing.start_span`,
which writes every span onto the host plane of whatever profiler session
is active in the process, this one or anyone else's.
"""

import contextlib
import logging
import os
import time

logger = logging.getLogger(__name__)

PROFILE_DIR_ENV_VAR = "GORDO_TPU_PROFILE_DIR"

#: distinguishable "the profiler call failed" result (None is a valid
#: return for start/stop)
_FAILED = object()


def _profiler_call(what: str, fn):
    """
    Run one ``jax.profiler`` operation, returning :data:`_FAILED` (and
    warning) instead of raising — broken jax, profiler quirks or nested
    traces must never break the traced workload. The single guard behind
    every profiler touch point here.
    """
    try:
        import jax

        return fn(jax)
    except Exception:
        logger.warning("Could not %s", what, exc_info=True)
        return _FAILED


def profile_dir() -> str:
    """Configured profile dump directory, or '' when profiling is off."""
    return os.environ.get(PROFILE_DIR_ENV_VAR, "")


@contextlib.contextmanager
def maybe_trace(name: str, directory: str = ""):
    """
    Trace the region into ``<directory>/<name>-<unix_ms>`` when a directory
    is configured (argument wins over env); no-op otherwise. Never lets a
    profiler failure break the traced workload.
    """
    directory = directory or profile_dir()
    if not directory:
        yield
        return

    target = os.path.join(directory, f"{name}-{int(time.time() * 1000)}")
    started = (
        _profiler_call(
            "start jax profiler trace",
            lambda jax: jax.profiler.start_trace(target),
        )
        is not _FAILED
    )
    try:
        yield
    finally:
        if started and (
            _profiler_call(
                "stop jax profiler trace",
                lambda jax: jax.profiler.stop_trace(),
            )
            is not _FAILED
        ):
            logger.info("Wrote profiler trace to %s", target)
