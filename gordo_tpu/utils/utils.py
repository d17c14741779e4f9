"""
Reference parity: gordo/util/utils.py (capture_args) and
gordo/util/__init__.py (replace_all_non_ascii_chars).
"""

import functools
import inspect
import logging
import os
import re
from pathlib import Path

logger = logging.getLogger(__name__)


def capture_args(init):
    """
    Decorator for ``__init__`` that records the call's arguments on
    ``self._params`` so objects can round-trip through ``to_dict`` /
    ``from_dict`` (reference: gordo/util/utils.py:6-49).

    Positional args are resolved to their parameter names via the signature;
    defaults for parameters not passed are captured too, so the stored dict is
    the *effective* configuration.
    """

    @functools.wraps(init)
    def wrapper(self, *args, **kwargs):
        sig = inspect.signature(init)
        bound = sig.bind(self, *args, **kwargs)
        bound.apply_defaults()
        params = dict(bound.arguments)
        params.pop("self", None)
        # flatten a trailing **kwargs capture into the params dict itself
        for name, p in sig.parameters.items():
            if p.kind is inspect.Parameter.VAR_KEYWORD and name in params:
                params.update(params.pop(name))
            if p.kind is inspect.Parameter.VAR_POSITIONAL and name in params:
                params[name] = list(params[name])
        self._params = params
        return init(self, *args, **kwargs)

    return wrapper


def replace_all_non_ascii_chars_with_default(value: str, default: str = "-") -> str:
    """Replace every non-ASCII character in ``value`` with ``default``."""
    return re.sub(r"[^\x00-\x7F]", default, value)


#: the persistent compile cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: does not place it: a fixed path inside the checkout. The path is part
#: of every cache key, so a directory that moves (a temp name, a pid, a
#: uid, a time) never hits.
DEFAULT_COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parents[2] / ".jax_cache"
)


def enable_compile_cache(min_compile_seconds: float = 0.5) -> None:
    """
    Turn on JAX's persistent compilation cache so repeat processes skip
    re-compiling (sub-second programs fall under JAX's default 1s
    persistence threshold and recompile every run without the lowered
    ``min_compile_seconds``).

    The cache is placed from OUTSIDE: when ``JAX_COMPILATION_CACHE_DIR``
    is set JAX has already read it and no directory is set in code;
    otherwise the one fixed in-checkout :data:`DEFAULT_COMPILE_CACHE_DIR`.
    The cache is an optimization, never a requirement: an unwritable
    directory is JAX's to warn about, and telemetry never gates it.
    """
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_seconds)
    )
    # An executable's own metadata (the scope names of parallel/fleet.py and
    # models/specs.py, source lines) is what a profiler trace of it shows.
    # JAX leaves metadata out of the cache key by default, so an entry
    # compiled from another version of the source would be loaded for this
    # one and show THAT version's names, or none. With it in the key, an
    # edit that moves a traced line costs each program one compile.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    global _active_compile_cache_dir
    _active_compile_cache_dir = directory
    try:
        from gordo_tpu.observability import emit_event

        # the event makes the resolved directory (and thereby which runs
        # shared it) visible in telemetry reports (docs/observability.md)
        emit_event(
            "compile_cache_enabled",
            directory=directory,
            min_compile_seconds=float(min_compile_seconds),
        )
    except Exception:  # noqa: BLE001 - telemetry never gates the cache
        logger.debug("compile_cache_enabled event not emitted", exc_info=True)


#: the directory the last successful enable_compile_cache pointed JAX at
_active_compile_cache_dir: "str | None" = None


def compile_cache_dir() -> "str | None":
    """The active persistent compile-cache directory (None = never
    enabled in this process, or disabled)."""
    return _active_compile_cache_dir


def compile_cache_dir_bytes(directory: "str | None" = None) -> "int | None":
    """
    Total on-disk bytes under the persistent compile cache (the
    ``gordo_compile_cache_dir_bytes`` gauge the builder samples at build
    start/end), or None when no cache is enabled/readable — the
    CPU-test-friendly null, like the HBM watermark fields.
    """
    directory = directory if directory is not None else _active_compile_cache_dir
    if not directory:
        return None
    total = 0
    try:
        for root, _, files in os.walk(directory):
            for fname in files:
                try:
                    total += os.path.getsize(os.path.join(root, fname))
                except OSError:
                    continue
    except OSError:
        return None
    return total
