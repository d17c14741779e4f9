"""
The program-cache subsystem: ONE abstraction for every compiled XLA
program this codebase holds on to (ROADMAP "Next directions" #2; the
goodput argument is PAPERS.md arXiv:2502.06982 — compile time is
reserved-but-idle device time, and for a fleet of thousands of tiny
models it dominates every fresh process).

Three layers:

- :mod:`cache` — :class:`ProgramCache`, the in-memory LRU of live
  compiled programs (trainer epoch/val/predict programs, the fleet
  scorer's vmapped apply, AOT-loaded serving executables), bounded by
  the HBM watermark sampler's headroom when the device reports real
  numbers and by a count bound on CPU/null devices. All
  `program_cache_*` events and `gordo_program_cache_*` metrics are
  emitted here.
- :mod:`store` — :class:`ProgramStore`, serialized AOT executables on
  disk beside the build artifacts (``<collection>/.programs/``) with a
  compatibility manifest (jax/jaxlib version, backend, device kind).
  Every load is guarded: manifest mismatch, deserialize failure or a
  corrupt payload degrades to a retrace, never to an error.
- :mod:`aot` — build-time export: lower + AOT-compile the serving
  programs for a built collection and ship them beside the artifacts,
  so a fresh server process deserializes instead of re-tracing
  (docs/performance.md "AOT executable cache").
"""

from .cache import (
    ProgramCache,
    evict_lru,
    hbm_headroom,
    serving_program_cache,
)
from .store import (
    MANIFEST_FILENAME,
    PROGRAMS_DIRNAME,
    ProgramStore,
    StoreIncompatible,
    device_fingerprint,
    open_store,
    program_key_digest,
)
from .aot import export_serving_programs, serving_row_buckets

__all__ = [
    "ProgramCache",
    "evict_lru",
    "hbm_headroom",
    "serving_program_cache",
    "MANIFEST_FILENAME",
    "PROGRAMS_DIRNAME",
    "ProgramStore",
    "StoreIncompatible",
    "device_fingerprint",
    "open_store",
    "program_key_digest",
    "export_serving_programs",
    "serving_row_buckets",
]
