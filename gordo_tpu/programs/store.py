"""
ProgramStore: serialized AOT executables on disk, beside the artifacts.

Layout (under a built collection directory)::

    <collection>/.programs/manifest.json     # compatibility + program index
    <collection>/.programs/<digest>.xprog    # one serialized executable

The dot-prefixed directory follows the lifecycle convention: it is never
listed as a model by ``/models`` (dirs only, dot-names excluded) nor as
a revision by ``/revisions``.

An XLA executable is compiled for ONE exact (jax, jaxlib, backend,
device kind) world and ONE exact argument shape. The manifest records
the world; each program's key records the shape. A store whose manifest
does not match the loading process is treated as absent — the server
retraces, emits ``program_cache_fallback``, and serves correctly (slower
cold start, never an error). The same ladder applies per program:
missing key, corrupt payload, deserialize error all degrade to retrace.

Serialization rides ``jax.experimental.serialize_executable`` (the
Julia→TPU "compile the whole thing ahead of time" move from PAPERS.md
arXiv:1810.09868, applied to serving): ``serialize`` returns
``(payload, in_tree, out_tree)``; the treedefs pickle alongside the
payload in one file. On JAX versions without that module the store
declines to write (build logs it; the persistent compile cache from
``utils.enable_compile_cache`` remains the fallback warm-start layer).
"""

import hashlib
import json
import logging
import os
import pickle
import typing
from pathlib import Path

from gordo_tpu.utils import atomic

logger = logging.getLogger(__name__)

PROGRAMS_DIRNAME = ".programs"
MANIFEST_FILENAME = "manifest.json"

#: bump on any layout/pickle-contract change: a loader that doesn't
#: recognize the version must fall back to retrace, not guess
STORE_FORMAT_VERSION = 1

PROGRAM_SUFFIX = ".xprog"


class StoreIncompatible(RuntimeError):
    """Manifest does not match this process's jax/backend/device world."""


def device_fingerprint() -> typing.Dict[str, typing.Any]:
    """
    The compatibility world an executable is valid in. Everything here
    must match EXACTLY between the serializing and deserializing
    process; any drift (a jax upgrade, a different TPU generation, a
    CPU build loaded on TPU) invalidates the whole store.
    """
    import jax
    import jaxlib

    device = jax.devices()[0]
    return {
        "format_version": STORE_FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(device, "device_kind", str(device)),
    }


def program_key_digest(key: typing.Dict[str, typing.Any]) -> str:
    """Stable digest of a JSON-able program key (shape key + program
    identity); the on-disk filename and the manifest index key."""
    canonical = json.dumps(key, sort_keys=True, default=str)
    return hashlib.sha1(canonical.encode()).hexdigest()


class ProgramStore:
    """
    Read/write access to one collection's ``.programs`` directory.

    Writers (the build-time export) call :meth:`save` per program and
    :meth:`write_manifest` once; readers come through :func:`open_store`
    which refuses incompatible manifests up front so per-program loads
    only deal with per-program failures.
    """

    def __init__(self, directory: typing.Union[str, os.PathLike]):
        self.directory = Path(directory)
        self._index: typing.Dict[str, dict] = {}

    @property
    def manifest_path(self) -> Path:
        return self.directory / MANIFEST_FILENAME

    # -- writing --------------------------------------------------------
    def save(self, key: typing.Dict[str, typing.Any], compiled) -> str:
        """
        Serialize one AOT-compiled executable (a ``jax.stages.Compiled``)
        under ``key``. Returns the digest. Raises when this JAX cannot
        serialize executables — callers treat AOT export as best-effort.
        """
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        digest = program_key_digest(key)
        path = self.directory / f"{digest}{PROGRAM_SUFFIX}"
        atomic.atomic_write_bytes(path, blob)
        self._index[digest] = {
            "key": key,
            "file": path.name,
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
        }
        return digest

    def write_manifest(self) -> Path:
        """Publish the manifest (atomically) for what :meth:`save` wrote."""
        payload = {
            **device_fingerprint(),
            "programs": self._index,
        }
        return atomic.atomic_write_json(
            self.manifest_path, payload, indent=2, sort_keys=True
        )

    # -- reading --------------------------------------------------------
    def read_manifest(self) -> dict:
        with open(self.manifest_path) as fh:
            return json.load(fh)

    def verify_compatible(self) -> None:
        """Raise :class:`StoreIncompatible` naming the first mismatched
        manifest field, or return quietly."""
        manifest = self.read_manifest()
        expected = device_fingerprint()
        for field, want in expected.items():
            got = manifest.get(field)
            if got != want:
                raise StoreIncompatible(
                    f"program store at {self.directory} was built for "
                    f"{field}={got!r}, this process is {want!r}"
                )
        self._index = dict(manifest.get("programs") or {})

    def has(self, key: typing.Dict[str, typing.Any]) -> bool:
        return program_key_digest(key) in self._index

    def keys(self) -> typing.List[dict]:
        return [entry["key"] for entry in self._index.values()]

    def load(self, key: typing.Dict[str, typing.Any]) -> typing.Callable:
        """
        Deserialize the executable stored under ``key``. Raises on any
        failure (missing file, corrupt payload, deserialize error) —
        the ProgramCache catches and falls back to retrace. The
        ``program:corrupt`` chaos seam mangles the payload HERE, so a
        chaos run exercises the exact byte-level failure a torn disk
        write or partial rsync would produce.
        """
        import jax
        from jax.experimental import serialize_executable

        from gordo_tpu.robustness import faults

        digest = program_key_digest(key)
        entry = self._index[digest]
        blob = (self.directory / entry["file"]).read_bytes()
        blob = faults.corrupt_program_payload(blob, digest=digest)
        # a torn or mangled payload must fail HERE: bytes flipped inside
        # the object code can deserialize cleanly and then crash the
        # process at dispatch, which no fallback rung can absorb
        expected = entry.get("sha256")
        if expected and hashlib.sha256(blob).hexdigest() != expected:
            raise ValueError(
                f"program payload {entry['file']} does not match its "
                "manifest checksum (torn write or corruption)"
            )
        payload, in_tree, out_tree = pickle.loads(blob)
        # serving programs are compiled for ONE device (the scorer places
        # nothing); left to its default, deserialize_and_load spreads the
        # executable over every local device, and on a multi-device host
        # (4 chips, or the suite's 8 virtual CPUs) each dispatch then
        # fails "expected 8 shards" and silently retraces
        return serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=jax.local_devices()[:1],
        )


def store_directory(
    collection_dir: typing.Union[str, os.PathLike]
) -> Path:
    return Path(collection_dir) / PROGRAMS_DIRNAME


def open_store(
    collection_dir: typing.Union[str, os.PathLike]
) -> typing.Optional[ProgramStore]:
    """
    The reading entry point: the collection's program store, verified
    compatible — or None (logged; the caller retraces). The
    ``program_cache_fallback`` accounting for an incompatible/corrupt
    manifest happens here once per open, not per program.
    """
    from gordo_tpu.programs.cache import serving_program_cache

    directory = store_directory(collection_dir)
    if not directory.is_dir() or not (directory / MANIFEST_FILENAME).is_file():
        return None
    store = ProgramStore(directory)
    try:
        store.verify_compatible()
    except StoreIncompatible as exc:
        logger.warning("Ignoring AOT program store: %s", exc)
        serving_program_cache().report_fallback(
            str(directory), "manifest_mismatch"
        )
        return None
    except Exception as exc:  # noqa: BLE001 - unreadable manifest = absent
        logger.warning(
            "Unreadable AOT program manifest at %s (%s); retracing",
            directory,
            exc,
        )
        serving_program_cache().report_fallback(
            str(directory), "manifest_error"
        )
        return None
    return store
