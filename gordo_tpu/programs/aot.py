"""
Build-time AOT compilation of serving programs.

The paper's regime is thousands of tiny models, so XLA compile time —
not math — dominates every fresh serving process (docs/performance.md:
the driver's r05 CPU-fallback run spent ~50 s of ~180 s in warmup). The fix is the
Julia→TPU full-compilation move (PAPERS.md arXiv:1810.09868): compile
at BUILD time, once, and make serving cold start a deserialize.

:func:`export_serving_programs` stacks a built collection exactly the
way the server's fleet scorer will (same grouping, same digests — the
key-parity guarantee comes from using ``FleetScorer.export_programs``
itself), AOT-compiles each group's dispatch at the serving row buckets,
and serializes the executables into ``<collection>/.programs/`` with a
compatibility manifest. The single-process fleet builder calls this at
the end of ``build()``; ``gordo-tpu build-fleet --aot-cache`` is the
CLI switch, and the function stands alone for re-exporting an existing
collection (multi-worker builds, a jax upgrade).
"""

import contextlib
import logging
import os
import typing
from pathlib import Path

logger = logging.getLogger(__name__)

#: row buckets compiled at build time — the power-of-two buckets
#: serving pads request rows into (fleet_serving._pow2_bucket). 128
#: covers the reference's own 100-sample benchmark shape, 256 the
#: "small/typical request" bucket the preload warm forward targets.
DEFAULT_ROW_BUCKETS = (128, 256)

ROW_BUCKETS_ENV_VAR = "GORDO_AOT_ROW_BUCKETS"


@contextlib.contextmanager
def fresh_compile():
    """
    Compile inside this block past JAX's persistent compile cache — on
    the CPU backend only. An XLA:CPU executable that came from a cache
    HIT re-serializes into a payload that loads and then cannot execute
    ("Function ... not found"), so an export over a warm cache shipped a
    store whose every program fell back at dispatch. On TPU the
    re-serialized executable runs (``chip_smoke.py`` twice in one call:
    the second build exports over a warm cache and serves with zero
    fallbacks), and the hit is worth keeping there.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if (
        jax.default_backend() != "cpu"
        or not jax.config.jax_enable_compilation_cache
    ):
        yield
        return
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def serving_row_buckets() -> typing.Tuple[int, ...]:
    """The row buckets to AOT-compile: ``GORDO_AOT_ROW_BUCKETS`` (comma
    separated) or the defaults. Malformed entries are dropped, logged."""
    raw = os.environ.get(ROW_BUCKETS_ENV_VAR)
    if not raw:
        return DEFAULT_ROW_BUCKETS
    buckets = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            value = int(part)
        except ValueError:
            logger.warning(
                "Ignoring non-integer %s entry %r", ROW_BUCKETS_ENV_VAR, part
            )
            continue
        if value > 0:
            buckets.append(value)
    return tuple(buckets) or DEFAULT_ROW_BUCKETS


def export_serving_programs(
    collection_dir: typing.Union[str, os.PathLike],
    models: typing.Optional[typing.Dict[str, typing.Any]] = None,
    row_buckets: typing.Optional[typing.Sequence[int]] = None,
) -> dict:
    """
    AOT-compile and serialize a built collection's serving programs
    beside its artifacts. ``models`` (name -> built model) skips the
    reload when the builder still holds them; otherwise every
    non-dot artifact directory under ``collection_dir`` is loaded.

    Returns a report dict ``{"n_programs", "n_machines", "directory"}``.
    Best-effort end to end: a collection with no JAX estimators, a JAX
    that cannot serialize, or a per-program compile failure all land on
    an empty/partial store plus a log line — the build's artifacts are
    never gated on the cache that exists to make serving them faster.
    """
    from gordo_tpu import serializer
    from gordo_tpu.server.fleet_serving import fleet_scorer_from_models

    from .store import ProgramStore, store_directory

    base = Path(collection_dir)
    if models is None:
        models = {}
        for name in sorted(os.listdir(base)):
            art_dir = base / name
            if name.startswith(".") or not art_dir.is_dir():
                continue
            try:
                models[name] = serializer.load(art_dir)
            except Exception as exc:  # noqa: BLE001 - per-model tolerance
                logger.warning(
                    "AOT export: skipping %s (does not load: %s)", name, exc
                )
    report = {
        "n_programs": 0,
        "n_machines": len(models),
        "directory": str(store_directory(base)),
    }
    if not models:
        return report
    scorer, _, fallback = fleet_scorer_from_models(models)
    if scorer is None:
        logger.info(
            "AOT export: no JAX estimators among %d model(s); nothing to "
            "compile", len(models),
        )
        return report
    store = ProgramStore(store_directory(base))
    exported = scorer.export_programs(store, row_buckets=row_buckets)
    report["n_programs"] = len(exported)
    report["n_machines"] = len(scorer.names) + len(fallback)
    logger.info(
        "AOT export: %d serving program(s) for %d machine(s) -> %s",
        len(exported), len(scorer.names), store.directory,
    )
    return report
