"""
FleetModelBuilder: build MANY Machines in one XLA program per bucket.

The reference trains each Machine in its own Argo pod (one container, one
Keras fit — SURVEY.md §3.1). Here the fleet is the unit: Machines are
bucketed by architecture/shape (gordo_tpu.parallel.bucketing), each bucket's
data is stacked and padded onto a common grid, and a single vmapped,
mesh-sharded program trains every model in the bucket simultaneously —
including the cross-validation folds used for anomaly-threshold calibration,
which run as additional fleet fits with per-machine fold masks instead of
per-machine sklearn loops.

Supported model shapes (the reference's flagship configs):

- a bare JAX estimator definition (AutoEncoder / LSTM*),
- sklearn Pipeline(prefix transformers... , JAX estimator) — prefix
  transformers are fitted per machine on host (they are tiny) and applied
  before stacking,
- DiffBasedAnomalyDetector wrapping either of the above.

Anything else falls back to the per-machine ModelBuilder path, so the fleet
builder never rejects a config — it just loses the batching speedup.

Outputs are per-machine (model, Machine) pairs with the same artifact layout
and metadata as ModelBuilder, so serving and clients are oblivious to how
the model was trained.
"""

import json
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeoutError
from datetime import datetime, timezone
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd
from sklearn.base import BaseEstimator, TransformerMixin
from sklearn.model_selection import TimeSeriesSplit
from sklearn.pipeline import Pipeline

from gordo_tpu import __version__, serializer
from gordo_tpu.builder.build_model import ModelBuilder
from gordo_tpu.client.utils import backoff_seconds
from gordo_tpu.data import _get_dataset
from gordo_tpu.machine import Machine
from gordo_tpu.machine.metadata import (
    BuildMetadata,
    CrossValidationMetaData,
    DatasetBuildMetadata,
    ModelBuildMetadata,
)
from gordo_tpu.models.anomaly.diff import DiffBasedAnomalyDetector
from gordo_tpu.models.core import BaseJaxEstimator
from gordo_tpu.observability import (
    emit_event,
    get_registry,
    memory_watermarks,
    tracing,
    write_telemetry_report,
)
from gordo_tpu.parallel.bucketing import (
    BucketPlan,
    get_policy,
    plan_padding_waste,
    timestep_bucket,
)
from gordo_tpu.parallel.fleet import FleetTrainer, StackedData
from gordo_tpu.parallel.mesh import auto_device_mesh
from gordo_tpu.parallel.precision import (
    DEFAULT_PRECISION_TOLERANCE,
    cast_params,
    mae,
    mae_parity,
    resolve_precision,
)
from gordo_tpu.robustness import faults
from gordo_tpu.utils import atomic

logger = logging.getLogger(__name__)

#: Per-build casualty record persisted next to the artifacts; the model
#: server reads it to 409 predictions against failed/quarantined machines
#: (docs/robustness.md).
BUILD_REPORT_FILENAME = "build_report.json"


class MachineFetchError(RuntimeError):
    """One machine's data fetch failed after its retry budget."""

    def __init__(self, machine_name: str, attempts: int, cause: BaseException):
        super().__init__(
            f"Data fetch for machine {machine_name!r} failed after "
            f"{attempts} attempt(s): {cause!r}"
        )
        self.machine_name = machine_name
        self.attempts = attempts
        self.cause = cause


def _find_jax_estimator(model) -> Optional[BaseJaxEstimator]:
    """Terminal JAX estimator inside (possibly nested) model, or None."""
    if isinstance(model, BaseJaxEstimator):
        return model
    if isinstance(model, DiffBasedAnomalyDetector):
        return _find_jax_estimator(model.base_estimator)
    if isinstance(model, Pipeline):
        return _find_jax_estimator(model.steps[-1][1])
    return None


def _prefix_transformers(model) -> List[TransformerMixin]:
    """
    Host-side transformer steps applied before the JAX estimator, in
    application order — recursing the same wrappers _find_jax_estimator
    does, so nested pipelines surface their inner scalers too.
    """
    if isinstance(model, DiffBasedAnomalyDetector):
        return _prefix_transformers(model.base_estimator)
    if isinstance(model, Pipeline):
        outer = [step for _, step in model.steps[:-1]]
        return outer + _prefix_transformers(model.steps[-1][1])
    return []


class FleetModelBuilder:
    """
    Parameters
    ----------
    machines
        The Machines to build (possibly heterogeneous; they are bucketed).
    mesh
        Device mesh to shard fleets over; None = single default device.
    data_threads
        Thread-pool width for the I/O-bound data-fetch phase.
    on_error
        Per-machine failure policy (docs/robustness.md). ``"raise"``
        (default, the reference's semantics): the first machine whose
        data fetch or build fails aborts the whole build. ``"skip"``:
        the casualty is recorded — cause and attempt count, in
        ``build_report.json`` and the telemetry report — and the
        surviving machines build on; the machine is the fault domain,
        not the fleet.
    fetch_retries
        Retries per machine for the data-fetch phase (exponential
        backoff between attempts; the fetch that dies three times on a
        flapping source shouldn't cost the build).
    fetch_timeout
        Per-machine cap, in seconds, on waiting for one machine's fetch
        (all attempts included). None = wait forever. A machine that
        times out is a fetch failure under ``on_error``.
    fetch_backoff
        Seconds to sleep before retry ``attempt`` (1-based); defaults to
        the client's shared exponential policy
        (``client.utils.backoff_seconds``).
    initial_params
        Warm-start initialization (docs/lifecycle.md): machine name →
        host param pytree (the served artifact's ``est.params_``). A
        bucket whose machines ALL have an entry trains from those
        params instead of a fresh init — both the CV fold fits and the
        final fit, so refit thresholds are calibrated against the same
        warm trajectory the candidate trains along. A bucket with any
        machine missing (or a tree that no longer matches the model
        spec) falls back to cold init with a warning — warm start is an
        optimization, never a correctness gate.
    fault_sites
        ``GORDO_FAULT_INJECT`` sites whose nan-mode specs may poison
        this build's fits (robustness/faults.py). The default is the
        ordinary ``("train",)``; lifecycle refits pass
        ``("train", "refit")`` so ``refit:nan:<machine>`` targets refit
        builds without touching unrelated training.
    bucket_policy
        The bucketing-compiler grouping policy (``"exact"`` |
        ``"padded"`` | a ready :class:`~gordo_tpu.parallel.bucketing.
        BucketPolicy`; docs/parallelism.md "Bucketing compiler").
        ``"exact"`` — the default — is the historical one-program-per-
        exact-geometry grouping, pinned bit-identical. ``"padded"``
        coalesces same-architecture-family machines with ragged feature
        widths into one program at power-of-two padded dims; pad
        columns are masked out of loss/metrics/early-stopping during
        training and stripped from predictions at serving.
    precision
        Inference precision mode (``"float32"`` | ``"bf16"`` |
        ``"auto"``; docs/performance.md "Mixed precision"). float32 —
        the default — is the historical path, pinned bit-identical with
        no calibration pass. ``"auto"`` calibrates every machine's bf16
        predictions against its float32 build and serves bf16 only
        where the reconstruction-MAE delta clears
        ``precision_tolerance`` (the per-machine decision lands on
        ``est.precision_`` and in ``build_report.json``). ``"bf16"``
        is the operator override: every machine serves bf16, deltas
        still measured and reported, tolerance breaches logged but not
        enforced. Training is always float32 — precision is an
        inference-time cast of the finished params.
    precision_tolerance
        Relative reconstruction-MAE tolerance for the bf16 calibration
        (default 0.25, the padded-parity bound).
    prefetch_depth
        Host->device transfer pipelining depth (default 0 = off, the
        historical bit-identical path). >0 double-buffers the builder's
        per-bucket stacked-data transfer (docs/performance.md "transfer
        pipelining").
    """

    def __init__(
        self,
        machines: List[Machine],
        mesh=None,
        data_threads: int = 8,
        auto_mesh: bool = False,
        on_error: str = "raise",
        fetch_retries: int = 2,
        fetch_timeout: Optional[float] = None,
        fetch_backoff: Callable[[int], float] = backoff_seconds,
        initial_params: Optional[Dict[str, Any]] = None,
        fault_sites: Tuple[str, ...] = ("train",),
        aot_cache: bool = False,
        bucket_policy: Any = "exact",
        precision: str = "float32",
        precision_tolerance: float = DEFAULT_PRECISION_TOLERANCE,
        prefetch_depth: int = 0,
    ):
        self.machines = machines
        if mesh is None and auto_mesh:
            mesh = auto_device_mesh()
        self.mesh = mesh
        self.data_threads = data_threads
        if on_error not in ("raise", "skip"):
            raise ValueError(
                f"on_error must be 'raise' or 'skip', got {on_error!r}"
            )
        self.on_error = on_error
        self.fetch_retries = max(0, int(fetch_retries))
        self.fetch_timeout = fetch_timeout
        self.fetch_backoff = fetch_backoff
        self.initial_params = initial_params
        self.fault_sites = tuple(fault_sites)
        #: the bucketing compiler's grouping policy (exact|padded); the
        #: ledger's work plan derives from the same object, so a build's
        #: grouping and its plan fingerprint can never disagree
        self._policy = get_policy(bucket_policy)
        self.bucket_policy = self._policy.name
        #: inference precision mode; stamped onto the policy so every
        #: planned ProgramKey (and through it every ledger unit digest)
        #: carries it — a worker built at one precision can never join
        #: a ledger planned at another
        self.precision = resolve_precision(precision)
        self.precision_tolerance = float(precision_tolerance)
        self._policy.precision = self.precision
        self.prefetch_depth = max(0, int(prefetch_depth))
        #: machine name -> calibration decision of the last build
        #: ({"precision", "mae_delta", "forced"}); empty for float32
        #: builds (no calibration pass runs)
        self.precision_decisions_: Dict[str, dict] = {}
        #: AOT-compile + serialize the built collection's SERVING
        #: programs beside the artifacts (<output>/.programs/), so a
        #: fresh server's cold start is a deserialize instead of a
        #: retrace (docs/performance.md "AOT executable cache"). Off by
        #: default at the API layer (tests build thousands of tiny
        #: fleets); the build-fleet CLI defaults it ON.
        self.aot_cache = bool(aot_cache)
        #: the last build's bucket plan (set by _build_all; the
        #: benchmark and tests read program counts from it)
        self.plan_: Optional[List[BucketPlan]] = None
        #: per-bucket telemetry accumulated by _build_bucket, assembled
        #: into telemetry_report_ (and persisted next to artifacts) by
        #: build()
        self._bucket_reports: List[dict] = []
        self.telemetry_report_: Optional[dict] = None
        #: casualty records of the last build: machines whose fetch or
        #: build failed (on_error="skip"), and machines the non-finite
        #: guard quarantined during training
        self.build_failures_: List[dict] = []
        self.quarantined_: List[dict] = []
        self.build_report_: Optional[dict] = None

    # -- data ------------------------------------------------------------
    def _fetch_one(self, machine: Machine):
        faults.inject("fetch", machine.name)
        dataset = _get_dataset(machine.dataset.to_dict())
        start = time.time()
        X, y = dataset.get_data()
        return {
            "machine": machine,
            "dataset": dataset,
            "X": X,
            "y": y if y is not None else X,
            "query_duration": time.time() - start,
        }

    def _fetch_with_retries(self, machine: Machine):
        """One machine's fetch with its own retry/backoff budget; raises
        :class:`MachineFetchError` (cause + attempt count) when spent."""
        attempts = self.fetch_retries + 1
        for attempt in range(1, attempts + 1):
            try:
                return self._fetch_one(machine)
            except Exception as exc:
                if attempt >= attempts:
                    raise MachineFetchError(machine.name, attempt, exc) from exc
                delay = self.fetch_backoff(attempt)
                logger.warning(
                    "Data fetch for machine %s failed (attempt %d of %d): "
                    "%r; retrying in %.1fs",
                    machine.name, attempt, attempts, exc, delay,
                )
                time.sleep(delay)

    def fetch_data(
        self, machines: List[Machine]
    ) -> Tuple[List[dict], List[dict]]:
        """
        Fetch every machine's data concurrently, each machine in its OWN
        fault domain: per-machine futures with retry/backoff
        (``fetch_retries`` / ``fetch_backoff``) and an optional
        per-machine wait cap (``fetch_timeout``).

        Returns ``(fetched, failures)`` — successes in the input order,
        and one record per casualty (machine, stage, error, attempts).
        Under ``on_error="raise"`` the first casualty re-raises its
        ORIGINAL cause (exception types map to pod exit codes,
        cli.ExceptionsReporter) instead of returning; under ``"skip"``
        the survivors come back and the casualties are recorded.
        """
        failures: List[dict] = []
        fetched: List[dict] = []
        pool = ThreadPoolExecutor(max_workers=self.data_threads)
        hung = False
        # per-machine fetch spans attach to the bucket/build span through
        # an explicit parent — pool workers do not inherit the contextvar
        parent_ctx = tracing.current_context()

        def task(machine: Machine, started_at: dict):
            started_at["t"] = time.monotonic()
            with tracing.start_span(
                "build.fetch", parent=parent_ctx, machine=machine.name
            ):
                return self._fetch_with_retries(machine)

        try:
            futures = []
            for machine in machines:
                started_at: dict = {"t": None}
                futures.append(
                    (machine, pool.submit(task, machine, started_at), started_at)
                )
            # last time ANY machine resolved: while queued fetches wait
            # behind running ones, this is how _await_fetch tells a
            # busy pool (keep waiting) from one wedged by hung fetches
            progress = {"t": time.monotonic()}
            for machine, future, started_at in futures:
                try:
                    fetched.append(
                        self._await_fetch(future, started_at, progress)
                    )
                except FutureTimeoutError:
                    hung = True  # the worker thread cannot be interrupted
                    future.cancel()
                    if self.on_error == "raise":
                        raise TimeoutError(
                            f"Data fetch for machine {machine.name!r} "
                            f"exceeded {self.fetch_timeout}s"
                        )
                    failures.append(self._record_failure(
                        machine.name,
                        phase="fetch",
                        error=f"TimeoutError: fetch exceeded "
                        f"{self.fetch_timeout}s",
                        attempts=None,
                    ))
                except MachineFetchError as exc:
                    if self.on_error == "raise":
                        raise exc.cause
                    failures.append(self._record_failure(
                        machine.name,
                        phase="fetch",
                        error=repr(exc.cause),
                        attempts=exc.attempts,
                    ))
                finally:
                    progress["t"] = time.monotonic()
            return fetched, failures
        finally:
            # wait=False + cancel: a hung fetch thread must not wedge the
            # surviving buckets' build at pool teardown
            pool.shutdown(wait=not hung, cancel_futures=True)

    def _await_fetch(self, future, started_at: dict, progress: dict):
        """
        Wait for one machine's fetch, charging ``fetch_timeout`` against
        the time the fetch has actually been RUNNING — a machine queued
        behind other fetches must not be falsely recorded as its own
        timeout while the pool is making progress. When the pool is
        WEDGED (hung fetches hold every worker and nothing has resolved
        for a whole ``fetch_timeout``), queued machines time out too —
        the bound must hold even when the hung feeds outnumber the
        threads.
        """
        if self.fetch_timeout is None:
            return future.result()
        while True:
            start = started_at["t"]
            if start is None:
                # still queued: poll without starting the machine's clock,
                # unless the whole pool has stalled for a full budget
                if time.monotonic() - progress["t"] > self.fetch_timeout:
                    raise FutureTimeoutError()
                try:
                    return future.result(timeout=0.2)
                except FutureTimeoutError:
                    continue
            remaining = start + self.fetch_timeout - time.monotonic()
            if remaining <= 0:
                raise FutureTimeoutError()
            return future.result(timeout=remaining)

    def _record_failure(
        self,
        machine_name: str,
        phase: str,
        error: str,
        attempts: Optional[int],
    ) -> dict:
        """One casualty: log + build_failures_ + event + counter."""
        record = {
            "machine": machine_name,
            "phase": phase,
            "error": error,
            "attempts": attempts,
        }
        self.build_failures_.append(record)
        logger.error(
            "Machine %s failed in %s phase (on_error=skip; recorded): %s",
            machine_name, phase, error,
        )
        emit_event("build_machine_failed", **record)
        get_registry().counter(
            "gordo_build_machines_failed_total",
            "Machines dropped from fleet builds by per-machine failures",
            ("phase",),
        ).inc(phase=phase)
        return record

    # -- build -----------------------------------------------------------
    def build(
        self,
        output_dir_base: Optional[Union[str, Path]] = None,
        resume: bool = False,
    ) -> List[Tuple[BaseEstimator, Machine]]:
        """
        Build every machine; returns per-machine (model, machine) pairs in
        the original order. Artifacts land at
        ``<output_dir_base>/<machine.name>`` when a base dir is given —
        flushed per BUCKET as each completes, not at the end, so a runtime
        crash mid-build (a TPU worker dying UNAVAILABLE under a
        1000-machine build) loses only the in-flight bucket.

        ``resume`` (requires ``output_dir_base``): machines whose artifact
        directory already loads are reused instead of rebuilt, so re-running
        the same build command after a crash completes the fleet at
        bucket-level granularity. The reference's whole-model resume is the
        sha3 build cache (reference gordo/builder/build_model.py:521-578);
        this is the same idea at the fleet's artifact layer, where the
        crash-unit is a bucket rather than a pod.

        Returns (model, machine) pairs for the machines that BUILT, in
        the original order — under ``on_error="skip"`` failed machines
        are absent from the result and recorded in ``build_failures_`` /
        ``build_report.json`` instead (under the default ``"raise"``
        every machine builds or the call raises, so the result covers
        all of them).
        """
        # the whole build is one trace: bucket/fetch/cv/fit/serialize
        # spans hang off this root, and every event emitted on the build
        # thread (build_started/bucket_finished/build_crashed/...) is
        # stamped with its trace id
        with tracing.start_span(
            "build.fleet", n_machines=len(self.machines), resume=bool(resume)
        ):
            return self._build_all(output_dir_base, resume)

    def _build_all(
        self,
        output_dir_base: Optional[Union[str, Path]] = None,
        resume: bool = False,
    ) -> List[Tuple[BaseEstimator, Machine]]:
        if resume and output_dir_base is None:
            raise ValueError("resume=True requires output_dir_base")
        base = Path(output_dir_base) if output_dir_base is not None else None

        build_start = time.time()
        started_iso = str(datetime.now(timezone.utc).astimezone())
        self._bucket_reports = []
        self.telemetry_report_ = None
        self.build_failures_ = []
        self.quarantined_ = []
        self.build_report_ = None
        self.precision_decisions_ = {}
        emit_event(
            "build_started",
            n_machines=len(self.machines),
            output_dir=str(base) if base is not None else None,
            resume=bool(resume),
        )
        self._compile_cache_start_bytes = self._sample_compile_cache()

        results: Dict[str, Tuple[BaseEstimator, Machine]] = {}
        to_build = list(self.machines)
        if resume:
            reused, remaining = self._scan_resumable(to_build, base)
            results.update(reused)
            if results:
                logger.info(
                    "Resume: %d/%d machines already built under %s",
                    len(results), len(to_build), base,
                )
                emit_event(
                    "resume",
                    n_reused=len(results),
                    n_total=len(to_build),
                    output_dir=str(base),
                )
            to_build = remaining

        with tracing.start_span(
            "build.plan", policy=self.bucket_policy, n_machines=len(to_build)
        ):
            plans = self._policy.plan(to_build)
        self._emit_plan_telemetry(plans, n_machines=len(to_build))
        logger.info(
            "Fleet build: %d machines in %d buckets (policy=%s)",
            len(to_build), len(plans), self.bucket_policy,
        )

        try:
            for plan in plans:
                results.update(self._build_bucket_entry(plan.machines, base))
        except BaseException as exc:
            # the crash context the round-5 worker deaths never left
            # behind: what was in flight and how memory looked at death
            emit_event(
                "build_crashed",
                error=repr(exc),
                n_machines_done=len(results),
                n_machines_total=len(self.machines),
                device_memory=memory_watermarks(),
            )
            raise

        n_resumed = len(self.machines) - len(to_build)
        if base is not None and self.aot_cache:
            self._export_aot_programs(base, results)
        self._finish_telemetry(
            base=base,
            build_start=build_start,
            started_iso=started_iso,
            n_built=len(results) - n_resumed,
            n_resumed=n_resumed,
            n_buckets=len(plans),
        )
        return [results[m.name] for m in self.machines if m.name in results]

    def _emit_plan_telemetry(
        self, plans: List[BucketPlan], n_machines: int
    ) -> None:
        """
        Publish the bucketing compiler's plan: one ``bucket_planned``
        event (programs that will compile, machines per program, the
        planned padding-waste fraction across the feature axes) and the
        ``gordo_build_padding_waste_ratio`` gauge. The same numbers back
        the ``gordo-tpu buckets plan`` dry-run, so what an operator
        previews is what a build reports.
        """
        waste = plan_padding_waste(plans)
        self.plan_ = plans
        emit_event(
            "bucket_planned",
            policy=self.bucket_policy,
            n_programs=len(plans),
            n_machines=n_machines,
            machines_per_program=[len(p.machines) for p in plans],
            padding_waste_ratio=round(waste, 6),
        )
        get_registry().gauge(
            "gordo_build_padding_waste_ratio",
            "Planned fraction of padded (inert) feature cells across the "
            "last build's programs (0 = exact geometry)",
        ).set(waste)

    def _scan_resumable(
        self, machines: List[Machine], base: Path
    ) -> Tuple[
        Dict[str, Tuple[BaseEstimator, Machine]], List[Machine]
    ]:
        """
        The resume scan: machines whose artifact under ``base`` already
        loads AND matches their current model/dataset config come back
        as reused (model, machine) pairs; the rest need rebuilding.
        Shared by the whole-fleet resume path and per-unit resume in
        multi-worker builds (``build_unit(resume=True)``).

        A prior run's casualties must NOT resume: a quarantined
        machine's artifact holds frozen last-good params, and reusing
        it while this run rewrites ``build_report.json`` would erase
        the quarantine record and serve those params as healthy.
        Rebuild them instead — a clean rebuild clears the record
        legitimately, a still-faulting one re-records it.
        """
        prior_casualties = self._prior_casualties(base)
        reused: Dict[str, Tuple[BaseEstimator, Machine]] = {}
        remaining: List[Machine] = []
        for machine in machines:
            art_dir = base / machine.name
            if machine.name in prior_casualties:
                logger.info(
                    "Resume: rebuilding %s (recorded as %s by the "
                    "previous run)",
                    machine.name, prior_casualties[machine.name],
                )
                remaining.append(machine)
                continue
            # artifacts flush atomically (serializer.dump renames a
            # complete temp dir into place), so no torn model.pkl /
            # metadata.json split can exist; the explicit file check
            # remains only so load_metadata's parent-directory
            # fallback can't pick up an unrelated metadata.json from
            # OUTPUT_DIR itself
            if not (art_dir / "metadata.json").is_file():
                remaining.append(machine)
                continue
            try:
                model = serializer.load(art_dir)
                stored = serializer.load_metadata(art_dir)
                current = machine.to_dict()
                if (
                    stored.get("model") != current.get("model")
                    or stored.get("dataset") != current.get("dataset")
                ):
                    logger.warning(
                        "Artifact at %s was built from a different "
                        "model/dataset config; rebuilding %s",
                        art_dir, machine.name,
                    )
                    remaining.append(machine)
                    continue
                # graft the current request's user metadata/runtime onto
                # the stored build metadata, like
                # ModelBuilder._restore_cached
                stored["metadata"]["user_defined"] = (
                    machine.metadata.user_defined
                )
                stored["runtime"] = machine.runtime
                restored_machine = Machine.unvalidated(**stored)
            except Exception:  # partial/corrupt artifact: rebuild
                logger.warning(
                    "Artifact at %s exists but does not load; rebuilding %s",
                    art_dir, machine.name,
                )
                remaining.append(machine)
                continue
            reused[machine.name] = (model, restored_machine)
            if self.precision != "float32":
                # a reused artifact's calibration decision rides its
                # pickle (est.precision_); surface it so a --resume
                # build's report still names every machine's precision
                est = _find_jax_estimator(model)
                if est is not None:
                    self.precision_decisions_[machine.name] = {
                        "precision": getattr(
                            est, "precision_", "float32"
                        ),
                        "mae_delta": getattr(
                            est, "precision_mae_delta_", None
                        ),
                        "forced": False,
                        "resumed": True,
                    }
        return reused, remaining

    def _flush_pairs(self, pairs, base: Optional[Path]) -> None:
        """Serialize (model, machine) pairs under ``base`` — one atomic
        artifact directory per machine — and emit the flush event."""
        if base is None:
            return
        pairs = list(pairs)
        for model, machine in pairs:
            with tracing.start_span("build.serialize", machine=machine.name):
                ModelBuilder._save_model(
                    model=model,
                    machine=machine,
                    output_dir=base / machine.name,
                )
        emit_event("bucket_flush", n_models=len(pairs), output_dir=str(base))

    def _build_bucket_entry(
        self, bucket: List[Machine], base: Optional[Path]
    ) -> Dict[str, Tuple[BaseEstimator, Machine]]:
        """
        One bucket end to end: the vmapped fleet path when the bucket
        has a JAX estimator, the per-machine :class:`ModelBuilder`
        fallback otherwise — artifacts flushed as they complete, and
        per-machine casualties recorded under ``on_error="skip"``. Both
        the whole-fleet loop and the multi-worker ledger (one bucket =
        one work unit, builder/ledger.py) build through here.
        """
        results: Dict[str, Tuple[BaseEstimator, Machine]] = {}
        prototype = serializer.from_definition(bucket[0].model)
        if _find_jax_estimator(prototype) is None:
            logger.info(
                "Bucket of %d machine(s) has no JAX estimator; falling "
                "back to per-machine builds",
                len(bucket),
            )
            for machine in bucket:
                try:
                    results[machine.name] = ModelBuilder(machine).build()
                except Exception as exc:
                    if self.on_error == "raise":
                        raise
                    self._record_failure(
                        machine.name, phase="build",
                        error=repr(exc), attempts=None,
                    )
                    continue
                # flush per machine: these unbatched builds are the
                # slowest, so the crash-loss window matters most here
                self._flush_pairs([results[machine.name]], base)
            return results
        try:
            built_bucket = self._build_bucket(bucket)
        except Exception as exc:
            if self.on_error == "raise":
                raise
            # a training-level failure's blast radius is the
            # bucket: record every machine of it not already
            # recorded by the finer-grained fetch/precheck paths
            already = {f["machine"] for f in self.build_failures_}
            for machine in bucket:
                if machine.name not in already:
                    self._record_failure(
                        machine.name, phase="build",
                        error=repr(exc), attempts=None,
                    )
            return results
        results.update(built_bucket)
        self._flush_pairs(built_bucket.values(), base)
        return results

    def build_unit(
        self,
        unit_machines: List[Machine],
        output_dir_base: Union[str, Path],
        resume: bool = False,
    ) -> Tuple[dict, Dict[str, Tuple[BaseEstimator, Machine]]]:
        """
        Build ONE ledger work unit — the machines of a single bucket —
        flushing artifacts under ``output_dir_base`` and returning
        ``(unit_report, built)``: the JSON-serializable record the
        ledger commits (built/resumed/failed/quarantined machine lists
        + bucket telemetry) and the in-memory (model, machine) pairs.

        ``resume`` reuses machines whose artifacts already load — the
        same artifact-level scan the whole-fleet resume path runs, so a
        multi-worker ``--resume`` skips committed units at the LEDGER
        level and already-flushed machines of uncommitted units here.

        Per-unit state is reset on entry, so one builder instance can
        build many units in sequence; the global ``build_report.json``
        is assembled by the ledger's finalize step from the committed
        unit records, not here (builder/ledger.py).
        """
        base = Path(output_dir_base)
        self._bucket_reports = []
        self.build_failures_ = []
        self.quarantined_ = []
        self.precision_decisions_ = {}
        reused: Dict[str, Tuple[BaseEstimator, Machine]] = {}
        to_build = list(unit_machines)
        if resume:
            reused, to_build = self._scan_resumable(to_build, base)
            if reused:
                logger.info(
                    "Resume: %d/%d machines of this unit already built "
                    "under %s",
                    len(reused), len(unit_machines), base,
                )
                emit_event(
                    "resume",
                    n_reused=len(reused),
                    n_total=len(unit_machines),
                    output_dir=str(base),
                )
        built = (
            self._build_bucket_entry(to_build, base) if to_build else {}
        )
        results = {**reused, **built}
        report = {
            "built": sorted(results),
            "resumed": sorted(reused),
            "failed": [dict(r) for r in self.build_failures_],
            "quarantined": [dict(r) for r in self.quarantined_],
            "buckets": [dict(r) for r in self._bucket_reports],
            "precision": {
                name: dict(rec)
                for name, rec in self.precision_decisions_.items()
            },
        }
        return report, results

    def _sample_compile_cache(self) -> Optional[int]:
        """
        Sample the persistent XLA compile cache's on-disk size into the
        ``gordo_compile_cache_dir_bytes`` gauge — called at build start
        AND end; the returned size lets ``_build_all`` stash the start
        value so the persisted telemetry report records the GROWTH (the
        gauge alone is last-write-wins and would only show the end).
        Null-graceful when no cache was enabled in this process, like
        the HBM watermark fields.
        """
        from gordo_tpu.utils import compile_cache_dir_bytes

        size = compile_cache_dir_bytes()
        if size is None:
            return None
        get_registry().gauge(
            "gordo_compile_cache_dir_bytes",
            "On-disk bytes of the persistent XLA compile cache",
        ).set(size)
        return size

    def _export_aot_programs(
        self, base: Path, results: Dict[str, Tuple[BaseEstimator, Machine]]
    ) -> None:
        """
        Build-time AOT: compile + serialize the collection's serving
        programs beside the artifacts from the models still in memory.
        Best-effort end to end — the artifacts are already flushed, and
        a failed export only costs the next server its instant cold
        start, never the build.
        """
        from gordo_tpu.programs import export_serving_programs

        try:
            export_serving_programs(
                base,
                models={name: pair[0] for name, pair in results.items()},
            )
        except Exception as exc:  # noqa: BLE001 - export is best-effort
            logger.warning("AOT serving-program export failed: %s", exc)

    def _finish_telemetry(
        self,
        base: Optional[Path],
        build_start: float,
        started_iso: str,
        n_built: int,
        n_resumed: int,
        n_buckets: int,
    ) -> None:
        """Assemble (and persist, when building to disk) the build's
        telemetry report from the per-bucket records."""
        wall = time.time() - build_start
        # rate counts machines BUILT this run: resume-reused artifacts
        # were loaded, not built, and counting them would inflate the
        # north-star models/hour ~(total/rebuilt)x on a mostly-warm resume
        rate = n_built / wall * 3600 if wall > 0 else None
        report = {
            "kind": "fleet_build",
            "started": started_iso,
            "finished": str(datetime.now(timezone.utc).astimezone()),
            "wall_time_s": wall,
            "n_machines": len(self.machines),
            "n_built": n_built,
            "n_resumed": n_resumed,
            "n_buckets": n_buckets,
            "bucket_policy": self.bucket_policy,
            "precision": self.precision,
            "models_per_hour": rate,
            "device_memory": memory_watermarks(),
            "buckets": self._bucket_reports,
            "on_error": self.on_error,
            "machines_failed": list(self.build_failures_),
            "machines_quarantined": list(self.quarantined_),
        }
        self.telemetry_report_ = report
        self.build_report_ = {
            "version": 1,
            "kind": "fleet_build_report",
            "started": started_iso,
            "finished": report["finished"],
            "on_error": self.on_error,
            "n_machines": len(self.machines),
            "n_built": n_built,
            "n_resumed": n_resumed,
            "n_failed": len(self.build_failures_),
            "n_quarantined": len(self.quarantined_),
            "failed": list(self.build_failures_),
            "quarantined": list(self.quarantined_),
            "precision": {
                "mode": self.precision,
                "tolerance": self.precision_tolerance,
                "machines": {
                    name: dict(rec)
                    for name, rec in self.precision_decisions_.items()
                },
            },
        }
        reg = get_registry()
        reg.counter(
            "gordo_build_models_total", "Models produced by fleet builds"
        ).inc(n_built)
        reg.histogram(
            "gordo_build_seconds", "Whole fleet-build wall time"
        ).observe(wall)
        if rate is not None:
            reg.gauge(
                "gordo_build_models_per_hour", "Most recent build's rate"
            ).set(rate)
        peak = report["device_memory"].get("peak_bytes_in_use")
        if peak is not None:
            reg.gauge(
                "gordo_build_peak_hbm_bytes",
                "Peak device memory observed across builds",
            ).set_max(peak)
        end_bytes = self._sample_compile_cache()
        if end_bytes is not None:
            start_bytes = getattr(self, "_compile_cache_start_bytes", None)
            report["compile_cache"] = {
                "start_bytes": start_bytes,
                "end_bytes": end_bytes,
                "grown_bytes": (
                    end_bytes - start_bytes if start_bytes is not None else None
                ),
            }
        if base is not None:
            write_telemetry_report(base, report)
            self._write_build_report(base)
        emit_event(
            "build_finished",
            n_machines=len(self.machines),
            n_resumed=n_resumed,
            n_failed=len(self.build_failures_),
            n_quarantined=len(self.quarantined_),
            wall_time_s=round(wall, 4),
            models_per_hour=rate,
        )

    @staticmethod
    def _prior_casualties(base: Path) -> Dict[str, str]:
        """Machine -> status from an earlier run's ``build_report.json``
        under ``base`` ({} when absent/unreadable)."""
        path = base / BUILD_REPORT_FILENAME
        try:
            with open(path) as fh:
                report = json.load(fh)
        except (OSError, ValueError):
            return {}
        out: Dict[str, str] = {}
        for record in report.get("failed") or []:
            if record.get("machine"):
                out[record["machine"]] = (
                    f"{record.get('phase', 'build')}-failed"
                )
        for record in report.get("quarantined") or []:
            if record.get("machine"):
                out[record["machine"]] = "quarantined"
        return out

    def _write_build_report(self, base: Path) -> Path:
        """
        Persist ``build_report.json`` next to the artifacts — atomically,
        since the model server polls it to decide which machines to 409.
        """
        return atomic.atomic_write_json(
            base / BUILD_REPORT_FILENAME,
            self.build_report_,
            indent=2,
            sort_keys=True,
            default=str,
        )

    def _build_bucket(
        self, bucket: List[Machine]
    ) -> Dict[str, Tuple[BaseEstimator, Machine]]:
        with tracing.start_span("build.bucket", n_machines=len(bucket)):
            return self._build_bucket_traced(bucket)

    def _build_bucket_traced(
        self, bucket: List[Machine]
    ) -> Dict[str, Tuple[BaseEstimator, Machine]]:
        bucket_start = time.time()
        # chaos seam: a `worker:die:fetch` spec kills THIS process here —
        # lease held, nothing published (robustness/faults.py)
        faults.worker_die("fetch")
        fetched, fetch_failures = self.fetch_data(bucket)
        if fetch_failures:
            # on_error="skip" (raise already propagated): the casualties
            # are recorded; the bucket shrinks to the survivors
            bucket = [item["machine"] for item in fetched]
            if not bucket:
                return {}

        # Per-machine host-side prep: build the model object, fit prefix
        # transformers, transform X.
        models = [serializer.from_definition(item["machine"].model) for item in fetched]
        for model, item in zip(models, fetched):
            seed = item["machine"].evaluation.get("seed", 0)
            ModelBuilder._inject_seed(model, seed)
        estimators = [_find_jax_estimator(m) for m in models]
        Xs_t: List[np.ndarray] = []
        ys_np: List[np.ndarray] = []
        for model, item in zip(models, fetched):
            X_t = np.asarray(item["X"], dtype=np.float32)
            for transformer in _prefix_transformers(model):
                X_t = np.asarray(transformer.fit_transform(X_t), dtype=np.float32)
            Xs_t.append(X_t)
            ys_np.append(np.asarray(item["y"], dtype=np.float32))

        # Architecture spec from the first estimator (identical family
        # across the bucket by construction). The program's tensor dims
        # come from the bucketing policy: the exact policy returns the
        # bucket's (uniform) real widths unchanged; the padded policy
        # rounds the post-transform maxima up to power-of-two buckets so
        # ragged-width machines share this one compiled program
        # (docs/parallelism.md "Bucketing compiler").
        in_widths = [X_t.shape[1] for X_t in Xs_t]
        out_widths = [y_np.shape[1] for y_np in ys_np]
        f_prog, f_out_prog = self._policy.program_dims(in_widths, out_widths)
        proto_est = estimators[0]
        proto_est.kwargs.update(
            {"n_features": f_prog, "n_features_out": f_out_prog}
        )
        spec = proto_est._build_spec()
        lookahead = proto_est.lookahead if spec.windowed else 0

        # fail loudly BEFORE training if any machine cannot fill one window
        # (the solo path fails at its predict; masks would otherwise let a
        # short machine "train" on nothing and crash only at serve time) —
        # under on_error="skip" only THAT machine leaves the bucket
        if spec.windowed:
            min_rows = spec.lookback_window + lookahead
            short = [i for i, X_t in enumerate(Xs_t) if len(X_t) < min_rows]
            if short:
                message = (
                    "{name}: {rows} rows after transforms; this windowed "
                    f"model needs at least {min_rows} (lookback "
                    f"{spec.lookback_window} + lookahead {lookahead})"
                )
                if self.on_error == "raise":
                    from gordo_tpu.data.base import InsufficientDataError

                    item, X_t = fetched[short[0]], Xs_t[short[0]]
                    raise InsufficientDataError(
                        "Machine "
                        + message.format(
                            name=item["machine"].name, rows=len(X_t)
                        )
                    )
                for i in short:
                    self._record_failure(
                        fetched[i]["machine"].name,
                        phase="build",
                        error="InsufficientDataError: " + message.format(
                            name=fetched[i]["machine"].name,
                            rows=len(Xs_t[i]),
                        ),
                        attempts=None,
                    )
                keep = [i for i in range(len(fetched)) if i not in set(short)]
                fetched = [fetched[i] for i in keep]
                models = [models[i] for i in keep]
                estimators = [estimators[i] for i in keep]
                Xs_t = [Xs_t[i] for i in keep]
                ys_np = [ys_np[i] for i in keep]
                bucket = [item["machine"] for item in fetched]
                if not bucket:
                    return {}

        # row-count preservation per machine, on its own data: the license
        # for sharing one model_offset probe across the bucket (below)
        rows_preserved = all(
            len(X_t) == len(item["X"]) for item, X_t in zip(fetched, Xs_t)
        )

        # Stack to a common power-of-two grid (so ragged buckets share one
        # compiled program geometry), pad fleet to mesh multiple. Feature
        # axes pad to the program dims; ragged output widths produce the
        # feature_out_weight mask that keeps pad columns out of
        # loss/metrics/early-stopping (parallel/fleet.py).
        n_grid = timestep_bucket(max(len(x) for x in Xs_t))
        m_padded = FleetTrainer.pad_fleet_size(len(bucket), self.mesh)
        Xs_grid = Xs_t
        ys_grid = ys_np
        data = StackedData.from_ragged(
            Xs_grid,
            ys_grid,
            n_machines_padded=m_padded,
            n_timesteps=n_grid,
            n_features=f_prog,
            n_features_out=f_out_prog,
            prefetch_depth=self.prefetch_depth,
        )

        # one compiled fleet program per bucket geometry from here on —
        # the count the padded policy exists to shrink
        get_registry().counter(
            "gordo_build_programs_compiled_total",
            "Compiled fleet programs (one per bucket geometry) built by "
            "fleet builds",
            ("kind",),
        ).inc(kind=self.bucket_policy)
        fit_args = proto_est.extract_supported_fit_args(proto_est.kwargs)
        epochs = int(fit_args.get("epochs", 1))
        batch_size = int(fit_args.get("batch_size", 32))
        es_kwargs = self._early_stopping_kwargs(fit_args)

        trainer = FleetTrainer(
            spec,
            lookahead=lookahead,
            mesh=self.mesh,
            fault_sites=self.fault_sites,
        )
        # Per-machine PRNG keys are the SOLO path's init key for the
        # machine's evaluation seed (models/core.py: solo_init_key) —
        # independent of fleet composition, and giving the same machine
        # identical init params whichever builder trains it (quality
        # parity between the two paths is a product promise).
        from gordo_tpu.models.core import solo_init_key

        keys = np.stack(
            [
                np.asarray(
                    solo_init_key(item["machine"].evaluation.get("seed", 0))
                )
                for item in fetched
            ]
            + [np.asarray(solo_init_key(0))] * (m_padded - len(bucket))
        )

        machine_names = [item["machine"].name for item in fetched]
        warm_params = self._stack_warm_params(machine_names, int(m_padded))

        # -- CV folds as masks: threshold calibration + scores ------------
        start_cv = time.time()
        with tracing.start_span("build.cv", n_machines=len(bucket)):
            fold_records = self._run_cv_folds(
                trainer, data, keys, bucket, Xs_grid, ys_grid, models,
                epochs=epochs, batch_size=batch_size, es_kwargs=es_kwargs,
                machine_names=machine_names, warm_params=warm_params,
            )
        cv_duration = time.time() - start_cv

        # -- final full fit ----------------------------------------------
        # chaos seam: `worker:die:train` dies mid-train — CV done, final
        # fit unstarted, no artifacts flushed
        faults.worker_die("train")
        start_fit = time.time()
        with tracing.start_span(
            "build.fit", n_machines=len(bucket), epochs=epochs
        ):
            params, losses = trainer.fit(
                data, keys, epochs=epochs, batch_size=batch_size,
                machine_names=machine_names, params=warm_params, **es_kwargs
            )
        fit_duration = time.time() - start_fit

        # -- quarantine bookkeeping: the FINAL fit's verdict is what the
        # persisted params reflect (a quarantined machine's artifact
        # holds its last finite epoch's params — build_report.json names
        # it so serving can degrade instead of returning garbage)
        n_bucket_quarantined = 0
        healthy = getattr(trainer, "healthy_", None)
        if healthy is not None and not healthy[: len(fetched)].all():
            q_epochs = trainer.quarantine_epoch_
            for i in np.flatnonzero(~healthy[: len(fetched)]):
                name = fetched[i]["machine"].name
                n_bucket_quarantined += 1
                self.quarantined_.append(
                    {"machine": name, "epoch": int(q_epochs[i])}
                )
                logger.warning(
                    "Machine %s was quarantined at epoch %d; its artifact "
                    "holds the last finite params and serving will 409 it",
                    name, int(q_epochs[i]),
                )

        # -- bf16 calibration (precision != float32) ----------------------
        # measure each machine's reconstruction-MAE delta between the
        # float32 program and a bf16 cast of the SAME params/data — the
        # parity statistic the padded policy is judged by — and decide
        # per machine whether it may serve bf16. The float32 default
        # skips this entirely (no calibration pass, bit-identical build).
        precision_records: Dict[str, dict] = {}
        if self.precision != "float32":
            with tracing.start_span(
                "build.calibrate",
                n_machines=len(fetched),
                mode=self.precision,
            ):
                precision_records = self._calibrate_precision(
                    trainer, params, data,
                    machine_names=machine_names,
                    estimators=estimators,
                    Xs_grid=Xs_grid,
                    ys_grid=ys_grid,
                    out_widths=out_widths,
                    spec=spec,
                    lookahead=lookahead,
                )

        # -- unstack into per-machine models + metadata -------------------
        # one bulk device->host transfer for the whole bucket's params
        host_params = trainer.unstack_all(params, len(fetched))
        bucket_offset: Optional[int] = None
        out: Dict[str, Tuple[BaseEstimator, Machine]] = {}
        for i, (model, est, item) in enumerate(zip(models, estimators, fetched)):
            machine: Machine = item["machine"]
            est.spec_ = spec
            est.params_ = host_params[i]
            # the PROGRAM dims are the model's true tensor widths (its
            # module was built with them); a padded machine additionally
            # records its real (active) widths so predict/serving pad
            # inputs and strip pad columns from responses
            # (docs/serving.md "Padded programs")
            est.n_features_ = f_prog
            est.n_features_out_ = f_out_prog
            if in_widths[i] != f_prog or out_widths[i] != f_out_prog:
                est.n_active_features_ = in_widths[i]
                est.n_active_features_out_ = out_widths[i]
            val_series = getattr(trainer, "val_losses_", None)
            # a NaN column marks a machine too small for any validation
            # samples — it has no val_loss history, like the solo path
            # with n_val == 0
            machine_val = (
                val_series[:, i]
                if val_series is not None and not np.isnan(val_series[:, i]).any()
                else None
            )
            est.history_ = {
                "loss": [float(l[i]) for l in losses],
                "params": {
                    "epochs": epochs,
                    "batch_size": batch_size,
                    "samples": int(len(Xs_grid[i])),
                    "metrics": ["loss"]
                    + (["val_loss"] if machine_val is not None else []),
                    "fleet_size": len(bucket),
                },
            }
            if machine_val is not None:
                est.history_["val_loss"] = [float(x) for x in machine_val]
            if isinstance(model, DiffBasedAnomalyDetector):
                model.scaler.fit(item["y"])
                self._apply_thresholds(model, fold_records, i)

            # model_offset = rows the prediction is shorter than the input:
            # pure window arithmetic (lookback/lookahead) for this bucket's
            # single architecture — so probe it once per bucket instead of
            # paying a full predict per machine (one device roundtrip
            # each). Sharing is only sound while no prefix
            # transformer changes row counts (a data-dependent dropper
            # would make the offset machine-specific); `rows_preserved`
            # checks exactly that on every machine's own data, falling
            # back to per-machine probes otherwise.
            if not rows_preserved:
                offset = ModelBuilder._determine_offset(model, item["X"])
            else:
                if bucket_offset is None:
                    bucket_offset = ModelBuilder._determine_offset(model, item["X"])
                offset = bucket_offset
            scores = {
                metric: folds for metric, folds in fold_records["scores"][i].items()
            }
            machine_out = Machine.unvalidated(**machine.to_dict())
            machine_out.metadata.build_metadata = BuildMetadata(
                model=ModelBuildMetadata(
                    model_offset=offset,
                    model_creation_date=str(datetime.now(timezone.utc).astimezone()),
                    model_builder_version=__version__,
                    model_training_duration_sec=fit_duration,
                    cross_validation=CrossValidationMetaData(
                        cv_duration_sec=cv_duration,
                        scores=scores,
                        splits=fold_records["splits"][i],
                    ),
                    model_meta=ModelBuilder._extract_metadata_from_model(model),
                ),
                dataset=DatasetBuildMetadata(
                    query_duration_sec=item["query_duration"],
                    dataset_meta=item["dataset"].get_metadata(),
                ),
            )
            out[machine.name] = (model, machine_out)

        # -- bucket telemetry: rate, final-fit timings, HBM watermark ------
        bucket_wall = time.time() - bucket_start
        bucket_memory = memory_watermarks()
        bucket_report = (
            {
                "n_machines": len(bucket),
                "n_machines_padded": int(m_padded),
                "n_timesteps_grid": int(n_grid),
                "n_features": int(f_prog),
                "n_features_out": int(f_out_prog),
                "bucket_policy": self.bucket_policy,
                # measured (post-transform) feature-axis padding of this
                # program's stack — the build-time counterpart of the
                # plan's estimate
                "padding_waste_ratio": (
                    1.0
                    - (sum(in_widths) + sum(out_widths))
                    / (len(bucket) * (f_prog + f_out_prog))
                ),
                "epochs": epochs,
                "batch_size": batch_size,
                "cv_duration_s": cv_duration,
                "fit_duration_s": fit_duration,
                "bucket_wall_s": bucket_wall,
                "n_machines_quarantined": n_bucket_quarantined,
                # lifecycle refits init from the served revision's params
                # (docs/lifecycle.md); False also covers a refit that FELL
                # BACK to cold init, so the report never overclaims
                "warm_start": warm_params is not None,
                "models_per_hour": (
                    len(bucket) / bucket_wall * 3600 if bucket_wall > 0 else None
                ),
                # the final full fit's telemetry (compile split, steady
                # epoch time, sensor-timesteps/s) — fold fits overwrite
                # this attribute, the final fit runs last
                "fit": getattr(trainer, "fit_telemetry_", None),
                "device_memory": bucket_memory,
                "precision": self.precision,
            }
        )
        if precision_records:
            bucket_report["precision_decisions"] = {
                name: dict(rec) for name, rec in precision_records.items()
            }
        self._bucket_reports.append(bucket_report)
        get_registry().histogram(
            "gordo_build_bucket_seconds",
            "Per-bucket wall time (data fetch + CV + fit + unstack)",
        ).observe(bucket_wall)
        peak = bucket_memory.get("peak_bytes_in_use")
        if peak is not None:
            get_registry().gauge(
                "gordo_build_peak_hbm_bytes",
                "Peak device memory observed across builds",
            ).set_max(peak)
        emit_event(
            "bucket_finished",
            n_machines=len(bucket),
            wall_time_s=round(bucket_wall, 4),
            peak_bytes_in_use=peak,
        )
        return out

    def _calibrate_precision(
        self,
        trainer: FleetTrainer,
        params: Any,
        data: StackedData,
        *,
        machine_names: List[str],
        estimators: List[BaseJaxEstimator],
        Xs_grid: List[np.ndarray],
        ys_grid: List[np.ndarray],
        out_widths: List[int],
        spec: Any,
        lookahead: int,
    ) -> Dict[str, dict]:
        """
        The bf16 calibration pass (docs/performance.md "Mixed
        precision"): predict the whole bucket once at float32 and once
        with params/inputs cast to bfloat16 (exactly the cast serving
        performs), then compare each machine's reconstruction MAE over
        its REAL rows and ACTIVE output columns. A machine whose
        relative MAE delta clears ``precision_tolerance`` may serve
        bf16; one that doesn't stays float32 — under ``--precision
        bf16`` the operator override serves bf16 anyway (breaches
        logged, never silent), while a ``precision:degrade`` chaos spec
        forces the float32 fallback in either mode. Decisions are
        stamped on the estimators (``est.precision_`` — pickled with
        the artifact, so they survive ``--resume`` and ride into
        serving group keys) and recorded for ``build_report.json``.
        """
        import jax.numpy as jnp

        preds32 = np.asarray(
            trainer.predict(params, data.X), dtype=np.float32
        )
        params16 = cast_params(params, jnp.bfloat16)
        X16 = jnp.asarray(data.X).astype(jnp.bfloat16)
        preds16 = np.asarray(
            trainer.predict(params16, X16), dtype=np.float32
        )
        offset = (
            spec.lookback_window - 1 + lookahead if spec.windowed else 0
        )
        records: Dict[str, dict] = {}
        n_bf16 = 0
        worst = 0.0
        hist = get_registry().histogram(
            "gordo_build_precision_mae_delta",
            "Per-machine relative reconstruction-MAE delta of the bf16 "
            "cast vs the float32 build, measured at calibration",
        )
        for i, name in enumerate(machine_names):
            est = estimators[i]
            n_out = max(0, len(Xs_grid[i]) - offset)
            cols = int(out_widths[i])
            y_true = np.asarray(ys_grid[i], dtype=np.float32)[
                offset : offset + n_out, :cols
            ]
            mae32 = mae(preds32[i, :n_out, :cols], y_true)
            mae16 = mae(preds16[i, :n_out, :cols], y_true)
            delta, within = mae_parity(
                mae32, mae16, self.precision_tolerance
            )
            forced = faults.precision_degrade(name)
            if forced:
                decided = "float32"
            elif self.precision == "bf16":
                decided = "bf16"
                if not within:
                    logger.warning(
                        "Machine %s: bf16 MAE delta %.4f exceeds "
                        "tolerance %.4f but --precision bf16 overrides "
                        "the fallback",
                        name, delta, self.precision_tolerance,
                    )
            else:
                decided = "bf16" if within else "float32"
            est.precision_ = decided
            est.precision_mae_delta_ = float(delta)
            records[name] = {
                "precision": decided,
                "mae_delta": float(delta),
                "forced": bool(forced),
            }
            hist.observe(float(delta))
            worst = max(worst, float(delta))
            n_bf16 += decided == "bf16"
        n_fallback = len(machine_names) - n_bf16
        if n_fallback:
            get_registry().counter(
                "gordo_build_precision_fallbacks_total",
                "Machines whose bf16 calibration failed (or was "
                "chaos-forced to fail) and stayed float32",
            ).inc(n_fallback)
        self.precision_decisions_.update(records)
        emit_event(
            "precision_calibrated",
            mode=self.precision,
            tolerance=self.precision_tolerance,
            n_machines=len(machine_names),
            n_bf16=n_bf16,
            n_float32=n_fallback,
            worst_mae_delta=round(worst, 6),
        )
        return records

    def _stack_warm_params(
        self, machine_names: List[str], m_padded: int
    ) -> Optional[Any]:
        """
        The bucket's warm-start init (docs/lifecycle.md): stack
        ``initial_params[name]`` host trees along a leading fleet axis,
        padding with the first machine's tree (padded rows carry zero
        sample weight, so their init is inert). None — cold init — when
        warm start is off, any machine lacks an entry, or the trees no
        longer share one structure (a changed model config).
        """
        if not self.initial_params:
            return None
        trees = [self.initial_params.get(name) for name in machine_names]
        missing = [n for n, t in zip(machine_names, trees) if t is None]
        if missing:
            logger.warning(
                "Warm start: no initial params for %s; bucket falls back "
                "to cold init",
                missing,
            )
            return None
        import jax

        trees = trees + [trees[0]] * (m_padded - len(trees))
        try:
            return jax.tree_util.tree_map(
                lambda *leaves: np.stack(
                    [np.asarray(leaf, dtype=np.float32) for leaf in leaves]
                ),
                *trees,
            )
        except (ValueError, TypeError) as exc:
            logger.warning(
                "Warm start: param trees do not stack (%s); bucket falls "
                "back to cold init",
                exc,
            )
            return None

    @staticmethod
    def _early_stopping_kwargs(fit_args: dict) -> dict:
        """
        Map a bucket's fit configuration onto the fleet trainer's kwargs:
        ``validation_split`` becomes the per-machine holdout (the solo path
        holds out the last fraction of samples whether or not it early-
        stops, models/core.py:264-272 — the fleet must too, or it would
        train on the solo path's validation data), and an EarlyStopping
        callback becomes the per-machine gate, monitoring the validation
        loss exactly when the solo callback would (``val_loss`` monitor
        with a configured split, or its documented fallback to ``loss``).
        Only min-mode loss-family monitors translate; anything else trains
        the full epoch budget (with a warning, so the divergence from the
        single-machine path is visible).
        """
        from gordo_tpu.models.callbacks import EarlyStopping
        from gordo_tpu.models.core import _materialize_callbacks

        out: dict = {}
        vs = float(fit_args.get("validation_split") or 0.0)
        if vs > 0.0:
            out["validation_split"] = vs
        for cb in _materialize_callbacks(fit_args.get("callbacks")):
            if not isinstance(cb, EarlyStopping):
                logger.warning(
                    "Fleet build: callback %s does not translate to the "
                    "fleet path and is ignored there",
                    type(cb).__name__,
                )
                continue
            if "loss" not in cb.monitor or cb.mode == "max":
                logger.warning(
                    "Fleet build: EarlyStopping(monitor=%r, mode=%r) does "
                    "not translate to the fleet path (loss-family metrics "
                    "only); training the full epoch budget",
                    cb.monitor,
                    cb.mode,
                )
                return out
            out.update(
                {
                    "early_stopping_patience": int(cb.patience),
                    "early_stopping_min_delta": abs(float(cb.min_delta)),
                    "early_stopping_start_from_epoch": int(cb.start_from_epoch),
                    # per-machine best-epoch snapshot on device, matching
                    # the single-machine path's Keras semantics
                    "restore_best_weights": bool(cb.restore_best_weights),
                    "early_stopping_on_val": "val" in cb.monitor and vs > 0.0,
                }
            )
            return out
        return out

    def _run_cv_folds(
        self,
        trainer: FleetTrainer,
        data: StackedData,
        keys: np.ndarray,
        bucket: List[Machine],
        Xs_grid: List[np.ndarray],
        ys_grid: List[np.ndarray],
        models: List[BaseEstimator],
        epochs: int,
        batch_size: int,
        n_splits: int = 3,
        es_kwargs: Optional[dict] = None,
        machine_names: Optional[List[str]] = None,
        warm_params: Optional[Any] = None,
    ) -> dict:
        """
        TimeSeriesSplit folds, trained fleet-wide with per-machine train
        masks; returns per-machine thresholds and scores (the reference
        computes these per machine in anomaly/diff.py:134-224).

        ``es_kwargs`` applies the same early stopping to fold fits as the
        final fit — the single-machine path's cross_validate clones also
        run their configured callbacks, and thresholds calibrated from
        fully-trained fold models would be too strict for an early-stopped
        served model.
        """
        from sklearn import metrics as skmetrics

        M, n_grid = data.sample_weight.shape
        splitter = TimeSeriesSplit(n_splits=n_splits)
        spec = trainer.spec
        lb = spec.lookback_window if spec.windowed else 1
        la = trainer.lookahead

        per_machine_folds: List[List[dict]] = [
            list(splitter.split(np.zeros((len(x), 1)))) for x in Xs_grid
        ]

        scores: List[Dict[str, dict]] = [dict() for _ in bucket]
        splits: List[dict] = [dict() for _ in bucket]
        tag_thresholds: List[Optional[pd.Series]] = [None] * len(bucket)
        agg_thresholds: List[Optional[float]] = [None] * len(bucket)
        tag_thr_per_fold: List[dict] = [dict() for _ in bucket]
        agg_thr_per_fold: List[dict] = [dict() for _ in bucket]
        metric_funcs = {
            "explained-variance-score": skmetrics.explained_variance_score,
            "r2-score": skmetrics.r2_score,
            "mean-squared-error": skmetrics.mean_squared_error,
            "mean-absolute-error": skmetrics.mean_absolute_error,
        }
        raw_scores: List[Dict[str, list]] = [
            {m: [] for m in metric_funcs} for _ in bucket
        ]

        for fold in range(n_splits):
            train_mask = np.zeros((M, n_grid), dtype=np.float32)
            for i in range(len(bucket)):
                train_idx, test_idx = per_machine_folds[i][fold]
                train_mask[i, train_idx] = 1.0
                splits[i].update(
                    {
                        f"fold-{fold + 1}-n-train": int(len(train_idx)),
                        f"fold-{fold + 1}-n-test": int(len(test_idx)),
                    }
                )
            fold_params, _ = trainer.fit(
                data,
                keys,
                epochs=epochs,
                batch_size=batch_size,
                extra_weight=train_mask,
                machine_names=machine_names,
                params=warm_params,
                **(es_kwargs or {}),
            )
            preds = trainer.predict(fold_params, data.X)  # (M, n_out, f_out)

            for i, model in enumerate(models):
                _, test_idx = per_machine_folds[i][fold]
                # model output row j corresponds to input row j + lb - 1 + la
                out_offset = lb - 1 + la
                test_out_rows = test_idx - out_offset
                valid = test_out_rows >= 0
                test_out_rows = test_out_rows[valid]
                rows_in = test_idx[valid]
                # predictions carry the PROGRAM's (possibly padded)
                # output width; scores and thresholds are computed on
                # the machine's real columns only (ys_grid is unpadded)
                y_pred = preds[i][test_out_rows][:, : ys_grid[i].shape[1]]
                y_true = ys_grid[i][rows_in]

                for metric_name, func in metric_funcs.items():
                    raw_scores[i][metric_name].append(float(func(y_true, y_pred)))

                if isinstance(model, DiffBasedAnomalyDetector):
                    from sklearn.base import clone as sk_clone

                    # same scaler config as the model, fitted on fold-train
                    # targets only (parity with diff.py: the fold model's
                    # scaler is fitted during the fold fit, pre-test)
                    train_idx_i, _ = per_machine_folds[i][fold]
                    scaler = sk_clone(model.scaler).fit(ys_grid[i][train_idx_i])
                    scaled_true = scaler.transform(y_true)
                    scaled_pred = scaler.transform(y_pred)
                    scaled_mse = pd.Series(
                        ((scaled_pred - scaled_true) ** 2).mean(axis=1)
                    )
                    mae = pd.DataFrame(np.abs(y_pred - y_true))
                    agg_thr = scaled_mse.rolling(6).min().max()
                    tag_thr = mae.rolling(6).min().max()
                    tag_thr.name = f"fold-{fold}"
                    agg_thr_per_fold[i][f"fold-{fold}"] = (
                        float(agg_thr) if np.isfinite(agg_thr) else None
                    )
                    tag_thr_per_fold[i][f"fold-{fold}"] = tag_thr
                    tag_thresholds[i] = tag_thr
                    agg_thresholds[i] = agg_thr

        for i in range(len(bucket)):
            for metric_name, folds in raw_scores[i].items():
                arr = np.asarray(folds)
                entry = {
                    "fold-mean": float(arr.mean()),
                    "fold-std": float(arr.std()),
                    "fold-max": float(arr.max()),
                    "fold-min": float(arr.min()),
                }
                entry.update(
                    {f"fold-{k + 1}": float(v) for k, v in enumerate(folds)}
                )
                scores[i][metric_name] = entry

        return {
            "scores": scores,
            "splits": splits,
            "tag_thresholds": tag_thresholds,
            "agg_thresholds": agg_thresholds,
            "tag_thr_per_fold": tag_thr_per_fold,
            "agg_thr_per_fold": agg_thr_per_fold,
        }

    @staticmethod
    def _apply_thresholds(model: DiffBasedAnomalyDetector, fold_records: dict, i: int):
        # observability parity with the solo cv-fast-path flag: this
        # detector's thresholds came from the bucket's vmapped fold masks
        model.cv_fleet_masks_ = True
        model.feature_thresholds_ = fold_records["tag_thresholds"][i]
        agg = fold_records["agg_thresholds"][i]
        model.aggregate_threshold_ = float(agg) if agg is not None else None
        model.feature_thresholds_per_fold_ = pd.DataFrame(
            {k: v for k, v in fold_records["tag_thr_per_fold"][i].items()}
        ).T
        model.aggregate_thresholds_per_fold_ = fold_records["agg_thr_per_fold"][i]
        model.smooth_aggregate_threshold_ = None
        model.smooth_feature_thresholds_ = None
