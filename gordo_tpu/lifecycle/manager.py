"""
The lifecycle cycle (docs/lifecycle.md): one ``tick`` closes the loop
serving → drift → warm-start refit → shadow gate → blue/green
promotion.

A tick against a healthy fleet is a no-op: every machine's anomaly
statistics sit under their calibrated thresholds, the
:class:`~gordo_tpu.lifecycle.drift.DriftMonitor` reports nothing, and
no revision is created. When drift IS detected, only the drifted subset
refits (warm-started from the served params, per-machine fault
isolation via the PR-4 casualty machinery), each candidate is
shadow-scored against the live revision on a holdout window, and a new
sibling revision publishes atomically with every decision recorded in
``promotion_report.json``. The whole cycle is one trace
(``lifecycle.tick`` → ``lifecycle.drift`` / ``lifecycle.refit`` /
``lifecycle.shadow`` / ``lifecycle.promote``, with the refit's own
``build.fleet`` tree nested under it).
"""

import dataclasses
import json
import logging
import os
import time
import typing

import pandas as pd

from gordo_tpu import serializer
from gordo_tpu.lifecycle import promote as promote_mod
from gordo_tpu.lifecycle.drift import DriftAssessment, DriftMonitor
from gordo_tpu.lifecycle.refit import (
    DEFAULT_SHADOW_TOLERANCE,
    ShadowVerdict,
    degrade_params,
    shadow_gate,
    shadow_score,
)
from gordo_tpu.machine import Machine
from gordo_tpu.observability import emit_event, get_registry, tracing
from gordo_tpu.robustness import faults
from gordo_tpu.utils.compat import normalize_frequency

logger = logging.getLogger(__name__)

#: lifecycle state lives in a dot-directory next to the revisions, so
#: it can never be listed or selected as one
STATE_DIRNAME = ".lifecycle"


@dataclasses.dataclass
class LifecycleConfig:
    """Knobs of one lifecycle cycle (CLI flags map 1:1 onto these)."""

    #: drift/refit data window (ISO datetimes). None = each machine's
    #: own training window from its build metadata — the right default
    #: for re-scoring a static deployment; a scheduled daemon passes a
    #: sliding recent window.
    window_start: typing.Optional[str] = None
    window_end: typing.Optional[str] = None
    #: last fraction of the window held out of refit training and used
    #: for shadow scoring (candidate and live model, same frames)
    holdout_fraction: float = 0.25
    #: candidate may not regress live holdout error by more than this
    shadow_tolerance: float = DEFAULT_SHADOW_TOLERANCE
    ewma_alpha: float = 0.3
    ratio_threshold: float = 1.0
    exceedance_threshold: float = 0.5
    min_observations: int = 1
    fetch_retries: int = 1
    #: per-machine cap (seconds) on BOTH the drift-scan window fetch
    #: and the refit build's fetches — one hung data-source connection
    #: must not wedge the tick (or the watch daemon) forever. None =
    #: wait indefinitely.
    fetch_timeout: typing.Optional[float] = None
    #: streaming observation feed (docs/lifecycle.md "Scan-free
    #: ticks"): path of the JSONL event log whose accumulated
    #: ``stream_observation`` events feed the drift monitor for
    #: streamed machines — those machines skip the window-fetch scan
    #: entirely (the tick pays a fetch only if one of them actually
    #: drifts and must refit). None = the ``GORDO_TPU_EVENT_LOG`` env
    #: var at tick time (the same pipeline the server emits into).
    stream_observations: typing.Optional[str] = None
    #: assemble + publish the new revision; False stops after the
    #: shadow verdicts (a dry run: report only, no revision)
    promote: bool = True
    #: re-point the latest symlink at the new revision (only possible
    #: when the collection pointer IS a symlink)
    repoint: bool = True

    def __post_init__(self):
        if not 0.0 < float(self.holdout_fraction) < 1.0:
            raise ValueError(
                f"holdout_fraction must be in (0, 1), got "
                f"{self.holdout_fraction}"
            )
        if self.window_start is not None and self.window_end is not None:
            # a global override that is empty is an operator error and
            # fails fast; per-machine metadata problems degrade
            # per-machine instead (drift_scan_failed)
            if pd.Timestamp(self.window_end) <= pd.Timestamp(self.window_start):
                raise ValueError(
                    f"Empty lifecycle window: {self.window_start} -> "
                    f"{self.window_end}"
                )


@dataclasses.dataclass
class TickResult:
    """What one cycle did (the CLI prints this as JSON)."""

    base_revision: str
    revision: typing.Optional[str]
    revision_dir: typing.Optional[str]
    n_machines: int
    monitored: typing.List[str]
    drifted: typing.List[str]
    promoted: typing.List[str]
    rejected: typing.List[str]
    quarantined: typing.List[str]
    report: dict
    report_path: typing.Optional[str]
    wall_time_s: float

    @property
    def noop(self) -> bool:
        return self.revision is None

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["noop"] = self.noop
        return out


class LifecycleManager:
    """
    Parameters
    ----------
    collection_dir
        The served "latest" — either the revision directory itself or
        the ``latest`` symlink the server's ``MODEL_COLLECTION_DIR``
        names (the promotion flips the symlink; a plain directory can
        only be promoted into a sibling selectable via ``?revision=``).
    config
        :class:`LifecycleConfig`; None = defaults.
    monitor
        Pre-built :class:`DriftMonitor`; None builds one persisting
        under ``<revisions parent>/.lifecycle/drift_state.json``.
    """

    def __init__(
        self,
        collection_dir: typing.Union[str, os.PathLike],
        config: typing.Optional[LifecycleConfig] = None,
        monitor: typing.Optional[DriftMonitor] = None,
    ):
        self.pointer = str(collection_dir)
        self.config = config or LifecycleConfig()
        live_dir = os.path.realpath(self.pointer)
        self.state_dir = os.path.join(os.path.dirname(live_dir), STATE_DIRNAME)
        self.monitor = monitor or DriftMonitor(
            state_path=os.path.join(self.state_dir, "drift_state.json"),
            ewma_alpha=self.config.ewma_alpha,
            ratio_threshold=self.config.ratio_threshold,
            exceedance_threshold=self.config.exceedance_threshold,
            min_observations=self.config.min_observations,
        )

    # -- the cycle -------------------------------------------------------

    def tick(self) -> TickResult:
        """One full cycle; see the module docstring."""
        with tracing.start_span("lifecycle.tick", pointer=self.pointer):
            result = self._tick_traced()
        self._persist_last_tick(result)
        return result

    def _persist_last_tick(self, result: TickResult) -> None:
        """The watch daemon's member snapshot for the plane rollup
        (docs/observability.md "Plane rollup and control signals"):
        ``.lifecycle/last_tick.json``, written atomically per tick, is
        the file-shaped /telemetry/snapshot a poller reads to compute
        ``drift_scan_staleness_s``. Telemetry only — a failed write
        never fails the tick."""
        from gordo_tpu.observability import rollup as rollup_mod

        payload = rollup_mod.snapshot_payload(
            role="lifecycle",
            revision=result.revision or result.base_revision,
            status={
                "last_tick_unix_ms": int(time.time() * 1000),
                "base_revision": result.base_revision,
                "revision": result.revision,
                "n_machines": result.n_machines,
                "n_monitored": len(result.monitored),
                "n_drifted": len(result.drifted),
                "n_promoted": len(result.promoted),
                "n_quarantined": len(result.quarantined),
                "wall_time_s": round(result.wall_time_s, 4),
            },
        )
        path = os.path.join(self.state_dir, "last_tick.json")
        try:
            os.makedirs(self.state_dir, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(payload, fh, default=str)
            os.replace(tmp, path)
        except OSError as exc:
            logger.warning("Lifecycle last-tick snapshot not written: %s", exc)

    def _tick_traced(self) -> TickResult:
        start = time.perf_counter()
        live_dir = os.path.realpath(self.pointer)
        base_revision = os.path.basename(live_dir)
        carried = self._base_casualties(live_dir)
        names = sorted(
            name
            for name in os.listdir(live_dir)
            if not name.startswith(".")
            and os.path.isdir(os.path.join(live_dir, name))
            and name not in carried
        )

        decisions: typing.Dict[str, dict] = {
            name: {"decision": "carried", "reason": reason}
            for name, reason in carried.items()
        }
        live_models: typing.Dict[str, typing.Any] = {}
        machines_meta: typing.Dict[str, dict] = {}
        monitored: typing.List[str] = []

        fetched: typing.Dict[str, tuple] = {}
        # the streaming feed first (docs/lifecycle.md "Scan-free
        # ticks"): machines whose accumulated stream_observation events
        # cover this revision are assessed from those statistics and
        # SKIP the window-fetch scan — the serving plane already scored
        # their live data continuously
        streamed_stats = self._consume_stream_observations(base_revision)
        streamed: typing.Set[str] = set()
        with tracing.start_span("lifecycle.drift", n_machines=len(names)):
            for name in sorted(streamed_stats):
                if name not in names:
                    continue
                stats = streamed_stats[name]
                try:
                    assessment = self.monitor.observe_stats(
                        name,
                        ratio=stats["ratio"],
                        exceedance=stats["exceedance"],
                        revision=base_revision,
                    )
                except ValueError as exc:
                    logger.warning(
                        "Lifecycle: stream observations for %s unusable "
                        "(%s); machine falls back to the scan",
                        name, exc,
                    )
                    continue
                streamed.add(name)
                monitored.append(name)
                decisions[name] = {
                    "decision": "retained",
                    "reason": "no_drift",
                    "source": "stream",
                    "drift": assessment.to_dict(),
                }
            # serial metadata loads (local disk, cheap), then window
            # fetches POOLED in bounded chunks (per-machine network I/O
            # — the builder's fetch-pool shape), each machine scored on
            # the main thread as its fetch lands: the model artifact is
            # only loaded when its window is in hand, and model AND
            # frames stay resident ONLY while drifted — a tick's
            # footprint is O(pool width + drifted), never O(fleet).
            # The MACHINE is the fault domain throughout: one machine's
            # fetch/scoring failure is recorded on that machine and the
            # scan continues — never aborting the tick or losing the
            # observations already made.
            scan_windows: typing.Dict[str, dict] = {}
            scan_failures: typing.Dict[str, str] = {}
            for name in names:
                if name in streamed:
                    continue  # scan-free: the stream already scored it
                meta = self._load_metadata(live_dir, name)
                # the monitorability check loads the model and DROPS it
                # (scoring reloads later): a second local deserialize is
                # far cheaper than the network window fetch a
                # never-monitorable machine would otherwise pay every
                # tick of the daemon
                if meta is None or self._load_monitorable(live_dir, name) is None:
                    decisions[name] = {
                        "decision": "retained",
                        "reason": "not_monitored",
                    }
                    continue
                machines_meta[name] = meta
                try:
                    scan_windows[name] = self._machine_window(meta)
                except Exception as exc:  # noqa: BLE001 - fault domain
                    scan_failures[name] = str(exc)
            for name, data in self._iter_windows(
                scan_windows, machines_meta, scan_failures
            ):
                model = self._load_monitorable(live_dir, name)
                if model is None:
                    # it WAS monitorable moments ago; treat the reload
                    # racing an artifact change as a scan failure
                    scan_failures[name] = (
                        "artifact became unloadable during the scan"
                    )
                    continue
                try:
                    assessment = self._score_one(
                        name, model, data, base_revision,
                        machines_meta[name],
                    )
                except Exception as exc:  # noqa: BLE001 - fault domain
                    scan_failures[name] = str(exc)
                    continue
                monitored.append(name)
                decisions[name] = {
                    "decision": "retained",
                    "reason": "no_drift",
                    "drift": assessment.to_dict(),
                }
                if assessment.drifted:
                    # what warm start and shadow scoring will read
                    live_models[name] = model
                    fetched[name] = data
            for name in sorted(scan_failures):
                logger.warning(
                    "Lifecycle: drift scan failed for %s (%s); machine "
                    "retained this tick",
                    name, scan_failures[name],
                )
                decisions[name] = {
                    "decision": "retained",
                    "reason": "drift_scan_failed",
                    "error": scan_failures[name],
                }
            monitored.sort()
        self.monitor.save()
        self._commit_stream_cursor()
        drifted = [n for n in monitored if self.monitor.state(n).drifted]
        get_registry().gauge(
            "gordo_lifecycle_drifted_machines",
            "Machines currently past a drift criterion (last tick)",
        ).set(len(drifted))

        if not drifted:
            return self._finish(
                start, base_revision, names, monitored, drifted,
                decisions=decisions, promoted=[], rejected=[],
                quarantined=[], revision_dir=None,
            )
        logger.info(
            "Drift detected on %d/%d machines: %s",
            len(drifted), len(monitored), drifted,
        )

        # streamed machines drifted without any scan fetch; refit and
        # shadow still need the live model and a data window, so pay
        # that I/O NOW, for exactly the drifted streamed subset — the
        # scan-free tick's only window fetches, O(drifted) by
        # construction (docs/lifecycle.md "Scan-free ticks")
        stream_failures: typing.Dict[str, str] = {}
        to_fetch: typing.Dict[str, dict] = {}
        for name in [n for n in drifted if n in streamed]:
            meta = machines_meta.get(name) or self._load_metadata(
                live_dir, name
            )
            model = self._load_monitorable(live_dir, name)
            if meta is None or model is None:
                stream_failures[name] = "model or metadata not loadable"
                continue
            machines_meta[name] = meta
            try:
                scan_windows[name] = self._machine_window(meta)
            except Exception as exc:  # noqa: BLE001 - fault domain
                stream_failures[name] = str(exc)
                continue
            to_fetch[name] = scan_windows[name]
            live_models[name] = model
        if to_fetch:
            for name, data in self._iter_windows(
                to_fetch, machines_meta, stream_failures
            ):
                fetched[name] = data
        for name in sorted(stream_failures):
            logger.warning(
                "Lifecycle: refit window for streamed machine %s "
                "unavailable (%s); machine retained this tick",
                name, stream_failures[name],
            )
            decisions[name].update(
                decision="retained",
                reason="refit_data_unavailable",
                error=stream_failures[name],
            )
            drifted.remove(name)
            live_models.pop(name, None)
        if not drifted:
            return self._finish(
                start, base_revision, names, monitored, drifted,
                decisions=decisions, promoted=[], rejected=[],
                quarantined=[], revision_dir=None,
            )

        # every drifted machine's window is now computed (scan, or the
        # refit-time fetch above) — reuse those exact values
        window = {name: scan_windows[name] for name in drifted}
        with tracing.start_span("lifecycle.refit", n_machines=len(drifted)):
            candidates, quarantine_records, refit_failures = self._refit(
                drifted, machines_meta, window, live_models
            )

        promoted: typing.List[str] = []
        rejected: typing.List[str] = []
        quarantined: typing.List[str] = []
        with tracing.start_span("lifecycle.shadow", n_machines=len(drifted)):
            for name in drifted:
                record = decisions[name]
                record["drift"] = record.get("drift") or {}
                if name in quarantine_records:
                    quarantined.append(name)
                    record.update(
                        decision="quarantined",
                        reason="refit_nonfinite",
                        quarantine=quarantine_records[name],
                    )
                    continue
                if name not in candidates:
                    record.update(
                        decision="retained",
                        reason="refit_failed",
                        error=refit_failures.get(name),
                    )
                    continue
                verdict = self._shadow_one(
                    name, live_models[name], candidates[name][0],
                    fetched[name], window[name],
                )
                record["shadow"] = verdict.to_dict()
                if verdict.promote:
                    promoted.append(name)
                    record.update(
                        decision="promoted", reason="drifted_passed_shadow"
                    )
                else:
                    rejected.append(name)
                    record.update(
                        decision="retained", reason="shadow_rejected"
                    )
                    emit_event(
                        "refit_rejected",
                        machine=name,
                        live_score=verdict.live_score,
                        candidate_score=verdict.candidate_score,
                        tolerance=verdict.tolerance,
                    )

        revision_dir: typing.Optional[str] = None
        if self.config.promote and (promoted or quarantined):
            with tracing.start_span(
                "lifecycle.promote",
                n_promoted=len(promoted),
                n_quarantined=len(quarantined),
            ):
                revision_dir = str(
                    self._promote(
                        live_dir, base_revision, decisions, candidates,
                        quarantine_records,
                    )
                )
                if self.config.repoint and os.path.islink(self.pointer):
                    promote_mod.repoint_latest(self.pointer, revision_dir)
                # the new revision starts every machine on a fresh drift
                # baseline (new params for promoted machines, and the
                # revision binding would reset the rest on next tick
                # anyway)
                self.monitor.reset()
                self.monitor.save()

        return self._finish(
            start, base_revision, names, monitored, drifted,
            decisions=decisions, promoted=promoted, rejected=rejected,
            quarantined=quarantined, revision_dir=revision_dir,
        )

    # -- phases ----------------------------------------------------------

    @staticmethod
    def _load_metadata(live_dir: str, name: str) -> typing.Optional[dict]:
        """The machine's build metadata (None = unreadable): the cheap
        per-machine load the scan pays up front — the model artifact
        itself is deferred to scoring time, so it need not stay
        resident for the whole scan."""
        try:
            return serializer.load_metadata(os.path.join(live_dir, name))
        except Exception as exc:  # noqa: BLE001 - per-machine tolerance
            logger.warning(
                "Lifecycle: metadata for %s does not load (%s)", name, exc
            )
            return None

    def _load_monitorable(
        self, live_dir: str, name: str
    ) -> typing.Optional[typing.Any]:
        """The machine's model when the artifact loads and is an
        anomaly detector with calibrated thresholds; None = the machine
        cannot be drift-monitored."""
        from gordo_tpu.models.anomaly.base import AnomalyDetectorBase

        try:
            model = serializer.load(os.path.join(live_dir, name))
        except Exception as exc:  # noqa: BLE001 - per-machine tolerance
            logger.warning("Lifecycle: artifact %s does not load (%s)", name, exc)
            return None
        threshold = getattr(model, "aggregate_threshold_", None)
        if not isinstance(model, AnomalyDetectorBase) or not threshold:
            logger.debug(
                "Lifecycle: %s is not an anomaly detector with calibrated "
                "thresholds; not drift-monitored",
                name,
            )
            return None
        return model

    def _iter_windows(
        self,
        scan_windows: typing.Dict[str, dict],
        machines_meta: typing.Dict[str, dict],
        scan_failures: typing.Dict[str, str],
    ) -> typing.Iterator[typing.Tuple[str, tuple]]:
        """
        Yield ``(name, (X, y))`` over each machine's scan window,
        fetched concurrently in pool-width chunks (per-machine network
        I/O — serially this would dominate tick wall-clock at fleet
        scale, while fetching the WHOLE fleet before scoring would hold
        every window's frames at once). The consumer scores and drops
        each window before the next chunk is submitted, so retained
        frames stay bounded by the chunk. A machine whose fetch raises
        or exceeds ``fetch_timeout`` lands in ``scan_failures`` instead
        of being yielded.
        """
        from concurrent.futures import ThreadPoolExecutor
        from concurrent.futures import TimeoutError as FutureTimeoutError

        if not scan_windows:
            return
        ordered = sorted(scan_windows)
        width = min(8, len(ordered))
        pool = ThreadPoolExecutor(max_workers=width)
        hung = False
        try:
            for i in range(0, len(ordered), width):
                futures = {
                    name: pool.submit(
                        self._fetch_window,
                        machines_meta[name],
                        scan_windows[name]["start"],
                        scan_windows[name]["end"],
                    )
                    for name in ordered[i : i + width]
                }
                for name, future in futures.items():
                    try:
                        yield name, future.result(
                            timeout=self.config.fetch_timeout
                        )
                    except FutureTimeoutError:
                        hung = True  # the worker cannot be interrupted
                        future.cancel()
                        scan_failures[name] = (
                            f"window fetch exceeded "
                            f"{self.config.fetch_timeout}s"
                        )
                    except Exception as exc:  # noqa: BLE001 - fault domain
                        scan_failures[name] = str(exc)
        finally:
            # the builder's discipline (fleet_build.fetch_data): a hung
            # fetch thread must not wedge the rest of the tick at pool
            # teardown
            pool.shutdown(wait=not hung, cancel_futures=True)

    def _score_one(
        self,
        name: str,
        model: typing.Any,
        data: tuple,
        base_revision: str,
        meta: dict,
    ) -> DriftAssessment:
        """Anomaly-score one machine's fetched window (main thread —
        the device program) and feed the monitor."""
        X, y = data
        shift = faults.drift_shift_scale(name)
        if shift is not None:
            # the chaos harness's synthetic sensor drift: inputs AND
            # targets move together, as a real drifting sensor's would
            # (X and y are the same physical signals here)
            X = X + shift
            y = y + shift
        frequency = pd.tseries.frequencies.to_offset(
            normalize_frequency(meta["dataset"].get("resolution", "10min"))
        )
        frame = model.anomaly(X, y, frequency=frequency)
        return self.monitor.observe(
            name, frame, threshold=float(model.aggregate_threshold_),
            revision=base_revision,
        )

    def _refit(
        self,
        drifted: typing.List[str],
        machines_meta: typing.Dict[str, dict],
        window: typing.Dict[str, dict],
        live_models: typing.Dict[str, typing.Any],
    ) -> typing.Tuple[
        typing.Dict[str, tuple], typing.Dict[str, dict], typing.Dict[str, str]
    ]:
        """
        Warm-start refit of exactly the drifted subset, in memory (no
        artifact flush — promotion serializes the winners), initialized
        from the live models the drift scan already holds. Returns
        ``(candidates, quarantine_records, refit_failures)``.
        """
        from gordo_tpu.builder.fleet_build import FleetModelBuilder
        from gordo_tpu.lifecycle.refit import warm_params_from_models

        refit_machines = []
        for name in drifted:
            spec = json.loads(json.dumps(machines_meta[name], default=str))
            # train on the window HEAD only: the holdout tail is the
            # shadow gate's unseen data
            spec["dataset"]["train_start_date"] = window[name]["start"]
            spec["dataset"]["train_end_date"] = window[name]["split"]
            refit_machines.append(Machine.unvalidated(**spec))

        builder = FleetModelBuilder(
            refit_machines,
            on_error="skip",  # one poisoned machine must not kill the cycle
            fetch_retries=self.config.fetch_retries,
            fetch_timeout=self.config.fetch_timeout,
            initial_params=warm_params_from_models(live_models),
            fault_sites=("train", "refit"),
        )
        built = builder.build()
        candidates = {machine.name: (model, machine) for model, machine in built}
        quarantine_records = {
            rec["machine"]: dict(rec) for rec in builder.quarantined_
        }
        refit_failures = {
            rec["machine"]: f"{rec.get('phase', 'build')}: {rec.get('error')}"
            for rec in builder.build_failures_
        }
        # a quarantined machine's "candidate" holds frozen rolled-back
        # params; it must never reach the shadow gate
        for name in quarantine_records:
            candidates.pop(name, None)
        return candidates, quarantine_records, refit_failures

    def _shadow_one(
        self,
        name: str,
        live_model: typing.Any,
        candidate_model: typing.Any,
        data: tuple,
        window: dict,
    ) -> ShadowVerdict:
        """Score candidate vs live on the holdout tail of the window —
        sliced from the frames the drift scan already fetched (``data``
        is the full-window ``(X, y)``), not re-fetched: the gate judges
        on the very data drift was observed on, and the shadow phase
        pays no further network I/O."""
        from gordo_tpu.builder.fleet_build import _find_jax_estimator

        degrade = faults.refit_degrade_scale(name)
        if degrade is not None:
            est = _find_jax_estimator(candidate_model)
            if est is not None and getattr(est, "params_", None) is not None:
                est.params_ = degrade_params(est.params_, degrade)
        X, y = data
        split = pd.Timestamp(window["split"])
        X = X.loc[X.index >= split]
        y = y.loc[y.index >= split]
        live_score = shadow_score(live_model, X, y)
        candidate_score = shadow_score(candidate_model, X, y)
        return ShadowVerdict(
            machine=name,
            live_score=live_score,
            candidate_score=candidate_score,
            tolerance=self.config.shadow_tolerance,
            promote=shadow_gate(
                live_score, candidate_score, self.config.shadow_tolerance
            ),
        )

    def _promote(
        self,
        live_dir: str,
        base_revision: str,
        decisions: typing.Dict[str, dict],
        candidates: typing.Dict[str, tuple],
        quarantine_records: typing.Dict[str, dict],
    ):
        base_report = self._read_build_report(live_dir)
        build_report = {
            "kind": "lifecycle_promotion",
            "base_revision": base_revision,
            "on_error": "skip",
            "failed": list(base_report.get("failed") or []),
            "quarantined": list(base_report.get("quarantined") or [])
            + [
                {"machine": name, "epoch": rec.get("epoch"), "phase": "refit"}
                for name, rec in sorted(quarantine_records.items())
            ],
        }
        build_report["n_failed"] = len(build_report["failed"])
        build_report["n_quarantined"] = len(build_report["quarantined"])
        promotion_report = {
            "kind": "lifecycle_promotion",
            "base_revision": base_revision,
            "window": {
                "start": self.config.window_start,
                "end": self.config.window_end,
                "holdout_fraction": self.config.holdout_fraction,
            },
            "shadow_tolerance": self.config.shadow_tolerance,
            "decisions": decisions,
            "counts": _decision_counts(decisions),
        }
        return promote_mod.assemble_revision(
            live_dir, decisions, candidates, build_report, promotion_report
        )

    # -- bookkeeping -----------------------------------------------------

    def _finish(
        self,
        start: float,
        base_revision: str,
        names: typing.List[str],
        monitored: typing.List[str],
        drifted: typing.List[str],
        decisions: typing.Dict[str, dict],
        promoted: typing.List[str],
        rejected: typing.List[str],
        quarantined: typing.List[str],
        revision_dir: typing.Optional[str],
    ) -> TickResult:
        wall = time.perf_counter() - start
        revision = (
            os.path.basename(revision_dir) if revision_dir is not None else None
        )
        reg = get_registry()
        reg.histogram(
            "gordo_lifecycle_tick_seconds", "One whole lifecycle cycle"
        ).observe(wall)
        counter = reg.counter(
            "gordo_lifecycle_machines_total",
            "Lifecycle decisions by outcome",
            ("outcome",),
        )
        for name in drifted:
            if name in promoted:
                counter.inc(outcome="promoted")
            elif name in quarantined:
                counter.inc(outcome="quarantined")
            elif name in rejected:
                counter.inc(outcome="rejected")
            else:
                counter.inc(outcome="retained")
        report = {
            "base_revision": base_revision,
            "revision": revision,
            "decisions": decisions,
            "counts": _decision_counts(decisions),
        }
        report_path = (
            os.path.join(revision_dir, promote_mod.PROMOTION_REPORT_FILENAME)
            if revision_dir is not None
            else None
        )
        if revision is not None:
            emit_event(
                "revision_promoted",
                revision=revision,
                base_revision=base_revision,
                n_promoted=len(promoted),
                n_rejected=len(rejected),
                n_quarantined=len(quarantined),
            )
        emit_event(
            "lifecycle_tick_finished",
            base_revision=base_revision,
            revision=revision,
            n_machines=len(names),
            n_monitored=len(monitored),
            n_drifted=len(drifted),
            n_promoted=len(promoted),
            n_rejected=len(rejected),
            n_quarantined=len(quarantined),
            wall_time_s=round(wall, 4),
        )
        return TickResult(
            base_revision=base_revision,
            revision=revision,
            revision_dir=revision_dir,
            n_machines=len(names),
            monitored=monitored,
            drifted=drifted,
            promoted=promoted,
            rejected=rejected,
            quarantined=quarantined,
            report=report,
            report_path=report_path,
            wall_time_s=wall,
        )

    def _consume_stream_observations(
        self, base_revision: str
    ) -> typing.Dict[str, dict]:
        """
        Drain accumulated ``stream_observation`` events from the event
        log (config ``stream_observations``, default the
        ``GORDO_TPU_EVENT_LOG`` pipeline the serving plane emits into)
        and aggregate them per machine, weighted by row count — exactly
        the statistic one scan window over the same rows would produce.
        A byte cursor under ``.lifecycle/`` makes consumption
        incremental across ticks (each observation feeds the monitor
        once); a truncated/rotated log resets it, and a torn trailing
        line is left for the next tick. Observations stamped by a
        DIFFERENT revision are dropped (counted) — the tick assesses
        ``base_revision``, and the monitor's revision binding must not
        be reset backwards by a pre-roll straggler.
        """
        from gordo_tpu.observability.events import EVENT_LOG_ENV_VAR

        self._pending_stream_cursor = None
        path = self.config.stream_observations or os.environ.get(
            EVENT_LOG_ENV_VAR, ""
        )
        if not path or not os.path.isfile(path):
            return {}
        path = os.path.abspath(path)
        cursor_path = os.path.join(self.state_dir, "stream_cursor.json")
        offset = 0
        try:
            with open(cursor_path) as fh:
                cursor = json.load(fh)
            if cursor.get("path") == path:
                offset = int(cursor.get("offset", 0))
        except (OSError, ValueError, TypeError):
            offset = 0
        try:
            if os.path.getsize(path) < offset:
                offset = 0  # rotated/truncated: start over
        except OSError:
            return {}
        totals: typing.Dict[str, typing.List[float]] = {}
        consumed = offset
        dropped_revisions = 0
        try:
            with open(path, "rb") as fh:
                fh.seek(offset)
                for raw in fh:
                    if not raw.endswith(b"\n"):
                        break  # torn trailing line: next tick's problem
                    consumed += len(raw)
                    try:
                        record = json.loads(raw)
                    except ValueError:
                        continue
                    if record.get("event") != "stream_observation":
                        continue
                    machine = record.get("machine")
                    try:
                        n = int(record.get("n") or 0)
                        ratio = float(record.get("ratio_mean"))
                        exceedance = float(record.get("exceedance"))
                    except (TypeError, ValueError):
                        continue
                    if not machine or n <= 0:
                        continue
                    if record.get("revision") != base_revision:
                        dropped_revisions += 1
                        continue
                    bucket = totals.setdefault(machine, [0.0, 0.0, 0.0])
                    bucket[0] += n
                    bucket[1] += n * ratio
                    bucket[2] += n * exceedance
        except OSError as exc:
            logger.warning(
                "Lifecycle: stream observation log %s unreadable (%s); "
                "falling back to the scan", path, exc,
            )
            return {}
        if consumed != offset:
            # NOT persisted here: the cursor only advances once the
            # drained statistics are safe in the monitor's saved state
            # (_commit_stream_cursor, after monitor.save()) — a tick
            # that dies in between must re-drain, not silently discard
            # the consumed drift evidence
            self._pending_stream_cursor = (
                cursor_path,
                {"path": path, "offset": consumed},
            )
        if dropped_revisions:
            logger.info(
                "Lifecycle: dropped %d stream observation(s) stamped by "
                "other revisions than %s",
                dropped_revisions, base_revision,
            )
        return {
            machine: {
                "n": int(n),
                "ratio": ratio_sum / n,
                "exceedance": exceedance_sum / n,
            }
            for machine, (n, ratio_sum, exceedance_sum) in totals.items()
        }

    def _commit_stream_cursor(self) -> None:
        """Persist the advanced stream-observation cursor — called only
        after ``monitor.save()`` so consumption is at-least-once: a
        crash between drain and save re-drains the same bytes (the
        monitor's windowed state makes the re-feed idempotent enough;
        losing the evidence is the failure that matters)."""
        from gordo_tpu.utils.atomic import atomic_write_json

        pending = getattr(self, "_pending_stream_cursor", None)
        if pending:
            atomic_write_json(*pending)
            self._pending_stream_cursor = None

    def _machine_window(self, meta: dict) -> dict:
        """The machine's drift/refit window and its holdout split point
        (ISO strings) — the config override, or its own train window."""
        dataset = meta["dataset"]
        start = pd.Timestamp(
            self.config.window_start or dataset["train_start_date"]
        )
        end = pd.Timestamp(self.config.window_end or dataset["train_end_date"])
        if end <= start:
            raise ValueError(
                f"Empty lifecycle window: {start} -> {end}"
            )
        split = start + (end - start) * (1.0 - self.config.holdout_fraction)
        return {
            "start": start.isoformat(),
            "split": split.isoformat(),
            "end": end.isoformat(),
        }

    @staticmethod
    def _fetch_window(meta: dict, start: str, end: str):
        """(X, y) for one machine over [start, end], via its own
        dataset config (the builder's fetch path, without the pool)."""
        from gordo_tpu.data import _get_dataset

        config = json.loads(json.dumps(meta["dataset"], default=str))
        config["train_start_date"] = start
        config["train_end_date"] = end
        X, y = _get_dataset(config).get_data()
        return X, (y if y is not None else X)

    @staticmethod
    def _base_casualties(live_dir: str) -> typing.Dict[str, str]:
        """Machine -> reason for the live revision's recorded
        casualties: they are 409'd as served, cannot be drift-scored,
        and carry their records into any promoted revision."""
        report = LifecycleManager._read_build_report(live_dir)
        out: typing.Dict[str, str] = {}
        for record in report.get("failed") or []:
            if record.get("machine"):
                out[record["machine"]] = (
                    f"{record.get('phase', 'build')}_failed"
                )
        for record in report.get("quarantined") or []:
            if record.get("machine"):
                out[record["machine"]] = "quarantined"
        return out

    @staticmethod
    def _read_build_report(live_dir: str) -> dict:
        path = os.path.join(live_dir, promote_mod.BUILD_REPORT_FILENAME)
        try:
            with open(path) as fh:
                return json.load(fh)
        except FileNotFoundError:
            return {}
        except (OSError, ValueError):
            logger.warning("Unreadable build report at %s; ignoring", path)
            return {}


def _decision_counts(decisions: typing.Dict[str, dict]) -> dict:
    counts: typing.Dict[str, int] = {}
    for record in decisions.values():
        counts[record["decision"]] = counts.get(record["decision"], 0) + 1
    return counts
