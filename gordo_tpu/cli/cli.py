"""
CLI entry points (reference parity: gordo/cli/cli.py).

Commands: ``build`` (one Machine per process — reference semantics),
``build-fleet`` (TPU-native addition: a bucket of Machines trained as one
vmapped XLA program per architecture bucket — the fleet builder that
replaces one-pod-per-model), ``run-server``, ``lint`` (the
gordo_tpu.analysis static/JAX-discipline checker), plus the
``workflow``, ``client``, ``telemetry``, ``trace`` and ``lifecycle``
groups.

Note: the reference snapshot plants a fault raising FileNotFoundError for
machine names containing "err" (gordo/cli/cli.py:178-179); that is a bug in
the snapshot and is deliberately not replicated.
"""

import json
import logging
import os
import sys
import traceback
from typing import Any, List, Tuple, cast

import click
import jinja2
import numpy as np
import yaml

from gordo_tpu import __version__, serializer, utils
from gordo_tpu.builder import FleetModelBuilder, ModelBuilder
from gordo_tpu.builder import ledger as fleet_ledger
from gordo_tpu.cli.buckets import buckets_cli
from gordo_tpu.cli.client import client as gordo_client
from gordo_tpu.cli.custom_types import HostIP, key_value_par
from gordo_tpu.cli.exceptions_reporter import ExceptionsReporter, ReportLevel
from gordo_tpu.cli.gameday import gameday_cli
from gordo_tpu.cli.lifecycle import lifecycle_cli
from gordo_tpu.cli.lint import lint_cli, lockgraph_cli
from gordo_tpu.cli.plane import rollup_cli, slo_cli, top_cli
from gordo_tpu.cli.profile import profile_cli
from gordo_tpu.cli.trace import trace_cli
from gordo_tpu.cli.tune import tune_cli
from gordo_tpu.cli.workflow_generator import workflow_cli
from gordo_tpu.data.base import InsufficientDataError
from gordo_tpu.data.datasets import InsufficientDataAfterRowFilteringError
from gordo_tpu.data.providers import NoSuitableDataProviderError
from gordo_tpu.data.sensor_tag import SensorTagNormalizationError
from gordo_tpu.machine import Machine
from gordo_tpu.reporters.base import ReporterException

logger = logging.getLogger(__name__)

#: Exception class → pod exit code (reference: cli.py:36-49; the azure
#: datalake transfer error has no equivalent in this stack).
_exceptions_reporter = ExceptionsReporter(
    (
        (Exception, 1),
        (PermissionError, 20),
        (FileNotFoundError, 30),
        (SensorTagNormalizationError, 60),
        (NoSuitableDataProviderError, 70),
        (InsufficientDataError, 80),
        (InsufficientDataAfterRowFilteringError, 81),
        (ReporterException, 90),
    )
)


@click.group("gordo-tpu")
@click.version_option(version=__version__, message=__version__)
@click.option(
    "--log-level",
    type=str,
    default="INFO",
    help="Run with custom log-level.",
    envvar="GORDO_LOG_LEVEL",
)
@click.pass_context
def gordo(gordo_ctx: click.Context, **ctx):
    """gordo-tpu: build, serve and orchestrate fleets of time-series models on TPU."""
    logging.basicConfig(
        level=getattr(logging, str(gordo_ctx.params.get("log_level")).upper()),
        format=(
            "[%(asctime)s] %(levelname)s "
            "[%(name)s.%(funcName)s:%(lineno)d] %(message)s"
        ),
    )
    gordo_ctx.obj = gordo_ctx.params


_build_options = [
    click.option(
        "--model-register-dir",
        default=None,
        envvar="MODEL_REGISTER_DIR",
        type=click.Path(exists=False, file_okay=False, dir_okay=True),
        help="Directory indexing built models for reuse (the build cache).",
    ),
    click.option(
        "--print-cv-scores",
        help="Print CV scores to stdout (Katib key=value format)",
        is_flag=True,
        default=False,
    ),
    click.option(
        "--model-parameter",
        type=key_value_par,
        multiple=True,
        default=(),
        help="key,value pair injected into jinja variables of a string "
        "model config; repeatable.",
    ),
    click.option(
        "--exceptions-reporter-file",
        envvar="EXCEPTIONS_REPORTER_FILE",
        help="JSON output file for exception information",
    ),
    click.option(
        "--exceptions-report-level",
        type=click.Choice(ReportLevel.get_names(), case_sensitive=False),
        default=ReportLevel.MESSAGE.name,
        envvar="EXCEPTIONS_REPORT_LEVEL",
        help="Detail level for exception reporting",
    ),
]


def _refuse_local_workers_off_cpu(n_workers: int, option: str) -> None:
    """
    ``n_workers`` > 1 local processes each initialize JAX, and an
    accelerator belongs to ONE process at a time — the rest hang or fail
    on the held chip. So off the CPU more than one local worker is a
    usage error. Decided from ``JAX_PLATFORMS`` alone: asking JAX for its
    devices here would initialize the backend in this parent, which then
    holds the chip its own workers need — the fault itself.
    """
    if n_workers > 1 and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise click.UsageError(
            f"{option} {n_workers}: every local worker process needs the "
            "accelerator, and a chip belongs to one process at a time. "
            "Run one worker per chip (scale out by replica or host), or "
            "set JAX_PLATFORMS=cpu for a CPU deployment."
        )


def _with_build_options(fn):
    for option in reversed(_build_options):
        fn = option(fn)
    return fn


def _report_and_exit(exceptions_reporter_file: str, exceptions_report_level: str):
    """Shared failure path: JSON report + typed exit code."""
    traceback.print_exc()
    exc_type, exc_value, exc_traceback = sys.exc_info()
    exit_code = _exceptions_reporter.exception_exit_code(exc_type)
    if exceptions_reporter_file:
        _exceptions_reporter.safe_report(
            cast(
                ReportLevel,
                ReportLevel.get_by_name(
                    exceptions_report_level, ReportLevel.EXIT_CODE
                ),
            ),
            exc_type,
            exc_value,
            exc_traceback,
            exceptions_reporter_file,
            max_message_len=2024 - 500,
        )
    sys.exit(exit_code)


@click.command()
@click.argument("machine-config", envvar="MACHINE", type=yaml.safe_load)
@click.argument("output-dir", default="/data", envvar="OUTPUT_DIR")
@_with_build_options
def build(
    machine_config: dict,
    output_dir: str,
    model_register_dir: str,
    print_cv_scores: bool,
    model_parameter: List[Tuple[str, Any]],
    exceptions_reporter_file: str,
    exceptions_report_level: str,
):
    """
    Build one model from MACHINE-CONFIG and write it to OUTPUT-DIR
    (reference: cli.py:80-206; env-driven in pods: MACHINE, OUTPUT_DIR).
    """
    try:
        utils.enable_compile_cache()
        if model_parameter and isinstance(machine_config["model"], str):
            machine_config["model"] = expand_model(
                machine_config["model"], dict(model_parameter)
            )
        machine = Machine.from_config(
            machine_config, project_name=machine_config["project_name"]
        )
        logger.info("Building, output will be at: %s", output_dir)

        # Round-trip the model config through the serializer so defaults are
        # expanded into the stored definition (reference: cli.py:164-168).
        machine.model = serializer.into_definition(
            serializer.from_definition(machine.model)
        )

        builder = ModelBuilder(machine=machine)
        _, machine_out = builder.build(output_dir, model_register_dir)

        machine_out.report()

        if print_cv_scores:
            for score in get_all_score_strings(machine_out):
                print(score)
    except Exception:
        _report_and_exit(exceptions_reporter_file, exceptions_report_level)
    else:
        return 0


@click.command("build-fleet")
@click.argument(
    "machines-config",
    envvar="MACHINES",
    type=yaml.safe_load,
    required=False,
    default=None,
)
@click.argument("output-dir", default="/data", envvar="OUTPUT_DIR")
@click.option(
    "--workers",
    default="1",
    envvar="GORDO_BUILD_WORKERS",
    show_default=True,
    help="Shard the build's buckets across this many worker PROCESSES "
    "coordinated through a crash-tolerant work ledger on the shared "
    "output volume ('auto' sizes to the host). 1 (the default) is the "
    "plain single-process build — no ledger, no lease files. See "
    "docs/robustness.md 'Multi-worker builds'.",
)
@click.option(
    "--worker-id",
    type=int,
    default=None,
    envvar="GORDO_WORKER_ID",
    help="Run as ONE worker of a multi-worker build (joins the ledger "
    "under OUTPUT-DIR instead of spawning workers). Normally set by "
    "the orchestrator; set it yourself to run workers across hosts "
    "sharing the output volume.",
)
@click.option(
    "--lease-ttl",
    type=click.FloatRange(min=0, min_open=True),
    default=fleet_ledger.DEFAULT_LEASE_TTL_S,
    envvar="GORDO_LEASE_TTL",
    show_default=True,
    help="Seconds a work unit's lease may go without a heartbeat before "
    "a live worker steals it (a SIGKILL'd worker costs one unit of "
    "rework, not the build).",
)
@click.option(
    "--max-attempts",
    type=click.IntRange(min=1),
    default=fleet_ledger.DEFAULT_MAX_ATTEMPTS,
    envvar="GORDO_MAX_ATTEMPTS",
    show_default=True,
    help="Worker deaths a unit survives before it is poisoned: recorded "
    "as a per-machine casualty in build_report.json instead of "
    "crash-looping the fleet.",
)
@click.option(
    "--machines-from",
    type=click.Path(exists=True, dir_okay=False),
    default=None,
    help="Read MACHINES-CONFIG from this JSON/YAML file instead of the "
    "argument/env var — Linux caps each exec string at 128KB, which "
    "thousand-machine configs outgrow; the multi-worker orchestrator "
    "hands its workers their config this way via the ledger directory.",
)
@click.option(
    "--ledger-status",
    "ledger_status_dir",
    type=click.Path(exists=False, file_okay=False, dir_okay=True),
    default=None,
    help="Print the multi-worker ledger's state under this build output "
    "directory — unit states, attempts, per-worker last-heartbeat age "
    "(spot a stalled worker BEFORE its lease expires) — and exit.",
)
@click.option(
    "--resume/--no-resume",
    default=False,
    envvar="GORDO_FLEET_RESUME",
    help="Reuse machines whose artifacts already load from OUTPUT-DIR and "
    "build only the rest — artifacts flush per bucket, so re-running after "
    "a runtime crash completes the fleet instead of restarting it.",
)
@click.option(
    "--on-error",
    type=click.Choice(["raise", "skip"]),
    default="raise",
    envvar="GORDO_ON_ERROR",
    show_default=True,
    help="Per-machine failure policy: 'raise' aborts the build on the "
    "first machine whose data fetch or build fails (reference "
    "semantics); 'skip' records the casualty in build_report.json (and "
    "the telemetry report) and builds the surviving machines — the "
    "machine, not the fleet, is the fault domain.",
)
@click.option(
    "--fetch-retries",
    type=click.IntRange(min=0),
    default=2,
    envvar="GORDO_FETCH_RETRIES",
    show_default=True,
    help="Per-machine retries for the data-fetch phase (exponential "
    "backoff between attempts).",
)
@click.option(
    "--fetch-timeout",
    type=click.FloatRange(min=0, min_open=True),
    default=None,
    envvar="GORDO_FETCH_TIMEOUT",
    help="Per-machine cap, in seconds, on waiting for one machine's "
    "data fetch (all attempts included); unset waits forever.",
)
@click.option(
    "--aot-cache/--no-aot-cache",
    default=True,
    envvar="GORDO_AOT_CACHE",
    show_default=True,
    help="AOT-compile + serialize the built collection's serving "
    "programs beside the artifacts (OUTPUT-DIR/.programs) with a "
    "jax/backend/device compatibility manifest, so a fresh server's "
    "cold start deserializes instead of re-tracing "
    "(docs/performance.md 'AOT executable cache').",
)
@click.option(
    "--bucket-policy",
    type=click.Choice(["exact", "padded"]),
    default="exact",
    envvar="GORDO_BUCKET_POLICY",
    show_default=True,
    help="Bucketing-compiler grouping policy (docs/parallelism.md "
    "'Bucketing compiler'): 'exact' compiles one program per exact "
    "(config, n_features, n_features_out) geometry — the historical "
    "grouping, bit-identical; 'padded' fuses same-architecture-family "
    "machines with ragged feature widths into one program at "
    "power-of-two padded dims (fewer compiles; pad columns are masked "
    "out of training and stripped from responses). Preview with "
    "`gordo-tpu buckets plan`.",
)
@click.option(
    "--precision",
    type=click.Choice(["float32", "bf16", "auto"]),
    default="float32",
    envvar="GORDO_PRECISION",
    show_default=True,
    help="Inference precision mode (docs/performance.md 'Mixed "
    "precision'): 'float32' is the historical bit-identical path (no "
    "calibration pass); 'auto' calibrates every machine's bf16 "
    "predictions against its float32 build and serves bf16 only where "
    "the MAE delta clears --precision-tolerance (per-machine decision "
    "in build_report.json); 'bf16' is the operator override — every "
    "machine serves bf16, breaches logged but not enforced. Training "
    "always runs float32.",
)
@click.option(
    "--precision-tolerance",
    type=click.FloatRange(min=0),
    default=0.25,
    envvar="GORDO_PRECISION_TOLERANCE",
    show_default=True,
    help="Relative reconstruction-MAE tolerance for the bf16 "
    "calibration — the same bound padded-vs-exact parity is held to.",
)
@click.option(
    "--prefetch-depth",
    type=click.IntRange(min=0, max=8),
    default=0,
    envvar="GORDO_PREFETCH_DEPTH",
    show_default=True,
    help="Host->device transfer pipelining depth (docs/performance.md "
    "'transfer pipelining'): 0 is the historical single-transfer path "
    "(bit-identical); >0 double-buffers the builder's stacked-data "
    "transfer so later slices stream while the first is consumed.",
)
@_with_build_options
def build_fleet(
    machines_config: list,
    output_dir: str,
    resume: bool,
    on_error: str,
    bucket_policy: str,
    precision: str,
    precision_tolerance: float,
    prefetch_depth: int,
    fetch_retries: int,
    fetch_timeout: float,
    aot_cache: bool,
    workers: str,
    worker_id: int,
    lease_ttl: float,
    max_attempts: int,
    machines_from: str,
    ledger_status_dir: str,
    model_register_dir: str,
    print_cv_scores: bool,
    model_parameter: List[Tuple[str, Any]],
    exceptions_reporter_file: str,
    exceptions_report_level: str,
):
    """
    Build MANY models in one process: machines are bucketed by architecture
    and each bucket trains as a single vmapped, mesh-sharded XLA program
    (TPU-native replacement for the reference's one-pod-per-machine fan-out;
    SURVEY.md §2.10/§7.6). MACHINES-CONFIG is a YAML list of machine
    configs; artifacts land at OUTPUT-DIR/<machine-name>/.

    With ``--workers N`` (or ``--worker-id`` on N hosts sharing the
    output volume) the buckets shard across N worker processes
    coordinated through a crash-tolerant work ledger: a killed worker's
    units are lease-stolen and rebuilt by the survivors, costing one
    unit of rework instead of the build (docs/robustness.md).
    """
    try:
        if ledger_status_dir is not None:
            _print_ledger_status(
                ledger_status_dir, lease_ttl=lease_ttl,
                max_attempts=max_attempts,
            )
            return 0
        if machines_from is not None:
            with open(machines_from) as fh:
                machines_config = yaml.safe_load(fh)
        if machines_config is None:
            raise click.UsageError(
                "MACHINES-CONFIG is required (argument or MACHINES env var)"
            )
        # the collection's tuning profile (docs/tuning.md) fills in knobs
        # still at their built-in defaults; anything set on the CLI or
        # through its env var wins. No profile -> strict no-op.
        from gordo_tpu.tuning import profile as tuning_profile

        profile_overrides = tuning_profile.apply_to_click_params(
            click.get_current_context(),
            output_dir,
            # the TUNABLE builder/ledger knobs only — non-tunable knobs
            # (max_attempts, fetch retries/timeouts) never get profile
            # recommendations, by registry declaration
            {
                "bucket_policy": "bucket_policy",
                "build_workers": "workers",
                "lease_ttl": "lease_ttl",
                "precision": "precision",
                "prefetch_depth": "prefetch_depth",
            },
            subsystem="builder",
        )
        bucket_policy = profile_overrides.get("bucket_policy", bucket_policy)
        lease_ttl = profile_overrides.get("lease_ttl", lease_ttl)
        precision = profile_overrides.get("precision", precision)
        prefetch_depth = profile_overrides.get(
            "prefetch_depth", prefetch_depth
        )
        if "workers" in profile_overrides:
            workers = str(profile_overrides["workers"])
        n_workers = 1
        if str(workers).strip().lower() != "1":
            n_workers = fleet_ledger.resolve_workers(workers)
        if worker_id is None and n_workers > 1:
            _refuse_local_workers_off_cpu(n_workers, "build-fleet --workers")
            # orchestrator: the children parse/expand the config
            # themselves, so pass it through verbatim (via env — large
            # configs outgrow argv)
            # no positionals: the children read MACHINES and OUTPUT_DIR
            # from the env (orchestrate sets both); a positional here
            # would bind to the child's machines-config slot
            worker_args = [
                "--workers", str(n_workers),
                "--lease-ttl", str(lease_ttl),
                "--max-attempts", str(max_attempts),
                "--on-error", on_error,
                "--fetch-retries", str(fetch_retries),
                "--bucket-policy", bucket_policy,
                "--precision", precision,
                "--precision-tolerance", str(precision_tolerance),
                "--prefetch-depth", str(prefetch_depth),
            ]
            if fetch_timeout is not None:
                worker_args += ["--fetch-timeout", str(fetch_timeout)]
            if resume:
                worker_args += ["--resume"]
            if print_cv_scores:
                worker_args += ["--print-cv-scores"]
            for key, value in model_parameter:
                worker_args += ["--model-parameter", f"{key},{value}"]
            logger.info(
                "Fleet-building %d machines with %d ledger workers, "
                "output at: %s",
                len(machines_config), n_workers, output_dir,
            )
            report = fleet_ledger.orchestrate(
                n_workers,
                machines_config,
                str(output_dir),
                worker_args,
                resume=resume,
                on_error=on_error,
            )
            _print_casualties(report)
            if aot_cache:
                # serving groups span work units, so the export runs
                # once over the finalized collection (reloading from
                # the just-flushed artifacts), not per worker. Same
                # contract as the single-worker export: best-effort —
                # a failed cache export never fails a completed build
                from gordo_tpu.programs import export_serving_programs

                utils.enable_compile_cache()
                try:
                    export_serving_programs(output_dir)
                except Exception as exc:  # noqa: BLE001
                    logger.warning(
                        "AOT serving-program export failed: %s", exc
                    )
            return 0

        utils.enable_compile_cache()
        machines = []
        for machine_config in machines_config:
            if model_parameter and isinstance(machine_config["model"], str):
                machine_config["model"] = expand_model(
                    machine_config["model"], dict(model_parameter)
                )
            machine = Machine.from_config(
                machine_config, project_name=machine_config["project_name"]
            )
            machine.model = serializer.into_definition(
                serializer.from_definition(machine.model)
            )
            machines.append(machine)
        builder = FleetModelBuilder(
            machines,
            on_error=on_error,
            fetch_retries=fetch_retries,
            fetch_timeout=fetch_timeout,
            bucket_policy=bucket_policy,
            precision=precision,
            precision_tolerance=precision_tolerance,
            prefetch_depth=prefetch_depth,
            # worker processes skip the export: serving groups span
            # units, so the orchestrator exports over the finalized
            # collection instead
            aot_cache=aot_cache and worker_id is None,
        )

        if worker_id is not None:
            logger.info(
                "Fleet worker %d joining the ledger under %s "
                "(%d machines total)",
                worker_id, output_dir, len(machines),
            )
            if aot_cache:
                # manual multi-host mode has no orchestrator process to
                # export over the finalized collection — say so instead
                # of silently dropping the flag
                logger.warning(
                    "--aot-cache has no effect on a --worker-id build "
                    "(serving groups span work units); run `gordo-tpu "
                    "programs compile %s` after the build completes",
                    output_dir,
                )

            def _report_unit(built):
                for _, machine_out in built.values():
                    machine_out.report()
                    if print_cv_scores:
                        for score in get_all_score_strings(machine_out):
                            print(f"{machine_out.name}: {score}")

            report = fleet_ledger.run_worker(
                builder,
                output_dir,
                worker_id,
                lease_ttl=lease_ttl,
                max_attempts=max_attempts,
                resume=resume,
                on_unit_built=_report_unit,
            )
            _print_casualties(report)
            return 0

        logger.info(
            "Fleet-building %d machines, output at: %s", len(machines), output_dir
        )
        built = builder.build(output_dir_base=output_dir, resume=resume)
        for _, machine_out in built:
            machine_out.report()
            if print_cv_scores:
                for score in get_all_score_strings(machine_out):
                    print(f"{machine_out.name}: {score}")
        _print_casualties(
            {
                "failed": builder.build_failures_,
                "quarantined": builder.quarantined_,
            }
        )
    except click.ClickException:
        raise
    except Exception:
        _report_and_exit(exceptions_reporter_file, exceptions_report_level)
    else:
        return 0


def _print_casualties(report: dict) -> None:
    """The FAILED/QUARANTINED stdout lines of a ledger build, from the
    merged ``build_report.json`` (the in-process casualty attributes
    only cover THIS worker's units)."""
    for record in report.get("failed") or []:
        print(
            f"FAILED {record.get('machine')} ({record.get('phase')}): "
            f"{record.get('error')}"
        )
    for record in report.get("quarantined") or []:
        print(
            f"QUARANTINED {record.get('machine')} at epoch "
            f"{record.get('epoch')} (artifact holds last finite params)"
        )


def _print_ledger_status(
    output_dir: str, lease_ttl: float, max_attempts: int
) -> None:
    """Human-readable ``--ledger-status`` report: unit states plus
    per-worker last-heartbeat age, so an operator can spot a stalled
    worker BEFORE its lease expires (cross-linked from the lifecycle
    ``watch`` runbook, docs/lifecycle.md)."""
    probe = fleet_ledger.Ledger(
        output_dir, worker_id="status",
        lease_ttl=lease_ttl, max_attempts=max_attempts,
    )
    try:
        status = probe.status()
    except FileNotFoundError:
        click.echo(
            f"No ledger under {output_dir} (single-worker builds keep none)"
        )
        return
    counts = status["counts"]
    click.echo(
        f"Ledger {status['ledger_dir']}: "
        f"{counts['done']} done / {counts['leased']} leased / "
        f"{counts['pending']} pending / {counts['casualty']} poisoned "
        f"(lease TTL {status['lease_ttl_s']}s, "
        f"max attempts {status['max_attempts']})"
    )
    for unit in status["units"]:
        state = unit["state"]
        line = f"  {unit['unit']}  {state:<8} ({unit['n_machines']} machines)"
        if state == "leased":
            age = unit.get("heartbeat_age_s")
            line += (
                f"  worker {unit.get('worker')}  attempt "
                f"{unit.get('attempt')}  heartbeat "
                f"{age if age is not None else '?'}s ago"
            )
            if unit.get("expired"):
                line += "  ** EXPIRED: steal imminent **"
        elif state == "done":
            line += (
                f"  worker {unit.get('worker')}  attempt {unit.get('attempt')}"
            )
        elif state == "casualty":
            line += f"  poisoned after {unit.get('attempts')} attempt(s)"
        click.echo(line)
    if status["workers"]:
        click.echo("Workers:")
        for wid, info in status["workers"].items():
            line = (
                f"  {wid}  pid {info.get('pid')}  last heartbeat "
                f"{info['last_heartbeat_age_s']}s ago"
            )
            if info.get("stalled"):
                line += (
                    f"  ** STALLED (> TTL "
                    f"{info.get('lease_ttl_s', status['lease_ttl_s'])}s) **"
                )
            click.echo(line)
    if status.get("aborted"):
        click.echo(f"ABORTED: {status['aborted']}")
    if status.get("finalized"):
        click.echo("Finalized: build_report.json written")


def expand_model(model_config: str, model_parameters: dict):
    """
    Render jinja variables in a string model config
    (reference: cli.py:209-240).
    """
    try:
        template = jinja2.Environment(
            loader=jinja2.BaseLoader(), undefined=jinja2.StrictUndefined
        ).from_string(model_config)
        model_config = template.render(**model_parameters)
    except jinja2.exceptions.UndefinedError as e:
        raise ValueError("Model parameter missing value!") from e
    logger.info("Expanded model config: %s", model_config)
    return yaml.safe_load(model_config)


def get_all_score_strings(machine) -> List[str]:
    """
    CV scores as ``metric_fold=value`` lines for Katib hyperparameter
    search to scrape (reference: cli.py:243-275).
    """
    all_scores = []
    scores = machine.metadata.build_metadata.model.cross_validation.scores
    for metric_name, metric_scores in scores.items():
        metric_name = metric_name.replace(" ", "-")
        for score_name, score_val in metric_scores.items():
            score_name = score_name.replace(" ", "-")
            all_scores.append(f"{metric_name}_{score_name}={score_val}")
    return all_scores


@click.command("sweep")
@click.argument("machine-config", envvar="MACHINE", type=yaml.safe_load)
@click.option(
    "--param",
    "grid_params",
    multiple=True,
    required=True,
    help="Hyperparameter grid entry 'name=v1,v2,...' (repeatable; all "
    "entries must list the same number of values). Names are optax "
    "optimizer args; the reference dialect's 'lr'/'decay' spellings work.",
)
@click.option("--epochs", type=int, default=None, help="Override model epochs")
@click.option("--batch-size", type=int, default=None, help="Override batch size")
@click.option(
    "--exceptions-reporter-file",
    envvar="EXCEPTIONS_REPORTER_FILE",
    help="JSON output file for exception information",
)
@click.option(
    "--exceptions-report-level",
    type=click.Choice(ReportLevel.get_names(), case_sensitive=False),
    default=ReportLevel.MESSAGE.name,
    envvar="EXCEPTIONS_REPORT_LEVEL",
    help="Detail level for exception reporting",
)
def sweep_cli(
    machine_config: dict,
    grid_params,
    epochs,
    batch_size,
    exceptions_reporter_file,
    exceptions_report_level,
):
    """
    Tune MACHINE-CONFIG's optimizer hyperparameters: every grid variant
    trains simultaneously as one vmapped program sharded over the fleet
    mesh axis (the TPU-native replacement for one-Katib-trial-per-pod),
    then per-trial losses print in Katib key=value form, best first.
    Trials train with the SAME epochs/batch-size the build would use
    (config values, or the build defaults), so rankings transfer.
    """
    grid: dict = {}
    grid_len = None
    for entry in grid_params:
        name, _, values = entry.partition("=")
        if not values:
            raise click.BadParameter(f"--param needs name=v1,v2,... got {entry!r}")
        try:
            parsed = [float(v) for v in values.split(",")]
        except ValueError:
            raise click.BadParameter(
                f"--param values must be numbers, got {entry!r}"
            )
        if grid_len is not None and len(parsed) != grid_len:
            raise click.BadParameter(
                "--param entries must list the same number of values "
                f"({grid_len} vs {len(parsed)} in {entry!r})"
            )
        grid_len = len(parsed)
        grid[name.strip()] = parsed

    try:
        from gordo_tpu.builder.fleet_build import (
            _find_jax_estimator,
            _prefix_transformers,
        )
        from gordo_tpu.data import _get_dataset
        from gordo_tpu.parallel import HyperparamSweep, auto_device_mesh

        machine = Machine.from_config(
            machine_config,
            project_name=machine_config.get("project_name", "sweep"),
        )
        model = serializer.from_definition(machine.model)
        estimator = _find_jax_estimator(model)
        if estimator is None:
            raise click.ClickException(
                "Sweeps need a JAX estimator in the model config"
            )

        dataset = _get_dataset(machine.dataset.to_dict())
        X, y = dataset.get_data()
        X_t = np.asarray(X, dtype="float32")
        for transformer in _prefix_transformers(model):
            X_t = np.asarray(transformer.fit_transform(X_t), dtype="float32")
        y_t = np.asarray(y, dtype="float32") if y is not None else X_t

        estimator.kwargs.update(
            {"n_features": X_t.shape[1], "n_features_out": y_t.shape[1]}
        )
        spec = estimator._build_spec()

        sweep = HyperparamSweep(
            spec,
            grid,
            lookahead=estimator.lookahead if spec.windowed else 0,
            mesh=auto_device_mesh(),
        )
        # same regime as build/build-fleet (core.py fit defaults), so the
        # winning hyperparameters transfer to the build that uses them
        result = sweep.fit(
            X_t,
            y_t,
            epochs=(
                epochs
                if epochs is not None
                else int(estimator.kwargs.get("epochs", 1))
            ),
            batch_size=(
                batch_size
                if batch_size is not None
                else int(estimator.kwargs.get("batch_size", 32))
            ),
        )
    except click.ClickException:
        raise
    except Exception:
        _report_and_exit(exceptions_reporter_file, exceptions_report_level)
    for trial, (hyperparams, loss) in enumerate(result.ranking()):
        hp = " ".join(f"{k}={v:g}" for k, v in hyperparams.items())
        print(f"trial-{trial}: {hp} loss={loss}")
    best = " ".join(f"{k}={v:g}" for k, v in result.best_hyperparams.items())
    print(f"best: {best}")
    return 0


@click.group("programs")
def programs_cli():
    """The AOT executable cache (docs/performance.md): compile/inspect
    a built collection's serialized serving programs."""


@programs_cli.command("compile")
@click.argument(
    "directory", type=click.Path(exists=True, file_okay=False, dir_okay=True)
)
@click.option(
    "--row-buckets",
    default=None,
    help="Comma-separated request row buckets to compile "
    "(default: GORDO_AOT_ROW_BUCKETS or 128,256).",
)
def programs_compile(directory: str, row_buckets: str):
    """
    (Re-)export DIRECTORY's serving programs into DIRECTORY/.programs:
    for an existing collection built elsewhere (multi-host ledger
    workers, a collection moved to a new jax/backend, or a pre-AOT
    build). Loads every artifact, stacks the fleet-serving groups
    exactly as the server will, and serializes one executable per
    (group, row-bucket) with the compatibility manifest.
    """
    from gordo_tpu.programs import export_serving_programs

    utils.enable_compile_cache()
    buckets = None
    if row_buckets:
        try:
            buckets = [
                int(part) for part in row_buckets.split(",") if part.strip()
            ]
        except ValueError:
            raise click.BadParameter(
                f"--row-buckets must be comma-separated integers, got "
                f"{row_buckets!r}"
            )
    report = export_serving_programs(directory, row_buckets=buckets)
    print(
        f"exported {report['n_programs']} program(s) for "
        f"{report['n_machines']} machine(s) -> {report['directory']}"
    )
    return 0


@click.group("telemetry")
def telemetry_cli():
    """Inspect fleet telemetry: build reports and event logs."""


@telemetry_cli.command("summarize")
@click.argument(
    "directory", type=click.Path(exists=True, file_okay=False, dir_okay=True)
)
@click.option(
    "--as-json",
    is_flag=True,
    help="Emit the collected reports as JSON instead of the human summary.",
)
def telemetry_summarize(directory: str, as_json: bool):
    """
    Aggregate every ``telemetry_report*.json`` and ``*.jsonl`` event log
    under DIRECTORY (a build output dir, or a root holding many) into one
    human-readable fleet summary: machines built, models/hour, compile vs
    steady-state epoch time, training throughput, peak device memory,
    casualties, compile-cache growth, per-subsystem event sections
    (batching, ledger, router, streaming, lifecycle, programs, tuning),
    and any crash context the event logs captured. ``--as-json`` emits
    the versioned machine-readable payload (``schema_version``) instead.
    """
    from gordo_tpu.observability.report import (
        summarize_directory,
        summary_payload,
    )

    if as_json:
        click.echo(json.dumps(summary_payload(directory), indent=2, default=str))
    else:
        click.echo(summarize_directory(directory))


@click.command("run-server")
@click.option(
    "--host",
    type=HostIP(),
    default="0.0.0.0",
    envvar="GORDO_SERVER_HOST",
    show_default=True,
    help="The host to run the server on.",
)
@click.option(
    "--port",
    type=click.IntRange(1, 65535),
    default=5555,
    envvar="GORDO_SERVER_PORT",
    show_default=True,
    help="The port to run the server on.",
)
@click.option(
    "--workers",
    type=click.IntRange(1, 32),
    default=1,
    envvar="GORDO_SERVER_WORKERS",
    show_default=True,
    help="Pre-forked worker processes sharing one listening socket. More "
    "than 1 is refused unless JAX_PLATFORMS=cpu (the chip is exclusive "
    "to a process); raise for CPU-bound deployments.",
)
@click.option(
    "--threads",
    type=int,
    default=8,
    envvar="GORDO_SERVER_THREADS",
    help="Per-worker bound on concurrently handled requests.",
)
@click.option(
    "--worker-connections",
    type=int,
    default=None,
    envvar="GORDO_SERVER_WORKER_CONNECTIONS",
    help="Per-worker bound on simultaneously accepted connections.",
)
@click.option(
    "--batch-wait-ms",
    type=click.FloatRange(min=0),
    default=0.0,
    envvar="GORDO_BATCH_WAIT_MS",
    show_default=True,
    help="Dynamic-batching latency-SLO cap: coalesce concurrent fleet "
    "requests for up to this long into one stacked device dispatch "
    "(docs/serving.md). 0 disables batching — a strict pass-through of "
    "the direct-dispatch path.",
)
@click.option(
    "--queue-limit",
    type=click.IntRange(min=1),
    default=64,
    envvar="GORDO_BATCH_QUEUE_LIMIT",
    show_default=True,
    help="Batching admission control: requests beyond this many waiting "
    "in the queue shed with a structured 503 + Retry-After.",
)
@click.option(
    "--scorer-cache-size",
    type=click.IntRange(min=1),
    default=16,
    envvar="GORDO_SCORER_CACHE_SIZE",
    show_default=True,
    help="Count bound on the resident fleet-scorer (and batcher) LRU "
    "caches when the device reports no memory stats (CPU/null "
    "backends). On accelerators with memory stats the bound is the "
    "HBM watermark sampler's measured headroom instead "
    "(docs/performance.md 'AOT executable cache').",
)
@click.option(
    "--aot-cache/--no-aot-cache",
    default=True,
    envvar="GORDO_AOT_CACHE",
    show_default=True,
    help="Map build-time AOT-serialized serving executables "
    "(<collection>/.programs) in at preload/first-use instead of "
    "re-tracing; any missing/incompatible/corrupt entry silently "
    "falls back to a retrace.",
)
@click.option(
    "--shard-manifest",
    type=click.Path(exists=True, dir_okay=False),
    default=None,
    envvar="GORDO_SHARD_MANIFEST",
    help="Sharded serving plane (docs/serving.md): JSON manifest naming "
    "the replica set ({'replicas': [...], 'vnodes': N, optional "
    "'replica_id'}). This replica then serves only its consistent-hash "
    "share of the collection and answers a structured 421 for machines "
    "the ring assigns elsewhere (the router's failover requests carry "
    "an adopt header that bypasses it). Omit for the historical "
    "whole-collection replica.",
)
@click.option(
    "--replica-id",
    default=None,
    envvar="GORDO_REPLICA_ID",
    help="This replica's id on the ring; overrides the manifest's own, "
    "so one shared manifest file can serve every replica.",
)
@click.option(
    "--log-level",
    type=click.Choice(["debug", "info", "warning", "error", "critical"]),
    default="debug",
    envvar="GORDO_SERVER_LOG_LEVEL",
    show_default=True,
    help="The log level for the server.",
)
@click.option(
    "--with-prometheus",
    is_flag=True,
    help="Enable Prometheus request metrics.",
)
def run_server_cli(
    host,
    port,
    workers,
    threads,
    worker_connections,
    batch_wait_ms,
    queue_limit,
    scorer_cache_size,
    aot_cache,
    shard_manifest,
    replica_id,
    log_level,
    with_prometheus,
):
    """Run the model server (reference: cli.py:278-374)."""
    from click.core import ParameterSource

    from gordo_tpu.server import app as server_app

    _refuse_local_workers_off_cpu(workers, "run-server --workers")
    config = {
        "AOT_CACHE": aot_cache,
        "SHARD_MANIFEST": shard_manifest,
        "REPLICA_ID": replica_id,
    }
    # tuned knobs ride into config only when set explicitly (flag or env
    # var); left at their built-in default they fall through build_app's
    # env -> tuning-profile -> default resolution, so the collection's
    # tuning_profile.json supplies measured defaults while explicit
    # configuration always wins (docs/tuning.md "Precedence").
    ctx = click.get_current_context()
    for config_key, param_name, value in (
        ("BATCH_WAIT_MS", "batch_wait_ms", batch_wait_ms),
        ("BATCH_QUEUE_LIMIT", "queue_limit", queue_limit),
        ("SCORER_CACHE_SIZE", "scorer_cache_size", scorer_cache_size),
    ):
        if ctx.get_parameter_source(param_name) != ParameterSource.DEFAULT:
            config[config_key] = value
    if with_prometheus:
        config["ENABLE_PROMETHEUS"] = True
    server_app.run_server(
        host,
        port,
        workers,
        log_level,
        config=config,
        threads=threads,
        worker_connections=worker_connections,
    )


@click.command("run-router")
@click.option(
    "--host",
    type=HostIP(),
    default="0.0.0.0",
    envvar="GORDO_ROUTER_HOST",
    show_default=True,
    help="The host to run the router on.",
)
@click.option(
    "--port",
    type=click.IntRange(1, 65535),
    default=5556,
    envvar="GORDO_ROUTER_PORT",
    show_default=True,
    help="The port to run the router on.",
)
@click.option(
    "--replica",
    "replicas",
    multiple=True,
    metavar="ID=URL",
    envvar="GORDO_ROUTER_REPLICAS",
    help="One shard replica as id=base-url (repeatable), e.g. "
    "--replica r0=http://10.0.0.4:5555. The ids must match the "
    "replicas' shard manifest; membership can be changed at runtime "
    "via POST /router/replicas.",
)
@click.option(
    "--collection-dir",
    "collection_dir",
    type=click.Path(file_okay=False),
    default=None,
    envvar="MODEL_COLLECTION_DIR",
    help="The served model collection's latest revision directory (or "
    "its `latest` symlink) — same artifacts the replicas serve. Falls "
    "back to the MODEL_COLLECTION_DIR env var; required one way or the "
    "other, since every request's revision resolves against it.",
)
@click.option(
    "--vnodes",
    type=click.IntRange(min=1),
    default=64,
    envvar="GORDO_ROUTER_VNODES",
    show_default=True,
    help="Virtual nodes per replica on the consistent-hash ring; must "
    "match the replicas' shard manifest.",
)
@click.option(
    "--eject-after",
    type=click.IntRange(min=1),
    default=3,
    envvar="GORDO_ROUTER_EJECT_AFTER",
    show_default=True,
    help="Consecutive failures before a replica is ejected and its "
    "shard fails over to ring successors.",
)
@click.option(
    "--backoff-scale",
    type=click.FloatRange(min=0.001),
    default=0.25,
    envvar="GORDO_ROUTER_BACKOFF_SCALE",
    show_default=True,
    help="Scale on the house 8/16/32s backoff schedule for ejection "
    "windows (0.25 -> 2/4/8s).",
)
@click.option(
    "--probe-interval",
    type=click.FloatRange(min=0),
    default=1.0,
    envvar="GORDO_ROUTER_PROBE_INTERVAL_S",
    show_default=True,
    help="Seconds between /healthz probes of ejected replicas (half-open "
    "re-adoption); 0 disables active probing.",
)
@click.option(
    "--hedge-ms",
    type=click.FloatRange(min=0),
    default=0.0,
    envvar="GORDO_ROUTER_HEDGE_MS",
    show_default=True,
    help="Straggler hedging: a shard call silent for this long gets ONE "
    "duplicate sent to the next routable successor, first completion "
    "wins. 0 disables.",
)
@click.option(
    "--replica-timeout",
    type=click.FloatRange(min=0.1),
    default=30.0,
    envvar="GORDO_ROUTER_REPLICA_TIMEOUT_S",
    show_default=True,
    help="Per-call timeout against replicas, seconds.",
)
@click.option(
    "--max-inflight",
    type=click.IntRange(min=1),
    default=64,
    envvar="GORDO_ROUTER_MAX_INFLIGHT",
    show_default=True,
    help="Router admission control: concurrent prediction requests past "
    "this shed with a structured 503 + Retry-After.",
)
@click.option(
    "--threads",
    type=int,
    default=32,
    envvar="GORDO_ROUTER_THREADS",
    show_default=True,
    help="Bound on concurrently handled requests (each fleet request "
    "fans out on its own worker pool).",
)
@click.option(
    "--rollup-interval",
    type=click.FloatRange(min=0),
    default=0.0,
    envvar="GORDO_ROLLUP_INTERVAL_S",
    show_default=True,
    help="Plane telemetry rollup: seconds between polls of every "
    "replica's /telemetry/snapshot, merged into the router's /status "
    "and /metrics. 0 keeps the strict no-op (no poller thread; /status "
    "polls on demand).",
)
@click.option(
    "--rollup-retention",
    type=click.IntRange(min=1),
    default=500,
    envvar="GORDO_ROLLUP_RETENTION",
    show_default=True,
    help="Merged snapshots kept in the persisted rollup JSONL (oldest "
    "trimmed).",
)
@click.option(
    "--rollup-persist",
    type=click.Path(dir_okay=False),
    default=None,
    envvar="GORDO_ROLLUP_PERSIST",
    help="JSONL path periodic merged snapshots persist to (next to the "
    "artifacts, so `gordo-tpu tune` ingests them as observations). "
    "Unset disables persistence.",
)
@click.option(
    "--log-level",
    type=click.Choice(["debug", "info", "warning", "error", "critical"]),
    default="info",
    envvar="GORDO_ROUTER_LOG_LEVEL",
    show_default=True,
    help="The log level for the router.",
)
def run_router_cli(
    host,
    port,
    replicas,
    collection_dir,
    vnodes,
    eject_after,
    backoff_scale,
    probe_interval,
    hedge_ms,
    replica_timeout,
    max_inflight,
    threads,
    log_level,
    rollup_interval,
    rollup_retention,
    rollup_persist,
):
    """
    Run the sharded-serving router (docs/serving.md "Sharded serving
    plane"): fronts N run-server shard replicas over one collection,
    fanning fleet requests out by consistent hash and surviving any one
    replica's death via ejection + failover to ring successors.
    """
    from gordo_tpu.router.app import parse_replica_entries, run_router

    # the envvar arrives as one comma-separated string; the repeated
    # flag arrives as a tuple of id=url entries — one shared parser
    try:
        replica_map = parse_replica_entries(replicas)
    except ValueError as exc:
        raise click.UsageError(str(exc))
    if not replica_map:
        raise click.UsageError(
            "At least one --replica id=url is required "
            "(or GORDO_ROUTER_REPLICAS)"
        )
    # fail the launch, not the first request: before this guard a router
    # started without the env var died with a KeyError when the first
    # prediction tried to resolve its revision
    if not collection_dir:
        raise click.UsageError(
            "--collection-dir is required (or export "
            "MODEL_COLLECTION_DIR): the router resolves every request's "
            "revision against the served collection directory"
        )
    os.environ["MODEL_COLLECTION_DIR"] = collection_dir
    config = {
        "REPLICAS": replica_map,
        "VNODES": vnodes,
        "EJECT_AFTER": eject_after,
        "BACKOFF_SCALE": backoff_scale,
        "PROBE_INTERVAL_S": probe_interval,
        "HEDGE_MS": hedge_ms,
        "REPLICA_TIMEOUT_S": replica_timeout,
        "MAX_INFLIGHT": max_inflight,
        "ROLLUP_INTERVAL_S": rollup_interval,
        "ROLLUP_RETENTION": rollup_retention,
        "ROLLUP_PERSIST_PATH": rollup_persist,
    }
    run_router(host, port, log_level, config=config, threads=threads)


gordo.add_command(workflow_cli)
gordo.add_command(build)
gordo.add_command(build_fleet)
gordo.add_command(sweep_cli)
gordo.add_command(run_server_cli)
gordo.add_command(run_router_cli)
gordo.add_command(gordo_client)
gordo.add_command(buckets_cli)
gordo.add_command(programs_cli)
gordo.add_command(telemetry_cli)
gordo.add_command(trace_cli)
gordo.add_command(profile_cli)
gordo.add_command(tune_cli)
gordo.add_command(lint_cli)
gordo.add_command(lockgraph_cli)
gordo.add_command(lifecycle_cli)
gordo.add_command(slo_cli)
gordo.add_command(top_cli)
gordo.add_command(rollup_cli)
gordo.add_command(gameday_cli)

if __name__ == "__main__":
    gordo()
