"""
``gordo-tpu lifecycle`` — continuous fleet operation (docs/lifecycle.md):

- ``tick``   one drift → refit → shadow → promote cycle
- ``watch``  scheduled ticks (a daemon loop around ``tick``)
- ``report`` render a revision's promotion decision trail
"""

import json
import logging
import os
import sys
import time

import click

logger = logging.getLogger(__name__)


def _config_from_params(params: dict):
    from gordo_tpu.lifecycle import LifecycleConfig

    return LifecycleConfig(
        window_start=params["window_start"],
        window_end=params["window_end"],
        holdout_fraction=params["holdout_fraction"],
        shadow_tolerance=params["shadow_tolerance"],
        ewma_alpha=params["ewma_alpha"],
        ratio_threshold=params["ratio_threshold"],
        exceedance_threshold=params["exceedance_threshold"],
        min_observations=params["min_observations"],
        fetch_retries=params["fetch_retries"],
        fetch_timeout=params["fetch_timeout"],
        stream_observations=params["stream_observations"],
        promote=params["promote"],
        repoint=params["repoint"],
    )


_tick_options = [
    click.option(
        "--model-collection-dir",
        envvar="MODEL_COLLECTION_DIR",
        required=True,
        type=click.Path(exists=True),
        help="The served latest revision directory, or the `latest` "
        "symlink a promotion re-points.",
    ),
    click.option(
        "--window-start",
        default=None,
        help="Drift/refit window start (ISO datetime); default: each "
        "machine's own training window.",
    ),
    click.option("--window-end", default=None, help="Window end (ISO)."),
    click.option(
        "--holdout-fraction",
        type=float,
        default=0.25,
        show_default=True,
        help="Window tail held out of refit training for shadow scoring.",
    ),
    click.option(
        "--shadow-tolerance",
        type=float,
        default=0.10,
        show_default=True,
        help="Max fractional holdout-error regression a candidate may "
        "ship with.",
    ),
    click.option(
        "--ewma-alpha", type=float, default=0.3, show_default=True,
        help="Newest-observation weight in the drift EWMAs.",
    ),
    click.option(
        "--ratio-threshold", type=float, default=1.0, show_default=True,
        help="Drift when EWMA mean(anomaly/threshold) exceeds this.",
    ),
    click.option(
        "--exceedance-threshold", type=float, default=0.5, show_default=True,
        help="Drift when the EWMA fraction of timesteps over threshold "
        "exceeds this.",
    ),
    click.option(
        "--min-observations", type=int, default=1, show_default=True,
        help="Observations before a machine may be declared drifted.",
    ),
    click.option(
        "--fetch-retries",
        type=int,
        default=1,
        envvar="GORDO_FETCH_RETRIES",
        show_default=True,
        help="Per-machine retry count for refit data fetches.",
    ),
    click.option(
        "--fetch-timeout",
        type=float,
        default=None,
        envvar="GORDO_FETCH_TIMEOUT",
        help="Per-machine cap (seconds) on drift-scan and refit data "
        "fetches; a hung data source is recorded on its machine "
        "instead of wedging the tick. Default: wait indefinitely.",
    ),
    click.option(
        "--stream-observations",
        default=None,
        envvar="GORDO_TPU_EVENT_LOG",
        help="JSONL event log whose accumulated stream_observation "
        "events feed drift detection for streamed machines — those "
        "machines skip the window-fetch scan entirely "
        "(docs/lifecycle.md 'Scan-free ticks'). Default: the "
        "GORDO_TPU_EVENT_LOG pipeline the serving plane emits into.",
    ),
    click.option(
        "--promote/--no-promote",
        default=True,
        show_default=True,
        help="--no-promote stops after shadow verdicts (dry run: "
        "decisions reported, no revision created).",
    ),
    click.option(
        "--repoint/--no-repoint",
        default=True,
        show_default=True,
        help="Re-point the latest symlink at the promoted revision "
        "(only applies when the collection pointer is a symlink).",
    ),
]


def _with_tick_options(command):
    for option in reversed(_tick_options):
        command = option(command)
    return command


@click.group("lifecycle")
def lifecycle_cli():
    """Continuous operation: drift detection, warm-start refit and
    blue/green revision promotion (docs/lifecycle.md)."""


@lifecycle_cli.command("tick")
@_with_tick_options
def tick(**params):
    """Run ONE lifecycle cycle and print its summary as JSON."""
    from gordo_tpu.lifecycle import LifecycleManager

    manager = LifecycleManager(
        params["model_collection_dir"], config=_config_from_params(params)
    )
    result = manager.tick()
    click.echo(json.dumps(result.to_dict(), indent=2, sort_keys=True, default=str))


@lifecycle_cli.command("watch")
@_with_tick_options
@click.option(
    "--interval-s",
    type=float,
    default=300.0,
    show_default=True,
    help="Seconds between cycle starts.",
)
@click.option(
    "--max-cycles",
    type=int,
    default=0,
    show_default=True,
    help="Stop after this many cycles (0 = run forever).",
)
def watch(interval_s, max_cycles, **params):
    """Run cycles on a schedule (the daemon form of ``tick``).

    A cycle that fails logs and the loop continues — a transient data
    outage must not kill the daemon; a torn promotion retries next
    cycle with a fresh staging dir."""
    from gordo_tpu.lifecycle import LifecycleManager

    manager = LifecycleManager(
        params["model_collection_dir"], config=_config_from_params(params)
    )
    cycle = 0
    while True:
        cycle += 1
        started = time.monotonic()
        try:
            result = manager.tick()
            click.echo(
                json.dumps(
                    {"cycle": cycle, **result.to_dict()},
                    sort_keys=True,
                    default=str,
                )
            )
            if result.revision is not None and os.path.realpath(
                params["model_collection_dir"]
            ) != os.path.realpath(result.revision_dir):
                # published but NOT adopted (plain-dir pointer or
                # --no-repoint): the next cycle would start from the
                # same stale base, see the same drift, and publish a
                # near-identical sibling — every interval, forever.
                # Adoption is the operator's move here, so stop and say
                # so instead of burning refits.
                logger.warning(
                    "Revision %s was published but the collection "
                    "pointer still serves %s (plain directory or "
                    "--no-repoint); stopping watch — adopt the revision "
                    "(re-deploy or flip the symlink) and restart",
                    result.revision, result.base_revision,
                )
                return
        except Exception:
            logger.exception("Lifecycle cycle %d failed; continuing", cycle)
        if max_cycles and cycle >= max_cycles:
            return
        delay = interval_s - (time.monotonic() - started)
        if delay > 0:
            time.sleep(delay)


@lifecycle_cli.command("report")
@click.argument("revision_dir", type=click.Path(exists=True))
def report(revision_dir):
    """Render REVISION_DIR's promotion decision trail."""
    from gordo_tpu.lifecycle import read_promotion_report

    payload = read_promotion_report(revision_dir)
    if payload is None:
        click.echo(
            f"No {'promotion_report.json'} under {revision_dir} — not a "
            "lifecycle-promoted revision.",
            err=True,
        )
        sys.exit(1)
    click.echo(
        f"revision {payload.get('revision')} "
        f"(from {payload.get('base_revision')})"
    )
    counts = payload.get("counts") or {}
    click.echo(
        "  "
        + ", ".join(f"{k}: {v}" for k, v in sorted(counts.items()))
    )
    decisions = payload.get("decisions") or {}
    width = max((len(n) for n in decisions), default=0)
    for name in sorted(decisions):
        record = decisions[name]
        line = f"  {name:<{width}}  {record.get('decision'):<12}"
        reason = record.get("reason")
        if reason:
            line += f" {reason}"
        shadow = record.get("shadow")
        if shadow:
            line += (
                f"  (live {shadow['live_score']:.5f} vs "
                f"candidate {shadow['candidate_score']:.5f})"
            )
        click.echo(line)
