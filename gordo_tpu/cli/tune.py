"""
``gordo-tpu tune``: the telemetry-driven autotuner CLI (docs/tuning.md).

``tune plan`` is the ``buckets plan``-style dry run: ingest the
collection's telemetry corpus, fit the cost model, and print each
recommendation with the evidence rows behind it and the predicted-vs-
default delta — WITHOUT writing anything. ``tune fit`` writes the
versioned ``tuning_profile.json`` that ``build-fleet``/``run-server``
then load by default. ``tune plan --check`` is the CI drift gate
(scripts/build.sh): a committed profile whose knobs were renamed/removed
or whose values fell out of domain fails the build instead of being
silently ignored at load time. ``tune calibrate`` measures a fresh
corpus for fleets that have none.
"""

import json
import sys
import typing
from pathlib import Path

import click

from gordo_tpu.tuning import (
    TUNING_PROFILE_FILENAME,
    TuningProfileError,
    fit_recommendations,
    get_knob,
    load_profile,
    read_corpus,
    validate_profile,
    write_profile,
)
from gordo_tpu.tuning.corpus import Corpus
from gordo_tpu.tuning.model import Recommendation


@click.group("tune")
def tune_cli():
    """The telemetry-driven autotuner (docs/tuning.md): fit measured
    knob defaults from recorded telemetry."""


def _comma_floats(raw: str, flag: str) -> typing.List[float]:
    try:
        values = [float(part) for part in raw.split(",") if part.strip()]
    except ValueError:
        raise click.BadParameter(
            f"{flag} must be comma-separated numbers, got {raw!r}"
        )
    if not values:
        raise click.BadParameter(f"{flag} lists no values")
    return values


def _plan_payload(
    corpus: Corpus, recommendations: typing.Dict[str, Recommendation]
) -> dict:
    return {
        "corpus": corpus.meta(),
        "recommendations": {
            name: rec.to_dict() for name, rec in recommendations.items()
        },
    }


def _fmt_value(value) -> str:
    return f"{value:g}" if isinstance(value, float) else str(value)


def _render_plan(
    corpus: Corpus, recommendations: typing.Dict[str, Recommendation]
) -> typing.List[str]:
    lines = [
        f"Tuning plan: {len(corpus.observations)} observation(s) from "
        f"{corpus.n_files} corpus file(s)"
    ]
    for note in corpus.files:
        if note.error:
            lines.append(f"  skipped {note.path}: {note.error}")
    if not recommendations:
        lines.append(
            "No knob has enough evidence for a recommendation — defaults "
            "stand. Record more telemetry, or run `gordo-tpu tune "
            "calibrate`."
        )
        return lines
    for name, rec in sorted(recommendations.items()):
        knob = get_knob(name)
        current = _fmt_value(rec.default)
        lines.append(
            f"  {name} ({knob.flag or knob.env_var}): "
            f"{current} -> {_fmt_value(rec.value)}  "
            f"[{rec.source}, by {rec.signal} ({rec.objective})]"
        )
        if rec.improvement is not None:
            lines.append(
                f"    predicted {rec.signal}: "
                f"{rec.predicted_default:g} (default) -> "
                f"{rec.predicted:g} ({rec.improvement:+.1%})"
            )
        for arm in rec.evidence:
            marker = " <- best" if arm.value == rec.value else ""
            lines.append(
                f"    arm {_fmt_value(arm.value)}: "
                f"mean {arm.mean:g} (n={arm.n}){marker}"
            )
    return lines


def _check_profiles(root: Path) -> int:
    """The CI gate body: every ``tuning_profile.json`` under ``root``
    must load (known version) and survive registry validation. Returns
    the problem count (the exit code, lint-style)."""
    profiles = (
        [root]
        if root.is_file()
        else sorted(root.rglob(TUNING_PROFILE_FILENAME))
    )
    if not profiles:
        click.echo(f"No {TUNING_PROFILE_FILENAME} under {root} — nothing to check")
        return 0
    n_problems = 0
    for path in profiles:
        try:
            profile = load_profile(path)
        except TuningProfileError as exc:
            click.echo(f"{path}: FAIL: {exc}")
            n_problems += 1
            continue
        problems = validate_profile(profile)
        for problem in problems:
            click.echo(f"{path}: FAIL: {problem}")
        n_problems += len(problems)
        if not problems:
            n_recs = len(profile.get("recommendations") or {})
            click.echo(f"{path}: ok ({n_recs} recommendation(s))")
    return n_problems


@tune_cli.command("plan")
@click.argument(
    "corpus",
    nargs=-1,
    type=click.Path(exists=True, file_okay=True, dir_okay=True),
)
@click.option(
    "--as-json",
    is_flag=True,
    help="Emit the plan as JSON instead of the human table.",
)
@click.option(
    "--check",
    is_flag=True,
    help="Drift gate instead of a plan: validate every committed "
    "tuning_profile.json under CORPUS against the CURRENT knob "
    "registry (unknown/renamed knob, out-of-domain value, future "
    "profile_version all fail); exit code is the problem count.",
)
def tune_plan(corpus: typing.Tuple[str, ...], as_json: bool, check: bool):
    """
    Dry-run the autotuner over the telemetry corpus under CORPUS
    (collection directories and/or individual files): each knob's
    recommended value, the evidence arms behind it, and the predicted
    delta against the built-in default. Writes nothing — ``tune fit``
    publishes the profile.
    """
    if not corpus:
        raise click.UsageError(
            "CORPUS is required: one or more collection directories / "
            "telemetry files"
        )
    if check:
        n_problems = 0
        for root in corpus:
            n_problems += _check_profiles(Path(root))
        sys.exit(min(n_problems, 125))
    parsed = read_corpus(corpus)
    recommendations = fit_recommendations(parsed)
    if as_json:
        click.echo(
            json.dumps(
                _plan_payload(parsed, recommendations),
                indent=2,
                sort_keys=True,
            )
        )
        return 0
    for line in _render_plan(parsed, recommendations):
        click.echo(line)
    return 0


@tune_cli.command("fit")
@click.argument(
    "corpus",
    nargs=-1,
    type=click.Path(exists=True, file_okay=True, dir_okay=True),
)
@click.option(
    "--out",
    type=click.Path(dir_okay=True, file_okay=True),
    default=None,
    help="Where to write the profile (default: tuning_profile.json in "
    "the FIRST corpus directory — the collection the profile tunes).",
)
def tune_fit(corpus: typing.Tuple[str, ...], out: str):
    """
    Fit the cost model over CORPUS and publish the versioned
    ``tuning_profile.json`` (atomically) that ``build-fleet`` and
    ``run-server`` will load by default for this collection.
    """
    if not corpus:
        raise click.UsageError(
            "CORPUS is required: one or more collection directories / "
            "telemetry files"
        )
    parsed = read_corpus(corpus)
    recommendations = fit_recommendations(parsed)
    if out is None:
        first_dir = next(
            (Path(c) for c in corpus if Path(c).is_dir()), None
        )
        if first_dir is None:
            raise click.UsageError(
                "--out is required when CORPUS lists no directory"
            )
        out = str(first_dir)
    path = write_profile(out, recommendations, parsed.meta())
    for line in _render_plan(parsed, recommendations):
        click.echo(line)
    click.echo(f"Profile written: {path}")
    return 0


@tune_cli.command("calibrate")
@click.argument(
    "output-dir",
    type=click.Path(exists=False, file_okay=False, dir_okay=True),
)
@click.option(
    "--batch-wait-sweep",
    required=True,
    help="The --batch-wait-ms arms (comma-separated ms) to sweep against "
    "an in-process server under open-loop load.",
)
@click.option(
    "--rps",
    type=click.FloatRange(min=0.1),
    default=20.0,
    show_default=True,
    help="Offered Poisson arrival rate for the serving sweep.",
)
@click.option(
    "--duration",
    type=click.FloatRange(min=1.0),
    default=5.0,
    show_default=True,
    help="Seconds per serving-sweep arm.",
)
@click.option(
    "--fit/--no-fit",
    "do_fit",
    default=True,
    show_default=True,
    help="Fit + write OUTPUT-DIR/tuning_profile.json from the fresh "
    "calibration corpus.",
)
def tune_calibrate(
    output_dir: str,
    batch_wait_sweep: str,
    rps: float,
    duration: float,
    do_fit: bool,
):
    """
    Measure a fresh corpus for a fleet that has none: a
    ``--batch-wait-ms`` open-loop serving sweep, written to
    OUTPUT-DIR/results_calibration.json — then (by default) fit the
    profile from it.
    """
    from gordo_tpu.tuning.calibrate import (
        CalibrationUnavailable,
        run_calibration,
    )

    waits = _comma_floats(batch_wait_sweep, "--batch-wait-sweep")
    Path(output_dir).mkdir(parents=True, exist_ok=True)
    try:
        path, _ = run_calibration(
            output_dir,
            batch_wait_sweep=waits,
            rps=rps,
            duration=duration,
        )
    except CalibrationUnavailable as exc:
        raise click.ClickException(str(exc))
    click.echo(f"Calibration corpus written: {path}")
    if do_fit:
        parsed = read_corpus([output_dir])
        recommendations = fit_recommendations(parsed)
        profile_path = write_profile(
            output_dir, recommendations, parsed.meta()
        )
        for line in _render_plan(parsed, recommendations):
            click.echo(line)
        click.echo(f"Profile written: {profile_path}")
    return 0


if __name__ == "__main__":
    sys.exit(tune_cli())
