"""
Tests for the telemetry-driven autotuner (gordo_tpu/tuning/,
docs/tuning.md): the schema-tolerant corpus reader (golden PR-1-era and
current telemetry reports), the cost model's measured path,
profile versioning (an unknown future profile_version refuses to load),
the explicit-always-wins precedence through build-fleet and build_app,
the strict no-profile no-op, and THE acceptance: a recorded CPU corpus
with a prefetch_depth sweep and a batching queue-wait histogram yields a
tuning_profile.json whose recommendations match the best measured arms,
which build-fleet and run-server then demonstrably apply (event +
metric) while explicit flags override.
"""

import json
import os

import pytest
import yaml
from click.testing import CliRunner

from gordo_tpu.cli import gordo
from gordo_tpu.observability import get_registry, read_events
from gordo_tpu.tuning import (
    PROFILE_VERSION,
    TuningProfileError,
    fit_recommendations,
    load_profile,
    read_corpus,
    recommended_values,
    resolve_profile_path,
    validate_profile,
)
from gordo_tpu.tuning.profile import (
    TUNING_PROFILE_FILENAME,
    load_collection_profile,
)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture
def runner():
    return CliRunner()


@pytest.fixture(autouse=True)
def _fresh_registry():
    get_registry().reset()
    yield
    get_registry().reset()


# --------------------------------------------------------------------------
# corpus fixtures: a PR-1-era report and a current one
# --------------------------------------------------------------------------

#: the shape PR-1 builds wrote: no compile_cache block, no bucket-policy
#: fields, no knob or dispatch telemetry in the fit block
PR1_ERA_REPORT = {
    "version": 1,
    "kind": "fleet_build",
    "n_machines": 4,
    "n_buckets": 2,
    "wall_time_s": 12.0,
    "models_per_hour": 1200.0,
    "device_memory": {"available": False, "peak_bytes_in_use": None},
    "buckets": [
        {
            "n_machines": 2,
            "epochs": 10,
            "fit": {
                "compile_time_s": 1.2,
                "first_epoch_s": 1.4,
                "sensor_timesteps_per_s": 9000.0,
                "epochs_run": 10,
            },
        }
    ],
}

#: a current report: bucket policy, compile-cache block, and a fit block
#: with the steady-state rate the tuner judges beside a knob's value
CURRENT_REPORT = {
    "version": 1,
    "kind": "fleet_build",
    "n_machines": 4,
    "n_buckets": 1,
    "wall_time_s": 8.0,
    "models_per_hour": 1800.0,
    "bucket_policy": "exact",
    "compile_cache": {"start_bytes": 0, "end_bytes": 1024, "grown_bytes": 1024},
    "device_memory": {"available": False, "peak_bytes_in_use": None},
    "buckets": [
        {
            "n_machines": 4,
            "epochs": 16,
            "fit": {
                "prefetch_depth": 2,
                "n_dispatches": 16,
                "epochs_run": 16,
                "steady_state_epoch_s": 0.05,
                "steady_state_sensor_timesteps_per_s": 52000.0,
                "dispatch_overhead_s": 0.08,
            },
        }
    ],
}


def _write(path, payload):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload))
    return path


# --------------------------------------------------------------------------
# corpus reader: schema evolution (the golden round-trips)
# --------------------------------------------------------------------------


def test_pr1_era_report_parses_without_loss(tmp_path):
    """A PR-1-era telemetry report (no compile_cache, no bucket-policy
    fields, no knob telemetry) flows through the corpus reader without
    an error: it simply contributes no observations — missing fields
    are tolerance, never failure."""
    _write(tmp_path / "telemetry_report.json", PR1_ERA_REPORT)
    corpus = read_corpus([tmp_path])
    assert corpus.n_files == 1
    assert corpus.files[0].error is None
    assert corpus.observations == []


def test_current_report_yields_observations(tmp_path):
    _write(tmp_path / "telemetry_report.json", CURRENT_REPORT)
    corpus = read_corpus([tmp_path])
    assert corpus.files[0].error is None
    depth_obs = corpus.for_knob("prefetch_depth")
    assert depth_obs, "current report's fit block must judge prefetch_depth"
    assert {o.value for o in depth_obs} == {2}
    metrics = {o.metric for o in depth_obs}
    assert "steady_state_sensor_timesteps_per_s" in metrics
    # bucket_policy stated at the top level inherits down to the
    # models_per_hour signal on the same object
    policy_obs = corpus.for_knob("bucket_policy")
    assert policy_obs and policy_obs[0].value == "exact"


def test_mixed_era_corpus_parses_both(tmp_path):
    """The schema-evolution pin: PR-1-era and current reports in ONE
    corpus both parse; observations come only from fields that exist."""
    _write(tmp_path / "old" / "telemetry_report.json", PR1_ERA_REPORT)
    _write(tmp_path / "new" / "telemetry_report.json", CURRENT_REPORT)
    corpus = read_corpus([tmp_path])
    assert corpus.n_files == 2
    assert not [f for f in corpus.files if f.error]
    assert corpus.for_knob("prefetch_depth")


def test_unreadable_file_is_note_not_crash(tmp_path):
    (tmp_path / "telemetry_report_torn.json").write_text('{"version": 1,')
    _write(tmp_path / "telemetry_report.json", CURRENT_REPORT)
    corpus = read_corpus([tmp_path])
    errors = [f for f in corpus.files if f.error]
    assert len(errors) == 1 and "torn" in errors[0].path
    assert corpus.for_knob("prefetch_depth")  # the good file still counted
    assert corpus.meta()["skipped"][0]["path"] == errors[0].path


def test_jsonl_torn_tail_skipped(tmp_path):
    lines = [
        json.dumps(
            {
                "event": "x",
                "prefetch_depth": 8,
                "steady_state_sensor_timesteps_per_s": 80000.0,
            }
        ),
        '{"event": "torn-by-a-cra',  # crashed writer
    ]
    (tmp_path / "events.jsonl").write_text("\n".join(lines))
    corpus = read_corpus([tmp_path])
    assert corpus.files[0].error is None
    assert [o.value for o in corpus.for_knob("prefetch_depth")] == [8]


def test_queue_wait_histogram_derivation(tmp_path):
    """A persisted batching queue-wait registry histogram (the
    {count, sum, buckets} snapshot shape) derives into the scalar
    queue_wait_* signals next to the batch_wait_ms arm it measures."""
    arm = {
        "batch_wait_ms": 5.0,
        "gordo_serve_batch_queue_wait_seconds": {
            "count": 100,
            "sum": 0.2,  # mean 2ms
            "buckets": {"0.001": 10, "0.005": 95, "0.01": 99, "+Inf": 100},
        },
        "gordo_serve_batch_requests": {
            "count": 20,
            "sum": 100,  # mean batch size 5
            "buckets": {"+Inf": 20},
        },
    }
    _write(tmp_path / "results_sweep.json", {"arms": [arm]})
    corpus = read_corpus([tmp_path])
    by_metric = {o.metric: o for o in corpus.for_knob("batch_wait_ms")}
    assert by_metric["queue_wait_mean_ms"].metric_value == pytest.approx(2.0)
    assert by_metric["queue_wait_p99_ms"].metric_value == pytest.approx(10.0)
    assert by_metric["mean_batch_size"].metric_value == pytest.approx(5.0)


def test_registry_snapshot_wrapper_recognized(tmp_path):
    """The registry-snapshot {'kind': 'histogram', 'series': [...]}
    wrapper (what a dumped get_registry().snapshot() looks like) is
    unwrapped before derivation."""
    wrapped = {
        "batch_wait_ms": 2.0,
        "gordo_serve_batch_queue_wait_seconds": {
            "kind": "histogram",
            "series": [
                {
                    "labels": {},
                    "value": {"count": 10, "sum": 0.05, "buckets": {"+Inf": 10}},
                }
            ],
        },
    }
    _write(tmp_path / "results_wrapped.json", wrapped)
    corpus = read_corpus([tmp_path])
    metrics = {o.metric for o in corpus.for_knob("batch_wait_ms")}
    assert "queue_wait_mean_ms" in metrics


def test_trajectory_rows_are_observations(tmp_path):
    """benchmarks/trajectory.json (make bench-summary) rides the same
    reader: a row naming a knob and restating its headline metric under
    the metric's own field name is an ordinary observation."""
    trajectory = {
        "trajectory_schema_version": 1,
        "entries": [
            {
                "file": "results_fleet_cpu_r05.json",
                "bench": "fleet",
                "revision": "r05",
                "headline_metric": "models_per_hour",
                "value": 1221.6,
                "units": "models/hour",
                "models_per_hour": 1221.6,
                "workers": 1,
            },
            {"file": "results_other.json", "bench": "other"},  # no knob: inert
        ],
    }
    _write(tmp_path / "trajectory.json", trajectory)
    corpus = read_corpus([tmp_path])
    obs = corpus.for_knob("build_workers")
    assert obs and obs[0].metric == "models_per_hour"


def test_context_inherits_downward(tmp_path):
    """A knob value stated on an ancestor object applies to signal
    fields on descendants (the telemetry-report nesting shape)."""
    doc = {
        "prefetch_depth": 2,
        "nested": {"deeper": {"steady_state_sensor_timesteps_per_s": 9e4}},
    }
    _write(tmp_path / "results_x.json", doc)
    corpus = read_corpus([tmp_path])
    obs = corpus.for_knob("prefetch_depth")
    assert obs and obs[0].value == 2
    assert obs[0].metric == "steady_state_sensor_timesteps_per_s"


# --------------------------------------------------------------------------
# cost model
# --------------------------------------------------------------------------


def _sweep_corpus(tmp_path, rows, name="results_sweep.json"):
    _write(tmp_path / name, {"arms": rows})
    return read_corpus([tmp_path])


def test_best_measured_arm_wins_max_objective(tmp_path):
    corpus = _sweep_corpus(
        tmp_path,
        [
            {"prefetch_depth": 0, "steady_state_sensor_timesteps_per_s": 14000.0},
            {"prefetch_depth": 2, "steady_state_sensor_timesteps_per_s": 52000.0},
            {"prefetch_depth": 4, "steady_state_sensor_timesteps_per_s": 81000.0},
        ],
    )
    rec = fit_recommendations(corpus)["prefetch_depth"]
    assert rec.value == 4 and rec.source == "measured"
    assert rec.objective == "max"
    assert rec.predicted == pytest.approx(81000.0)
    # default (0) was itself measured, so the delta is exact
    assert rec.predicted_default == pytest.approx(14000.0)
    assert rec.improvement > 0
    assert [arm.value for arm in rec.evidence] == [0, 2, 4]


def test_best_measured_arm_wins_min_objective(tmp_path):
    corpus = _sweep_corpus(
        tmp_path,
        [
            {"batch_wait_ms": 0.0, "p99_ms": 45.0},
            {"batch_wait_ms": 5.0, "p99_ms": 22.0},
            {"batch_wait_ms": 20.0, "p99_ms": 31.0},
        ],
    )
    rec = fit_recommendations(corpus)["batch_wait_ms"]
    assert rec.value == 5.0 and rec.objective == "min"


def test_interpolation_at_unmeasured_default(tmp_path):
    """The default's prediction interpolates piecewise-linearly between
    measured arms when the default itself was not swept."""
    corpus = _sweep_corpus(
        tmp_path,
        [
            {"batch_wait_ms": 10.0, "p99_ms": 30.0},
            {"batch_wait_ms": 30.0, "p99_ms": 50.0},
        ],
    )
    rec = fit_recommendations(corpus)["batch_wait_ms"]
    # default 0.0 is OUTSIDE the measured range: clamped, never
    # extrapolated
    assert rec.predicted_default == pytest.approx(30.0)


def test_single_arm_no_measured_recommendation(tmp_path):
    """One arm is not a sweep: no recommendation, the default stands
    (the tuner speaks only from measured arms)."""
    corpus = _sweep_corpus(
        tmp_path,
        [
            {"batch_wait_ms": 5.0, "p99_ms": 22.0},
            {"prefetch_depth": 2, "steady_state_sensor_timesteps_per_s": 9e4},
        ],
    )
    assert fit_recommendations(corpus) == {}


def test_empty_corpus_empty_recommendations(tmp_path):
    assert fit_recommendations(read_corpus([tmp_path])) == {}


# --------------------------------------------------------------------------
# profile: versioning + validation + precedence primitives
# --------------------------------------------------------------------------


def _minimal_profile(**recommendations):
    return {
        "profile_version": PROFILE_VERSION,
        "generated": "2026-08-04T00:00:00+00:00",
        "corpus": {},
        "recommendations": {
            name: {"value": value} for name, value in recommendations.items()
        },
    }


def test_profile_round_trip(tmp_path):
    path = _write(
        tmp_path / TUNING_PROFILE_FILENAME, _minimal_profile(prefetch_depth=8)
    )
    profile = load_profile(path)
    assert validate_profile(profile) == []
    assert recommended_values(profile) == {"prefetch_depth": 8}


def test_future_profile_version_refuses_to_load(tmp_path):
    """The versioning pin: an unknown FUTURE profile_version refuses
    with a clear error instead of silently applying half-understood
    recommendations."""
    payload = _minimal_profile(prefetch_depth=8)
    payload["profile_version"] = PROFILE_VERSION + 1
    path = _write(tmp_path / TUNING_PROFILE_FILENAME, payload)
    with pytest.raises(TuningProfileError) as err:
        load_profile(path)
    message = str(err.value)
    assert str(PROFILE_VERSION + 1) in message
    assert "newer than this build" in message
    # and the serving-side loader degrades to not-applying, never raising
    assert load_collection_profile(tmp_path) is None


def test_unversioned_profile_refuses(tmp_path):
    payload = _minimal_profile(prefetch_depth=8)
    del payload["profile_version"]
    path = _write(tmp_path / TUNING_PROFILE_FILENAME, payload)
    with pytest.raises(TuningProfileError, match="profile_version"):
        load_profile(path)


def test_validate_profile_catches_drift():
    """The tune plan --check body: renamed/removed knobs, out-of-domain
    values, and non-tunable recommendations are all named problems."""
    profile = _minimal_profile(prefetch_depth=9999)  # outside int 0..8
    profile["recommendations"]["renamed_knob"] = {"value": 1}
    profile["recommendations"]["max_attempts"] = {"value": 3}  # non-tunable
    problems = validate_profile(profile)
    assert len(problems) == 3
    assert any("unknown knob 'renamed_knob'" in p for p in problems)
    assert any("outside domain" in p for p in problems)
    assert any("non-tunable" in p for p in problems)


def test_recommended_values_skips_invalid_entries():
    """Serving must not fail on a drifted profile — invalid entries are
    skipped (the CI gate fails loudly instead)."""
    profile = _minimal_profile(prefetch_depth=8, batch_wait_ms=-4.0)
    profile["recommendations"]["ghost"] = {"value": 1}
    assert recommended_values(profile) == {"prefetch_depth": 8}


def test_resolve_profile_path_env_override(tmp_path, monkeypatch):
    target = _write(tmp_path / "p.json", _minimal_profile())
    monkeypatch.setenv("GORDO_TUNING_PROFILE", str(target))
    assert resolve_profile_path(None) == target
    monkeypatch.setenv("GORDO_TUNING_PROFILE", "off")
    assert resolve_profile_path(tmp_path) is None
    monkeypatch.delenv("GORDO_TUNING_PROFILE")
    assert resolve_profile_path(tmp_path) is None  # absent file
    _write(tmp_path / TUNING_PROFILE_FILENAME, _minimal_profile())
    assert resolve_profile_path(tmp_path) is not None


# --------------------------------------------------------------------------
# tune CLI
# --------------------------------------------------------------------------

PREFETCH_SWEEP = [
    {"prefetch_depth": 0, "steady_state_sensor_timesteps_per_s": 14000.0},
    {"prefetch_depth": 2, "steady_state_sensor_timesteps_per_s": 26000.0},
    {"prefetch_depth": 4, "steady_state_sensor_timesteps_per_s": 21000.0},
]

BATCH_WAIT_SWEEP = [
    {
        "batch_wait_ms": wait,
        "p99_ms": p99,
        "gordo_serve_batch_queue_wait_seconds": {
            "count": 100,
            "sum": 0.001 * wait * 100,
            "buckets": {"+Inf": 100},
        },
    }
    for wait, p99 in ((0.0, 45.0), (5.0, 22.0), (20.0, 31.0))
]


@pytest.fixture
def recorded_corpus(tmp_path):
    """THE acceptance corpus: a prefetch_depth sweep and a batching
    queue-wait-histogram sweep, recorded the way the harnesses write
    them."""
    corpus_dir = tmp_path / "corpus"
    _write(
        corpus_dir / "results_prefetch_sweep.json",
        {"bench_schema_version": 1, "prefetch_sweep": PREFETCH_SWEEP},
    )
    _write(
        corpus_dir / "results_batch_sweep.json",
        {"bench_schema_version": 1, "arms": BATCH_WAIT_SWEEP},
    )
    return corpus_dir


def test_tune_plan_shows_evidence(runner, recorded_corpus):
    result = runner.invoke(gordo, ["tune", "plan", str(recorded_corpus)])
    assert result.exit_code == 0, result.output
    assert "prefetch_depth" in result.output
    assert "--prefetch-depth" in result.output
    assert "0 -> 2" in result.output  # recommendation line
    assert "<- best" in result.output  # evidence arm marker
    assert "batch_wait_ms" in result.output


def test_tune_plan_as_json(runner, recorded_corpus):
    result = runner.invoke(
        gordo, ["tune", "plan", "--as-json", str(recorded_corpus)]
    )
    assert result.exit_code == 0, result.output
    payload = json.loads(result.output)
    assert payload["recommendations"]["prefetch_depth"]["value"] == 2
    assert payload["corpus"]["n_files"] == 2


def test_tune_fit_acceptance(runner, recorded_corpus):
    """The acceptance pin: the recorded corpus yields a
    tuning_profile.json whose recommended prefetch_depth and batch_wait_ms
    match the best measured arms."""
    result = runner.invoke(gordo, ["tune", "fit", str(recorded_corpus)])
    assert result.exit_code == 0, result.output
    profile = load_profile(recorded_corpus / TUNING_PROFILE_FILENAME)
    recs = profile["recommendations"]
    assert recs["prefetch_depth"]["value"] == 2  # best measured arm
    assert recs["batch_wait_ms"]["value"] == 5.0  # best measured arm
    assert recs["prefetch_depth"]["source"] == "measured"
    assert recs["prefetch_depth"]["evidence"]  # rows behind the call
    assert validate_profile(profile) == []


def test_tune_plan_check_gate(runner, tmp_path):
    """tune plan --check: a valid profile passes (exit 0); a future
    version or drifted knob fails with the problem count as exit
    code."""
    good = tmp_path / "good"
    _write(good / TUNING_PROFILE_FILENAME, _minimal_profile(prefetch_depth=8))
    result = runner.invoke(gordo, ["tune", "plan", "--check", str(good)])
    assert result.exit_code == 0, result.output
    assert "ok" in result.output

    bad = tmp_path / "bad"
    payload = _minimal_profile(prefetch_depth=8)
    payload["profile_version"] = PROFILE_VERSION + 7
    _write(bad / TUNING_PROFILE_FILENAME, payload)
    drifted = _minimal_profile(removed_knob=3)
    _write(bad / "sub" / TUNING_PROFILE_FILENAME, drifted)
    result = runner.invoke(gordo, ["tune", "plan", "--check", str(bad)])
    assert result.exit_code == 2, result.output
    assert "FAIL" in result.output

    empty = tmp_path / "empty"
    empty.mkdir()
    result = runner.invoke(gordo, ["tune", "plan", "--check", str(empty)])
    assert result.exit_code == 0  # nothing to check is not a failure


# --------------------------------------------------------------------------
# application: build-fleet + build_app precedence (event + metric)
# --------------------------------------------------------------------------

TUNE_MACHINE_YAML = """
name: tune-machine
project_name: tune-project
dataset:
  type: RandomDataset
  tags: [tag-0, tag-1, tag-2]
  target_tag_list: [tag-0, tag-1, tag-2]
  train_start_date: '2019-01-01T00:00:00+00:00'
  train_end_date: '2019-01-02T00:00:00+00:00'
  asset: gra
model:
  gordo_tpu.models.AutoEncoder:
    kind: feedforward_hourglass
    epochs: 2
"""


def _fleet_machines(n=2):
    return [
        yaml.safe_load(TUNE_MACHINE_YAML) | {"name": f"tune-m-{i}"}
        for i in range(n)
    ]


def _applied_events(event_log):
    return [
        e
        for e in read_events(str(event_log))
        if e["event"] == "tuning_profile_loaded"
    ]


def _gauge_knobs():
    snap = get_registry().snapshot().get("gordo_tuning_profile_applied")
    if not snap:
        return set()
    return {
        s["labels"]["knob"] for s in snap["series"] if s["value"] == 1.0
    }


def test_build_fleet_applies_profile(runner, tmp_path):
    """build-fleet loads the collection's profile by default: the
    recommended prefetch_depth is applied, and the application is
    attributable (event + metric)."""
    out_dir = tmp_path / "fleet-out"
    _write(out_dir / TUNING_PROFILE_FILENAME, _minimal_profile(prefetch_depth=2))
    event_log = tmp_path / "events.jsonl"
    result = runner.invoke(
        gordo,
        ["build-fleet", json.dumps(_fleet_machines()), str(out_dir)],
        env={"GORDO_TPU_EVENT_LOG": str(event_log)},
    )
    assert result.exit_code == 0, result.output
    events = _applied_events(event_log)
    assert len(events) == 1
    assert events[0]["applied"] == {"prefetch_depth": 2}
    assert events[0]["subsystem"] == "builder"
    assert _gauge_knobs() == {"prefetch_depth"}


def test_build_fleet_explicit_flag_overrides_profile(runner, tmp_path):
    """Precedence pin: an explicit --prefetch-depth beats the profile; the
    attribution event then names NO applied knobs."""
    out_dir = tmp_path / "fleet-out-explicit"
    _write(out_dir / TUNING_PROFILE_FILENAME, _minimal_profile(prefetch_depth=2))
    event_log = tmp_path / "events.jsonl"
    result = runner.invoke(
        gordo,
        [
            "build-fleet",
            json.dumps(_fleet_machines()),
            str(out_dir),
            "--prefetch-depth",
            "0",
        ],
        env={"GORDO_TPU_EVENT_LOG": str(event_log)},
    )
    assert result.exit_code == 0, result.output
    # nothing applied -> no attribution event (a fully-explicit config,
    # e.g. every ledger worker child, must not spam empty events)
    assert _applied_events(event_log) == []
    assert _gauge_knobs() == set()


def test_build_fleet_env_var_overrides_profile(runner, tmp_path):
    """The env-var spelling wins over the profile exactly like the
    flag (click's parameter-source view treats both as explicit)."""
    out_dir = tmp_path / "fleet-out-env"
    _write(out_dir / TUNING_PROFILE_FILENAME, _minimal_profile(prefetch_depth=2))
    event_log = tmp_path / "events.jsonl"
    result = runner.invoke(
        gordo,
        ["build-fleet", json.dumps(_fleet_machines()), str(out_dir)],
        env={
            "GORDO_PREFETCH_DEPTH": "0",
            "GORDO_TPU_EVENT_LOG": str(event_log),
        },
    )
    assert result.exit_code == 0, result.output
    assert _applied_events(event_log) == []
    assert _gauge_knobs() == set()


def test_profile_recommending_a_removed_knob_applies_the_rest(
    runner, tmp_path, caplog
):
    """A profile fitted before PR 29 may recommend ``epoch_chunk``, which
    the registry no longer has: the build skips that entry with the
    unknown-knob warning and applies the live knob beside it, and
    ``tune plan --check`` names the drift."""
    out_dir = tmp_path / "fleet-out-legacy"
    _write(
        out_dir / TUNING_PROFILE_FILENAME,
        _minimal_profile(epoch_chunk=4, prefetch_depth=2),
    )
    event_log = tmp_path / "events.jsonl"
    with caplog.at_level("WARNING", logger="gordo_tpu.tuning.profile"):
        result = runner.invoke(
            gordo,
            ["build-fleet", json.dumps(_fleet_machines()), str(out_dir)],
            env={"GORDO_TPU_EVENT_LOG": str(event_log)},
        )
    assert result.exit_code == 0, result.output
    (event,) = _applied_events(event_log)
    assert event["applied"] == {"prefetch_depth": 2}
    assert _gauge_knobs() == {"prefetch_depth"}
    assert any(
        "unknown/non-tunable" in r.getMessage() and "epoch_chunk" in r.getMessage()
        for r in caplog.records
    )
    check = runner.invoke(gordo, ["tune", "plan", "--check", str(out_dir)])
    assert check.exit_code == 1, check.output
    assert "unknown knob 'epoch_chunk'" in check.output


def test_build_fleet_no_profile_strict_noop(runner, tmp_path, monkeypatch):
    """With no profile present the load path never parses anything and
    leaves no attribution trail — the GORDO_FAULT_INJECT discipline."""
    from gordo_tpu.tuning import profile as tuning_profile

    def _must_not_parse(path):
        raise AssertionError(f"no-profile path parsed {path}")

    monkeypatch.setattr(tuning_profile, "load_profile", _must_not_parse)
    out_dir = tmp_path / "fleet-out-noop"
    event_log = tmp_path / "events.jsonl"
    result = runner.invoke(
        gordo,
        ["build-fleet", json.dumps(_fleet_machines()), str(out_dir)],
        env={"GORDO_TPU_EVENT_LOG": str(event_log)},
    )
    assert result.exit_code == 0, result.output
    assert _applied_events(event_log) == []
    assert _gauge_knobs() == set()


def test_build_app_applies_profile(tmp_path, monkeypatch):
    """run-server's build_app resolves unset serving knobs from the
    collection's profile (event + metric), env vars and explicit config
    both winning."""
    from gordo_tpu.server.app import build_app

    collection = tmp_path / "collection"
    _write(
        collection / TUNING_PROFILE_FILENAME,
        _minimal_profile(batch_wait_ms=7.5, batch_queue_limit=32),
    )
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(collection))

    app = build_app()
    assert app.config["BATCH_WAIT_MS"] == 7.5
    assert app.config["BATCH_QUEUE_LIMIT"] == 32
    assert app.config["SCORER_CACHE_SIZE"] == 16  # not in profile: default
    (event,) = _applied_events(event_log)
    assert event["subsystem"] == "server"
    assert event["applied"] == {"batch_wait_ms": 7.5, "batch_queue_limit": 32}
    assert _gauge_knobs() == {"batch_wait_ms", "batch_queue_limit"}

    # env var wins over the profile
    monkeypatch.setenv("GORDO_BATCH_WAIT_MS", "3")
    app = build_app()
    assert app.config["BATCH_WAIT_MS"] == 3.0
    assert app.config["BATCH_QUEUE_LIMIT"] == 32  # still from profile
    monkeypatch.delenv("GORDO_BATCH_WAIT_MS")

    # explicit config (the CLI flag path) wins over everything
    app = build_app({"BATCH_WAIT_MS": 11.0})
    assert app.config["BATCH_WAIT_MS"] == 11.0


def test_build_app_no_profile_strict_noop(tmp_path, monkeypatch):
    """No profile: build_app's knob resolution is byte-identical to the
    historical env->default fallback, parses nothing, and emits no
    attribution."""
    from gordo_tpu.server.app import build_app
    from gordo_tpu.tuning import profile as tuning_profile

    def _must_not_parse(path):
        raise AssertionError(f"no-profile path parsed {path}")

    monkeypatch.setattr(tuning_profile, "load_profile", _must_not_parse)
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(tmp_path / "absent"))
    app = build_app()
    assert app.config["BATCH_WAIT_MS"] == 0.0
    assert app.config["BATCH_QUEUE_LIMIT"] == 64
    assert app.config["SCORER_CACHE_SIZE"] == 16
    assert not event_log.exists() or _applied_events(event_log) == []


def test_profile_loading_disabled_by_env(tmp_path, monkeypatch):
    """GORDO_TUNING_PROFILE=off disables loading even with a profile
    present."""
    from gordo_tpu.server.app import build_app

    collection = tmp_path / "collection"
    _write(
        collection / TUNING_PROFILE_FILENAME, _minimal_profile(batch_wait_ms=7.5)
    )
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(collection))
    monkeypatch.setenv("GORDO_TUNING_PROFILE", "off")
    app = build_app()
    assert app.config["BATCH_WAIT_MS"] == 0.0


def test_run_server_cli_passes_only_explicit_knobs(runner, monkeypatch):
    """The run-server CLI forwards a tuned knob into config ONLY when
    set explicitly — left at its default it falls through to
    build_app's env -> profile -> default resolution."""
    import gordo_tpu.server.app as server_app

    captured = {}

    def _fake_run_server(*args, **kwargs):
        for value in list(args) + list(kwargs.values()):
            if isinstance(value, dict):
                captured.update(value)

    monkeypatch.setattr(server_app, "run_server", _fake_run_server)
    result = runner.invoke(gordo, ["run-server", "--batch-wait-ms", "4"])
    assert result.exit_code == 0, result.output
    assert captured.get("BATCH_WAIT_MS") == 4.0
    assert "BATCH_QUEUE_LIMIT" not in captured  # default: deferred
    assert "SCORER_CACHE_SIZE" not in captured

    captured.clear()
    result = runner.invoke(gordo, ["run-server"])
    assert result.exit_code == 0, result.output
    assert "BATCH_WAIT_MS" not in captured


# --------------------------------------------------------------------------
# calibration (the no-corpus path)
# --------------------------------------------------------------------------


def test_tune_calibrate_without_a_sweep_is_a_usage_error(runner, tmp_path):
    """The batch-wait sweep is all calibrate measures: asked for none it
    says so and writes nothing."""
    out = tmp_path / "calib"
    result = runner.invoke(gordo, ["tune", "calibrate", str(out)])
    assert result.exit_code == 2, result.output
    assert "--batch-wait-sweep" in result.output
    assert not out.exists()


@pytest.mark.slow
def test_tune_calibrate_end_to_end(runner, tmp_path):
    """tune calibrate measures a fresh batch_wait_ms corpus (a short
    in-process serving sweep) and fits a profile from it — calibration is
    just a way of growing a corpus. A real sweep, so marked slow."""
    collection_before = os.environ.get("MODEL_COLLECTION_DIR")
    out = tmp_path / "calib"
    result = runner.invoke(
        gordo,
        [
            "tune",
            "calibrate",
            str(out),
            "--batch-wait-sweep",
            "0,10",
            "--rps",
            "5",
            "--duration",
            "2",
        ],
    )
    assert result.exit_code == 0, result.output
    corpus_file = out / "results_calibration.json"
    assert corpus_file.exists()
    payload = json.loads(corpus_file.read_text())
    assert payload["bench_schema_version"] == 1
    # the serving sweep's requests must have actually succeeded — a
    # wrong route/body shape would file everything under errors and
    # leave arms without latency evidence
    for arm in payload["batch_wait_sweep"]:
        assert arm["requests"] > 0, arm
        assert arm["errors"] == 0, arm
        assert "p99_ms" in arm
    profile = load_profile(out / TUNING_PROFILE_FILENAME)
    assert validate_profile(profile) == []
    corpus = read_corpus([out])
    assert corpus.for_knob("batch_wait_ms")
    # the sweep's throwaway collection env var must not leak
    assert os.environ.get("MODEL_COLLECTION_DIR") == collection_before
