"""
Chaos suite for the per-machine fault domains (docs/robustness.md):
every degradation path — isolated fetch failure, non-finite quarantine,
torn checkpoints, degraded serving, client handling of permanent 409s —
driven through the ``GORDO_FAULT_INJECT`` harness, plus the guarantee
the whole feature stands on: a fault in ONE machine leaves every other
machine's results bit-identical to a fault-free run.
"""

import json

import numpy as np
import pytest

import jax

from gordo_tpu.machine import Machine
from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.parallel.fleet import FleetTrainer, StackedData
from gordo_tpu.robustness import InjectedFault, faults
from tests.conftest import GORDO_BASE_TARGETS, GORDO_PROJECT, GORDO_TARGETS

F = 3


@pytest.fixture(autouse=True)
def _fresh_faults(monkeypatch):
    """Each test starts with no fault spec and no cached fire counts."""
    monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR, raising=False)
    faults.reset()
    yield
    faults.reset()


def make_fleet_data(m=3, n=96, seed=0):
    rng = np.random.default_rng(seed)
    Xs = [rng.random((n, F)).astype("float32") for _ in range(m)]
    return StackedData.from_ragged(Xs, [x.copy() for x in Xs])


def assert_trees_bitequal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def make_machine(name, epochs=2):
    return Machine(
        name=name,
        project_name="chaos",
        model={
            "gordo_tpu.models.AutoEncoder": {
                "kind": "feedforward_hourglass",
                "epochs": epochs,
                "batch_size": 16,
            }
        },
        dataset={
            "type": "RandomDataset",
            "train_start_date": "2017-12-25 06:00:00Z",
            "train_end_date": "2017-12-26 06:00:00Z",
            "tags": [["Tag 1", None], ["Tag 2", None]],
        },
    )


# -- the injection registry itself ---------------------------------------


def test_fault_spec_grammar():
    specs = faults.parse_spec(
        "fetch:raise:machine-3;train:nan:machine-7@epoch:2;ckpt:torn"
    )
    assert [(s.site, s.mode, s.target) for s in specs] == [
        ("fetch", "raise", "machine-3"),
        ("train", "nan", "machine-7"),
        ("ckpt", "torn", None),
    ]
    assert specs[1].param_int("epoch") == 2
    assert specs[0].matches_target("machine-3")
    assert not specs[0].matches_target("machine-4")
    assert specs[2].matches_target("anything")  # no target = any

    with pytest.raises(ValueError, match="unknown site"):
        faults.parse_spec("fletch:raise")
    with pytest.raises(ValueError, match="site:mode"):
        faults.parse_spec("fetch")
    with pytest.raises(ValueError, match="key:value"):
        faults.parse_spec("fetch:raise@oops")


def test_unset_env_is_strict_noop(monkeypatch):
    """With GORDO_FAULT_INJECT unset, seams never even PARSE — the hot
    path pays one os.environ lookup and nothing else."""
    def explode(_):
        raise AssertionError("parse_spec called with fault injection off")

    monkeypatch.setattr(faults, "parse_spec", explode)
    assert faults.active_registry() is None
    faults.inject("fetch", "anything")  # no raise, no parse
    assert faults.train_nan_injection(["a"], 1) is None
    assert faults.tear_checkpoint_files("/nonexistent") is False


def test_inject_attempts_budget(monkeypatch):
    """@attempts:N makes a fault transient: it fires N times, then the
    seam passes — the retry-recovery exercise."""
    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR, "fetch:raise:m-1@attempts:2"
    )
    for _ in range(2):
        with pytest.raises(InjectedFault):
            faults.inject("fetch", "m-1")
    faults.inject("fetch", "m-1")  # third call passes
    faults.inject("fetch", "m-0")  # other machines never fault


# -- non-finite quarantine in the fused fleet program --------------------


@pytest.mark.parametrize("fault_epoch", [0, 2])
def test_injected_nan_quarantines_exactly_one_machine(monkeypatch, fault_epoch):
    """train:nan at the epoch the fault names (the first, or a later one)
    freezes exactly the targeted machine — its params roll back to the
    last finite epoch — while the OTHER machines' losses and params stay
    bit-identical to a fault-free run, with the same host-sync budget."""
    data = make_fleet_data()
    spec = feedforward_hourglass(n_features=F)
    keys = FleetTrainer(spec).machine_keys(3)
    names = ["m-0", "m-1", "m-2"]

    clean = FleetTrainer(spec, donate=False)
    p_clean, l_clean = clean.fit(
        data, keys, epochs=6, batch_size=16, machine_names=names
    )
    assert clean.healthy_.all()
    assert (clean.quarantine_epoch_ == -1).all()

    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR, f"train:nan:m-1@epoch:{fault_epoch}"
    )
    import gordo_tpu.parallel.fleet as fleet_mod

    calls = {"n": 0}
    real = fleet_mod.host_fetch

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(fleet_mod, "host_fetch", counting)
    faulted = FleetTrainer(spec, donate=False)
    p_bad, l_bad = faulted.fit(
        data, keys, epochs=6, batch_size=16, machine_names=names
    )
    # quarantine reporting rode the EXISTING fetches: 2 syncs total
    # (setup weights + end-of-fit history), the plain-fit budget
    assert calls["n"] <= 2

    assert list(faulted.healthy_) == [True, False, True]
    assert list(faulted.quarantine_epoch_) == [-1, fault_epoch, -1]
    assert faulted.fit_telemetry_["n_machines_quarantined"] == 1
    assert np.isfinite(l_bad[:fault_epoch, 1]).all()
    assert np.isnan(l_bad[fault_epoch, 1])

    # the OTHERS: bit-identical losses and params vs the no-fault run
    np.testing.assert_array_equal(l_clean[:, 0], l_bad[:, 0])
    np.testing.assert_array_equal(l_clean[:, 2], l_bad[:, 2])
    for lc, lb in zip(jax.tree.leaves(p_clean), jax.tree.leaves(p_bad)):
        np.testing.assert_array_equal(np.asarray(lc)[0], np.asarray(lb)[0])
        np.testing.assert_array_equal(np.asarray(lc)[2], np.asarray(lb)[2])

    # the casualty froze at its last finite epoch: entering the fault's
    # epoch == a clean run of that many epochs (none: the initial params)
    ref = FleetTrainer(spec, donate=False)
    monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR)
    p_ref, _ = ref.fit(data, keys, epochs=fault_epoch, batch_size=16)
    for lr, lb in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_bad)):
        np.testing.assert_array_equal(np.asarray(lr)[1], np.asarray(lb)[1])


def test_real_nonfinite_data_quarantines_without_injection():
    """The guard is not injection theater: a machine whose SENSOR DATA
    carries NaN (the bad-feed scenario) quarantines at its first epoch
    through the exact same mask, no fault spec involved."""
    rng = np.random.default_rng(0)
    Xs = [rng.random((96, F)).astype("float32") for _ in range(3)]
    Xs[1][10, 1] = np.nan
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    spec = feedforward_hourglass(n_features=F)
    trainer = FleetTrainer(spec, donate=False)
    keys = trainer.machine_keys(3)
    params, losses = trainer.fit(data, keys, epochs=3, batch_size=16)

    assert list(trainer.healthy_) == [True, False, True]
    assert trainer.quarantine_epoch_[1] == 0
    # frozen at init: the rolled-back params are the vmapped init values
    init = trainer.init_params(keys, F)
    for li, lp in zip(jax.tree.leaves(init), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(li)[1], np.asarray(lp)[1])
    # the healthy machines trained normally
    assert np.isfinite(losses[:, 0]).all() and np.isfinite(losses[:, 2]).all()


def test_quarantine_disabled_optout():
    """quarantine_nonfinite=False restores the raw behavior (no healthy
    outputs, no rollback) for callers that want NaNs to propagate."""
    rng = np.random.default_rng(0)
    Xs = [rng.random((96, F)).astype("float32") for _ in range(2)]
    Xs[0][5, 0] = np.nan
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    spec = feedforward_hourglass(n_features=F)
    trainer = FleetTrainer(spec, donate=False, quarantine_nonfinite=False)
    keys = trainer.machine_keys(2)
    params, losses = trainer.fit(data, keys, epochs=2, batch_size=16)
    assert trainer.healthy_ is None
    assert np.isnan(losses[:, 0]).all()  # NaN propagated, as asked


# -- isolated fetch/build failures in the fleet builder ------------------


def _build_fleet(machines, out, **kwargs):
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    builder = FleetModelBuilder(
        machines, fetch_backoff=lambda attempt: 0.0, **kwargs
    )
    return builder, builder.build(output_dir_base=out)


@pytest.mark.slow
def test_fetch_fault_builds_survivors_bit_identical(monkeypatch, tmp_path):
    """The acceptance scenario: one machine's fetch dies and another
    goes NaN mid-training in a 16-machine build; under on_error=skip the
    build SUCCEEDS, both casualties land in build_report.json, and every
    survivor's artifact is bit-identical to a fault-free build."""
    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import _find_jax_estimator

    names = [f"chaos-m-{i}" for i in range(16)]
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))

    _, clean = _build_fleet(
        [make_machine(n) for n in names], tmp_path / "clean"
    )
    assert len(clean) == 16

    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR,
        "fetch:raise:chaos-m-2;train:nan:chaos-m-7@epoch:1",
    )
    builder, built = _build_fleet(
        [make_machine(n) for n in names],
        tmp_path / "faulted",
        on_error="skip",
        fetch_retries=1,
    )
    built_names = [m.name for _, m in built]
    assert "chaos-m-2" not in built_names
    assert len(built) == 15

    # both casualties named, with cause and attempt count
    report = json.loads(
        (tmp_path / "faulted" / "build_report.json").read_text()
    )
    assert report["on_error"] == "skip"
    assert [f["machine"] for f in report["failed"]] == ["chaos-m-2"]
    assert report["failed"][0]["phase"] == "fetch"
    assert report["failed"][0]["attempts"] == 2
    assert "InjectedFault" in report["failed"][0]["error"]
    assert report["quarantined"] == [{"machine": "chaos-m-7", "epoch": 1}]
    # and mirrored into the telemetry report
    telemetry = json.loads(
        (tmp_path / "faulted" / "telemetry_report.json").read_text()
    )
    assert telemetry["machines_failed"] == report["failed"]
    assert telemetry["machines_quarantined"] == report["quarantined"]

    # every SURVIVOR is bit-identical to the fault-free build
    for name in names:
        if name in ("chaos-m-2", "chaos-m-7"):
            continue
        clean_est = _find_jax_estimator(serializer.load(tmp_path / "clean" / name))
        bad_est = _find_jax_estimator(serializer.load(tmp_path / "faulted" / name))
        np.testing.assert_array_equal(
            clean_est.history_["loss"], bad_est.history_["loss"]
        )
        assert_trees_bitequal(clean_est.params_, bad_est.params_)

    # the event log names what actually happened
    from gordo_tpu.observability import read_events

    events = read_events(str(event_log))
    kinds = {e["event"] for e in events}
    assert {"fault_injected", "build_machine_failed"} <= kinds
    quarantine_events = [
        e for e in events if e["event"] == "machine_quarantined"
    ]
    assert {e["machine"] for e in quarantine_events} == {"chaos-m-7"}


def test_fetch_retry_recovers_transient_fault(monkeypatch, tmp_path):
    """A fetch that fails once and then succeeds (@attempts:1) costs a
    retry, not the machine: everything builds, nothing is recorded."""
    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR, "fetch:raise:flappy-1@attempts:1"
    )
    machines = [make_machine(f"flappy-{i}") for i in range(3)]
    builder, built = _build_fleet(
        machines, tmp_path / "out", on_error="skip", fetch_retries=1
    )
    assert len(built) == 3
    assert builder.build_failures_ == []
    report = json.loads((tmp_path / "out" / "build_report.json").read_text())
    assert report["n_failed"] == 0


def test_resume_rebuilds_prior_casualties(monkeypatch, tmp_path):
    """A --resume re-run must not reuse a casualty's artifact (a
    quarantined artifact holds frozen params, and reusing it would
    erase its build_report.json record and serve it as healthy): prior
    casualties REBUILD, and a clean rebuild clears the record."""
    out = tmp_path / "out"
    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR,
        "fetch:raise:res-2;train:nan:res-1@epoch:0",
    )
    names = [f"res-{i}" for i in range(3)]
    builder, built = _build_fleet(
        [make_machine(n) for n in names], out,
        on_error="skip", fetch_retries=0,
    )
    # res-2 fetch-failed (absent); res-1 quarantined but still flushed
    assert [m.name for _, m in built] == ["res-0", "res-1"]
    report = json.loads((out / "build_report.json").read_text())
    assert report["n_failed"] == 1 and report["n_quarantined"] == 1

    # faults cleared; resume must rebuild BOTH casualties cleanly
    monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR)
    faults.reset()
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    resumed = FleetModelBuilder(
        [make_machine(n) for n in names], on_error="skip"
    ).build(output_dir_base=out, resume=True)
    assert [m.name for _, m in resumed] == names
    report = json.loads((out / "build_report.json").read_text())
    assert report["n_failed"] == 0 and report["n_quarantined"] == 0
    # and the server would now serve all three
    from gordo_tpu import serializer

    for name in names:
        assert serializer.load(out / name) is not None


def test_old_format_es_checkpoint_restores_es_state(tmp_path):
    """A checkpoint whose extra predates the quarantine mask (ES state
    only) still restores that ES state — the 'healthy' template key is
    optional, not a reason to drop to the bare layout."""
    from gordo_tpu.parallel.checkpoint import FleetCheckpointer

    es_state = {
        "active": np.array([True, False]),
        "best": np.array([0.5, 0.25]),
    }
    ckpt = FleetCheckpointer(tmp_path / "ckpt")
    ckpt.save(2, _small_tree(2.0), _small_tree(12.0), extra=es_state)
    ckpt.wait()

    template = dict(es_state, healthy=np.ones(2, dtype=bool))
    params, _, epoch, extra = ckpt.restore_with_extra(
        _small_tree(9.0), _small_tree(9.0), template,
        optional_extra_keys=("healthy",),
    )
    assert epoch == 2
    assert extra is not None and "healthy" not in extra
    np.testing.assert_array_equal(extra["active"], es_state["active"])
    np.testing.assert_array_equal(extra["best"], es_state["best"])
    ckpt.close()


def test_layout_mismatch_never_deletes_checkpoints(tmp_path):
    """A plain quarantine fit's {healthy}-only checkpoint resumed by an
    early-stopping fit is a LAYOUT difference, not corruption: the
    healthy state restores (via the optional-keys-only template) and no
    checkpoint is deleted — only manifest-confirmed torn steps are."""
    from gordo_tpu.parallel.checkpoint import FleetCheckpointer

    healthy = {"healthy": np.array([True, False, True])}
    ckpt = FleetCheckpointer(tmp_path / "ckpt", keep=5)
    ckpt.save(0, _small_tree(0.0), _small_tree(10.0), extra=healthy)
    ckpt.save(1, _small_tree(1.0), _small_tree(11.0), extra=healthy)
    ckpt.wait()

    es_template = dict(
        healthy,
        active=np.ones(3, dtype=bool),
        best=np.full(3, np.inf),
    )
    params, _, epoch, extra = ckpt.restore_with_extra(
        _small_tree(9.0), _small_tree(9.0), es_template,
        optional_extra_keys=("healthy",),
    )
    assert epoch == 1
    assert extra is not None and "active" not in extra
    np.testing.assert_array_equal(extra["healthy"], healthy["healthy"])
    # both checkpoints still on disk: nothing was "torn"
    assert (tmp_path / "ckpt" / "0").is_dir()
    assert (tmp_path / "ckpt" / "1").is_dir()
    ckpt.close()


def test_stale_flush_tmp_dirs_are_invisible_and_cleaned(
    trained_model_collection, monkeypatch, tmp_path
):
    """A kill -9 mid-flush leaves a dot-prefixed temp dir; /models must
    not advertise it and the next flush of that machine cleans it."""
    from gordo_tpu import serializer
    from gordo_tpu.server import build_app
    from gordo_tpu.server import utils as server_utils

    stale = trained_model_collection / ".ghost.tmp-99999"
    stale.mkdir()
    try:
        monkeypatch.setenv(
            "MODEL_COLLECTION_DIR", str(trained_model_collection)
        )
        server_utils.clear_caches()
        from werkzeug.test import Client as WerkzeugClient

        resp = WerkzeugClient(build_app()).get(
            f"/gordo/v0/{GORDO_PROJECT}/models"
        )
        assert ".ghost.tmp-99999" not in resp.get_json()["models"]
    finally:
        stale.rmdir()

    # dump() clears a DEAD writer's stale temp dir for the same artifact
    # (4194300 sits at the top of the pid space: never a live process)
    leftover = tmp_path / ".m.tmp-4194300"
    leftover.mkdir()
    (leftover / "model.pkl").write_bytes(b"torn")
    serializer.dump({"x": 1}, tmp_path / "m")
    assert not leftover.exists()
    assert serializer.load(tmp_path / "m") == {"x": 1}


def test_on_error_raise_keeps_reference_semantics(monkeypatch):
    """Default policy: the original exception type aborts the build (it
    maps to a pod exit code via cli.ExceptionsReporter)."""
    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "fetch:raise:dead-0")
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    builder = FleetModelBuilder(
        [make_machine("dead-0")], fetch_retries=0
    )
    with pytest.raises(InjectedFault):
        builder.build()


def test_on_error_validation():
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    with pytest.raises(ValueError, match="on_error"):
        FleetModelBuilder([], on_error="ignore")


# -- torn checkpoints ----------------------------------------------------


def _small_tree(value):
    return {"w": np.full((4, 4), value, dtype=np.float32)}


def test_torn_checkpoint_falls_back_to_previous_epoch(monkeypatch, tmp_path):
    """ckpt:torn truncates the just-committed checkpoint; restore
    detects the manifest mismatch and resumes from the previous kept
    epoch instead of crashing."""
    from gordo_tpu.parallel.checkpoint import FleetCheckpointer

    ckpt = FleetCheckpointer(tmp_path / "ckpt", keep=5)
    ckpt.save(0, _small_tree(0.0), _small_tree(10.0))
    ckpt.wait()
    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "ckpt:torn")
    ckpt.save(1, _small_tree(1.0), _small_tree(11.0))
    ckpt.wait()  # manifest stamped, then the injected tear
    monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR)

    params, opt, epoch = ckpt.restore(_small_tree(9.0), _small_tree(9.0))
    assert epoch == 0
    np.testing.assert_array_equal(params["w"], _small_tree(0.0)["w"])
    np.testing.assert_array_equal(opt["w"], _small_tree(10.0)["w"])
    ckpt.close()


def test_corrupt_payload_without_manifest_falls_back(tmp_path):
    """Even with no manifest (crash before the stamp), a checkpoint
    whose restore throws falls back to the previous epoch."""
    from gordo_tpu.parallel.checkpoint import (
        MANIFEST_FILENAME,
        FleetCheckpointer,
    )

    ckpt = FleetCheckpointer(tmp_path / "ckpt", keep=5)
    ckpt.save(0, _small_tree(0.0), _small_tree(10.0))
    ckpt.save(3, _small_tree(3.0), _small_tree(13.0))
    ckpt.wait()
    step_dir = tmp_path / "ckpt" / "3"
    (step_dir / MANIFEST_FILENAME).unlink()
    victim = max(
        (p for p in step_dir.rglob("*") if p.is_file()),
        key=lambda p: p.stat().st_size,
    )
    victim.write_bytes(victim.read_bytes()[: victim.stat().st_size // 2])

    params, _, epoch = ckpt.restore(_small_tree(9.0), _small_tree(9.0))
    assert epoch == 0
    np.testing.assert_array_equal(params["w"], _small_tree(0.0)["w"])
    ckpt.close()


def test_torn_checkpoint_resume_through_trainer(monkeypatch, tmp_path):
    """End-to-end: a fleet fit resumes through a torn latest checkpoint
    and finishes with the SAME results as an uninterrupted run — the
    tear costs the epochs since the previous checkpoint, not the fit."""
    from gordo_tpu.parallel.checkpoint import FleetCheckpointer

    data = make_fleet_data(m=2, n=64)
    spec = feedforward_hourglass(n_features=F)
    straight = FleetTrainer(spec, donate=False)
    keys = straight.machine_keys(2)
    p_straight, l_straight = straight.fit(data, keys, epochs=6, batch_size=16)

    trainer = FleetTrainer(spec, donate=False)
    ckpt = FleetCheckpointer(tmp_path / "ckpt", keep=5)
    trainer.fit(
        data, keys, epochs=3, batch_size=16,
        checkpointer=ckpt, checkpoint_every=1,
    )
    ckpt.wait()
    # tear the latest (epoch 2) checkpoint after the fact
    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "ckpt:torn")
    assert faults.tear_checkpoint_files(tmp_path / "ckpt" / "2")
    monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR)

    p_resumed, l_resumed = trainer.fit(
        data, keys, epochs=6, batch_size=16,
        checkpointer=ckpt, checkpoint_every=1,
    )
    ckpt.close()
    # resume fell back to epoch 1, so epochs 2..5 re-ran
    assert l_resumed.shape[0] == 4
    np.testing.assert_array_equal(l_straight[2:], l_resumed)
    assert_trees_bitequal(p_straight, p_resumed)


# -- degraded serving + client handling ----------------------------------


QUARANTINED = GORDO_BASE_TARGETS[0]
GHOST = "ghost-machine"


@pytest.fixture
def degraded_collection(trained_model_collection):
    """The session collection plus a build report naming one quarantined
    model (exists on disk) and one fetch-failed ghost (no artifact)."""
    report = {
        "version": 1,
        "kind": "fleet_build_report",
        "on_error": "skip",
        "failed": [
            {
                "machine": GHOST,
                "phase": "fetch",
                "error": "IOError: sensor feed unreachable",
                "attempts": 3,
            }
        ],
        "quarantined": [{"machine": QUARANTINED, "epoch": 1}],
    }
    path = trained_model_collection / "build_report.json"
    path.write_text(json.dumps(report))
    try:
        yield trained_model_collection
    finally:
        path.unlink()


@pytest.fixture
def degraded_server(degraded_collection, monkeypatch):
    from gordo_tpu.server import build_app
    from gordo_tpu.server import utils as server_utils

    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(degraded_collection))
    server_utils.clear_caches()
    return build_app()


@pytest.fixture
def degraded_client(degraded_server):
    from werkzeug.test import Client as WerkzeugClient

    return WerkzeugClient(degraded_server)


def _sensor_payload(n=10):
    rows = np.random.default_rng(1).random((n, 4)).tolist()
    return rows


def test_models_endpoint_surfaces_casualties(degraded_client):
    resp = degraded_client.get(f"/gordo/v0/{GORDO_PROJECT}/models")
    assert resp.status_code == 200
    payload = resp.get_json()
    assert QUARANTINED not in payload["models"]
    assert GORDO_TARGETS[0] in payload["models"]
    assert payload["unavailable"][QUARANTINED]["reason"] == "quarantined"
    assert payload["unavailable"][GHOST]["reason"] == "fetch_failed"
    assert payload["unavailable"][GHOST]["attempts"] == 3


def test_prediction_against_quarantined_machine_is_409(degraded_client):
    resp = degraded_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/{QUARANTINED}/prediction",
        json={"X": _sensor_payload()},
    )
    assert resp.status_code == 409
    payload = resp.get_json()
    assert payload["unavailable"][QUARANTINED]["reason"] == "quarantined"
    # anomaly path refuses identically
    resp = degraded_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/{QUARANTINED}/anomaly/prediction",
        json={"X": _sensor_payload(), "y": _sensor_payload()},
    )
    assert resp.status_code == 409


def test_fleet_prediction_with_casualty_is_409_naming_it(degraded_client):
    resp = degraded_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/prediction/fleet",
        json={
            "machines": {
                GORDO_TARGETS[0]: _sensor_payload(),
                QUARANTINED: _sensor_payload(),
            }
        },
    )
    assert resp.status_code == 409
    payload = resp.get_json()
    assert set(payload["unavailable"]) == {QUARANTINED}
    # the healthy subset alone still serves
    resp = degraded_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/prediction/fleet",
        json={"machines": {GORDO_TARGETS[0]: _sensor_payload()}},
    )
    assert resp.status_code == 200


def test_metadata_still_served_for_quarantined(degraded_client):
    """Casualties 409 on PREDICTIONS; their metadata stays inspectable
    (operators need it to debug the quarantine)."""
    resp = degraded_client.get(
        f"/gordo/v0/{GORDO_PROJECT}/{QUARANTINED}/metadata"
    )
    assert resp.status_code == 200


def test_client_records_unavailable_as_permanent_failure(degraded_server):
    """Client.predict_fleet: the 409 casualty becomes a per-machine
    error in PredictionResult — ZERO retries (permanent condition) — and
    the healthy machines still come back with frames."""
    import dateutil.parser

    from gordo_tpu.client import Client
    from gordo_tpu.data.providers import RandomDataProvider
    from tests.utils import loopback_session

    client = Client(
        project=GORDO_PROJECT,
        host="localhost",
        port=8888,
        scheme="http",
        data_provider=RandomDataProvider(),
        session=loopback_session(degraded_server),
        parallelism=2,
        n_retries=0,
    )
    retries_before = _retry_count()
    start = dateutil.parser.isoparse("2019-01-01T00:00:00+00:00")
    end = dateutil.parser.isoparse("2019-01-01T04:00:00+00:00")
    results = {
        name: (frame, errors)
        for name, frame, errors in client.predict_fleet(
            start, end, targets=[GORDO_TARGETS[0], QUARANTINED]
        )
    }
    healthy_frame, healthy_errors = results[GORDO_TARGETS[0]]
    assert healthy_errors == []
    assert len(healthy_frame) > 0
    bad_frame, bad_errors = results[QUARANTINED]
    assert len(bad_frame) == 0
    assert any("unavailable" in msg for msg in bad_errors)
    assert any("quarantined" in msg for msg in bad_errors)
    assert _retry_count() == retries_before  # no backoff loop burned

    # the per-machine path refuses the same way
    machine = {
        m.name: m for m in client._get_machines(machine_names=[QUARANTINED])
    }[QUARANTINED]
    result = client.predict_single_machine(
        machine=machine, start=start, end=end,
        revision=client._get_latest_revision(),
    )
    assert len(result.predictions) == 0
    assert any("unavailable" in msg for msg in result.error_messages)


def _retry_count() -> float:
    from gordo_tpu.observability import get_registry

    counter = get_registry().counter(
        "gordo_client_retries_total",
        "Prediction POST retries after IO errors",
        ("path",),
    )
    return sum(s["value"] for s in counter.snapshot()["series"])


def test_serve_fault_injection_is_distinguishable_503(
    monkeypatch, gordo_ml_server_client
):
    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR, f"serve:raise:{GORDO_TARGETS[0]}"
    )
    resp = gordo_ml_server_client.post(
        f"/gordo/v0/{GORDO_PROJECT}/{GORDO_TARGETS[0]}/prediction",
        json={"X": _sensor_payload()},
    )
    assert resp.status_code == 503
    assert "Fault injection" in resp.get_json()["error"]


# -- backoff jitter ------------------------------------------------------


def test_backoff_jitter_is_seedable_and_bounded():
    from gordo_tpu.client.utils import backoff_seconds, seed_backoff_jitter

    # unjittered: the documented exact schedule
    assert [backoff_seconds(n) for n in (1, 2, 3, 7)] == [8, 16, 32, 300]

    seed_backoff_jitter(7)
    first = [backoff_seconds(n, jitter=0.25) for n in range(1, 6)]
    seed_backoff_jitter(7)
    again = [backoff_seconds(n, jitter=0.25) for n in range(1, 6)]
    assert first == again  # deterministic under a seed
    for n, value in enumerate(first, start=1):
        base = min(2 ** (n + 2), 300)
        assert base * 0.75 <= value <= base
    # two seeds decorrelate (the anti-thundering-herd property)
    seed_backoff_jitter(8)
    other = [backoff_seconds(n, jitter=0.25) for n in range(1, 6)]
    assert other != first
    seed_backoff_jitter(None)


# -- the runtime file channel (game days) --------------------------------


def _series_value(snap, name, **labels):
    for s in snap.get(name, {}).get("series", []):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return 0.0


def test_fault_file_channel_arms_and_disarms_mid_process(
    monkeypatch, tmp_path
):
    """GORDO_FAULT_INJECT_FILE is the runtime activation channel: a
    game-day runner rewrites the file and an ALREADY-RUNNING process
    changes behavior on its next seam consultation — no restart, no env
    churn. Unset (or file missing/empty) stays the strict no-op."""
    path = tmp_path / "faults.spec"
    monkeypatch.delenv(faults.FAULT_INJECT_FILE_ENV_VAR, raising=False)
    assert faults.active_registry() is None  # unset: strict no-op

    monkeypatch.setenv(faults.FAULT_INJECT_FILE_ENV_VAR, str(path))
    assert faults.active_registry() is None  # missing file: disarmed
    faults.inject("fetch", "m-1")  # no raise

    faults.arm_file(path, "fetch:raise:m-1")
    with pytest.raises(InjectedFault):
        faults.inject("fetch", "m-1")
    faults.inject("fetch", "m-0")  # untargeted machines never fault

    faults.disarm_file(path)
    faults.inject("fetch", "m-1")  # disarmed mid-process
    assert faults.active_registry() is None


def test_fault_file_arm_validates_spec_first(tmp_path):
    path = tmp_path / "faults.spec"
    with pytest.raises(ValueError, match="unknown site"):
        faults.arm_file(path, "fletch:raise")
    assert not path.exists()  # a typo'd arm writes NOTHING


def test_fault_env_grammar_wins_over_file(monkeypatch, tmp_path):
    path = tmp_path / "faults.spec"
    faults.arm_file(path, "fetch:raise:m-1")
    monkeypatch.setenv(faults.FAULT_INJECT_FILE_ENV_VAR, str(path))
    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "ckpt:torn")
    registry = faults.active_registry()
    assert [s.site for s in registry.specs] == ["ckpt"]
    faults.inject("fetch", "m-1")  # the file's spec is shadowed


def test_fault_file_rearm_restarts_attempts_budget(monkeypatch, tmp_path):
    """Re-arming the SAME spec string restarts its @attempts budget —
    the file rewrite invalidates the cached registry, so scenario N+1
    never inherits scenario N's exhausted budgets."""
    path = tmp_path / "faults.spec"
    monkeypatch.setenv(faults.FAULT_INJECT_FILE_ENV_VAR, str(path))
    faults.arm_file(path, "fetch:raise:m-1@attempts:1")
    with pytest.raises(InjectedFault):
        faults.inject("fetch", "m-1")
    faults.inject("fetch", "m-1")  # budget exhausted

    faults.arm_file(path, "fetch:raise:m-1@attempts:1")
    with pytest.raises(InjectedFault):
        faults.inject("fetch", "m-1")  # fresh registry, fresh budget


def test_reset_restarts_env_attempts_budget(monkeypatch):
    """faults.reset() is the scenario boundary: registries are cached
    per spec string process-globally, so without it a rerun of the same
    spec inherits exhausted @attempts budgets."""
    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR, "fetch:raise:m-1@attempts:1"
    )
    with pytest.raises(InjectedFault):
        faults.inject("fetch", "m-1")
    faults.inject("fetch", "m-1")  # exhausted

    faults.reset()
    with pytest.raises(InjectedFault):
        faults.inject("fetch", "m-1")  # the rerun fires again


def test_fault_firing_bumps_site_counter(monkeypatch):
    """Every firing bumps gordo_fault_fired_total{site} — the metric
    twin of the fault_injected event (scenario reports read the
    delta)."""
    from gordo_tpu.observability import get_registry

    before = _series_value(
        get_registry().snapshot(), "gordo_fault_fired_total", site="fetch"
    )
    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "fetch:raise:m-1")
    for _ in range(3):
        with pytest.raises(InjectedFault):
            faults.inject("fetch", "m-1")
    after = _series_value(
        get_registry().snapshot(), "gordo_fault_fired_total", site="fetch"
    )
    assert after == before + 3


def test_every_known_site_exercised_by_suite():
    """Inventory gate: every site parse_spec accepts must be FIRED by at
    least one spec string somewhere in the test suite — a chaos seam no
    test arms is a seam whose failure mode nobody has ever watched."""
    import pathlib
    import re

    corpus = "".join(
        p.read_text()
        for p in pathlib.Path(__file__).parent.glob("*.py")
    )
    unexercised = sorted(
        site
        for site in faults._KNOWN_SITES
        if not re.search(rf"{site}:[a-z]", corpus)
    )
    assert not unexercised, (
        f"fault sites never armed by any test: {unexercised}"
    )
