"""
The crash-tolerant global work ledger (docs/robustness.md "Multi-worker
builds"): claim exclusivity, TTL steal with tombstone attempt counting,
the double-commit guard, poisoned units, torn-lease and clock-skew edge
cases, real-process claim races, and the acceptance scenario — a
2-worker build surviving a SIGKILL'd worker via lease steal with
results bit-identical to a single-worker fault-free run.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
import yaml
from click.testing import CliRunner

from gordo_tpu import serializer
from gordo_tpu.builder import ledger as ledger_mod
from gordo_tpu.builder.fleet_build import FleetModelBuilder
from gordo_tpu.builder.ledger import Ledger, WorkUnit, plan_units
from gordo_tpu.machine import Machine
from gordo_tpu.observability import read_events
from gordo_tpu.robustness import faults
from gordo_tpu.utils import atomic

RACER = os.path.join(os.path.dirname(__file__), "support", "_ledger_racer.py")


@pytest.fixture(autouse=True)
def _fresh_faults(monkeypatch):
    monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR, raising=False)
    monkeypatch.delenv(faults.WORKER_ID_ENV_VAR, raising=False)
    faults.reset()
    yield
    faults.reset()


def make_machine(name, epochs=1):
    return Machine(
        name=name,
        project_name="ledger-test",
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


def make_units(n=3):
    return [
        WorkUnit(uid=f"u{i:03d}-test", machines=(f"m-{i}",)) for i in range(n)
    ]


def make_ledger(tmp_path, worker_id, ttl=30.0, max_attempts=3, units=None):
    ledger = Ledger(
        tmp_path, worker_id, lease_ttl=ttl, max_attempts=max_attempts
    )
    ledger.ensure_plan(units if units is not None else make_units())
    return ledger


def unit_report(claimed):
    return {
        "built": list(claimed.machines),
        "failed": [],
        "quarantined": [],
        "buckets": [],
    }


# -- plan ----------------------------------------------------------------


def test_plan_units_deterministic_and_config_sensitive():
    machines = [make_machine("a"), make_machine("b"), make_machine("c", epochs=2)]
    units = plan_units(machines)
    assert units == plan_units(list(machines))
    # same-architecture machines share a bucket; a different config is a
    # different unit
    rosters = sorted(u.machines for u in units)
    assert rosters == [("a", "b"), ("c",)]
    changed = plan_units([make_machine("a"), make_machine("b"), make_machine("c", epochs=3)])
    assert {u.uid for u in changed} != {u.uid for u in units}


def test_plan_units_exact_digests_pinned_to_legacy():
    """The default (exact) policy's unit digests are byte-identical to
    the historical bucket_machines-based plan, so existing ledgers and
    resumes keep working across the bucketing-compiler refactor."""
    import hashlib

    from gordo_tpu.parallel.bucketing import bucket_machines

    machines = [make_machine("a"), make_machine("b"), make_machine("c", epochs=2)]
    digests = []
    for (model_key, n_feat, n_feat_out), bucket in bucket_machines(
        machines
    ).items():
        names = tuple(m.name for m in bucket)
        digest = hashlib.sha1(
            json.dumps(
                [model_key, n_feat, n_feat_out, list(names)], sort_keys=True
            ).encode()
        ).hexdigest()
        digests.append((digest, names))
    digests.sort()
    legacy = [
        WorkUnit(uid=f"u{index:03d}-{digest[:10]}", machines=names)
        for index, (digest, names) in enumerate(digests)
    ]
    assert plan_units(machines) == legacy
    assert plan_units(machines, policy="exact") == legacy


def test_plan_units_policy_changes_fingerprint():
    """Flipping --bucket-policy must change the plan fingerprint even
    when the GROUPING happens to coincide (uniform-width fleets), so a
    mismatched worker can never join a live ledger silently."""
    machines = [make_machine("a"), make_machine("b")]
    exact_units = plan_units(machines)
    padded_units = plan_units(machines, policy="padded")
    # same rosters (uniform widths: nothing to fuse) ...
    assert sorted(u.machines for u in exact_units) == sorted(
        u.machines for u in padded_units
    )
    # ... but distinct identities
    assert {u.uid for u in exact_units} != {u.uid for u in padded_units}
    assert ledger_mod.plan_fingerprint(exact_units) != ledger_mod.plan_fingerprint(
        padded_units
    )


def test_plan_units_padded_fuses_ragged_buckets():
    """The padded policy plans FEWER, larger units: one per fused
    program rather than one per exact geometry."""
    machines = [make_machine("a"), make_machine("b")]
    cfg = machines[0].to_dict()
    cfg["name"] = "c3"
    cfg["dataset"] = dict(cfg["dataset"])
    cfg["dataset"]["tags"] = [["Tag 1", None], ["Tag 2", None], ["Tag 3", None]]
    machines.append(Machine.from_dict(cfg))
    assert len(plan_units(machines)) == 2  # widths 2 and 3
    padded = plan_units(machines, policy="padded")
    assert len(padded) == 2  # buckets 2 and 4: 3 rounds up alone
    cfg4 = dict(cfg)
    cfg4["name"] = "c4"
    cfg4["dataset"] = dict(cfg4["dataset"])
    cfg4["dataset"]["tags"] = [[f"Tag {t}", None] for t in range(1, 5)]
    machines.append(Machine.from_dict(cfg4))
    assert len(plan_units(machines)) == 3
    fused = plan_units(machines, policy="padded")
    assert len(fused) == 2  # 3- and 4-wide fuse at bucket 4
    assert sorted(u.machines for u in fused) == [("a", "b"), ("c3", "c4")]


def test_ensure_plan_policy_mismatch_refuses_to_join(tmp_path):
    """A worker running a different --bucket-policy against a live
    ledger must refuse, like a config mismatch — same artifact tree,
    different program geometries."""
    machines = [make_machine("a"), make_machine("b")]
    first = Ledger(tmp_path, "w0")
    first.ensure_plan(plan_units(machines), bucket_policy="exact")
    second = Ledger(tmp_path, "w1")
    with pytest.raises(
        ledger_mod.LedgerPlanMismatch, match="--bucket-policy exact"
    ):
        second.ensure_plan(
            plan_units(machines, policy="padded"), bucket_policy="padded"
        )
    # the same policy + same config still joins fine
    second.ensure_plan(plan_units(machines), bucket_policy="exact")


def test_resolve_workers():
    assert ledger_mod.resolve_workers("1") == 1
    assert ledger_mod.resolve_workers(3) == 3
    auto = ledger_mod.resolve_workers("auto")
    assert 1 <= auto <= 4
    with pytest.raises(ValueError):
        ledger_mod.resolve_workers("0")


def test_joining_a_mismatched_plan_refuses(tmp_path):
    make_ledger(tmp_path, 0, units=make_units(3))
    with pytest.raises(ledger_mod.LedgerPlanMismatch):
        make_ledger(tmp_path, 1, units=make_units(4))


# -- claim / steal -------------------------------------------------------


def test_claims_are_exclusive(tmp_path):
    w0 = make_ledger(tmp_path, 0, units=make_units(2))
    w1 = make_ledger(tmp_path, 1, units=make_units(2))
    c0, c1 = w0.claim_next(), w1.claim_next()
    assert c0.uid != c1.uid
    assert w0.claim_next() is None  # both units leased, neither expired
    assert not w0.all_resolved()


def test_fresh_lease_is_not_stolen(tmp_path):
    w0 = make_ledger(tmp_path, 0, ttl=30.0, units=make_units(1))
    w1 = make_ledger(tmp_path, 1, ttl=30.0, units=make_units(1))
    assert w0.claim_next() is not None
    assert w1.claim_next() is None


def test_steal_after_ttl_with_events(tmp_path, monkeypatch):
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    w0 = make_ledger(tmp_path, 0, ttl=0.2, units=make_units(1))
    w1 = make_ledger(tmp_path, 1, ttl=0.2, units=make_units(1))
    claimed = w0.claim_next()
    assert claimed.attempt == 1 and not claimed.stolen
    time.sleep(0.3)  # no heartbeat: worker 0 is "dead"
    stolen = w1.claim_next()
    assert stolen is not None and stolen.uid == claimed.uid
    assert stolen.attempt == 2 and stolen.stolen
    events = {e["event"] for e in read_events(str(event_log))}
    assert "worker_died" in events and "lease_stolen" in events
    died = next(
        e for e in read_events(str(event_log)) if e["event"] == "worker_died"
    )
    assert died["worker"] == "0" and died["observed_by"] == "1"
    # the tombstone is the attempt record (unique suffix per steal, so
    # racing stealers can never clobber each other's death records)
    tombstones = [
        p
        for p in (tmp_path / ".ledger" / "units").iterdir()
        if p.name.startswith(f"{claimed.uid}.tombstone-")
    ]
    assert len(tombstones) == 1


def test_heartbeat_keeps_lease_alive(tmp_path):
    w0 = make_ledger(tmp_path, 0, ttl=0.4, units=make_units(1))
    w1 = make_ledger(tmp_path, 1, ttl=0.4, units=make_units(1))
    claimed = w0.claim_next()
    w0.start_heartbeat()
    try:
        time.sleep(0.9)  # > 2 TTLs, but the heartbeat refreshes mtime
        assert w1.claim_next() is None
    finally:
        w0.stop_heartbeat()
    assert w0.commit(claimed.uid, unit_report(claimed))


def test_torn_lease_file_still_steals(tmp_path):
    """A crash between lease create and body write leaves an empty
    file: liveness still rides the mtime, ownership is unknown — an
    expired torn lease is stolen like any other."""
    units = make_units(1)
    w1 = make_ledger(tmp_path, 1, ttl=0.2, units=units)
    lease = tmp_path / ".ledger" / "units" / f"{units[0].uid}.lease"
    lease.write_text("")  # torn: no JSON body
    old = time.time() - 5.0
    os.utime(lease, (old, old))
    stolen = w1.claim_next()
    assert stolen is not None and stolen.uid == units[0].uid
    assert stolen.attempt == 2  # the dead attempt still counted
    # unreadable garbage body behaves the same
    w2 = make_ledger(tmp_path, 2, ttl=0.2, units=make_units(1))
    lease.write_text("{not json")
    os.utime(lease, (old, old))
    # w1's own fresh lease was replaced by garbage: w2 steals it
    stolen2 = w2.claim_next()
    assert stolen2 is not None and stolen2.attempt == 3


def test_clock_skew_future_mtime_reads_fresh(tmp_path):
    """A skewed writer whose heartbeats land in the future must read as
    ALIVE: skew can delay a steal, never cause one early."""
    units = make_units(1)
    w0 = make_ledger(tmp_path, 0, ttl=0.2, units=units)
    w1 = make_ledger(tmp_path, 1, ttl=0.2, units=units)
    claimed = w0.claim_next()
    lease = tmp_path / ".ledger" / "units" / f"{claimed.uid}.lease"
    future = time.time() + 3600.0
    os.utime(lease, (future, future))
    time.sleep(0.3)  # well past the TTL on OUR clock
    assert w1.claim_next() is None


# -- commit --------------------------------------------------------------


def test_commit_writes_done_and_releases(tmp_path):
    w0 = make_ledger(tmp_path, 0, units=make_units(1))
    claimed = w0.claim_next()
    assert w0.commit(claimed.uid, unit_report(claimed))
    units_dir = tmp_path / ".ledger" / "units"
    assert (units_dir / f"{claimed.uid}.done").exists()
    assert not (units_dir / f"{claimed.uid}.lease").exists()
    assert w0.all_resolved()
    # recommit of a resolved unit is refused
    assert not w0.commit(claimed.uid, unit_report(claimed))


def test_double_commit_guard_after_steal(tmp_path, monkeypatch):
    """The stalled worker wakes, finds its lease stolen, and must NOT
    commit; exactly one done record ever exists."""
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    w0 = make_ledger(tmp_path, 0, ttl=0.2, units=make_units(1))
    w1 = make_ledger(tmp_path, 1, ttl=0.2, units=make_units(1))
    claimed = w0.claim_next()
    time.sleep(0.3)
    stolen = w1.claim_next()
    assert stolen is not None
    # the stalled worker finishes its build and tries to commit
    assert w0.commit(claimed.uid, unit_report(claimed)) is False
    assert w1.commit(stolen.uid, unit_report(stolen)) is True
    done = [
        p
        for p in os.listdir(tmp_path / ".ledger" / "units")
        if p.endswith(".done")
    ]
    assert len(done) == 1
    record = json.loads(
        (tmp_path / ".ledger" / "units" / done[0]).read_text()
    )
    assert record["worker"] == "1" and record["attempt"] == 2
    events = [e["event"] for e in read_events(str(event_log))]
    assert "lease_lost" in events


def test_lease_stall_double_commit_guard_with_heartbeats(
    tmp_path, monkeypatch
):
    """The `lease:stall` chaos site end to end: worker 0 keeps working
    but its heartbeat thread goes silent, the lease expires mid-build,
    worker 1 steals and commits, worker 0's late commit is refused."""
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "lease:stall:0")
    faults.reset()
    w0 = make_ledger(tmp_path, 0, ttl=0.3, units=make_units(1))
    w1 = make_ledger(tmp_path, 1, ttl=0.3, units=make_units(1))
    claimed = w0.claim_next()
    w0.start_heartbeat()  # beats are skipped by the stall spec
    w1.start_heartbeat()
    try:
        time.sleep(0.6)
        stolen = w1.claim_next()
        assert stolen is not None and stolen.uid == claimed.uid
        assert w0.commit(claimed.uid, unit_report(claimed)) is False
        assert w1.commit(stolen.uid, unit_report(stolen)) is True
    finally:
        w0.stop_heartbeat()
        w1.stop_heartbeat()
    events = [e["event"] for e in read_events(str(event_log))]
    assert "fault_injected" in events  # the stall announced itself
    assert "lease_stolen" in events and "lease_lost" in events


# -- poisoning -----------------------------------------------------------


def test_unit_poisoned_after_max_attempts(tmp_path, monkeypatch):
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    units = [WorkUnit(uid="u000-test", machines=("m-0", "m-1"))]
    ttl = 0.15
    for attempt_worker in range(2):  # two claims, both "die"
        w = make_ledger(
            tmp_path, attempt_worker, ttl=ttl, max_attempts=2, units=units
        )
        assert w.claim_next() is not None
        time.sleep(ttl + 0.1)
    w_last = make_ledger(tmp_path, 9, ttl=ttl, max_attempts=2, units=units)
    assert w_last.claim_next() is None  # poisoned, not re-leased
    assert w_last.all_resolved()
    report = w_last.finalize(on_error="skip")
    assert report["n_failed"] == 2 and report["n_built"] == 0
    by_machine = {r["machine"]: r for r in report["failed"]}
    assert set(by_machine) == {"m-0", "m-1"}
    for record in by_machine.values():
        assert record["phase"] == "build"
        assert "poisoned" in record["error"]
        assert record["attempts"] == 2
    events = [e for e in read_events(str(event_log)) if e["event"] == "unit_poisoned"]
    assert len(events) == 1 and events[0]["attempts"] == 2


# -- finalize ------------------------------------------------------------


def test_finalize_merges_unit_reports(tmp_path):
    units = make_units(2)
    w0 = make_ledger(tmp_path, 0, units=units)
    for _ in range(2):
        claimed = w0.claim_next()
        report = unit_report(claimed)
        if claimed.machines == ("m-1",):
            report["failed"] = [
                {"machine": "m-1x", "phase": "fetch", "error": "boom", "attempts": 1}
            ]
            report["quarantined"] = [{"machine": "m-1", "epoch": 0}]
        assert w0.commit(claimed.uid, report)
    merged = w0.finalize(on_error="skip")
    assert merged["kind"] == "fleet_build_report"
    assert merged["n_built"] == 2
    assert merged["n_failed"] == 1 and merged["failed"][0]["machine"] == "m-1x"
    assert merged["n_quarantined"] == 1
    # the report landed on disk for the server, atomically
    on_disk = json.loads((tmp_path / "build_report.json").read_text())
    assert on_disk == merged
    telemetry = json.loads((tmp_path / "telemetry_report.json").read_text())
    assert telemetry["ledger"]["n_units"] == 2
    assert telemetry["ledger"]["steals"] == 0


# -- status --------------------------------------------------------------


def test_ledger_status_states_and_heartbeat_ages(tmp_path):
    units = make_units(3)
    w0 = make_ledger(tmp_path, 0, ttl=60.0, units=units)
    w0.register_worker()
    claimed = w0.claim_next()
    done = w0.claim_next()
    assert w0.commit(done.uid, unit_report(done))
    status = w0.status()
    assert status["counts"] == {
        "pending": 1, "leased": 1, "done": 1, "casualty": 0
    }
    by_state = {u["state"]: u for u in status["units"]}
    leased = by_state["leased"]
    assert leased["unit"] == claimed.uid
    assert leased["worker"] == "0" and leased["attempt"] == 1
    assert leased["heartbeat_age_s"] is not None
    assert leased["heartbeat_age_s"] < 60.0 and not leased["expired"]
    assert status["workers"]["0"]["last_heartbeat_age_s"] is not None
    assert not status["workers"]["0"]["stalled"]


def test_status_uses_recorded_ttl_not_probe_ttl(tmp_path):
    """Expiry/stall verdicts come from the TTL the lease recorded at
    claim time — a probe run without repeating --lease-ttl must still
    judge a 0.3s-TTL build by 0.3s, not by its own 60s default."""
    units = make_units(1)
    w0 = make_ledger(tmp_path, 0, ttl=0.3, units=units)
    w0.register_worker()
    claimed = w0.claim_next()
    time.sleep(0.5)  # expired by the BUILD's ttl, fresh by the probe's
    probe = Ledger(tmp_path, "status")  # default 60s TTL
    status = probe.status()
    leased = next(u for u in status["units"] if u["state"] == "leased")
    assert leased["unit"] == claimed.uid
    assert leased["lease_ttl_s"] == 0.3 and leased["expired"]
    assert status["workers"]["0"]["stalled"]
    # ...and a FINALIZED build's silent workers are not "stalled"
    assert w0.commit(claimed.uid, unit_report(claimed))
    w0.finalize(on_error="raise")
    time.sleep(0.4)
    status = probe.status()
    assert status["finalized"]
    assert not status["workers"]["0"]["stalled"]


def test_owns_and_steal_skips_committed_units(tmp_path):
    units = make_units(1)
    w0 = make_ledger(tmp_path, 0, ttl=0.2, units=units)
    w1 = make_ledger(tmp_path, 1, ttl=0.2, units=units)
    claimed = w0.claim_next()
    assert w0.owns(claimed.uid) and not w1.owns(claimed.uid)
    # holder commits just before the would-be steal: the stealer must
    # not re-lease (and rebuild) a done unit
    assert w0.commit(claimed.uid, unit_report(claimed))
    time.sleep(0.3)
    assert w1.claim_next() is None
    assert not (
        tmp_path / ".ledger" / "units" / f"{claimed.uid}.lease"
    ).exists()


def test_orchestrator_finalizes_when_last_worker_dies_pre_finalize(tmp_path):
    """All units committed but no worker lived to finalize: the
    orchestrator's probe merges the report itself instead of failing a
    complete build (or trusting a stale report on disk)."""
    units = make_units(2)
    w0 = make_ledger(tmp_path, 0, units=units)
    for _ in range(2):
        claimed = w0.claim_next()
        assert w0.commit(claimed.uid, unit_report(claimed))
    # simulate "died before finalize": no build_report.json on disk,
    # plus a stale report that must NOT be what orchestrate returns
    stale = {"n_built": 999, "kind": "stale"}
    (tmp_path / "build_report.json").write_text(json.dumps(stale))
    probe = Ledger(tmp_path, "orchestrator")
    assert probe.all_resolved()
    report = probe.finalize(on_error="raise")
    assert report["n_built"] == 2 and report["kind"] == "fleet_build_report"
    on_disk = json.loads((tmp_path / "build_report.json").read_text())
    assert on_disk["n_built"] == 2


def test_ledger_status_cli(tmp_path):
    units = make_units(2)
    w0 = make_ledger(tmp_path, 0, ttl=45.0, units=units)
    w0.register_worker()
    claimed = w0.claim_next()
    from gordo_tpu.cli import gordo

    result = CliRunner().invoke(
        gordo,
        [
            "build-fleet", "--ledger-status", str(tmp_path),
            "--lease-ttl", "45",
        ],
    )
    assert result.exit_code == 0, result.output
    assert claimed.uid in result.output
    assert "leased" in result.output and "pending" in result.output
    assert "last heartbeat" in result.output  # per-worker heartbeat age
    # and on a directory with no ledger at all
    empty = tmp_path / "empty"
    empty.mkdir()
    result = CliRunner().invoke(
        gordo, ["build-fleet", "--ledger-status", str(empty)]
    )
    assert result.exit_code == 0
    assert "No ledger" in result.output


# -- the atomic helpers the ledger stands on -----------------------------


def test_atomic_write_json_round_trip_and_replace(tmp_path):
    path = tmp_path / "sub" / "report.json"
    atomic.atomic_write_json(path, {"a": 1}, indent=2, sort_keys=True)
    assert json.loads(path.read_text()) == {"a": 1}
    atomic.atomic_write_json(path, {"a": 2})
    assert json.loads(path.read_text()) == {"a": 2}
    # no staging debris
    assert [p.name for p in (tmp_path / "sub").iterdir()] == ["report.json"]


def test_atomic_create_json_is_exclusive(tmp_path):
    path = tmp_path / "done.json"
    atomic.atomic_create_json(path, {"w": 1})
    with pytest.raises(FileExistsError):
        atomic.atomic_create_json(path, {"w": 2})
    assert json.loads(path.read_text()) == {"w": 1}
    assert [p.name for p in tmp_path.iterdir()] == ["done.json"]


def test_atomic_symlink_swap(tmp_path):
    (tmp_path / "r1").mkdir()
    (tmp_path / "r2").mkdir()
    pointer = tmp_path / "latest"
    atomic.atomic_symlink_swap("r1", pointer)
    assert os.readlink(pointer) == "r1"
    atomic.atomic_symlink_swap("r2", pointer)
    assert os.readlink(pointer) == "r2"


def test_atomic_publish_dir_replaces_whole_dir(tmp_path):
    staging = tmp_path / ".staging"
    staging.mkdir()
    (staging / "f").write_text("new")
    dest = tmp_path / "artifact"
    dest.mkdir()
    (dest / "old").write_text("old")
    atomic.atomic_publish_dir(staging, dest)
    assert (dest / "f").read_text() == "new"
    assert not (dest / "old").exists()
    assert not staging.exists()


# -- real-process claim races --------------------------------------------


def _run_racers(
    tmp_path, n_workers, n_units, lease_ttl=10.0, max_attempts=3,
    build_sleep=0.01, env_extra=None, timeout=120,
):
    env = {
        k: v for k, v in os.environ.items()
        if k not in (faults.FAULT_INJECT_ENV_VAR, faults.WORKER_ID_ENV_VAR)
    }
    env.update(env_extra or {})
    procs, outs = [], []
    for wid in range(n_workers):
        out_file = tmp_path / f"racer-{wid}.log"
        outs.append(out_file)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, RACER, str(tmp_path), str(wid),
                    str(n_units), str(out_file), str(lease_ttl),
                    str(max_attempts), str(build_sleep),
                ],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True,
            )
        )
    # release the start barrier once every racer is ready (dead racers
    # release it too, so a startup crash surfaces as its exit code)
    deadline = time.time() + 90.0
    while time.time() < deadline:
        ready = sum(
            1
            for wid in range(n_workers)
            if (tmp_path / f".racer-ready-{wid}").exists()
        )
        if ready == n_workers or any(p.poll() is not None for p in procs):
            break
        time.sleep(0.02)
    (tmp_path / ".racer-go").touch()
    codes = []
    for proc in procs:
        try:
            _, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
        codes.append((proc.returncode, err))
    claims: dict = {}
    commits: dict = {}
    for wid, out_file in enumerate(outs):
        if not out_file.exists():
            continue
        for line in out_file.read_text().splitlines():
            parts = line.split()
            if parts[0] == "CLAIM":
                claims.setdefault(parts[1], []).append((wid, int(parts[2])))
            elif parts[0] == "COMMIT" and parts[2] == "True":
                commits.setdefault(parts[1], []).append(wid)
    return claims, commits, codes


def test_two_process_claim_race_never_double_builds(tmp_path):
    """Two real processes racing one ledger: every unit is built by
    exactly one worker and committed exactly once — the O_EXCL claim is
    the only arbiter (no steals: leases stay heartbeated)."""
    n_units = 8
    claims, commits, codes = _run_racers(
        tmp_path, n_workers=2, n_units=n_units, lease_ttl=10.0
    )
    for code, err in codes:
        assert code == 0, err[-2000:]
    assert len(claims) == n_units
    for uid, claimants in claims.items():
        assert len(claimants) == 1, f"{uid} double-built: {claimants}"
    assert len(commits) == n_units
    assert all(len(c) == 1 for c in commits.values())
    # both workers actually participated
    workers_used = {w for cs in claims.values() for w, _ in cs}
    assert workers_used == {0, 1}


def test_race_with_precommit_death_recovers(tmp_path):
    """One racer dies between build and commit (`worker:die:commit`):
    the survivor steals the orphaned unit and the plan still resolves
    with every unit committed exactly once."""
    n_units = 5
    claims, commits, codes = _run_racers(
        tmp_path, n_workers=2, n_units=n_units,
        lease_ttl=0.6, build_sleep=0.05,
        env_extra={faults.FAULT_INJECT_ENV_VAR: "worker:die:commit@worker:0"},
    )
    # worker 0 died by design (exit 137)
    assert codes[0][0] == 137
    assert codes[1][0] == 0, codes[1][1][-2000:]
    assert len(commits) == n_units
    assert all(len(c) == 1 for c in commits.values())
    # the unit worker 0 died on was claimed twice (once each worker) —
    # that is the one allowed rework unit
    reworked = [uid for uid, cs in claims.items() if len(cs) > 1]
    assert len(reworked) == 1
    assert [w for w, _ in claims[reworked[0]]] == [0, 1]
    probe = Ledger(tmp_path, "probe", lease_ttl=0.6)
    report = probe.finalize(on_error="skip")
    assert report["n_built"] == n_units and report["n_failed"] == 0


@pytest.mark.slow
def test_claim_race_stress(tmp_path):
    """Stress variant: four processes, thirty units, one pre-commit
    death — still exactly-once commits across the board."""
    n_units = 30
    claims, commits, codes = _run_racers(
        tmp_path, n_workers=4, n_units=n_units,
        lease_ttl=0.8, build_sleep=0.02, timeout=300,
        env_extra={faults.FAULT_INJECT_ENV_VAR: "worker:die:commit@worker:2"},
    )
    assert codes[2][0] == 137
    for wid in (0, 1, 3):
        assert codes[wid][0] == 0, codes[wid][1][-2000:]
    assert len(commits) == n_units
    assert all(len(c) == 1 for c in commits.values())
    probe = Ledger(tmp_path, "probe", lease_ttl=0.8)
    assert probe.all_resolved()


# -- single-worker no-op pin ---------------------------------------------


def test_default_build_fleet_constructs_no_ledger(tmp_path, monkeypatch):
    """`--workers 1` (the default) must stay byte-identical in behavior
    to the pre-ledger path: no ledger directory, no lease files, and the
    ledger entry points never invoked — pinned like the fault/tracing/
    batching no-ops."""
    from gordo_tpu.cli import cli as cli_module
    from gordo_tpu.cli import gordo

    def explode(*args, **kwargs):
        raise AssertionError("ledger machinery invoked on a default build")

    monkeypatch.setattr(cli_module.fleet_ledger, "run_worker", explode)
    monkeypatch.setattr(cli_module.fleet_ledger, "orchestrate", explode)
    monkeypatch.setattr(cli_module.fleet_ledger, "Ledger", explode)
    out_dir = tmp_path / "out"
    machines = [
        yaml.safe_load(
            """
            name: solo-machine
            project_name: ledger-test
            dataset:
              type: RandomDataset
              tags: [tag-0, tag-1]
              train_start_date: '2019-01-01T00:00:00+00:00'
              train_end_date: '2019-01-02T00:00:00+00:00'
              asset: gra
            model:
              gordo_tpu.models.AutoEncoder:
                kind: feedforward_hourglass
                epochs: 1
            """
        )
    ]
    result = CliRunner().invoke(
        gordo, ["build-fleet", json.dumps(machines), str(out_dir)]
    )
    assert result.exit_code == 0, result.output
    assert (out_dir / "solo-machine" / "model.pkl").is_file()
    assert not (out_dir / ledger_mod.LEDGER_DIRNAME).exists()
    assert not list(out_dir.rglob("*.lease"))


def test_multi_worker_resume_reuses_artifacts(tmp_path):
    """Ledger resume is two-level: committed units never reclaim, and an
    UNCOMMITTED unit's already-flushed artifacts are reused by the same
    scan the single-worker resume path runs (no wasteful retrain)."""
    machines = [
        make_machine("r-0"), make_machine("r-1"), make_machine("r-2", epochs=2)
    ]
    report = ledger_mod.run_worker(
        FleetModelBuilder(machines), tmp_path, 0, lease_ttl=5.0
    )
    assert report["n_built"] == 3 and report["n_resumed"] == 0
    # simulate a worker dying AFTER flushing r-2's artifacts but BEFORE
    # committing its unit: drop that unit's done record (+ the finalize
    # marker, so the resume run re-merges)
    units_dir = tmp_path / ".ledger" / "units"
    for done in units_dir.glob("*.done"):
        if "r-2" in json.loads(done.read_text())["report"]["built"]:
            done.unlink()
    (tmp_path / ".ledger" / "finalized").unlink()
    artifact = tmp_path / "r-2" / "model.pkl"
    mtime_before = artifact.stat().st_mtime_ns

    report2 = ledger_mod.run_worker(
        FleetModelBuilder(machines), tmp_path, 1, lease_ttl=5.0, resume=True
    )
    # all three in the final report; r-2 reused, not rebuilt
    assert report2["n_built"] == 2 and report2["n_resumed"] == 1
    assert artifact.stat().st_mtime_ns == mtime_before


# -- the acceptance scenario ---------------------------------------------


def _acceptance_configs():
    def cfg(name, epochs):
        return {
            "name": name,
            "project_name": "chaos",
            "model": {
                "gordo_tpu.models.AutoEncoder": {
                    "kind": "feedforward_hourglass",
                    "epochs": epochs,
                    "batch_size": 16,
                }
            },
            "dataset": {
                "type": "RandomDataset",
                "train_start_date": "2017-12-25 06:00:00Z",
                "train_end_date": "2017-12-26 06:00:00Z",
                "tags": [["Tag 1", None], ["Tag 2", None]],
            },
        }

    # two buckets: epochs differ, so the plan has two units
    return [cfg("m-0", 1), cfg("m-1", 1), cfg("m-2", 2), cfg("m-3", 2)]


def test_two_worker_crash_recovery_acceptance(tmp_path):
    """THE acceptance criterion: a 2-worker build with `worker:die`
    injected mid-train on worker 0 completes via lease steal; every
    machine is built exactly once in the final output; params, training
    histories and `build_report.json` are bit-identical to a
    single-worker fault-free run of the same config."""
    configs = _acceptance_configs()
    mw_out = tmp_path / "multi"
    env = {
        k: v for k, v in os.environ.items()
        if k not in (faults.FAULT_INJECT_ENV_VAR, faults.WORKER_ID_ENV_VAR)
    }
    env[faults.FAULT_INJECT_ENV_VAR] = "worker:die:train@worker:0"
    proc = subprocess.run(
        [
            sys.executable, "-m", "gordo_tpu.cli", "build-fleet",
            json.dumps(configs), str(mw_out),
            "--workers", "2", "--lease-ttl", "5",
        ],
        env=env, capture_output=True, text=True, timeout=500,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]

    # the crash actually happened and was healed by a steal
    probe = Ledger(mw_out, "probe", lease_ttl=5.0)
    status = probe.status()
    assert status["counts"]["done"] == 2 and status["counts"]["casualty"] == 0
    attempts = sorted(u["attempt"] for u in status["units"])
    assert attempts == [1, 2], attempts  # one clean unit, one stolen

    # single-worker fault-free reference run, same config/flags
    machines = [
        Machine.from_config(c, project_name=c["project_name"]) for c in configs
    ]
    for machine in machines:
        machine.model = serializer.into_definition(
            serializer.from_definition(machine.model)
        )
    sw_out = tmp_path / "single"
    builder = FleetModelBuilder(machines)
    builder.build(output_dir_base=sw_out)

    # every machine exactly once, artifacts equivalent bit-for-bit at
    # the level the repo pins bit-identity (params + history; the raw
    # pickle bytes embed flax's process-global module counter, which
    # moves with build ORDER even across two single-worker runs)
    for config in configs:
        name = config["name"]
        mw_model = serializer.load(mw_out / name)
        sw_model = serializer.load(sw_out / name)
        np_mw = [np.asarray(x) for x in _tree_leaves(mw_model.params_)]
        np_sw = [np.asarray(x) for x in _tree_leaves(sw_model.params_)]
        assert len(np_mw) == len(np_sw)
        for a, b in zip(np_mw, np_sw):
            np.testing.assert_array_equal(a, b)
        assert mw_model.history_ == sw_model.history_

    mw_report = json.loads((mw_out / "build_report.json").read_text())
    sw_report = json.loads((sw_out / "build_report.json").read_text())
    for volatile in ("started", "finished"):
        mw_report.pop(volatile)
        sw_report.pop(volatile)
    assert mw_report == sw_report


def _tree_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)
