"""
Fleet checkpoint/resume tests: a preempted fit resumed from the last
checkpoint must land on exactly the params an uninterrupted fit produces
(epoch keys derive from fold_in(epoch), so the schedule is deterministic).
"""

import jax
import numpy as np
import pytest

from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.parallel import FleetCheckpointer, FleetTrainer, StackedData

RNG = np.random.default_rng(9)
N_MACHINES, N_ROWS, N_FEATURES = 3, 64, 4
EPOCHS = 4


def make_trainer_and_data():
    Xs = [RNG.random((N_ROWS, N_FEATURES)).astype("float32") for _ in range(N_MACHINES)]
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    spec = feedforward_hourglass(n_features=N_FEATURES)
    trainer = FleetTrainer(spec, donate=False)
    return trainer, data, trainer.machine_keys(N_MACHINES)


def test_resume_matches_uninterrupted(tmp_path):
    trainer, data, keys = make_trainer_and_data()

    straight_params, straight_losses = trainer.fit(
        data, keys, epochs=EPOCHS, batch_size=16
    )

    # "preempted" run: checkpoint every epoch, stop after 2
    ckpt = FleetCheckpointer(tmp_path / "ckpt")
    trainer.fit(data, keys, epochs=2, batch_size=16, checkpointer=ckpt)
    assert ckpt.latest_epoch() == 1

    # resumed run continues from epoch 2 and completes the schedule
    resumed_params, resumed_losses = trainer.fit(
        data, keys, epochs=EPOCHS, batch_size=16, checkpointer=ckpt
    )
    assert resumed_losses.shape[0] == EPOCHS - 2  # only the remaining epochs ran

    flat_a = jax.tree_util.tree_leaves(straight_params)
    flat_b = jax.tree_util.tree_leaves(resumed_params)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(
        straight_losses[2:], resumed_losses, rtol=1e-6
    )
    ckpt.close()


def test_early_stopping_resume_matches_uninterrupted(tmp_path):
    """An early-stopping fit killed after a checkpoint and resumed is the
    uninterrupted one: the per-machine stopping state rides the checkpoint,
    so a machine that had stopped stays stopped, the others stop at their
    own epochs, and losses and parameters come out bit for bit."""
    trainer, data, keys = make_trainer_and_data()
    es = dict(
        epochs=25, batch_size=16,
        early_stopping_patience=2, early_stopping_min_delta=2e-2,
    )
    straight_params, straight_losses = trainer.fit(data, keys, **es)
    straight = dict(trainer.fit_telemetry_)
    # the machines stop one by one, the last well before the budget
    assert straight["n_machines_early_stopped"] == N_MACHINES
    stop_epoch = straight["early_stop_epoch"]
    killed_after = stop_epoch - 3
    assert 0 < killed_after
    # one machine has stopped by then: its loss row no longer moves
    moved = np.diff(straight_losses[: killed_after + 1], axis=0) != 0
    assert not moved[-1].all() and moved[-1].any()

    ckpt = FleetCheckpointer(tmp_path / "ckpt")
    trainer.fit(
        data, keys, checkpointer=ckpt, **{**es, "epochs": killed_after + 1}
    )
    assert ckpt.latest_epoch() == killed_after

    resumed_params, resumed_losses = trainer.fit(
        data, keys, checkpointer=ckpt, **es
    )
    ckpt.close()
    resumed = trainer.fit_telemetry_
    assert resumed["resumed_from_epoch"] == killed_after + 1
    assert resumed["early_stop_epoch"] == stop_epoch
    assert resumed["n_machines_early_stopped"] == N_MACHINES
    np.testing.assert_array_equal(
        straight_losses[killed_after + 1 :], resumed_losses
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(straight_params),
        jax.tree_util.tree_leaves(resumed_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_every_n(tmp_path):
    trainer, data, keys = make_trainer_and_data()
    ckpt = FleetCheckpointer(tmp_path / "ckpt")
    trainer.fit(
        data, keys, epochs=4, batch_size=16, checkpointer=ckpt, checkpoint_every=2
    )
    # epochs 1 and 3 (0-indexed) are the multiples of 2
    assert ckpt.latest_epoch() == 3
    ckpt.close()


def test_restore_without_checkpoints_raises(tmp_path):
    ckpt = FleetCheckpointer(tmp_path / "empty")
    assert ckpt.latest_epoch() is None
    with pytest.raises(FileNotFoundError):
        ckpt.restore({}, {})
    ckpt.close()


def test_keep_limit(tmp_path):
    trainer, data, keys = make_trainer_and_data()
    ckpt = FleetCheckpointer(tmp_path / "ckpt", keep=2)
    trainer.fit(data, keys, epochs=5, batch_size=16, checkpointer=ckpt)
    ckpt.wait()
    import os

    steps = sorted(
        int(d) for d in os.listdir(tmp_path / "ckpt") if d.isdigit()
    )
    assert len(steps) <= 2
    assert steps[-1] == 4
    ckpt.close()


def test_checkpoint_extra_state_round_trip(tmp_path):
    """Early-stopping (or other host) state rides next to the orbax step."""
    import numpy as np

    from gordo_tpu.parallel.checkpoint import FleetCheckpointer
    from gordo_tpu.models.factories.feedforward import feedforward_hourglass
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

    rng = np.random.default_rng(0)
    X = rng.random((40, 3)).astype("float32")
    data = StackedData.from_ragged([X], [X.copy()])
    trainer = FleetTrainer(feedforward_hourglass(n_features=3), donate=False)
    keys = trainer.machine_keys(1)
    params, _ = trainer.fit(data, keys, epochs=1, batch_size=16)
    opt_state = trainer.init_opt_state(params)

    ckpt = FleetCheckpointer(str(tmp_path))
    extra = {"best": np.array([0.5]), "wait": np.array([2]),
             "active": np.array([True]), "last_loss": np.array([0.6])}
    ckpt.save(0, params, opt_state, extra=extra)
    ckpt.wait()
    p2, o2, epoch, restored = ckpt.restore_with_extra(params, opt_state, extra)
    assert epoch == 0 and restored is not None
    for key in extra:
        np.testing.assert_array_equal(restored[key], extra[key])

    # a checkpoint saved WITHOUT extra restores params and returns None
    ckpt.save(1, params, opt_state)
    ckpt.wait()
    p3, o3, epoch, missing = ckpt.restore_with_extra(
        params, opt_state, extra, epoch=1
    )
    assert epoch == 1 and missing is None
    ckpt.close()
