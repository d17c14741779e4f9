"""
Fleet-parallel training tests: the vmap-over-machines path sharded across
the 8 virtual CPU devices (SURVEY.md §4: multi-chip logic tested under
xla_force_host_platform_device_count).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gordo_tpu.builder.fleet_build import FleetModelBuilder
from gordo_tpu.machine import Machine
from gordo_tpu.models import AutoEncoder
from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.parallel import (
    FleetTrainer,
    StackedData,
    bucket_machines,
    get_device_mesh,
)


def make_fleet_data(m=4, n=100, f=3, seed=0):
    rng = np.random.default_rng(seed)
    Xs = [rng.random((n - 5 * i, f)).astype("float32") for i in range(m)]
    return Xs, [x.copy() for x in Xs]


def test_stacked_data_padding():
    Xs, ys = make_fleet_data(m=3, n=50)
    data = StackedData.from_ragged(Xs, ys, n_machines_padded=8)
    assert data.X.shape == (8, 50, 3)
    assert float(data.sample_weight[0].sum()) == 50
    assert float(data.sample_weight[1].sum()) == 45
    assert float(data.sample_weight[3:].sum()) == 0  # dummy machines


def test_fleet_trainer_unsharded():
    Xs, ys = make_fleet_data(m=3)
    data = StackedData.from_ragged(Xs, ys)
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec)
    keys = trainer.machine_keys(3)
    params, losses = trainer.fit(data, keys, epochs=3, batch_size=16)
    assert losses.shape == (3, 3)
    preds = trainer.predict(params, data.X)
    assert preds.shape == (3, 100, 3)


def test_fleet_trainer_sharded_over_mesh():
    mesh = get_device_mesh()  # 8 virtual CPU devices
    assert mesh.devices.size == 8
    m_padded = FleetTrainer.pad_fleet_size(5, mesh)
    assert m_padded == 8
    Xs, ys = make_fleet_data(m=5)
    data = StackedData.from_ragged(Xs, ys, n_machines_padded=m_padded)
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec, mesh=mesh)
    keys = trainer.machine_keys(m_padded)
    params, losses = trainer.fit(data, keys, epochs=2, batch_size=16)
    assert losses.shape == (2, 8)
    # params are actually sharded over the fleet axis
    leaf = jax.tree.leaves(params)[0]
    assert len(leaf.sharding.device_set) == 8
    preds = trainer.predict(params, data.X)
    assert preds.shape == (8, 100, 3)


def test_fleet_matches_single_machine_training():
    """A one-machine fleet must learn comparably to the single-model path."""
    t = np.linspace(0, 20, 200)
    X = np.stack([np.sin(t), np.cos(t), np.sin(2 * t)], axis=1).astype("float32")

    single = AutoEncoder(kind="feedforward_hourglass", epochs=20, batch_size=16, seed=0)
    single.fit(X, X)
    single_loss = single.get_metadata()["history"]["loss"][-1]

    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec)
    data = StackedData.from_ragged([X], [X.copy()])
    keys = trainer.machine_keys(1, seed=0)
    params, losses = trainer.fit(data, keys, epochs=20, batch_size=16)
    fleet_loss = float(losses[-1, 0])

    fleet_pred = trainer.predict(params, data.X)[0]
    assert fleet_pred.shape == single.predict(X).shape
    # same architecture/optimizer/data; different PRNG streams -> training
    # curves should land in the same regime
    assert fleet_loss < max(2 * single_loss, 0.05)
    assert losses[-1, 0] < losses[0, 0]


def test_fleet_step_count_matches_solo_on_padded_grid():
    """
    Timestep-grid padding must NOT inflate the per-epoch optimizer-step
    count. Each batch's loss is normalized by its own weight sum, so every
    extra batch is a full-magnitude Adam step: before the sample-cap fix,
    288 real rows on a 512-row grid trained ceil(512/32)=16 steps/epoch
    vs the solo path's ceil(288/32)=9 — the fleet silently trained ~1.8x
    the configured budget (measured: fleet reconstruction MAE 0.246 vs
    solo 0.393 on the same machine). With identical init keys the two
    paths' loss trajectories must now coincide (residual difference =
    shuffle-stream noise only).
    """
    from gordo_tpu.models.core import solo_init_key

    rng = np.random.default_rng(0)
    X = rng.random((288, 3)).astype("float32")

    single = AutoEncoder(kind="feedforward_hourglass", epochs=4, batch_size=32, seed=0)
    single.fit(X, X)
    solo_losses = np.asarray(single.history_["loss"])

    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec)
    data = StackedData.from_ragged([X], [X.copy()], n_timesteps=512)
    keys = np.stack([np.asarray(solo_init_key(0))])
    _, fleet_losses = trainer.fit(data, keys, epochs=4, batch_size=32)

    np.testing.assert_allclose(fleet_losses[:, 0], solo_losses, rtol=0.02)


@pytest.mark.slow
def test_fleet_windowed_lstm():
    from gordo_tpu.models.factories.lstm import lstm_model

    Xs, ys = make_fleet_data(m=2, n=60)
    data = StackedData.from_ragged(Xs, ys)
    spec = lstm_model(n_features=3, lookback_window=5)
    trainer = FleetTrainer(spec, lookahead=0)
    keys = trainer.machine_keys(2)
    params, losses = trainer.fit(data, keys, epochs=1, batch_size=16)
    preds = trainer.predict(params, data.X)
    assert preds.shape == (2, 60 - 5 + 1, 3)


@pytest.mark.slow
def test_fleet_predict_chunked_matches_direct():
    """Chunked windowed predict (n_out > batch_size) equals the direct path."""
    from gordo_tpu.models.factories.lstm import lstm_model

    Xs, ys = make_fleet_data(m=2, n=60)
    data = StackedData.from_ragged(Xs, ys)
    spec = lstm_model(n_features=3, lookback_window=5)
    trainer = FleetTrainer(spec, lookahead=0)
    keys = trainer.machine_keys(2)
    params, _ = trainer.fit(data, keys, epochs=1, batch_size=16)
    direct = trainer.predict(params, data.X)  # 56 windows <= default chunk
    chunked = trainer.predict(params, data.X, batch_size=9)  # 7 chunks, padded
    np.testing.assert_allclose(chunked, direct, rtol=1e-6, atol=1e-7)
    # compiled programs are cached per geometry (in the trainer's
    # ProgramCache under the "predict" namespace), not rebuilt per call
    def predict_programs():
        return [
            k for k in trainer._programs._entries if k[0] == "predict"
        ]

    assert len(predict_programs()) == 2
    trainer.predict(params, data.X, batch_size=9)
    assert len(predict_programs()) == 2
    # direct-path programs don't depend on batch_size: one shared entry
    trainer.predict(params, data.X, batch_size=4096)
    assert len(predict_programs()) == 2
    with pytest.raises(ValueError, match="batch_size"):
        trainer.predict(params, data.X, batch_size=0)


def test_fleet_early_stopping_masks_per_machine():
    """A stopped machine's params freeze while the rest keep training."""
    import jax

    Xs, ys = make_fleet_data(m=2, n=80)
    data = StackedData.from_ragged(Xs, ys)
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec, donate=False)
    keys = trainer.machine_keys(2)

    # huge min_delta: machine losses "never improve" after epoch 0, so with
    # patience=2 everything stops at epoch 2 and the loop ends early
    params, losses = trainer.fit(
        data,
        keys,
        epochs=20,
        batch_size=16,
        early_stopping_patience=2,
        early_stopping_min_delta=1e6,
    )
    assert losses.shape[0] == 3  # improve@0, wait@1, stop@2

    # params must be EXACTLY frozen from the stopping epoch: identical to a
    # plain fit that trains only the epochs the machine was active for.
    # (adam momentum / penalties would otherwise keep drifting them, which
    # zero-loss-weight masking alone cannot prevent)
    frozen = trainer.fit(
        data, keys, epochs=3, batch_size=16,
        # stopped after epoch 2 ran; params from epochs 0-2 are kept
    )[0]
    for es_leaf, plain_leaf in zip(
        jax.tree.leaves(params), jax.tree.leaves(frozen)
    ):
        np.testing.assert_array_equal(
            np.asarray(es_leaf), np.asarray(plain_leaf)
        )

    # per-machine: a machine on constant data plateaus and stops while its
    # fleet-mate keeps improving; its reported loss freezes at the last
    # active value (not 0), and the mate's keeps falling
    X_flat = np.full((60, 3), 0.5, dtype="float32")
    t = np.linspace(0, 6, 60)
    X_sig = np.stack([np.sin(t + i) for i in range(3)], 1).astype("float32")
    d2 = StackedData.from_ragged([X_flat, X_sig], [X_flat.copy(), X_sig.copy()])
    # min_delta=1e-2: the flat machine's per-epoch improvement decays
    # through 0.01 around epoch 9 while the signal machine's stays ~2x
    # above it for all 30 epochs — a wide margin either side, where the
    # original 1e-3 threshold was never crossed within the budget and the
    # scenario silently degenerated to no machine stopping
    p2, l2 = trainer.fit(
        d2, keys, epochs=30, batch_size=16,
        early_stopping_patience=1, early_stopping_min_delta=1e-2,
    )
    m0 = l2[:, 0]
    # frozen reported losses repeat the last active value exactly
    assert m0[-1] == m0[-2]
    assert m0[-1] > 0
    # the still-active machine improved after machine 0 froze
    assert l2[-1, 1] < l2[np.argmax(m0 == m0[-1]), 1]


def test_fleet_restore_best_weights():
    """With a diverging optimizer the restored params are the best epoch's,
    not the (worse) stopping epoch's — per machine, on device."""
    import jax
    import optax

    Xs, ys = make_fleet_data(m=2, n=80)
    data = StackedData.from_ragged(Xs, ys)
    spec = feedforward_hourglass(n_features=3)

    def run(restore):
        trainer = FleetTrainer(
            spec, donate=False, optimizer=optax.sgd(2.0)  # diverges
        )
        keys = trainer.machine_keys(2)
        params, losses = trainer.fit(
            data,
            keys,
            epochs=8,
            batch_size=16,
            early_stopping_patience=2,
            restore_best_weights=restore,
        )
        preds = trainer.predict(params, data.X)
        mse = ((preds - np.asarray(jax.device_get(data.y))) ** 2).mean(axis=(1, 2))
        return losses, mse

    losses, mse_restored = run(True)
    _, mse_final = run(False)
    # sanity: training really degraded after its best epoch
    assert (losses.min(axis=0) < losses[-1]).all(), losses
    # restored params reconstruct better than the stopping epoch's params
    assert (mse_restored < mse_final).all(), (mse_restored, mse_final)


@pytest.mark.parametrize("start_from", [0, 3])
def test_early_stopping_start_from_epoch_and_restore_best(start_from):
    """``early_stopping_start_from_epoch`` with ``restore_best_weights``
    and a split: nothing is decided before the start epoch, each machine
    stops ``patience`` epochs after its best monitored epoch, and leaves
    with that epoch's parameters."""
    Xs, ys = make_fleet_data(m=3, n=100)
    data = StackedData.from_ragged(Xs, ys)
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec, donate=False)
    keys = trainer.machine_keys(3)
    es = dict(
        batch_size=16,
        early_stopping_patience=2,
        early_stopping_min_delta=1e6,  # only the first decision improves
        early_stopping_start_from_epoch=start_from,
        validation_split=0.25,
    )
    params, losses = trainer.fit(
        data, keys, epochs=12, restore_best_weights=True, **es
    )
    # improve@start_from, wait, stop: had epochs before start_from been
    # judged, the fleet would have stopped at epoch 2 whatever start_from
    assert losses.shape[0] == start_from + 3
    assert trainer.val_losses_.shape == (start_from + 3, 3)
    assert np.isfinite(trainer.val_losses_).all()
    assert trainer.fit_telemetry_["early_stop_epoch"] == start_from + 2
    assert trainer.fit_telemetry_["n_machines_early_stopped"] == 3
    # every machine's best monitored epoch is start_from: the restored
    # parameters are those the same program holds after that epoch
    best_params, best_losses = trainer.fit(
        data, keys, epochs=start_from + 1, **es
    )
    np.testing.assert_array_equal(losses[: start_from + 1], best_losses)
    for restored, best in zip(
        jax.tree.leaves(params), jax.tree.leaves(best_params)
    ):
        np.testing.assert_array_equal(np.asarray(restored), np.asarray(best))


FIT_TELEMETRY_READ_BY_THE_BENCHMARK = (
    # chipbench/layer_metrics/*.py: steady_epoch_ms, dispatch_overhead_ms,
    # fit_prepare_ms, fit_collect_ms
    "steady_state_epoch_s", "dispatch_overhead_s", "n_dispatches",
    "prepare_s", "collect_s", "report_s",
)


@pytest.mark.parametrize("early_stopping", [False, True], ids=["plain", "early-stopping"])
def test_fit_telemetry_carries_what_the_benchmark_reads(early_stopping):
    """``fit_telemetry_`` of a plain and of an early-stopping fit carries
    every key ``chipbench/layer_metrics`` reads, as numbers, with one
    dispatch an epoch."""
    Xs, ys = make_fleet_data(m=2, n=80)
    data = StackedData.from_ragged(Xs, ys)
    trainer = FleetTrainer(feedforward_hourglass(n_features=3), donate=False)
    es = {"early_stopping_patience": 100} if early_stopping else {}
    trainer.fit(data, trainer.machine_keys(2), epochs=4, batch_size=16, **es)
    telemetry = trainer.fit_telemetry_
    for key in FIT_TELEMETRY_READ_BY_THE_BENCHMARK:
        assert isinstance(telemetry[key], (int, float)), (key, telemetry[key])
        assert telemetry[key] >= 0
    assert telemetry["n_dispatches"] == telemetry["epochs_run"] == 4
    assert telemetry["row_fetch"] == {"path": "gather", "epochs": 4}
    assert telemetry["first_epoch_s"] == telemetry["first_dispatch_s"] > 0
    # a plain fit syncs for its facts and for its history; early stopping
    # for its facts and once an epoch, its decision (which is the history)
    assert telemetry["n_host_syncs"] == (1 + 4 if early_stopping else 2)
    assert telemetry["decide_s"] > 0 if early_stopping else telemetry["decide_s"] == 0


def legacy_epoch_chunk_machine(with_key):
    """A machine config as PR 2 to PR 28 wrote them: ``epoch_chunk`` among
    the estimator's fit args."""
    fit_args = {"kind": "feedforward_hourglass", "epochs": 3, "batch_size": 16}
    if with_key:
        fit_args["epoch_chunk"] = 4
    return dict(
        name="legacy-m0",
        project_name="p",
        model={"gordo_tpu.models.AutoEncoder": fit_args},
        dataset={
            "type": "RandomDataset",
            "train_start_date": "2017-12-25 06:00:00Z",
            "train_end_date": "2017-12-26 06:00:00Z",
            "tags": [["Tag 1", None], ["Tag 2", None]],
        },
    )


@pytest.mark.parametrize("through", ["build-fleet", "sweep"])
def test_machine_config_with_an_epoch_chunk_fit_arg_still_builds(through):
    """An older machine config's ``epoch_chunk`` fit arg is accepted and
    ignored (``BaseJaxEstimator.supported_fit_args``): the key reaches no
    model factory, and the build and the sweep give what they give
    without it, bit for bit."""
    from gordo_tpu.builder.fleet_build import _find_jax_estimator

    results = []
    for with_key in (True, False):
        config = legacy_epoch_chunk_machine(with_key)
        if through == "build-fleet":
            ((model, _),) = FleetModelBuilder([Machine(**config)]).build()
            estimator = _find_jax_estimator(model)
            results.append(
                (np.asarray(estimator.history_["loss"]),
                 jax.tree.leaves(jax.device_get(estimator.params_)))
            )
        else:
            import json

            from click.testing import CliRunner

            from gordo_tpu.cli import gordo

            out = CliRunner().invoke(
                gordo,
                ["sweep", json.dumps(config), "--param", "lr=0.001,0.01"],
                catch_exceptions=False,
            )
            assert out.exit_code == 0, out.output
            results.append(
                [ln for ln in out.output.splitlines() if "loss=" in ln]
            )
    with_key, without = results
    if through == "build-fleet":
        np.testing.assert_array_equal(with_key[0], without[0])
        for a, b in zip(with_key[1], without[1]):
            np.testing.assert_array_equal(a, b)
    else:
        assert len(with_key) == 2 and with_key == without


def test_fleet_build_honors_early_stopping_config():
    """Machines configured with EarlyStopping train fewer epochs."""
    machine = Machine(
        name="es-m0",
        project_name="p",
        model={
            "gordo_tpu.models.AutoEncoder": {
                "kind": "feedforward_hourglass",
                "epochs": 40,
                "batch_size": 16,
                "callbacks": [
                    {
                        "keras.callbacks.EarlyStopping": {
                            "monitor": "loss",
                            "patience": 1,
                            "min_delta": 1000.0,
                        }
                    }
                ],
            }
        },
        dataset={
            "type": "RandomDataset",
            "train_start_date": "2017-12-25 06:00:00Z",
            "train_end_date": "2017-12-27 06:00:00Z",
            "tags": [["Tag 1", None], ["Tag 2", None]],
        },
    )
    (model, machine_out), = FleetModelBuilder([machine]).build()
    history = machine_out.metadata.build_metadata.model.model_meta["history"]
    # min_delta=1000 -> stop at epoch 1, far below the 40-epoch budget
    assert len(history["loss"]) == 2


def test_fleet_validation_split_exact_holdout():
    """validation_split must hold out exactly the last fraction of each
    machine's samples: training with the split equals training with a
    hand-built per-machine mask over the same rows (bit-identical params),
    and val losses land per machine per epoch."""
    import jax

    Xs, ys = make_fleet_data(m=2, n=100)  # real lengths 100, 95
    data = StackedData.from_ragged(Xs, ys)
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec, donate=False)
    keys = trainer.machine_keys(2)

    params_split, _ = trainer.fit(
        data, keys, epochs=2, batch_size=16, validation_split=0.25
    )
    assert trainer.val_losses_ is not None
    assert trainer.val_losses_.shape == (2, 2)
    assert np.isfinite(trainer.val_losses_).all()

    # hand-built equivalent: zero weight on the last 25% of REAL rows
    mask = np.ones((2, 100), dtype=np.float32)
    for i, x in enumerate(Xs):
        n_train = len(x) - int(len(x) * 0.25)
        mask[i, n_train:] = 0.0
    params_mask, _ = trainer.fit(
        data, keys, epochs=2, batch_size=16, extra_weight=mask
    )
    for a, b in zip(jax.tree.leaves(params_split), jax.tree.leaves(params_mask)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_validation_split_windowed_masks():
    """Windowed models: the train/val masks select exactly the sample split
    the solo path would (windows, not raw rows)."""
    from gordo_tpu.models.factories.lstm import lstm_model

    spec = lstm_model(n_features=3, lookback_window=5)
    trainer = FleetTrainer(spec, lookahead=0, donate=False)
    w = np.zeros((1, 60), dtype=np.float32)
    w[0, :50] = 1.0  # 50 real rows -> 46 windows
    rows, _ = jax.device_get(trainer._fit_facts(jnp.asarray(w)))
    assert rows.tolist() == [50]
    train_m, val_m, has_val, val_lo = trainer._validation_masks(
        jnp.asarray(w), rows, 0.25
    )
    assert train_m.dtype == val_m.dtype == jnp.float32
    train_m, val_m = np.asarray(train_m), np.asarray(val_m)
    assert has_val.tolist() == [True]
    assert val_lo == 35
    # 46 samples -> n_val=11, n_train=35; train windows need rows < 35+4
    assert train_m[0, :39].all() and not train_m[0, 39:].any()
    # val windows start at sample 35, inside the real region
    assert val_m[0, 35:50].all() and not val_m[0, :35].any()
    assert not val_m[0, 50:].any()


# -- what a fit learns of its weights, counted on the device -----------------
# The oracle is the host arithmetic ``fit`` used before the counts moved to
# the device: a float64 copy of the fetched (M, n) weights, numpy passes.


def oracle_facts(w_host, lookback=None, lookahead=0):
    """(rows, valid) per machine by the old numpy rule."""
    r = (np.asarray(w_host, dtype=np.float64) > 0).astype(np.int64)
    rows = r.sum(axis=1)
    if lookback is None:
        return rows, rows
    n_samples = r.shape[1] - lookback + 1 - lookahead
    c = np.concatenate(
        [np.zeros((r.shape[0], 1), dtype=np.int64), r.cumsum(axis=1)], axis=1
    )
    win_all = (c[:, lookback:] - c[:, :-lookback]) == lookback
    target = lookback - 1 + lookahead
    valid = win_all[:, :n_samples] & (r[:, target : target + n_samples] > 0)
    return rows, valid.sum(axis=1)


def oracle_validation_masks(w_host, validation_split, lookback=None, lookahead=0):
    """(train_mask, val_mask, has_val, val_lo) by the old numpy rule."""
    lb = lookback or 1
    w_host = np.asarray(w_host, dtype=np.float64)
    n_real = (w_host > 0).sum(axis=1).astype(np.int64)
    n_samples = np.maximum(n_real - lb + 1 - lookahead, 0)
    n_val = (n_samples * validation_split).astype(np.int64)
    n_train = n_samples - n_val
    t = np.arange(w_host.shape[1], dtype=np.int64)[None, :]
    train_cut = (n_train + lb - 1 + lookahead)[:, None]
    train_mask = (t < train_cut).astype(np.float32)
    val_mask = (t >= n_train[:, None]).astype(np.float32) * w_host.astype(
        np.float32
    )
    has_val = n_val > 0
    val_lo = int(n_train[has_val].min()) if has_val.any() else 0
    return train_mask, val_mask, has_val, val_lo


class OracleTrainer(FleetTrainer):
    """A trainer whose fit learns its facts and split masks the old way:
    the whole effective weights on the host, numpy in float64."""

    def _window(self):
        if not self.spec.windowed:
            return {}
        return {"lookback": self.spec.lookback_window, "lookahead": self.lookahead}

    def _fit_facts(self, w):
        rows, valid = oracle_facts(jax.device_get(w), **self._window())
        return rows.astype(np.int32), valid.astype(np.int32)

    def _validation_masks(self, w, rows, validation_split):
        train_mask, val_mask, has_val, val_lo = oracle_validation_masks(
            jax.device_get(w), validation_split, **self._window()
        )
        return (
            self._shard(jnp.asarray(train_mask)),
            self._shard(jnp.asarray(val_mask)),
            has_val,
            val_lo,
        )


FACTS_M, FACTS_N = 5, 200


def facts_weights(pattern):
    """Effective (M, n) float32 weights of one named shape."""
    w = np.ones((FACTS_M, FACTS_N), dtype=np.float32)
    if pattern == "ragged-prefix":
        for i in range(FACTS_M):
            w[i, FACTS_N - 31 * i :] = 0.0
    elif pattern == "holes":
        w[0, 50:53] = 0.0
        w[1, ::7] = 0.0
        w[2, 0] = 0.0
        w[3, -1] = 0.0
        w[4, 100:] = 0.0
        w[4, 150:160] = 1.0
    elif pattern == "weightless-machine":
        w[2] = 0.0
        w[3, 120:] = 0.0
    elif pattern == "fractional":
        w = np.random.default_rng(7).random((FACTS_M, FACTS_N)).astype(np.float32)
        w[w < 0.2] = 0.0
        w[1, 140:] = 0.0
    elif pattern == "fold-mask":
        # base ragged weights times a CV fold's train mask (extra_weight)
        w = facts_weights("ragged-prefix")
        w[:, 60:100] *= 0.0
    elif pattern == "broadcast":
        w = w[:1]
        w[0, 170:] = 0.0
        w[0, 20:24] = 0.0
    else:
        assert pattern == "all-ones", pattern
    return w


FACTS_PATTERNS = (
    "all-ones", "ragged-prefix", "holes", "weightless-machine", "fractional",
    "fold-mask", "broadcast",
)
FACTS_WINDOWS = (None, (1, 0), (1, 1), (8, 0), (8, 1), (64, 0), (64, 1))


@pytest.mark.parametrize("pattern", FACTS_PATTERNS)
@pytest.mark.parametrize(
    "window", FACTS_WINDOWS, ids=lambda w: "flat" if w is None else f"lb{w[0]}-la{w[1]}"
)
def test_device_weight_facts_equal_the_numpy_rule(window, pattern):
    """The two (M,) int32 count vectors a fit fetches are the old host
    arithmetic's, for any weight pattern and window."""
    from gordo_tpu.models.factories.lstm import lstm_model

    w = facts_weights(pattern)
    if window is None:
        trainer = FleetTrainer(feedforward_hourglass(n_features=3))
        want = oracle_facts(w)
    else:
        lookback, lookahead = window
        trainer = FleetTrainer(
            lstm_model(n_features=3, lookback_window=lookback),
            lookahead=lookahead,
        )
        want = oracle_facts(w, lookback=lookback, lookahead=lookahead)
    rows, valid = trainer._fit_facts(jnp.asarray(w))
    assert rows.dtype == valid.dtype == jnp.int32
    assert rows.shape == valid.shape == (w.shape[0],)
    np.testing.assert_array_equal(np.asarray(rows), want[0])
    np.testing.assert_array_equal(np.asarray(valid), want[1])


def test_fit_facts_refuse_a_grid_too_short_for_one_window():
    from gordo_tpu.models.factories.lstm import lstm_model

    trainer = FleetTrainer(lstm_model(n_features=3, lookback_window=8), lookahead=1)
    with pytest.raises(ValueError, match="Not enough timesteps"):
        trainer._fit_facts(jnp.ones((2, 8)))


def ragged_fit_case(case):
    """(data, fit kwargs) of one named fit."""
    Xs, ys = make_fleet_data(m=3, n=90)
    data = StackedData.from_ragged(Xs, ys, n_timesteps=128)
    fit_kwargs = {}
    if case in ("extra-weight", "split-under-a-fold"):
        fold = np.ones((3, 128), dtype=np.float32)
        fold[:, 30:50] = 0.0
        fold[1, ::9] = 0.5  # fractional weights must not move any count
        fit_kwargs["extra_weight"] = fold
    if case in ("validation-split", "split-under-a-fold", "early-stopping-split"):
        fit_kwargs["validation_split"] = 0.2
    if case == "early-stopping-split":
        # the split's validation loss decides: improve at epoch 0, stop at 1
        fit_kwargs.update(
            early_stopping_patience=1, early_stopping_min_delta=1e6,
            restore_best_weights=True,
        )
    return data, fit_kwargs


@pytest.mark.parametrize(
    "case",
    [
        "ragged", "extra-weight", "validation-split", "split-under-a-fold",
        "early-stopping-split",
    ],
)
def test_fit_is_bit_identical_to_a_fit_from_host_side_facts(case):
    """Counting on the device changes no number a fit returns: parameters,
    losses and ``val_losses_`` equal those of a fit driven by the old host
    arithmetic (whole weights fetched, float64 numpy, masks uploaded)."""
    data, fit_kwargs = ragged_fit_case(case)
    spec = feedforward_hourglass(n_features=3)
    results = []
    for cls in (FleetTrainer, OracleTrainer):
        trainer = cls(spec, donate=False)
        params, losses = trainer.fit(
            data, trainer.machine_keys(3), epochs=3, batch_size=16, **fit_kwargs
        )
        results.append((jax.device_get(params), losses, trainer.val_losses_,
                        trainer.fit_telemetry_["sensor_timesteps_trained"]))
    (p_new, l_new, v_new, t_new), (p_old, l_old, v_old, t_old) = results
    for a, b in zip(jax.tree.leaves(p_new), jax.tree.leaves(p_old)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(l_new, l_old)
    assert t_new == t_old
    if case == "early-stopping-split":
        assert l_new.shape[0] == 2  # the validation loss stopped the fleet
    if "validation_split" in fit_kwargs:
        np.testing.assert_array_equal(v_new, v_old)
        assert np.isfinite(v_new).all()
    else:
        assert v_new is None and v_old is None


@pytest.mark.parametrize("lookahead", [0, 1])
def test_windowed_split_masks_equal_the_numpy_rule(lookahead):
    """``_validation_masks`` builds on the device, bit for bit, the masks
    the host rule built: ragged prefixes, fractional weights, a machine
    too small for any validation sample, a weightless one."""
    from gordo_tpu.models.factories.lstm import lstm_model

    lb = 5
    w = np.zeros((5, 60), dtype=np.float32)
    w[0, :50] = 1.0
    w[1, :60] = np.linspace(0.1, 1.0, 60, dtype=np.float32)
    w[2, :lb + lookahead + 1] = 1.0   # two samples: int(2 * 0.25) == 0 held out
    w[4, :33] = 0.5                   # machine 3 stays weightless
    trainer = FleetTrainer(
        lstm_model(n_features=3, lookback_window=lb), lookahead=lookahead,
        donate=False,
    )
    rows, _ = jax.device_get(trainer._fit_facts(jnp.asarray(w)))
    train_m, val_m, has_val, val_lo = trainer._validation_masks(
        jnp.asarray(w), rows, 0.25
    )
    want = oracle_validation_masks(w, 0.25, lookback=lb, lookahead=lookahead)
    np.testing.assert_array_equal(np.asarray(train_m), want[0])
    np.testing.assert_array_equal(np.asarray(val_m), want[1])
    np.testing.assert_array_equal(has_val, want[2])
    assert has_val.tolist() == [True, True, False, False, True]
    assert val_lo == want[3]


def test_validation_split_that_leaves_no_training_samples_is_refused():
    """The refusal is host arithmetic on the fetched counts: a machine with
    samples, all of them held out. (``fit`` itself admits no split of 1.)"""
    trainer = FleetTrainer(feedforward_hourglass(n_features=3), donate=False)
    with pytest.raises(ValueError, match="leaves no training samples"):
        trainer._validation_masks(jnp.ones((2, 20)), np.array([20, 2]), 1.0)


def test_fleet_val_monitored_early_stopping():
    """val-loss-monitored early stopping stops on validation plateau and
    restores best-val params per machine (Keras parity for the solo path's
    EarlyStopping(monitor='val_loss', restore_best_weights=True))."""
    t = np.linspace(0, 20, 160)
    X = np.stack([np.sin(t), np.cos(t), np.sin(2 * t)], axis=1).astype("float32")
    data = StackedData.from_ragged([X], [X.copy()])
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec, donate=False)
    keys = trainer.machine_keys(1)

    params, losses = trainer.fit(
        data,
        keys,
        epochs=40,
        batch_size=16,
        validation_split=0.25,
        early_stopping_patience=1,
        early_stopping_min_delta=1e6,  # "never improves" -> stop fast
        restore_best_weights=True,
    )
    # improve@0 (first monitored), wait@1, stop@1 -> 2 epochs ran
    assert losses.shape[0] == 2
    assert trainer.val_losses_.shape[0] == 2


def test_fleet_validation_split_tiny_machine_falls_back_to_loss():
    """A machine too small for any validation samples must monitor its
    TRAINING loss (solo n_val==0 semantics), not a constant-0.0 val loss
    that would spuriously early-stop it at epoch 0; its val_loss history
    column is NaN (= absent)."""
    t = np.linspace(0, 20, 120)
    X_big = np.stack([np.sin(t), np.cos(t), np.sin(2 * t)], axis=1).astype(
        "float32"
    )
    X_tiny = X_big[:3]  # 3 rows -> int(3 * 0.25) == 0 validation samples
    data = StackedData.from_ragged(
        [X_big, X_tiny], [X_big.copy(), X_tiny.copy()]
    )
    spec = feedforward_hourglass(n_features=3)
    trainer = FleetTrainer(spec, donate=False)
    keys = trainer.machine_keys(2)

    params, losses = trainer.fit(
        data,
        keys,
        epochs=6,
        batch_size=16,
        validation_split=0.25,
        early_stopping_patience=4,
        early_stopping_min_delta=0.0,
    )
    # the tiny machine kept training (its train loss improves epoch over
    # epoch, so with patience=4 nothing stops within 6 epochs)
    assert losses.shape[0] == 6
    assert not np.isnan(trainer.val_losses_[:, 0]).any()
    assert np.isnan(trainer.val_losses_[:, 1]).all()


def test_early_stopping_kwargs_translation():
    """Solo EarlyStopping configs translate to the fleet gate, including
    val_loss monitors when a validation_split is configured (no silent
    train-loss substitution)."""
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    translate = FleetModelBuilder._early_stopping_kwargs

    with_val = translate(
        {
            "validation_split": 0.2,
            "callbacks": [
                {
                    "keras.callbacks.EarlyStopping": {
                        "monitor": "val_loss",
                        "patience": 3,
                        "restore_best_weights": True,
                    }
                }
            ],
        }
    )
    assert with_val["validation_split"] == 0.2
    assert with_val["early_stopping_patience"] == 3
    assert with_val["restore_best_weights"] is True
    assert with_val["early_stopping_on_val"] is True

    # monitor=val_loss with NO split: Keras falls back to training loss
    no_split = translate(
        {
            "callbacks": [
                {"keras.callbacks.EarlyStopping": {"monitor": "val_loss"}}
            ]
        }
    )
    assert "validation_split" not in no_split
    assert no_split["early_stopping_on_val"] is False

    # a split with no callback still holds out the data (training parity)
    just_split = translate({"validation_split": 0.1})
    assert just_split == {"validation_split": 0.1}


def test_fleet_build_val_loss_early_stopping(tmp_path):
    """End-to-end: a machine configured with validation_split + val_loss
    EarlyStopping fleet-builds with val_loss history and an early stop."""
    machine = Machine(
        name="es-val-m0",
        project_name="p",
        model={
            "gordo_tpu.models.AutoEncoder": {
                "kind": "feedforward_hourglass",
                "epochs": 40,
                "batch_size": 16,
                "validation_split": 0.25,
                "callbacks": [
                    {
                        "keras.callbacks.EarlyStopping": {
                            "monitor": "val_loss",
                            "patience": 1,
                            "min_delta": 1000.0,
                        }
                    }
                ],
            }
        },
        dataset={
            "type": "RandomDataset",
            "train_start_date": "2017-12-25 06:00:00Z",
            "train_end_date": "2017-12-27 06:00:00Z",
            "tags": [["Tag 1", None], ["Tag 2", None]],
        },
    )
    (model, machine_out), = FleetModelBuilder([machine]).build()
    history = machine_out.metadata.build_metadata.model.model_meta["history"]
    assert len(history["loss"]) == 2  # stopped far below the 40-epoch budget
    assert len(history["val_loss"]) == 2
    assert "val_loss" in history["params"]["metrics"]


def make_machines(n, epochs=2):
    return [
        Machine(
            name=f"machine-{i}",
            model={
                "gordo_tpu.models.anomaly.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        "sklearn.pipeline.Pipeline": {
                            "steps": [
                                "sklearn.preprocessing.MinMaxScaler",
                                {
                                    "gordo_tpu.models.AutoEncoder": {
                                        "kind": "feedforward_hourglass",
                                        "epochs": epochs,
                                    }
                                },
                            ]
                        }
                    }
                }
            },
            dataset={
                "type": "RandomDataset",
                "train_start_date": "2017-12-25 06:00:00Z",
                "train_end_date": "2017-12-27 06:00:00Z",
                "tags": [[f"Tag {t}", None] for t in range(3)],
            },
            project_name="fleet-proj",
        )
        for i in range(n)
    ]


def test_bucket_machines():
    machines = make_machines(4)
    buckets = bucket_machines(machines)
    assert len(buckets) == 1
    (key, bucket), = buckets.items()
    assert len(bucket) == 4


def test_fleet_model_builder_end_to_end(tmp_path):
    machines = make_machines(3)
    builder = FleetModelBuilder(machines, mesh=get_device_mesh())
    results = builder.build(output_dir_base=tmp_path)
    assert len(results) == 3
    for (model, machine), orig in zip(results, machines):
        assert machine.name == orig.name
        # anomaly thresholds calibrated per machine
        assert model.feature_thresholds_ is not None
        assert model.aggregate_threshold_ is not None
        scores = machine.metadata.build_metadata.model.cross_validation.scores
        assert "explained-variance-score" in scores
        # artifact saved and loadable
        from gordo_tpu import serializer

        loaded = serializer.load(tmp_path / machine.name)
        idx = np.random.default_rng(0).random((10, 3)).astype("float32")
        assert loaded.predict(idx).shape == (10, 3)


def reconstruction_mae(model, machine):
    """Window-aligned MAE of a built model on its own training data."""
    from gordo_tpu.data import _get_dataset

    X, y = _get_dataset(machine.dataset.to_dict()).get_data()
    predicted = model.predict(X)
    target = np.asarray(y)[-len(predicted):]
    return float(np.abs(np.asarray(predicted) - target).mean())


def test_fleet_solo_build_quality_parity():
    """
    The SAME machine built solo (ModelBuilder) and via FleetModelBuilder
    must reach reconstruction MAE within 10% of each other on its own
    training data — the fleet path's product promise. (Round-3 regression:
    fleet 0.246 vs solo 0.393, a 60% gap from grid-padding step inflation
    plus divergent init keys; measured post-fix difference is ~0.1%.)
    """
    from gordo_tpu.builder.build_model import ModelBuilder

    fleet_model, fleet_machine = FleetModelBuilder(make_machines(1, epochs=3)).build()[0]
    solo_model, solo_machine = ModelBuilder(make_machines(1, epochs=3)[0]).build()

    fleet_mae = reconstruction_mae(fleet_model, fleet_machine)
    solo_mae = reconstruction_mae(solo_model, solo_machine)
    assert abs(fleet_mae - solo_mae) <= 0.10 * solo_mae
    # and the training histories themselves must be in the same regime
    from gordo_tpu.builder.fleet_build import _find_jax_estimator

    fleet_loss = _find_jax_estimator(fleet_model).history_["loss"]
    solo_loss = _find_jax_estimator(solo_model).history_["loss"]
    np.testing.assert_allclose(fleet_loss, solo_loss, rtol=0.10)


@pytest.mark.parametrize(
    "model_cls, kind",
    [
        # lookahead-0 reconstructor and the fused-GRU family: window counts
        # interact with batch packing, so these have step-count-sensitive
        # semantics of their own beyond the feedforward case pinned above
        ("gordo_tpu.models.LSTMAutoEncoder", "lstm_hourglass"),
        ("gordo_tpu.models.GRUAutoEncoder", "gru_hourglass"),
    ],
)
@pytest.mark.slow
def test_fleet_solo_build_quality_parity_windowed(model_cls, kind):
    """
    Same contract as test_fleet_solo_build_quality_parity, for the windowed
    families (reference builds every family through the one path,
    gordo/builder/build_model.py:160-303): the SAME machine built solo and
    via the fleet must agree on reconstruction MAE (<=10%) and loss regime.
    """
    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.builder.fleet_build import _find_jax_estimator

    def make_machine():
        return Machine(
            name="windowed-parity",
            model={
                "gordo_tpu.models.anomaly.DiffBasedAnomalyDetector": {
                    "base_estimator": {
                        model_cls: {
                            "kind": kind,
                            "lookback_window": 6,
                            "epochs": 3,
                        }
                    }
                }
            },
            dataset={
                "type": "RandomDataset",
                "train_start_date": "2017-12-25 06:00:00Z",
                "train_end_date": "2017-12-26 06:00:00Z",
                "tags": [[f"Tag {t}", None] for t in range(3)],
            },
            project_name="fleet-proj",
        )

    fleet_model, fleet_machine = FleetModelBuilder([make_machine()]).build()[0]
    solo_model, solo_machine = ModelBuilder(make_machine()).build()

    fleet_mae = reconstruction_mae(fleet_model, fleet_machine)
    solo_mae = reconstruction_mae(solo_model, solo_machine)
    assert abs(fleet_mae - solo_mae) <= 0.10 * solo_mae
    fleet_loss = _find_jax_estimator(fleet_model).history_["loss"]
    solo_loss = _find_jax_estimator(solo_model).history_["loss"]
    np.testing.assert_allclose(fleet_loss, solo_loss, rtol=0.10)


def test_fleet_builder_fallback_non_jax(tmp_path):
    machines = [
        Machine(
            name="sk-machine",
            model={"sklearn.decomposition.PCA": {"n_components": 2}},
            dataset={
                "type": "RandomDataset",
                "train_start_date": "2017-12-25 06:00:00Z",
                "train_end_date": "2017-12-26 06:00:00Z",
                "tags": [["Tag 0", None], ["Tag 1", None]],
            },
            project_name="fleet-proj",
        )
    ]
    results = FleetModelBuilder(machines).build()
    model, machine = results[0]
    assert machine.metadata.build_metadata.model.model_offset == 0


@pytest.mark.parametrize(
    "n,window", [(100, 1), (100, 3), (64, 64), (517, 37), (16384, 64)]
)
def test_sliding_window_min_equals_reduce_window(n, window):
    """The windowed sample weights' log-step sliding minimum is bit for
    bit the reduce_window it replaced (which cost the TPU compiler ~125 s
    at the flagship 16,384 x 64), for powers of two and not, fractional
    weights included."""
    import jax.numpy as jnp

    from gordo_tpu.parallel.fleet import _sliding_window_min

    rng = np.random.default_rng(n + window)
    w = jnp.asarray(
        (rng.random(n) * (rng.random(n) > 0.2)).astype("float32")
    )
    reference = jax.lax.reduce_window(
        w, jnp.inf, jax.lax.min, (window,), (1,), "valid"
    )
    got = _sliding_window_min(w, window)
    assert got.shape == reference.shape == (n - window + 1,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(reference))


def test_bucket_unstack_uses_one_bulk_transfer(monkeypatch):
    """Param unstacking must stay ONE device_get per bucket: the
    per-machine-per-leaf variant pays ~2,800 roundtrips for a
    200-machine build."""
    import jax
    import jax.numpy as jnp

    from gordo_tpu.parallel.fleet import FleetTrainer

    calls = {"n": 0}
    real_device_get = jax.device_get

    def counting_device_get(tree):
        calls["n"] += 1
        return real_device_get(tree)

    monkeypatch.setattr(jax, "device_get", counting_device_get)
    stacked = {"w": jnp.ones((16, 4, 4)), "b": jnp.zeros((16, 4))}
    out = FleetTrainer.unstack_all(stacked, 16)
    assert calls["n"] == 1
    assert len(out) == 16 and out[3]["w"].shape == (4, 4)


@pytest.mark.slow
def test_fleet_offset_matches_solo_build():
    """model_offset is window arithmetic, identical for every machine in a
    bucket — the fleet builder probes it once per bucket; it must equal
    what a solo build of the same machine reports (lookback-1 for an
    LSTM-AE, 0 for the feedforward path)."""
    from gordo_tpu.builder.build_model import ModelBuilder

    lookback = 6
    machines = [
        Machine(
            name=f"off-m{i}",
            model={
                "gordo_tpu.models.LSTMAutoEncoder": {
                    "kind": "lstm_hourglass",
                    "lookback_window": lookback,
                    "epochs": 1,
                }
            },
            dataset={
                "type": "RandomDataset",
                "train_start_date": "2017-12-25 06:00:00Z",
                "train_end_date": "2017-12-26 06:00:00Z",
                "tags": [[f"Tag {t}", None] for t in range(3)],
            },
            project_name="t",
        )
        for i in range(3)
    ]
    fleet_results = FleetModelBuilder(machines).build()
    solo_model, solo_machine = ModelBuilder(machines[0]).build()

    solo_offset = solo_machine.metadata.build_metadata.model.model_offset
    assert solo_offset == lookback - 1
    for _model, machine in fleet_results:
        assert (
            machine.metadata.build_metadata.model.model_offset == solo_offset
        )


def test_fleet_build_rejects_machine_too_short_for_window():
    """A machine whose (resampled) data cannot fill one lookback window
    must fail the build loudly and by name — regardless of its position
    in the bucket — not train under masks and crash at serve time."""
    from gordo_tpu.data.base import InsufficientDataError

    def lstm_machine(name, hours):
        return Machine(
            name=name,
            model={
                "gordo_tpu.models.LSTMAutoEncoder": {
                    "kind": "lstm_hourglass",
                    "lookback_window": 12,
                    "epochs": 1,
                }
            },
            dataset={
                "type": "RandomDataset",
                "train_start_date": "2017-12-25 06:00:00Z",
                "train_end_date": f"2017-12-25 {6 + hours:02d}:00:00Z",
                "tags": [[f"Tag {t}", None] for t in range(3)],
            },
            project_name="t",
        )

    # second machine: 1 hour of 10-min samples = ~6 rows < lookback 12
    machines = [lstm_machine("long-enough", 12), lstm_machine("too-short", 1)]
    with pytest.raises(InsufficientDataError, match="too-short"):
        FleetModelBuilder(machines).build()


def test_fleet_built_detector_records_cv_mode(tmp_path):
    """Fleet-built anomaly detectors record their CV mode in metadata
    (cv-fleet-masks), the fleet counterpart of the solo cv-fast-path
    observability flag."""
    model, machine = FleetModelBuilder(make_machines(1, epochs=1)).build()[0]
    meta = model.get_metadata()
    assert meta.get("cv-fleet-masks") is True
    build_meta = machine.metadata.build_metadata.model.model_meta
    assert build_meta.get("cv-fleet-masks") is True


@pytest.mark.slow
def test_fleet_build_crash_resume(tmp_path):
    """Artifacts flush per bucket, and resume=True reuses them: a runtime
    crash mid-build (a TPU worker dying UNAVAILABLE under a
    1000-machine build) costs only the in-flight bucket on the re-run."""
    machines = make_machines(2)
    # second bucket: distinct tag count -> distinct (n_features) geometry
    wide_template = make_machines(1)[0].to_dict()
    extra = []
    for i in range(2):
        cfg = dict(wide_template)
        cfg["name"] = f"machine-wide-{i}"
        cfg["dataset"] = dict(cfg["dataset"])
        cfg["dataset"]["tags"] = [[f"Tag {t}", None] for t in range(4)]
        extra.append(Machine.from_dict(cfg))
    machines = machines + extra
    assert len(bucket_machines(machines)) == 2

    class CrashAfterFirstBucket(FleetModelBuilder):
        calls = 0

        def _build_bucket(self, bucket):
            type(self).calls += 1
            if type(self).calls == 2:
                raise RuntimeError("TPU worker process crashed or restarted")
            return super()._build_bucket(bucket)

    crashing = CrashAfterFirstBucket(machines)
    with pytest.raises(RuntimeError, match="crashed or restarted"):
        crashing.build(output_dir_base=tmp_path)

    # the completed bucket's artifacts were flushed before the crash
    flushed = sorted(p.name for p in tmp_path.iterdir())
    assert len(flushed) == 2, flushed

    class CountingBuilder(FleetModelBuilder):
        calls = 0

        def _build_bucket(self, bucket):
            type(self).calls += 1
            return super()._build_bucket(bucket)

    results = CountingBuilder(machines).build(
        output_dir_base=tmp_path, resume=True
    )
    assert CountingBuilder.calls == 1  # only the crashed bucket rebuilt
    assert [m.name for _, m in results] == [m.name for m in machines]
    for model, machine in results:
        # resumed machines carry their stored build metadata
        scores = machine.metadata.build_metadata.model.cross_validation.scores
        assert "explained-variance-score" in scores
        assert model.aggregate_threshold_ is not None


def test_fleet_build_resume_requires_output_dir():
    with pytest.raises(ValueError, match="output_dir_base"):
        FleetModelBuilder(make_machines(1)).build(resume=True)


@pytest.mark.slow
def test_fleet_build_resume_rejects_changed_config(tmp_path):
    """--resume must rebuild a machine whose stored artifact was built
    from a different model/dataset config (identity check, like the
    reference's sha3-keyed cache) instead of silently reusing it."""
    FleetModelBuilder(make_machines(1, epochs=2)).build(output_dir_base=tmp_path)

    changed = make_machines(1, epochs=3)  # different configured budget

    class CountingBuilder(FleetModelBuilder):
        calls = 0

        def _build_bucket(self, bucket):
            type(self).calls += 1
            return super()._build_bucket(bucket)

    results = CountingBuilder(changed).build(output_dir_base=tmp_path, resume=True)
    assert CountingBuilder.calls == 1  # rebuilt, not reused
    assert len(results) == 1
