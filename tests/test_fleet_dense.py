"""
The fused step loop (``ops/fleet_dense.py`` and the trainer's ``step_loop``
paths): fits whose epochs run their steps in the kernel, interpreted here,
against the same fits on the scan within 1e-6 in float32; a machine that
goes non-finite beside others in its block; and the rule that picks a path.

The choosers pick the kernels on a TPU only, and the step kernel for
batches of 128 rows and more, so the tests steer them HERE (``steer``), not
through a program option: the row fetch's chooser is asked with the backend
answered as the chip would, and only while it asks, so that the kernels
themselves still find the CPU and run interpreted; the step loop's is
answered, or asked so too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.factories.feedforward import (
    feedforward_hourglass,
    feedforward_model,
)
from gordo_tpu.models.factories.lstm import lstm_model
from gordo_tpu.ops import fleet_dense
from gordo_tpu.parallel import FleetTrainer, StackedData

TOLERANCE = 1e-6


def stacked(m=5, n=128, f=6, ragged=False, seed=0, f_out=None):
    """A fleet of ``m`` machines (``ragged``: machine i has fewer rows,
    ``f_out``: machine i's targets narrower, padded to the widest)."""
    rng = np.random.default_rng(seed)
    Xs, ys = [], []
    for i in range(m):
        rows = n - 7 * i if ragged else n
        x = rng.standard_normal((rows, f)).astype("float32")
        Xs.append(x)
        ys.append(x[:, : f - i % 2] if f_out else x.copy())
    return StackedData.from_ragged(Xs, ys, n_features_out=f_out)


def steer(monkeypatch, step_loop=None):
    """The row fetch's chooser answers as on a TPU, and only while it asks;
    the step loop's is asked so too, or answers ``step_loop`` (the tests'
    batches are smaller than the 128 rows the chooser asks for)."""

    def as_on_a_tpu(real):
        def ask(self, *args):
            with monkeypatch.context() as patch:
                patch.setattr(jax, "default_backend", lambda: "tpu")
                return real(self, *args)
        return ask

    monkeypatch.setattr(
        FleetTrainer, "_choose_row_fetch", as_on_a_tpu(FleetTrainer._choose_row_fetch)
    )
    monkeypatch.setattr(
        FleetTrainer, "_choose_step_loop",
        as_on_a_tpu(FleetTrainer._choose_step_loop) if step_loop is None
        else lambda *args: step_loop,
    )


def fit(monkeypatch, step_loop, data, spec=None, params=None, **fit_kwargs):
    """(params, losses, trainer) of one fit on the permuting fetch, its
    epochs' steps on ``step_loop``."""
    spec = spec or feedforward_hourglass(n_features=data.X.shape[-1])
    fit_kwargs = dict({"epochs": 3, "batch_size": 16}, **fit_kwargs)
    with monkeypatch.context() as patch:
        steer(patch, step_loop)
        trainer = FleetTrainer(spec)
        keys = trainer.machine_keys(data.n_machines, seed=3)
        if params is not None:
            params = jax.tree.map(jnp.copy, params)
        out, losses = trainer.fit(data, keys, params=params, **fit_kwargs)
    return jax.device_get(out), np.asarray(losses), trainer


def fit_both_ways(monkeypatch, data, spec=None, **fit_kwargs):
    scanned = fit(monkeypatch, "scan", data, spec, **fit_kwargs)
    fused = fit(monkeypatch, "fused", data, spec, **fit_kwargs)
    return scanned, fused


def assert_close_fits(scanned, fused, epochs=3):
    (p_s, l_s, t_s), (p_f, l_f, t_f) = scanned, fused
    for trainer in (t_s, t_f):
        assert trainer.fit_telemetry_["row_fetch"]["path"] == "permute_epoch"
    assert t_s.fit_telemetry_["step_loop"] == {"path": "scan", "epochs": epochs}
    assert t_f.fit_telemetry_["step_loop"] == {"path": "fused", "epochs": epochs}
    assert t_s.fit_telemetry_["fused_step_share"] == 0.0
    assert t_f.fit_telemetry_["fused_step_share"] == 1.0
    np.testing.assert_allclose(l_f, l_s, rtol=TOLERANCE, atol=TOLERANCE)
    for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_f)):
        np.testing.assert_allclose(b, a, rtol=TOLERANCE, atol=TOLERANCE)


# -- fits in the kernel against fits on the scan -----------------------------


def test_fused_fit_at_the_tiny_preset(monkeypatch):
    """ff50.fit1000's tiny preset: 5 machines of 128 rows x 6 tags, the
    hourglass 5-4-3-3-4-5, batches of 32."""
    assert_close_fits(*fit_both_ways(monkeypatch, stacked(), batch_size=32))


def test_fused_fit_over_blocks_the_fleet_does_not_fill(monkeypatch):
    """11 machines in blocks of 8: the second block holds 3 machines and
    5 places past the fleet's end, which the kernel leaves alone."""
    monkeypatch.setattr(fleet_dense, "block_size", lambda *args, **kw: 8)
    assert_close_fits(*fit_both_ways(monkeypatch, stacked(m=11)))


def test_fused_fit_with_overflow_slots_and_a_step_of_padding(monkeypatch):
    """Ragged machines of 100, 93, 86, 79 rows in batches of 16: seven
    steps, 112 slots, the shorter machines' last slots and the last one's
    whole last batch weigh nothing, a step that must leave it unchanged."""
    data = stacked(m=4, n=100, ragged=True)
    w = np.asarray(data.sample_weight)
    assert w[3].sum() <= 6 * 16  # machine 3's seventh batch is all padding
    assert_close_fits(*fit_both_ways(monkeypatch, data))


def test_fused_fit_with_masked_feature_columns(monkeypatch):
    """A padded bucket: odd machines' targets are one tag narrower, their
    pad column masked out of the loss."""
    data = stacked(m=4, f_out=6)
    assert data.feature_out_weight is not None
    assert_close_fits(*fit_both_ways(monkeypatch, data))


@pytest.mark.parametrize("func", ["tanh", "relu", "linear"])
@pytest.mark.parametrize("loss", ["mse", "mae"])
def test_fused_fit_with_each_activation_and_loss(monkeypatch, func, loss):
    spec = feedforward_model(
        n_features=6, encoding_dim=(5, 4), encoding_func=(func, func),
        decoding_dim=(4, 5), decoding_func=(func, func),
        compile_kwargs={"loss": loss},
    )
    assert_close_fits(*fit_both_ways(monkeypatch, stacked(m=3), spec))


def test_gated_epoch_leaves_an_early_stopped_machine_bit_unchanged(monkeypatch):
    """The epoch program itself, gated: machine 1 is inactive and its
    parameters and Adam state come out as they went in, bit for bit."""
    spec = feedforward_hourglass(n_features=6)
    trainer = FleetTrainer(spec)
    data = stacked(m=3)
    keys = trainer.machine_keys(3, seed=1)
    params = trainer.init_params(keys, 6)
    opt_state = trainer.init_opt_state(params)
    before = jax.device_get((params, opt_state))
    epoch = trainer._epoch_fn(
        data.n_timesteps, 16, True, gated=True,
        row_fetch="permute_epoch", step_loop="fused",
    )
    new_params, new_opt, _ = epoch(
        params, opt_state, keys, data.X, data.y, data.sample_weight,
        jnp.asarray([1.0, 0.0, 1.0]),
    )
    after = jax.device_get((new_params, new_opt))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a[1], b[1])
    moved = [
        not np.array_equal(a[0], b[0])
        for a, b in zip(jax.tree.leaves(before[0]), jax.tree.leaves(after[0]))
    ]
    assert all(moved)


@pytest.mark.parametrize("batch", [16, 128])
def test_a_poisoned_machine_leaves_its_block_neighbours_bit_equal(monkeypatch, batch):
    """NaN in one machine's rows: the machine is quarantined, and every
    other machine of its block trains to the bits of a fit in which the
    poisoned machine's rows were clean. No product mixes two machines,
    whether a grid step runs one batch or a tile of several."""
    clean = stacked(m=6, n=256)
    poisoned = StackedData(
        clean.X.at[2, 5, 1].set(jnp.nan), clean.y, clean.sample_weight
    )
    p_clean, _, _ = fit(monkeypatch, "fused", clean, batch_size=batch)
    p_bad, _, trainer = fit(monkeypatch, "fused", poisoned, batch_size=batch)
    assert trainer.fit_telemetry_["step_loop"]["path"] == "fused"
    assert np.asarray(trainer.healthy_).tolist() == [True, True, False, True, True, True]
    for a, b in zip(jax.tree.leaves(p_clean), jax.tree.leaves(p_bad)):
        np.testing.assert_array_equal(np.delete(a, 2, axis=0), np.delete(b, 2, axis=0))


# -- the chooser ----------------------------------------------------------------


def hourglass(**kwargs):
    return feedforward_hourglass(n_features=6, **kwargs)


FALLBACKS = {
    "a non-Adam optimizer": (lambda: hourglass(optimizer="SGD"), {}),
    "an optimizer passed in": (hourglass, {"optimizer": "passed"}),
    "an activation the kernel does not know": (
        lambda: feedforward_model(
            n_features=6, encoding_dim=(5,), encoding_func=("elu",),
            decoding_dim=(5,), decoding_func=("tanh",),
        ),
        {},
    ),
    "a loss the kernel does not know": (
        lambda: feedforward_model(n_features=6, compile_kwargs={"loss": "huber"}), {}
    ),
    "a bfloat16 model": (lambda: hourglass(dtype="bfloat16"), {}),
    "a windowed spec": (
        lambda: lstm_model(
            n_features=6, lookback_window=4, encoding_dim=(4,),
            encoding_func=("tanh",), decoding_dim=(4,), decoding_func=("tanh",),
        ),
        {},
    ),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_chooser_keeps_the_scan(monkeypatch, case):
    import optax

    make_spec, how = FALLBACKS[case]
    optimizer = optax.adam(1e-3) if how.get("optimizer") else None
    trainer = FleetTrainer(make_spec(), optimizer=optimizer)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    data = stacked(n=256)
    assert trainer._choose_step_loop(data, 128, None, "permute_epoch") == "scan"


def test_chooser_takes_the_kernel_where_it_serves(monkeypatch):
    trainer = FleetTrainer(hourglass())
    data = stacked(n=512)
    assert trainer._choose_step_loop(data, 128, None, "permute_epoch") == "scan"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trainer._choose_step_loop(data, 128, None, "permute_epoch") == "fused"
    assert trainer._choose_step_loop(data, 256, None, "permute_epoch") == "fused"
    assert trainer._choose_step_loop(data, 128, None, "gather") == "scan"
    # a batch of no whole 128-row tile would reach the kernel copied into
    # slabs padded to 128 lanes
    assert trainer._choose_step_loop(data, 100, None, "permute_epoch") == "scan"
    assert trainer._choose_step_loop(data, 32, None, "permute_epoch") == "scan"


@pytest.mark.parametrize("precision", ["highest", "float32"])
def test_chooser_keeps_the_scan_under_a_set_matrix_precision(monkeypatch, precision):
    """Under ``jax.default_matmul_precision`` the kernel's bfloat16 products
    would be less than the caller asked for: the plan refuses the spec and
    the scan runs, as the fetch keeps its input slab float32 there."""
    trainer = FleetTrainer(hourglass())
    data = stacked(n=512)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trainer._choose_step_loop(data, 128, None, "permute_epoch") == "fused"
    with jax.default_matmul_precision(precision):
        assert fleet_dense.plan(trainer.spec, 6) is None
        assert trainer._choose_step_loop(data, 128, None, "permute_epoch") == "scan"


def test_chooser_sizes_the_block_for_the_slab_the_fetch_stores(monkeypatch):
    """The block is sized for the input slab the fetch writes: bfloat16
    where the model reads its inputs through default-precision products
    alone, float32 under a set precision."""
    trainer = FleetTrainer(hourglass())
    data = stacked(n=512)
    xb = jax.ShapeDtypeStruct((128, 6), jnp.float32)
    assert trainer._inputs_in_products(xb)
    with jax.default_matmul_precision("highest"):
        assert not trainer._inputs_in_products(xb)
    asked = []
    real = fleet_dense.serves
    monkeypatch.setattr(
        fleet_dense, "serves", lambda *args: asked.append(args[-1]) or real(*args)
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trainer._choose_step_loop(data, 128, None, "permute_epoch") == "fused"
    assert asked == [2]


def test_fit_under_a_set_matrix_precision_runs_the_scan(monkeypatch):
    """A whole fit steered as on a TPU under ``"highest"``: the scan ran,
    ``fused_step_share`` 0.0."""
    with monkeypatch.context() as patch, jax.default_matmul_precision("highest"):
        steer(patch)
        trainer = FleetTrainer(hourglass())
        data = stacked(m=2, n=256)
        trainer.fit(data, trainer.machine_keys(2), epochs=2, batch_size=128)
    assert trainer.fit_telemetry_["row_fetch"]["path"] == "permute_epoch"
    assert trainer.fit_telemetry_["step_loop"] == {"path": "scan", "epochs": 2}
    assert trainer.fit_telemetry_["fused_step_share"] == 0.0


def test_fit_steered_by_the_chooser_takes_the_kernel(monkeypatch):
    """A whole fit whose choosers are asked as on a TPU, batches of 128:
    the kernel runs every epoch's steps, within 1e-6 of the scan's fit."""
    data = stacked(m=3, n=256)
    scanned = fit(monkeypatch, "scan", data, batch_size=128)
    with monkeypatch.context() as patch:
        steer(patch)
        trainer = FleetTrainer(hourglass())
        keys = trainer.machine_keys(3, seed=3)
        params, losses = trainer.fit(data, keys, epochs=3, batch_size=128)
    assert_close_fits(scanned, (jax.device_get(params), np.asarray(losses), trainer))


@pytest.mark.parametrize(
    "case",
    ["a non-Adam optimizer", "an activation the kernel does not know", "a windowed spec"],
)
def test_fallback_fit_reports_no_fused_steps(monkeypatch, case):
    """A whole fit on a fallback: the scan ran, ``fused_step_share`` 0.0."""
    spec = FALLBACKS[case][0]()
    windowed = spec.windowed
    with monkeypatch.context() as patch:
        if not windowed:
            steer(patch)
        trainer = FleetTrainer(spec)
        data = stacked(m=2, n=256)
        trainer.fit(data, trainer.machine_keys(2), epochs=2, batch_size=128)
    telemetry = trainer.fit_telemetry_
    assert telemetry["step_loop"] == {"path": "scan", "epochs": 2}
    assert telemetry["fused_step_share"] == 0.0


def test_plan_reads_the_spec():
    stack = fleet_dense.plan(feedforward_hourglass(n_features=50), 50)
    assert stack.widths == (50, 42, 33, 25, 25, 33, 42, 50)
    assert stack.funcs == ("tanh",) * 6 + ("linear",)
    assert stack.l1_flags == (False, True, True, False, False, False)
    assert (stack.learning_rate, stack.b1, stack.b2, stack.eps) == (1e-3, 0.9, 0.999, 1e-8)
    lr = fleet_dense.plan(hourglass(optimizer_kwargs={"lr": 0.01}), 6)
    assert lr.learning_rate == 0.01


def test_block_size_follows_the_vector_memory_budget():
    """ff50.fit1000's shape: blocks of whole sublane tiles within the
    budget; a small fleet is one block; a stack too wide for any block is
    not served."""
    stack = fleet_dense.plan(feedforward_hourglass(n_features=50), 50)
    block = fleet_dense.block_size(stack, 1000, 512, 32, x_itemsize=2)
    assert block % 8 == 0 and block >= 8
    assert (
        fleet_dense.vmem_bytes(stack, block, 512, 32, 2)
        + fleet_dense._VMEM_HEADROOM_BYTES <= fleet_dense._VMEM_BUDGET_BYTES
        < fleet_dense.vmem_bytes(stack, block + 8, 512, 32, 2)
        + fleet_dense._VMEM_HEADROOM_BYTES
    )
    assert fleet_dense.block_size(stack, 5, 512, 32) == 5
    wide = stack._replace(widths=(4096,) * 4)
    assert fleet_dense.block_size(wide, 1000, 512, 32) == 0
