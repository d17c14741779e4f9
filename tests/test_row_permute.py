"""
The row-permuting minibatch fetch (``ops/row_permute.py`` and the trainer's
``row_fetch`` paths): the kernel in interpret mode against ``table[idx]``
bit for bit, the rule that picks a path, and fits traced with the permuting
fetch against the gather path's, bit for bit.

The chooser picks the permuting path on a TPU only, so the fit tests steer
it HERE (``fit_both_ways``), not through a program option; the kernel then runs
in the Pallas interpreter, as ``ops/flash_attention.py``'s does on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.models.factories.lstm import lstm_model
from gordo_tpu.ops import row_permute
from gordo_tpu.parallel import FleetTrainer, StackedData


def table_and_indices(n, n_out, f, stack=None, seed=0):
    rng = np.random.default_rng(seed)
    lead = () if stack is None else (stack,)
    table = rng.standard_normal(lead + (n, f)).astype("float32")
    idx = np.stack([
        rng.permutation(max(n, n_out))[:n_out] % n
        for _ in range(stack or 1)
    ]).astype("int32").reshape(lead + (n_out,))
    return jnp.asarray(table), jnp.asarray(idx)


# -- the kernel ---------------------------------------------------------------


@pytest.mark.parametrize("f", [6, 50, 128])
@pytest.mark.parametrize("n,n_out", [(40, 40), (40, 24), (40, 48)])
def test_kernel_is_the_gather_bit_for_bit(f, n, n_out):
    table, idx = table_and_indices(n, n_out, f, stack=3)
    if n_out > n:
        # the trainer's overflow slots: sample 0, again and again
        idx = idx.at[:, n:].set(0)
    out = row_permute.permute_rows(table, idx)
    assert out.shape == (3, n_out, f) and out.dtype == jnp.float32
    np.testing.assert_array_equal(out, jax.vmap(lambda t, i: t[i])(table, idx))


def test_kernel_takes_one_table_without_a_stack():
    table, idx = table_and_indices(32, 32, 50)
    np.testing.assert_array_equal(
        row_permute.permute_rows(table, idx), table[idx]
    )


@pytest.mark.parametrize("n_machines", [1, 3, 11])
def test_epoch_batches_under_vmap(n_machines, monkeypatch):
    """The trainer's call: ``vmap`` over the fleet goes through the kernel a
    group of machines at a time; 11 machines in groups of 8 make the last
    group step back over machines the first has written."""
    monkeypatch.setattr(row_permute, "_group_size", lambda m, n, n_out: min(8, m))
    n, n_batches, batch = 40, 3, 16
    X, idx = table_and_indices(n, n_batches * batch, 6, stack=n_machines)
    y = X[..., :4] + 1.0
    idx = idx.at[:, n:].set(0)
    fetch = row_permute.epoch_batches(n_batches)
    xb, yb = jax.vmap(fetch)(X, y, idx)
    assert xb.shape == (n_machines, n_batches, batch, 6)
    assert yb.shape == (n_machines, n_batches, batch, 4)
    take = jax.vmap(lambda t, i: t[i])
    np.testing.assert_array_equal(xb.reshape(n_machines, -1, 6), take(X, idx))
    np.testing.assert_array_equal(yb.reshape(n_machines, -1, 4), take(y, idx))
    # and for one machine on its own, outside any vmap
    x0, y0 = fetch(X[0], y[0], idx[0])
    np.testing.assert_array_equal(x0, xb[0])
    np.testing.assert_array_equal(y0, yb[0])


def test_kernel_refuses_what_it_is_not_written_for():
    table, idx = table_and_indices(16, 16, 8)
    with pytest.raises(ValueError, match="float32"):
        row_permute.permute_rows(table.astype(jnp.bfloat16), idx)
    with pytest.raises(ValueError, match="tiles of 8"):
        row_permute.permute_rows(table, idx[:12])


def test_fleet_loop_takes_groups_that_stay_in_vector_memory():
    # ff50.fit1000: 16,384 packed rows are 8.4 MB a machine, four to a group
    assert row_permute._group_size(1000, 16384, 16384) == 4
    assert row_permute._group_size(3, 16384, 16384) == 3
    assert row_permute._group_size(1000, 512, 640) == 102
    assert row_permute._group_size(2, 10 ** 6, 10 ** 6) == 1


def test_vmem_budget_counts_padded_lanes():
    # ff50.fit1000's packed table: 16,384 rows of [x | y | 0] in 128 lanes,
    # in and out, double-buffered
    assert row_permute.vmem_bytes(16384, 16384, 100) == 4 * 16384 * 128 * 4
    table = lambda n, f: jax.ShapeDtypeStruct((n, f), jnp.float32)
    assert row_permute.serves(table(16384, 50), table(16384, 50), 16384)
    assert not row_permute.serves(table(100_000, 50), table(100_000, 50), 100_000)
    assert not row_permute.serves(table(64, 100), table(64, 50), 64)


# -- the rule that picks the path --------------------------------------------


def stacked(m=3, n=96, f=6, dtype="float32", ragged=False, seed=0):
    rng = np.random.default_rng(seed)
    rows = [n - (7 * i if ragged else 0) for i in range(m)]
    Xs = [rng.random((r, f)).astype("float32") for r in rows]
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    if dtype != "float32":
        data = StackedData(
            data.X.astype(dtype), data.y.astype(dtype), data.sample_weight
        )
    return data


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the chooser asks of the backend, answered as the chip would."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


CHOICES = {
    "stacked float32 rows on one TPU": ({}, {}, "permute_epoch"),
    "windowed spec": ({"windowed": True}, {}, "gather"),
    "broadcast_data": ({"trainer": {"broadcast_data": True}}, {}, "gather"),
    "bfloat16 table": ({}, {"dtype": "bfloat16"}, "gather"),
    "a table over the VMEM budget": ({}, {"n": 120_000, "m": 1}, "gather"),
    "rows too wide to pack": ({}, {"f": 80, "n": 64}, "gather"),
    "a batch that fills no 8-row tile": ({"batch": 10}, {}, "gather"),
}


@pytest.mark.parametrize("case", sorted(CHOICES))
def test_chooser_reads_what_it_can_see(case, on_a_tpu):
    how, data_kwargs, expected = CHOICES[case]
    data = stacked(**data_kwargs)
    f = data.X.shape[-1]
    spec = (
        lstm_model(n_features=f, lookback_window=4)
        if how.get("windowed")
        else feedforward_hourglass(n_features=f)
    )
    trainer = FleetTrainer(spec, **how.get("trainer", {}))
    assert trainer._choose_row_fetch(data, how.get("batch", 16), None) == expected


def test_chooser_keeps_the_gather_off_the_chip_and_on_a_mesh(monkeypatch):
    from gordo_tpu.parallel import get_device_mesh

    data = stacked()
    spec = feedforward_hourglass(n_features=6)
    assert jax.default_backend() == "cpu"
    assert FleetTrainer(spec)._choose_row_fetch(data, 16, None) == "gather"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meshed = FleetTrainer(spec, mesh=get_device_mesh())
    assert meshed._choose_row_fetch(data, 16, None) == "gather"


# -- fits on the permuting path against the gather path -----------------------


def fit_both_ways(monkeypatch, data, fit_kwargs=None):
    """(params, losses, telemetry) of the same fit on the gather path and on
    the permuting one: the second with the chooser's question about the
    backend answered as the chip would, and only while it asks, so that the
    kernel itself still finds the CPU and runs interpreted."""
    spec = feedforward_hourglass(n_features=data.X.shape[-1])
    fit_kwargs = dict({"epochs": 3, "batch_size": 16}, **(fit_kwargs or {}))
    real = FleetTrainer._choose_row_fetch

    def choose_as_on_a_tpu(self, *args):
        with monkeypatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            return real(self, *args)

    out = []
    for chooser in (real, choose_as_on_a_tpu):
        monkeypatch.setattr(FleetTrainer, "_choose_row_fetch", chooser)
        trainer = FleetTrainer(spec)
        keys = trainer.machine_keys(data.n_machines, seed=3)
        params, losses = trainer.fit(data, keys, **fit_kwargs)
        out.append((jax.device_get(params), losses, trainer.fit_telemetry_))
    monkeypatch.setattr(FleetTrainer, "_choose_row_fetch", real)
    return out


def assert_same_fit(gathered, permuted, epochs=3):
    (p_g, l_g, t_g), (p_p, l_p, t_p) = gathered, permuted
    assert t_g["row_fetch"] == {"path": "gather", "epochs": epochs}
    assert t_p["row_fetch"] == {"path": "permute_epoch", "epochs": epochs}
    np.testing.assert_array_equal(l_g, l_p)
    for a, b in zip(jax.tree.leaves(p_g), jax.tree.leaves(p_p)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("shuffle", [True, False])
def test_permuting_fit_is_the_gather_fit(monkeypatch, shuffle):
    both = fit_both_ways(monkeypatch, stacked(), {"shuffle": shuffle})
    assert_same_fit(*both)


def test_permuting_fit_with_ragged_weights_under_a_sample_cap(monkeypatch):
    """Ragged machines: the cap cuts the scan below the grid (n_pad < n),
    and padding rows sort behind the real ones with zero weight."""
    data = stacked(m=4, n=100, ragged=True)
    both = fit_both_ways(monkeypatch, data)
    assert_same_fit(*both)


def test_permuting_fit_with_overflow_slots(monkeypatch):
    """90 rows in batches of 16: six steps, 96 slots, the last six repeat
    sample 0 and weigh nothing."""
    both = fit_both_ways(monkeypatch, stacked(n=90))
    assert_same_fit(*both)


def test_permuting_fit_with_a_machine_of_no_weight(monkeypatch):
    data = stacked(m=3)
    w = data.sample_weight.at[1].set(0.0)
    data = StackedData(data.X, data.y, w)
    both = fit_both_ways(monkeypatch, data)
    assert_same_fit(*both)
    # the weightless machine took no step at all, on either path
    spec = feedforward_hourglass(n_features=6)
    trainer = FleetTrainer(spec)
    init = jax.device_get(trainer.init_params(trainer.machine_keys(3, seed=3), 6))
    for a, b in zip(jax.tree.leaves(init), jax.tree.leaves(both[1][0])):
        np.testing.assert_array_equal(a[1], b[1])


def test_fit_on_the_cpu_reports_the_gather(monkeypatch):
    trainer = FleetTrainer(feedforward_hourglass(n_features=6))
    data = stacked()
    trainer.fit(data, trainer.machine_keys(3), epochs=2, batch_size=16)
    assert trainer.fit_telemetry_["row_fetch"] == {"path": "gather", "epochs": 2}


def test_variadic_sort_orders_ties_as_argsort_does():
    """The permuting path takes its order from one stable three-operand
    sort; on tied keys it is argsort's order, and the weights come out in it."""
    rng = np.random.default_rng(5)
    keys = jnp.asarray(rng.integers(0, 7, 200).astype("float32"))
    w = jnp.asarray(rng.random(200).astype("float32"))
    _, order, w_sorted = jax.lax.sort(
        (keys, jnp.arange(200, dtype=jnp.int32), w), num_keys=1, is_stable=True
    )
    np.testing.assert_array_equal(order, jnp.argsort(keys))
    np.testing.assert_array_equal(w_sorted, w[jnp.argsort(keys)])
