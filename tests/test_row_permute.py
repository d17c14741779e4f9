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
from gordo_tpu.parallel.fleet import _read_by_products_alone


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


def fleet_tables(n_machines, n, n_out, fx, fy, seed=0):
    """(X, y, idx) of a fleet; slots past ``n`` repeat sample 0, as the
    trainer's overflow slots do."""
    X, idx = table_and_indices(n, n_out, fx, stack=n_machines, seed=seed)
    y, _ = table_and_indices(n, n_out, fy, stack=n_machines, seed=seed + 1)
    if n_out > n:
        idx = idx.at[:, n:].set(0)
    return X, y, idx


def rows_in_order(table, idx, n_batches):
    rows = jax.vmap(lambda t, i: t[i])(table, idx)
    return rows.reshape(rows.shape[0], n_batches, -1, rows.shape[-1])


# every tag count meets every machine count and every n_out beside n once
# (n = 40; batches of 8 or 16 rows, so one slab a machine that XLA cuts)
KERNEL_CASES = [
    (6, 1, 24, 3), (6, 3, 40, 5), (6, 11, 48, 3),
    (50, 3, 24, 3), (50, 11, 40, 5), (50, 1, 48, 3),
    (64, 11, 24, 3), (64, 1, 40, 5), (64, 3, 48, 3),
]


@pytest.mark.parametrize("f,n_machines,n_out,n_batches", KERNEL_CASES)
def test_kernel_is_the_gather_bit_for_bit(f, n_machines, n_out, n_batches):
    """The trainer's call, ``vmap`` over the fleet: one kernel call writes
    every machine's rows; 3 and 11 machines leave a group of 8 part empty."""
    X, y, idx = fleet_tables(n_machines, 40, n_out, f, f)
    xb, yb = jax.vmap(row_permute.epoch_batches(n_batches))(X, y, idx)
    assert xb.shape == (n_machines, n_batches, n_out // n_batches, f)
    assert xb.dtype == yb.dtype == jnp.float32
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, n_batches))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, n_batches))


# fleets big enough that a table lies with the tags outermost on the chip
# (``_tags_outermost``): a last group of 8 machines part empty, rows that
# fill no 128-row tile, the overflow slots; and a fleet whose input lies
# with the tags outermost and its target with the machines outermost
LAYOUT_CASES = {
    "6 tags, 26 machines": (26, 200, 256, 6, 6, 2, (True, True)),
    "50 tags, 59 machines": (59, 200, 192, 50, 50, 3, (True, True)),
    "50 and 64 tags, 64 machines": (64, 130, 128, 50, 64, 1, (True, False)),
}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_kernel_reads_either_layout_bit_for_bit(case):
    n_machines, n, n_out, fx, fy, n_batches, tags_out = LAYOUT_CASES[case]
    assert tuple(
        row_permute._tags_outermost(n_machines, f) for f in (fx, fy)
    ) == tags_out
    X, y, idx = fleet_tables(n_machines, n, n_out, fx, fy)
    xb, yb = jax.vmap(row_permute.epoch_batches(n_batches))(X, y, idx)
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, n_batches))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, n_batches))


# (machines, tags) -> whether XLA:TPU lays f32[M, n, f] with the tags
# outermost ({1,0,2}), as a described v5e's compiler chose for each
# (tests/test_chip_compile.py holds the compiler to it)
TABLE_LAYOUTS = {
    (1000, 50): True, (1001, 50): True, (56, 50): True, (57, 50): False,
    (58, 50): True, (1000, 64): False, (1001, 64): False, (1001, 6): True,
    (1000, 100): True, (1000, 61): True, (1001, 25): True, (3, 6): False,
}


@pytest.mark.parametrize("shape", sorted(TABLE_LAYOUTS))
def test_tags_lie_outermost_where_that_pads_less(shape):
    assert row_permute._tags_outermost(*shape) == TABLE_LAYOUTS[shape]


def test_kernel_takes_one_table_without_a_stack():
    """A fleet of one machine called straight, outside any vmap: the grid
    pads it to a group of 8 and writes the one machine's rows."""
    X, y, idx = fleet_tables(1, 32, 32, 50, 50)
    xb, yb = row_permute._fleet_batches(X, y, idx, 2, jnp.float32, True)
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, 2))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, 2))


@pytest.mark.parametrize("n_machines", [1, 3, 11])
def test_epoch_batches_under_vmap(n_machines):
    """Input and target tables of their own widths (6 and 4 tags), 40 rows
    that fill no 128-row tile, the overflow slots of 48; for one machine on
    its own, outside any vmap (a plain gather there), the same rows as
    under it."""
    n, n_batches, batch = 40, 3, 16
    X, y, idx = fleet_tables(n_machines, n, n_batches * batch, 6, 4)
    fetch = row_permute.epoch_batches(n_batches)
    xb, yb = jax.vmap(fetch)(X, y, idx)
    assert xb.shape == (n_machines, n_batches, batch, 6)
    assert yb.shape == (n_machines, n_batches, batch, 4)
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, n_batches))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, n_batches))
    x0, y0 = fetch(X[0], y[0], idx[0])
    np.testing.assert_array_equal(x0, xb[0])
    np.testing.assert_array_equal(y0, yb[0])


@pytest.mark.parametrize("n_machines", [3, 9])
def test_kernel_writes_a_slab_a_batch(n_machines):
    """Batches of 128 rows: the kernel writes each batch's slab itself, as
    at ff50.fit1000's 512, from a table of 300 rows (three 128-row tiles,
    the last part empty) and tags that fill the 128 lanes (61 + 67)."""
    X, y, idx = fleet_tables(n_machines, 300, 256, 61, 67)
    xb, yb = jax.vmap(row_permute.epoch_batches(2))(X, y, idx)
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, 2))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, 2))


def test_kernel_lays_the_blocks_past_its_loop():
    """1,160 rows in batches of 116: nine whole blocks of 128 (four steps
    of the loop that lays two blocks while it moves the next two, one block
    after it) and a last block of 8 rows."""
    X, y, idx = fleet_tables(3, 1100, 1160, 50, 50)
    xb, yb = jax.vmap(row_permute.epoch_batches(10))(X, y, idx)
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, 10))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, 10))


def test_bfloat16_input_slab_holds_the_rounded_rows():
    """What the compiled kernel stores where the step reads its inputs only
    through default-precision products: the same rows rounded to bfloat16,
    the target slab untouched."""
    X, y, idx = fleet_tables(3, 96, 96, 6, 4)
    xb, yb = row_permute._fleet_batches(X, y, idx, 6, jnp.bfloat16, True)
    assert xb.dtype == jnp.bfloat16 and yb.dtype == jnp.float32
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, 6).astype(jnp.bfloat16))
    np.testing.assert_array_equal(yb, rows_in_order(y, idx, 6))


def test_interpreted_kernel_keeps_the_input_slab_float32():
    """On the CPU a default-precision product takes float32: there the
    slab stays float32 whatever the caller says of its products."""
    X, y, idx = fleet_tables(3, 40, 48, 6, 6)
    fetch = row_permute.epoch_batches(3, input_in_products=True)
    xb, _ = jax.vmap(fetch)(X, y, idx)
    assert xb.dtype == jnp.float32
    np.testing.assert_array_equal(xb, rows_in_order(X, idx, 3))


def test_vmem_count_holds_a_group_and_the_slabs():
    # ff50.fit1000: 8 machines' 100 tags of 16,384 rows (52.4 MB), the
    # packed table (8.4 MB), five squares (one to pack, two sets of two
    # moved blocks), and the input and target slab blocks of 56 sublanes,
    # double-buffered (14.7 MB)
    group = 100 * 8 * 16384 * 4
    packed = 16384 * 128 * 4
    squares = 5 * 128 * 128 * 4
    slabs = 2 * (56 + 56) * 16384 * 4
    assert row_permute.vmem_bytes(16384, 16384, 50, 50, 1000) == (
        group + packed + squares + slabs
    )
    # rows pad to whole 128-row tiles; a machine's 6 and 4 tags, lying
    # outermost, pad to 8 sublanes each
    assert row_permute.vmem_bytes(130, 120, 6, 4, 3) == 4 * (
        (8 + 8) * 8 * 256 + 256 * 128 + 5 * 128 * 128 + 2 * (8 + 8) * 128
    )
    assert row_permute.smem_bytes(16384) == 8 * 16384 * 4


def test_vmem_budget_counts_padded_lanes():
    fleet = lambda n, f: jax.ShapeDtypeStruct((1000, n, f), jnp.float32)
    assert row_permute.serves(fleet(16384, 50), fleet(16384, 50), 16384)
    # 50 + 50 tags lying outermost: 20,864 rows a machine fit, 20,992 do
    # not (a group's tables alone 67 MB)
    assert row_permute.serves(fleet(20_864, 50), fleet(20_864, 50), 20_864)
    assert not row_permute.serves(fleet(20_992, 50), fleet(20_992, 50), 20_992)
    # one machine's tables lie machines outermost, its tags padded to 56
    one = lambda n, f: jax.ShapeDtypeStruct((n, f), jnp.float32)
    assert row_permute.serves(one(19_200, 50), one(19_200, 50), 19_200)
    assert not row_permute.serves(one(19_328, 50), one(19_328, 50), 19_328)
    assert not row_permute.serves(one(64, 100), one(64, 50), 64)
    # 61 + 67 tags fill the 128 lanes of a packed row, and fit
    assert row_permute.serves(one(4096, 61), one(4096, 67), 4096)


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
    "a batch that fills no 8-row tile": ({"batch": 10}, {}, "permute_epoch"),
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


# -- what the step reads of its input rows -----------------------------------


def hourglass_gradient():
    """(fn, x, params): the hourglass's loss gradient as ``fn(x, params)``,
    the form in which the trainer asks about its step."""
    spec = feedforward_hourglass(n_features=6)
    params = spec.module.init(jax.random.PRNGKey(0), jnp.zeros((4, 6)))

    def loss(x, p):
        out, penalty = spec.module.apply(p, x)
        return jnp.sum(out ** 2) + penalty

    x = jax.ShapeDtypeStruct((16, 6), jnp.float32)
    return jax.grad(loss, argnums=1), x, params


def _read_as_well(fn):
    return lambda x, p: (fn(x, p), jnp.sum(x))


def _highest(fn):
    def under_highest(x, p):
        with jax.default_matmul_precision("highest"):
            return fn(x, p)
    return under_highest


ROUNDING_CASES = {
    "the hourglass's gradient": (lambda fn: fn, True),
    "the gradient under a jit": (jax.jit, True),
    "the input summed besides": (_read_as_well, False),
    "products at the highest precision": (_highest, False),
    "the input returned": (lambda fn: lambda x, p: (fn(x, p), x), False),
}


@pytest.mark.parametrize("case", sorted(ROUNDING_CASES))
def test_rounding_rule_reads_what_the_step_does(case):
    wrap, expected = ROUNDING_CASES[case]
    fn, x, params = hourglass_gradient()
    assert _read_by_products_alone(wrap(fn), x, params) is expected


def test_rounding_rule_follows_the_default_precision():
    fn, x, params = hourglass_gradient()
    with jax.default_matmul_precision("float32"):
        assert not _read_by_products_alone(fn, x, params)


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


def test_permuting_fit_with_batches_of_no_whole_tile(monkeypatch):
    """Batches of 10 rows, 90 slots: the kernel writes a machine's rows as
    one slab that XLA cuts into batches."""
    both = fit_both_ways(monkeypatch, stacked(n=90), {"batch_size": 10})
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
