"""
The main path's programs, compiled at their real widths for a TPU v5e that
is DESCRIBED, not attached (on-chip-measurement guide, section 2): the
chip's own compiler is installed here and refuses what it would refuse
there — a slice not aligned to the tiling, a kernel over its VMEM limit, a
program that does not fit HBM. Nothing runs, so these say nothing about
results or times; they keep every later PR from shipping a program the
chip cannot compile, at no chip time.

Code that asks ``jax.default_backend()`` sees the CPU in such a compile,
so each case lowers the jitted step itself, and the flash kernel's mode is
steered HERE (``compiled_kernels``), not through a program option.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from gordo_tpu.models.factories.gru import gru_model
from gordo_tpu.models.factories.lstm import lstm_model
from gordo_tpu.models.factories.transformer import transformer_model
from gordo_tpu.ops import fleet_dense
from gordo_tpu.ops import flash_attention as flash_module
from gordo_tpu.ops import row_permute
from gordo_tpu.ops.flash_attention import flash_attention
from gordo_tpu.parallel.fleet import FleetTrainer

# the flagship plant (chip_smoke.py's full size; bench.py)
N_TAGS, LOOKBACK, BATCH, N_TIMESTEPS, N_MACHINES = 50, 64, 512, 16384, 8
ENC, DEC = (128, 64), (64, 128)


@pytest.fixture(scope="module")
def topology():
    """A described v5e 2x2 host; skipped only where the topology cannot be
    described (no TPU compiler in the installation)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")


@pytest.fixture(scope="module")
def chip(topology):
    """One described chip's sharding."""
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(autouse=True)
def _cache_off(no_persistent_compile_cache):
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip (the next run warns and compiles
    again) — keep it off around these."""


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Under a described-topology compile the backend still reads "cpu",
    which selects the Pallas interpreter; compile the Mosaic kernels, as
    the chip would."""
    for module in (flash_module, row_permute, fleet_dense):
        monkeypatch.setattr(
            module, "_interpret_for_backend", lambda backend: False
        )


def on_chip(tree, sharding):
    """Shapes of ``tree`` placed on the described device."""
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding
        ),
        tree,
    )


def plant_spec(factory=lstm_model, **kwargs):
    return factory(
        n_features=N_TAGS, lookback_window=LOOKBACK,
        encoding_dim=ENC, encoding_func=("tanh",) * len(ENC),
        decoding_dim=DEC, decoding_func=("tanh",) * len(DEC),
        fused=True, **kwargs,
    )


# -- the Pallas kernel -------------------------------------------------------


@pytest.mark.parametrize("seq", [1024, 16384])
def test_flash_attention_fwd_bwd_compiles(chip, seq):
    """Causal forward AND backward as Mosaic kernels (interpret=False), 4
    heads of 64 — phase 4's width; 16,384 is the long-context bound."""
    qkv = jax.ShapeDtypeStruct((1, seq, 4, 64), jnp.float32, sharding=chip)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=False) ** 2
        )

    text = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(qkv, qkv, qkv).compile().as_text()
    )
    # forward, dq, dk/dv
    assert text.count("tpu_custom_call") >= 3


def kernel_call(entry):
    """(name, operands) of the one Pallas kernel in an entry computation."""
    ((name, operands),) = re.findall(
        r"%(\S+) = \(\S+, \S+\) custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\"",
        entry,
    )
    return name, operands.split(", ")


def assert_tables_by_bitcast(entry, operands, tables):
    """Each table ``(param, machines, rows, tags)`` reaches the kernel as a
    bitcast of the entry's own parameter, in the order of its layout."""
    for operand, (param, n_machines, n, f) in zip(operands, tables):
        if row_permute._tags_outermost(n_machines, f):
            order = (f, n_machines, n)
        else:
            order = (n_machines, f, n)
        dims = ",".join(map(str, order))
        assert re.search(
            rf"{re.escape(operand)} = f32\[{dims}\]\S* bitcast\(%{param}\.", entry
        ), (operand, param)


# (machines, rows, input tags, target tags): ff50.fit1000's widths in two
# groups of the grid; a last group part empty; rows that fill no 128-row
# tile; tags that lie machines outermost; and the two layouts in one call
KERNEL_SHAPES = {
    "ff50.fit1000's widths": (16, N_TIMESTEPS, N_TAGS, N_TAGS),
    "1001 machines": (1001, N_TIMESTEPS, N_TAGS, N_TAGS),
    "16,100 rows": (1000, 16100, N_TAGS, N_TAGS),
    "64 tags": (1001, N_TIMESTEPS, 64, 64),
    "50 and 64 tags": (1001, N_TIMESTEPS, N_TAGS, 64),
}


@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_row_permute_kernel_compiles(chip, case):
    """The feedforward fleet's fetch, batches of 512, the input slab in
    bfloat16: each table reaches the kernel by bitcast, in the order of its
    layout, however the fleet fills its (8, 128) tiles (the kernel reads
    their padding in place)."""
    n_machines, n, fx, fy = KERNEL_SHAPES[case]
    n_batches = N_TIMESTEPS // BATCH
    X = jax.ShapeDtypeStruct((n_machines, n, fx), jnp.float32, sharding=chip)
    y = jax.ShapeDtypeStruct((n_machines, n, fy), jnp.float32, sharding=chip)
    idx = jax.ShapeDtypeStruct((n_machines, N_TIMESTEPS), jnp.int32, sharding=chip)
    text = (
        jax.jit(
            lambda x, y, i: row_permute._fleet_batches(
                x, y, i, n_batches, jnp.bfloat16, False
            )
        )
        .lower(X, y, idx).compile().as_text()
    )
    entry = text[text.index("\nENTRY "):]
    _, operands = kernel_call(entry)
    assert_tables_by_bitcast(
        entry, operands, [("x", n_machines, n, fx), ("y", n_machines, n, fy)]
    )


# (machines, tags) of stacked f32[M, 16384, f] tables, each side of the
# rule that says which of two layouts pads less
LAID_TABLES = [(1000, 50), (1001, 50), (56, 50), (57, 50), (1000, 64), (1001, 6), (3, 6)]


@pytest.mark.parametrize("shape", LAID_TABLES)
def test_tables_lie_as_the_fetch_expects(chip, shape):
    """``row_permute._tags_outermost`` is the compiler's own choice of
    layout for a stacked table: tags outermost ``{1,0,2}``, else machines
    outermost ``{1,2,0}``."""
    n_machines, f = shape
    table = jax.ShapeDtypeStruct((n_machines, N_TIMESTEPS, f), jnp.float32, sharding=chip)
    text = jax.jit(lambda x: x + 1).lower(table).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    (layout,) = re.findall(rf"f32\[{n_machines},{N_TIMESTEPS},{f}\]\{{([0-9,]+):\S* parameter", entry)
    expected = "1,0,2" if row_permute._tags_outermost(n_machines, f) else "1,2,0"
    assert layout == expected


# -- the fused recurrent train steps -----------------------------------------


@pytest.mark.parametrize(
    "factory,kwargs",
    [
        (lstm_model, {"schedule": "layer"}),
        (lstm_model, {"schedule": "stacked"}),
        (gru_model, {}),
    ],
    ids=["lstm-layer", "lstm-stacked", "gru"],
)
def test_fused_recurrent_train_step_compiles(chip, factory, kwargs):
    """One optimizer step of the fused scan at batch 512 x 64 x 50."""
    spec = plant_spec(factory, **kwargs)
    optimizer = spec.make_optimizer()
    x = jax.ShapeDtypeStruct((BATCH, LOOKBACK, N_TAGS), jnp.float32)
    y = jax.ShapeDtypeStruct((BATCH, N_TAGS), jnp.float32)
    params = jax.eval_shape(
        lambda: spec.module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, LOOKBACK, N_TAGS))
        )
    )
    opt_state = jax.eval_shape(optimizer.init, params)

    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            out, penalty = spec.module.apply(p, x)
            return jnp.mean((out - y) ** 2) + penalty

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return jax.tree.map(jnp.add, params, updates), opt_state, loss

    compiled = jax.jit(train_step).lower(
        *on_chip((params, opt_state, x, y), chip)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


# -- the fleet programs ------------------------------------------------------


def fleet_args(trainer, n_features, n_timesteps, sharding, n_machines=N_MACHINES):
    """(params, opt_state, keys, X, y, w) shapes of an M-machine fleet."""
    keys = jax.eval_shape(lambda: trainer.machine_keys(n_machines))
    params = jax.eval_shape(
        lambda: trainer.init_params(
            trainer.machine_keys(n_machines), n_features
        )
    )
    opt_state = jax.eval_shape(trainer.init_opt_state, params)
    grid = jax.ShapeDtypeStruct(
        (n_machines, n_timesteps, n_features), jnp.float32
    )
    w = jax.ShapeDtypeStruct((n_machines, n_timesteps), jnp.float32)
    return on_chip((params, opt_state, keys, grid, grid, w), sharding)


def test_fleet_epoch_program_compiles(chip):
    """build-fleet's epoch program for the flagship bucket: 8 machines x
    16,384 timesteps, batch 512, the quarantine guard on (the default)."""
    trainer = FleetTrainer(plant_spec(), lookahead=0)
    healthy = jax.ShapeDtypeStruct((N_MACHINES,), jnp.bool_, sharding=chip)
    compiled = trainer._epoch_fn(
        N_TIMESTEPS, BATCH, True, quarantine=True
    ).lower(*fleet_args(trainer, N_TAGS, N_TIMESTEPS, chip), healthy).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


# lstm50.fit's bucket and widths (the factory's defaults)
CELL_MACHINES, CELL_ENC, CELL_DEC = 4, (256, 128, 64), (64, 128, 256)


@pytest.fixture(scope="module")
def lstm_cell_epoch_program(chip):
    """``lstm50.fit``'s epoch program at the cell's own widths (4 machines,
    quarantine on), compiled once for the tests that read its text."""
    spec = lstm_model(
        n_features=N_TAGS, lookback_window=LOOKBACK,
        encoding_dim=CELL_ENC, encoding_func=("tanh",) * len(CELL_ENC),
        decoding_dim=CELL_DEC, decoding_func=("tanh",) * len(CELL_DEC),
        fused=True,
    )
    trainer = FleetTrainer(spec, lookahead=0)
    healthy = jax.ShapeDtypeStruct((CELL_MACHINES,), jnp.bool_, sharding=chip)
    return trainer._epoch_fn(
        N_TIMESTEPS, BATCH, True, quarantine=True
    ).lower(
        *fleet_args(trainer, N_TAGS, N_TIMESTEPS, chip, n_machines=CELL_MACHINES),
        healthy,
    ).compile()


def test_lstm_cell_epoch_program_fills_no_stacked_buffer(lstm_cell_epoch_program):
    """The time scans' stacked buffers, 4 machines x (64 steps x 512 rows),
    are allocated and never filled. Under ``jax.lax.scan`` and autodiff the
    step body wrote 78 of them whole, 6.5 GB a step, before the scans
    overwrote them row by row, and XLA's own rewrite of such a fill did
    not fire (PERF.md section 6, PR 30)."""
    stacked = rf"= \w+\[{CELL_MACHINES},{LOOKBACK * BATCH},(\d+)\]\S* "
    text = lstm_cell_epoch_program.as_text()
    assert not re.findall(stacked + r"broadcast\(", text)
    allocated = [
        (int(width), line) for width, line in re.findall(
            stacked + r"(custom-call\(.*)", text
        )
        if "AllocateBuffer" in line
    ]
    # a layer: hidden states and cell states forward, d_x backward, less
    # the first layer's d_x: its input is data and takes no cotangent
    widths = CELL_ENC + CELL_DEC
    assert len(allocated) == 3 * len(widths) - 1
    # and no layer's 4h-wide float32 gates: the backward loops make them
    # again (docs/performance.md, "The backward loop makes a step's gates
    # again"); forward, a layer stacks its own width alone
    for width, line in allocated:
        layer = int(re.search(r"/FusedLSTMLayer_(\d)/scan/", line)[1])
        assert width != 4 * widths[layer], line
        assert width == widths[layer] or "transpose(" in line, line
    assert lstm_cell_epoch_program.memory_analysis().temp_size_in_bytes < 8.5e9


_ITEM_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "pred": 1, "s8": 1, "u8": 1}


def test_lstm_cell_epoch_program_copies_no_stacked_buffer(lstm_cell_epoch_program):
    """The stacked buffers are row-flat, (machines, time*batch, width) as
    the products around the loops take them, so nothing is turned between
    a loop and a product: no ``copy`` or ``transpose`` at a loop's
    boundary. Laid (time, batch, width) they left their loops time-major
    and 13 copies turned them machine-major, 1.17 of the 1.30 GB such
    operations wrote a step (PERF.md section 6, PR 33). What is left,
    0.116 GB, is the input windows and Adam's leaves."""
    copies = re.findall(
        r"= (\w+)\[([\d,]+)\]\S* (?:copy|transpose)\((.*)",
        lstm_cell_epoch_program.as_text(),
    )
    large = [
        (size, rest)
        for dtype, dims, rest in copies
        if (size := _ITEM_BYTES[dtype] * np.prod([int(d) for d in dims.split(",")])) >= 1e6
    ]
    assert large, "the pattern must go on finding the program's copies"
    assert not [rest for _, rest in large if "/scan/" in rest]
    assert sum(size for size, _ in large) < 0.2e9
    # 4.96-5.43 GB while the forward loops stacked the gates, 1.74 since the
    # backward loops make them again. This figure is the heap the compiler
    # lays the temporaries in, which is what the chip reserves (the next
    # test holds it), and, once more, every stacked buffer with a slot of
    # its own outside the largest loop's: while the backward loops stacked
    # ``d_z``, the four last layers' ``d_z``, 0.54 GB (PERF.md sections 4
    # and 6)
    assert lstm_cell_epoch_program.memory_analysis().temp_size_in_bytes < 1.75e9


def test_lstm_cell_epoch_program_stacks_no_projected_input(lstm_cell_epoch_program):
    """The forward loops multiply a step's rows of ``x`` by the input kernel
    themselves: outside the time loops the forward pass writes no float32
    (machines, time*batch, 4h) buffer. Hoisted out of the scan the
    projection wrote six of them a step, ``z``, 1.88 GB, and the loops read
    them back a step at a time (PERF.md section 6, PR 35). A (…, 4h) width
    is 256 or more here; the backward pass's ``d_x`` is at most 256 wide
    and lies under ``transpose(``. The step's body allocates two stacked
    buffers a layer forward, the hidden and the cell states, and ``d_x``
    backward, less the first layer's, whose input is data. The program's
    stated peak, its arguments and the heap of its temporaries, what the
    chip reserves for it, fell with ``z``, 3.32 to 3.19 GB, with ``d_z``,
    to 2.96 GB, and with the gates, which the backward loops make again,
    to 1.08 GB (PERF.md sections 4 and 6)."""
    text = lstm_cell_epoch_program.as_text()
    step_body = max(
        re.split(r"\n(?=%[\w.\-]+ \()", text),
        key=lambda computation: computation.count("scan/empty"),
    )
    assert step_body.count("scan/empty") == 3 * len(CELL_ENC + CELL_DEC) - 1
    written = re.findall(
        rf"= f32\[{CELL_MACHINES},{LOOKBACK * BATCH},(\d+)\]\S* "
        r"(?:fusion|convolution|copy|transpose)\(.*op_name=\"([^\"]*)\"",
        text,
    )
    assert written, "the pattern must go on finding the stacked writes"
    assert not [
        (width, path) for width, path in written
        if int(width) >= 4 * min(CELL_ENC)
        and "transpose(" not in path and "/scan/while/body/" not in path
    ]
    assert lstm_cell_epoch_program.memory_analysis().peak_memory_in_bytes < 1.1e9


def test_lstm_cell_epoch_program_stacks_no_gate_cotangent(lstm_cell_epoch_program):
    """The backward loops multiply a step's ``d_gates`` by the input kernel
    and by the step's rows of ``x`` themselves: under ``transpose(`` no
    (machines, time*batch, 4h) buffer of a layer's own 4h is allocated or
    written, and no product stands under ``/scan/`` outside a time loop's
    body. Stacked as ``d_z`` the cotangent was 0.94 GB a step, written a
    step at a time and read whole by two products after each loop (PERF.md
    section 6, PR 36). What a backward loop stacks is ``d_x``, as wide as
    the layer's input: a 4h of 256 and an input of 256 both occur, so a
    buffer is told by the layer in its operation's path, not by its width.
    The first layer stacks none."""
    widths = CELL_ENC + CELL_DEC
    inputs = (N_TAGS,) + widths[:-1]
    text = lstm_cell_epoch_program.as_text()
    written = re.findall(
        rf"= (?:f32|bf16)\[{CELL_MACHINES},{LOOKBACK * BATCH},(\d+)\]\S* "
        r"(?:fusion|convolution|copy|transpose|custom-call|dynamic-update-slice)"
        r"\(.*op_name=\"[^\"]*transpose\([^\"]*/FusedLSTMLayer_(\d)/scan/([^\"]*)\"",
        text,
    )
    assert {int(layer) for _, layer, _ in written} == set(range(1, len(widths)))
    for width, layer, path in written:
        assert int(width) == inputs[int(layer)] != 4 * widths[int(layer)], path
    assert {"empty", "while/body/closed_call/lstm.bwd.products/dot_general"} <= {
        path for _, _, path in written
    }
    assert not re.findall(r"op_name=\"[^\"]*/scan/dot_general\"", text)


def backward_step_ops(text):
    """Per layer of the cell, what the fusions its backward loop's body
    calls under ``lstm.bwd.*`` compute: the count of each transcendental op
    and of the float32 (machines, batch, h) row reads of a stacked (machines,
    time*batch, h) buffer, which in the backward loop are the previous cell
    states and ``d_hs``."""
    computations = {
        m[1]: body for body in re.split(r"\n(?=%[\w.\-]+ \()", text)
        if (m := re.match(r"%([\w.\-]+) \(", body))
    }
    per_layer = []
    for layer, width in enumerate(CELL_ENC + CELL_DEC):
        step = (
            rf"transpose\([^\"]*/FusedLSTMLayer_{layer}/scan/while/body/"
            r"closed_call/lstm\.bwd\."
        )
        fused = "\n".join(
            computations[callee]
            for body in computations.values()
            for line in body.split("\n")
            if " fusion(" in line and re.search(step, line)
            for callee in re.findall(r"calls=%([\w.\-]+)", line)
        )
        params = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]+\])\S* parameter", fused))
        sliced = re.findall(
            rf"f32\[{CELL_MACHINES},{BATCH},{width}\]\S* dynamic-slice\((%[\w.\-]+)", fused
        )
        stacked = f"f32[{CELL_MACHINES},{LOOKBACK * BATCH},{width}]"
        per_layer.append({
            op: len(re.findall(rf"\S+ {op}\(", fused))
            for op in ("exponential", "divide", "tanh")
        } | {"row_reads": sum(params.get(p) == stacked for p in sliced)})
    return per_layer


def test_lstm_cell_backward_step_makes_each_activation_once(lstm_cell_epoch_program):
    """A backward step's transposed cell update computes each of the five
    activations once and reads the previous cell states' rows and ``d_hs``'s
    rows from HBM once each, in every layer. Transposed whole, the update
    compiled to two fusions a step, each computing sigmoid on i, f and o and
    ``act`` on g again (6 ``exponential``, 6 ``divide``, 3 ``tanh``) and
    each reading both rows, 8 MB a 256-wide step where 4 MB are needed
    (docs/performance.md, "The backward step's transposed cell update is
    made once")."""
    per_layer = backward_step_ops(lstm_cell_epoch_program.as_text())
    for layer, ops in enumerate(per_layer):
        assert ops["exponential"] == ops["divide"] == 3, (layer, ops)
        assert ops["tanh"] <= 2, (layer, ops)
        assert ops["row_reads"] == 2, (layer, ops)


def feedforward_epoch_program(
    chip, n_machines, n, batch, row_fetch, n_tags=N_TAGS, step_loop="scan"
):
    """The epoch program a feedforward fleet gets on one chip, compiled."""
    from gordo_tpu.models.factories.feedforward import feedforward_hourglass

    trainer = FleetTrainer(feedforward_hourglass(n_features=n_tags))
    healthy = jax.ShapeDtypeStruct((n_machines,), jnp.bool_, sharding=chip)
    return trainer._epoch_fn(
        n, batch, True, quarantine=True, row_fetch=row_fetch, step_loop=step_loop
    ).lower(
        *fleet_args(trainer, n_tags, n, chip, n_machines=n_machines), healthy
    ).compile()


def test_permuting_feedforward_epoch_program_compiles(chip, compiled_kernels):
    """The epoch program a TPU's feedforward fleet gets (``row_fetch``
    ``"permute_epoch"``), 48 machines of ff50.fit1000's 1000: six groups of
    the kernel's grid. The tables reach the kernel by bitcast, as they lie
    (rows on lanes, tags outermost); the kernel writes the step slabs, the
    input slab in bfloat16, and they reach the step loop by bitcast: no
    copy on either side, no fill and no update of the slabs."""
    n_machines = 48
    compiled = feedforward_epoch_program(
        chip, n_machines, N_TIMESTEPS, BATCH, "permute_epoch"
    )
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    name, operands = kernel_call(entry)
    # the tables: a bitcast of the entry's own parameters, no copy
    assert_tables_by_bitcast(
        entry, operands,
        [(table, n_machines, N_TIMESTEPS, N_TAGS) for table in ("Xi", "yi")],
    )
    # the slabs: (n_batches, M, f, batch) by bitcast into the step loop,
    # neither filled nor updated anywhere
    slab = rf"\[{N_TIMESTEPS // BATCH},{n_machines},(?:{N_TAGS},{BATCH}|{BATCH},{N_TAGS})\]"
    for op in ("broadcast", "dynamic-update-slice"):
        assert not re.search(rf"(?:f32|bf16){slab}\S* {op}\(", text), op
    outputs = re.findall(rf"%(\S+) = \S+ get-tuple-element\(%{re.escape(name)}\)", entry)
    assert len(outputs) == 2
    for output in outputs:
        users = re.findall(rf"= (\S+) (\S+)\([^)]*%{re.escape(output)}\b", entry)
        assert [op for _, op in users] == ["bitcast"], users
    assert re.search(rf"bf16\[{N_TIMESTEPS // BATCH},{n_machines},{N_TAGS},{BATCH}\]", entry)
    # the slabs and the step's own buffers: 269,369,856 bytes (272,838,144
    # with the XLA loop around the kernel and its fill)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.7e8


#: ff50.fit1000's epoch program's stated peak before its steps ran in one
#: kernel (compile, described v5e): the step kernel may not need more
FF50_SCAN_PEAK = 13_310_869_504


def test_ff50_epoch_runs_its_steps_in_one_kernel(chip, compiled_kernels):
    """ff50.fit1000's epoch program as a TPU gets it (1000 machines,
    ``step_loop`` ``"fused"``): the step slabs go from the fetch kernel
    into the step kernel with nothing between them (no copy, no
    transpose), the step kernel runs under ``fleet.loss_grad``, no XLA
    loop over the steps is left, and the program needs no more HBM than
    the scan's did."""
    n_machines = 1000
    compiled = feedforward_epoch_program(
        chip, n_machines, N_TIMESTEPS, BATCH, "permute_epoch", step_loop="fused"
    )
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    steps = re.search(
        r"%(fleet_steps\S*) = .* custom-call\(([^)]*)\), "
        r"custom_call_target=\"tpu_custom_call\".*op_name=\"([^\"]*)\"",
        entry,
    )
    assert steps, "no step kernel"
    assert "fleet.loss_grad" in steps.group(3)
    slabs = re.findall(
        r"%(\S+) = \S+ get-tuple-element\(%epoch_fetch\S*\), index=", entry
    )
    assert len(slabs) == 2
    for slab in slabs:
        users = re.findall(rf"%(\S+) = .*[(, ]%{re.escape(slab)}[,)]", entry)
        assert users == [steps.group(1)], (slab, users)
    assert " while(" not in entry
    assert compiled.memory_analysis().peak_memory_in_bytes <= FF50_SCAN_PEAK


#: sha256 of lstm50.fit's epoch program lowered for a described v5e (4
#: machines x 16,384 rows x 50 tags, lookback 64, batch 512, the guard on),
#: as it was before the fused step loop: a feedforward kernel must not move
#: a line of it
LSTM50_EPOCH_SHA256 = "72c9010b1ad9ce1056ce2b80f414b6af6de102a48f200ffad5a6a94bb95e59aa"


def test_lstm50_epoch_program_is_untouched_by_the_step_kernel(chip):
    import hashlib
    import json
    import pathlib

    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import _find_jax_estimator

    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / "chipbench/configs/lstm-ae-50tag.json").read_text())
    estimator = _find_jax_estimator(serializer.from_definition(config["model"]))
    estimator.kwargs.update(n_features=N_TAGS, n_features_out=N_TAGS)
    trainer = FleetTrainer(estimator._build_spec(), lookahead=0)
    healthy = jax.ShapeDtypeStruct((CELL_MACHINES,), jnp.bool_, sharding=chip)
    text = trainer._epoch_fn(N_TIMESTEPS, BATCH, False, quarantine=True).lower(
        *fleet_args(trainer, N_TAGS, N_TIMESTEPS, chip, n_machines=CELL_MACHINES),
        healthy,
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LSTM50_EPOCH_SHA256


def test_gather_path_takes_the_inputs_as_bfloat16(chip):
    """The same fleet on the per-step gathers (``row_fetch`` ``"gather"``):
    the program reads ``Xi`` once, as a copy rounded to bfloat16 before the
    step loop, so its products take the bits that the permuting path's
    bfloat16 input slab holds."""
    compiled = feedforward_epoch_program(chip, 48, N_TIMESTEPS, BATCH, "gather")
    text = compiled.as_text()
    users = re.findall(r"= (\S+) (\S+)\([^)]*%Xi\.\d+\b", text)
    assert [(shape.split("[")[0], op) for shape, op in users] == [("bf16", "copy")], users


# (machines, rows, batch): fleets that fill their tiles part, and batches
# of no whole 128-row tile, beside the peak that the fetch this kernel replaced (an XLA
# loop over groups of machines around a row-moving kernel) stated for the
# same program (compile, described v5e): a fetch that copied the tables or
# the slabs whole would state more
LOOP_FETCH_PEAKS = {
    "1001 machines": (1001, N_TIMESTEPS, BATCH, 13_373_507_072),
    "16,100 rows": (1000, 16100, BATCH, 13_210_015_232),
    "batches of 32": (600, N_TIMESTEPS, 32, 11_885_677_056),
}


@pytest.mark.parametrize("case", sorted(LOOP_FETCH_PEAKS))
def test_permuting_fetch_needs_no_more_hbm_than_before(chip, compiled_kernels, case):
    n_machines, n, batch, loop_fetch_peak = LOOP_FETCH_PEAKS[case]
    compiled = feedforward_epoch_program(chip, n_machines, n, batch, "permute_epoch")
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    _, operands = kernel_call(entry)
    assert_tables_by_bitcast(
        entry, operands, [(t, n_machines, n, N_TAGS) for t in ("Xi", "yi")]
    )
    assert compiled.memory_analysis().peak_memory_in_bytes < loop_fetch_peak


def test_fleet_epoch_program_compiles_for_four_chips(topology):
    """chip_smoke --chips 4: the same epoch program over the 2x2 host's
    fleet mesh, two machines a chip — no cross-chip collective belongs in
    it (machines are independent), and each chip holds a quarter."""
    from gordo_tpu.parallel.mesh import fleet_sharding, get_device_mesh

    mesh = get_device_mesh(devices=topology.devices)
    sharding = fleet_sharding(mesh)
    trainer = FleetTrainer(plant_spec(), lookahead=0, mesh=mesh)
    # shapes only: init_params would device_put onto the described mesh
    unsharded = FleetTrainer(plant_spec(), lookahead=0)
    healthy = jax.ShapeDtypeStruct((N_MACHINES,), jnp.bool_, sharding=sharding)
    compiled = trainer._epoch_fn(
        N_TIMESTEPS, BATCH, True, quarantine=True
    ).lower(
        *fleet_args(unsharded, N_TAGS, N_TIMESTEPS, sharding), healthy
    ).compile()
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
    single = 8.5e9  # the one-chip program's temp (M=8 on one device)
    assert compiled.memory_analysis().temp_size_in_bytes < single / 2


def test_fleet_predict_program_fits_hbm(chip):
    """build-fleet scores every CV fold through ``FleetTrainer.predict``.
    Chunked per MACHINE (8192 windows each) the flagship bucket asked for
    16.06 GB of 15.75 GB and the chip's compiler refused it; the chunk is
    now bounded per device."""
    trainer = FleetTrainer(plant_spec(), lookahead=0)
    params, _, _, X, _, _ = fleet_args(trainer, N_TAGS, N_TIMESTEPS, chip)
    chunk = trainer._predict_chunk(N_MACHINES, 8192)
    assert chunk * N_MACHINES <= 8192
    compiled = trainer._predict_fn(N_TIMESTEPS, chunk).lower(params, X).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_flash_fleet_epoch_program_compiles(chip, compiled_kernels):
    """chip_smoke's phase 4: the kernel under FleetTrainer's vmap —
    2-layer TransformerNet, d_model 256, 4 heads, lookback 1024."""
    lookback, batch, n_features = 1024, 8, 16
    spec = transformer_model(
        n_features=n_features, lookback_window=lookback, d_model=256,
        n_heads=4, n_layers=2, dropout=0.0, attention_impl="flash",
    )
    trainer = FleetTrainer(spec, lookahead=0)
    n = lookback + 3 * batch - 1
    healthy = jax.ShapeDtypeStruct((2,), jnp.bool_, sharding=chip)
    text = trainer._epoch_fn(n, batch, True, quarantine=True).lower(
        *fleet_args(trainer, n_features, n, chip, n_machines=2), healthy
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [256, 319], ids=["193_windows", "256_windows"])
def test_fleet_scorer_predict_program_compiles(chip, rows):
    """run-server's scoring program at the shape the build exports and a
    fleet POST dispatches, 8 machines x 256 rows: 193 windows, the time
    scans' batch, so step t's rows of a stacked buffer start at ``t*193``,
    aligned to no (8, 128) tile. And at a batch that is aligned."""
    from gordo_tpu.models import LSTMAutoEncoder
    from gordo_tpu.programs import ProgramCache
    from gordo_tpu.server.fleet_serving import FleetScorer

    X = np.random.default_rng(0).random((80, N_TAGS)).astype("float32")
    estimator = LSTMAutoEncoder(
        kind="lstm_model", lookback_window=LOOKBACK,
        encoding_dim=list(ENC), encoding_func=["tanh"] * len(ENC),
        decoding_dim=list(DEC), decoding_func=["tanh"] * len(DEC),
        fused=True, epochs=1, batch_size=16,
    )
    estimator.fit(X, X.copy())
    scorer = FleetScorer(
        {f"m{i}": estimator for i in range(N_MACHINES)},
        cache=ProgramCache("serving"),
    )
    (group,) = scorer._groups
    batch = jax.ShapeDtypeStruct(
        (N_MACHINES, rows, N_TAGS), jnp.float32, sharding=chip
    )
    compiled = group["apply"].lower(
        on_chip(group["params"], chip), batch
    ).compile()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (N_MACHINES, rows - LOOKBACK + 1, N_TAGS)
