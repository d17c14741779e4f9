"""
A fleet's epoch of minibatches, fetched by ONE Pallas TPU kernel.

:func:`epoch_batches` gives the trainer, once an epoch, every machine's rows
``X[m][order[m]]`` and ``y[m][order[m]]`` cut into the step loop's batches.
The kernel reads the stacked tables as they lie on the chip and writes the
step slabs as the step loop reads them, so that XLA adds no copy on either
side:

* A stacked table ``f32[M, n, f]`` lies rows-on-lanes in (8, 128) tiles,
  with the tags outermost (XLA:TPU's layout ``{1,0,2}``, physically
  ``(f, M, n)``: a tile holds 8 machines' rows of one tag) or, where that
  pads less (:func:`_tags_outermost`), the machines outermost (``{1,2,0}``,
  ``(M, f, n)``: a tile holds 8 tags' rows of one machine). The kernel takes
  each table as that transpose, which XLA hands over by bitcast, and its
  grid walks the fleet a group of 8 machines (one sublane tile) at a time:
  DMAs bring a group's tables into vector memory, whole tiles of the
  tables' own (the padding of a fleet's last tiles with them), and the
  next group's start as soon as this one's last machine has read its own.
* For each machine of the group it stacks the machine's input and target
  rows (strided loads where a machine is one sublane of every tile) into a
  128 x 128 square per 128 timesteps, input tags first and target tags from
  ``yoff``, and transposes the square: the packed table ``[n, 128]``, one
  ``[x | y]`` row per timestep.
* It moves the packed rows into the epoch's order by the machine's indices
  in scalar memory, 128 at a time, transposes each block of 128 back, and
  writes its input rows and its target rows into the slabs
  ``(n_batches, M, f, batch)``, the order in which the step loop's products
  take a batch. Every slab element is written once.

Data movement only: the slabs hold the tables' own bits (the input's
rounded to bfloat16 where the caller says its products take them so,
:func:`epoch_batches`). A batch that is not a multiple of 128 rows makes
the kernel write a machine's rows as one slab that XLA then cuts into
batches.

On the CPU backend (tests, rehearsals) the kernel runs in interpret mode,
on tables padded to whole tiles first; on ``tpu`` it compiles through
Mosaic; any other backend is an error (the rule of
``ops/flash_attention.py``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _interpret_for_backend, _round_up

#: machines a grid step brings in: one (8, 128) tile's sublanes, the least
#: a DMA may cut out of the rows-on-lanes tables
_GROUP = 8
#: column tiles of a machine packed in one iteration of the kernel's loop,
#: blocks of 128 moved rows laid back in one (beside the next blocks'
#: moves), and rows moved in one: independent work written out for the
#: scheduler to overlap, and no more, since every process that builds the
#: program traces and lowers what is written out again
_PACK_BY = 8
_LAY_BY = 2
_MOVE_BY = 32
#: what one kernel call may ask of the chip's vector memory (a v5e core has
#: 128 MiB): the group's tables, the packed table, the squares and the
#: double-buffered slabs, plus room for the compiler's own
_VMEM_BUDGET_BYTES = 96 * 1024 * 1024
_VMEM_HEADROOM_BYTES = 4 * 1024 * 1024
#: the group's indices in scalar memory (a v5e core has 1 MiB)
_SMEM_BUDGET_BYTES = 768 * 1024


def _tags_outermost(n_machines: int, f: int) -> bool:
    """Whether XLA:TPU lays a stacked ``f32[M, n, f]`` table with the tags
    outermost (``{1,0,2}``) rather than the machines (``{1,2,0}``): the
    layout that pads the table's (8, 128) tiles less, the machines'
    where both pad alike."""
    return f * _round_up(n_machines, _GROUP) < _round_up(f, _GROUP) * n_machines


def vmem_bytes(n: int, n_out: int, fx: int, fy: int, n_machines: int = 1) -> int:
    """VMEM the kernel's buffers take for ``(n, fx)`` input and ``(n, fy)``
    target float32 tables of ``n_machines`` machines and ``n_out`` fetched
    rows: a group's tables (a machine's tags pad to 8 sublanes where the
    machines lie outermost), the packed table (rows pad to 128 lanes), the
    128 x 128 squares (one to pack, two sets of moved blocks), and an input
    and a target slab block (tags pad to 8 sublanes) double-buffered by the
    pipeline."""
    n_pad, out_pad = _round_up(n, _LANES), _round_up(n_out, _LANES)

    def tags(f):
        return f if _tags_outermost(n_machines, f) else _round_up(f, 8)

    group = (tags(fx) + tags(fy)) * _GROUP * n_pad
    packed = n_pad * _LANES
    squares = (1 + 2 * _LAY_BY) * _LANES * _LANES
    slabs = 2 * (_round_up(fx, 8) + _round_up(fy, 8)) * out_pad
    return 4 * (group + packed + squares + slabs)


def smem_bytes(n_out: int) -> int:
    """Scalar memory a group's int32 indices take."""
    return 4 * _GROUP * _round_up(n_out, _LANES)


def serves(X, y, n_out: int) -> bool:
    """
    Whether :func:`epoch_batches` can fetch ``n_out`` rows an epoch from
    tables like ``X`` and ``y`` (``(M, n, f)``, or one machine's ``(n, f)``;
    only shapes and dtypes are read): float32 rows, an input row and its
    target row side by side within the 128 lanes of one packed row, and the
    kernel's buffers within its share of vector and scalar memory.
    """
    n, fx, fy = X.shape[-2], X.shape[-1], y.shape[-1]
    n_machines = X.shape[0] if X.ndim == 3 else 1
    return (
        jnp.dtype(X.dtype) == jnp.float32
        and jnp.dtype(y.dtype) == jnp.float32
        and fx + fy <= _LANES
        and vmem_bytes(n, n_out, fx, fy, n_machines) + _VMEM_HEADROOM_BYTES
        <= _VMEM_BUDGET_BYTES
        and smem_bytes(n_out) <= _SMEM_BUDGET_BYTES
    )


def _in_runs(n, run, by):
    """``run(start, count)`` over ``range(n)`` in runs of ``by``, one run an
    iteration of a loop of the kernel (Mosaic unrolls a loop whole or not
    at all), the rest after it: ``start`` is a multiple of ``by``, ``count``
    static, so that a run is written out and the scheduler can overlap its
    independent parts."""
    def each(c, carry):
        run(pl.multiple_of(c * by, by), by)
        return carry

    if n // by:
        jax.lax.fori_loop(0, n // by, each, 0)
    if n % by:
        run(n - n % by, n % by)


def _fetch_kernel(
    x_hbm, y_hbm, idx_hbm, x_out, y_out,
    x_group, y_group, square, packed, moved, idx_smem, sems,
    *, n_machines, n_groups, n_cols, n_out, fx, fy, yoff, width,
):
    g, j = pl.program_id(0), pl.program_id(1)
    m = g * _GROUP + j
    tables = (
        (x_hbm, x_group, sems.at[0], fx, _tags_outermost(n_machines, fx)),
        (y_hbm, y_group, sems.at[1], fy, _tags_outermost(n_machines, fy)),
    )

    def group_copies(group, act):
        machine0 = pl.multiple_of(group * _GROUP, _GROUP)
        for src, dst, sem, f, tags_out in tables:
            if tags_out:
                # (f, M, n): one (8, 128) tile of every tag a DMA, the
                # group's 8 machines by 128 timesteps
                def each(c, carry, src=src, dst=dst, sem=sem, f=f):
                    cols = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
                    act(pltpu.make_async_copy(
                        src.at[:, pl.ds(machine0, _GROUP), cols],
                        dst.at[pl.ds(c * f, f)], sem,
                    ))
                    return carry

                jax.lax.fori_loop(0, n_cols, each, 0)
            else:
                # (M, f, n): a machine's tiles a DMA (the last machine again
                # in the places of machines past the fleet's end)
                whole = (pl.ds(0, dst.shape[1]), pl.ds(0, dst.shape[2]))
                for u in range(_GROUP):
                    machine = jnp.minimum(machine0 + u, n_machines - 1)
                    act(pltpu.make_async_copy(src.at[(machine,) + whole], dst.at[u], sem))

    idx_copy = pltpu.make_async_copy(
        idx_hbm.at[pl.ds(g * idx_smem.shape[0], idx_smem.shape[0])],
        idx_smem, sems.at[2],
    )

    @pl.when((g == 0) & (j == 0))
    def _():
        group_copies(0, lambda copy: copy.start())

    @pl.when(j == 0)
    def _():
        idx_copy.start()
        group_copies(g, lambda copy: copy.wait())

    def at(k):
        if isinstance(k, int):
            return k * _LANES
        return pl.multiple_of(k * _LANES, _LANES)

    def tags_at(table, c):
        # machine j's rows of the table's tags, timesteps c * 128 on
        _, group, _, f, tags_out = table
        if tags_out:
            # one sublane of each tag's tile: the tiles as rows of 128
            # lanes, a column's tags after each other
            rows = group.reshape(n_cols * f * _GROUP, _LANES)
            return rows[pl.ds(c * f * _GROUP + j, f, stride=_GROUP), :]
        return group[j, pl.ds(0, f), pl.ds(at(c), _LANES)]

    def pack(c0, count):
        # machine j's 128 timesteps from c * 128, input tags first, then
        # target tags
        for c in (c0 + u for u in range(count)):
            square[pl.ds(0, fx), :] = tags_at(tables[0], c)
            square[pl.ds(yoff, fy), :] = tags_at(tables[1], c)
            packed[pl.ds(at(c), _LANES), :] = square[...].T

    # machine j's indices, flat: an index's address is one add
    first = j * (idx_smem.shape[0] // _GROUP)

    def move(k, rows, slot):
        # block k's rows into the epoch's order
        def run(r0, count):
            at_idx = first + at(k) + r0
            rows_out = moved.at[slot, pl.ds(r0, count)]

            def one(u, carry):
                row = idx_smem[at_idx + u]
                rows_out[pl.ds(u, 1), :] = packed[pl.ds(row, 1), :]
                return carry

            # written out whole, traced once
            jax.lax.fori_loop(0, count, one, 0, unroll=True)

        _in_runs(rows, run, _MOVE_BY)

    def lay(k, rows, slot):
        # block k back to tags-by-timesteps, into its slab
        block = moved[slot].T
        if width % _LANES == 0:
            slab, col = at(k) // width, at(k) % width
            if not isinstance(k, int):
                col = pl.multiple_of(col, _LANES)
        else:
            slab, col = 0, at(k)
        x_out[slab, :, pl.ds(col, rows)] = block[:fx, :rows].astype(x_out.dtype)
        y_out[slab, :, pl.ds(col, rows)] = block[yoff:yoff + fy, :rows]

    @pl.when(m < n_machines)
    def _():
        _in_runs(n_cols, pack, _PACK_BY)

        # the group's tables are read: the next group's come in meanwhile
        @pl.when((j == _GROUP - 1) & (g + 1 < n_groups))
        def _():
            group_copies(g + 1, lambda copy: copy.start())

        @pl.when(j == 0)
        def _():
            idx_copy.wait()

        # _LAY_BY blocks' moves beside the previous ones' laying, so that
        # the transposes overlap each other and the moves; the first
        # blocks' moves, and the blocks the loop leaves (a part-full last
        # one among them), go one by one
        full, tail = divmod(n_out, _LANES)
        steps = full // _LAY_BY

        def lays(i, half):
            for u in range(_LAY_BY):
                lay(i * _LAY_BY + u, _LANES, half * _LAY_BY + u)

        def step(i, carry):
            lays(i - 1, (i - 1) % 2)
            for u in range(_LAY_BY):
                move(i * _LAY_BY + u, _LANES, (i % 2) * _LAY_BY + u)
            return carry

        def first_moves(u, carry):
            move(u, _LANES, u)
            return carry

        def leftover(k, carry):
            move(k, _LANES, 0)
            lay(k, _LANES, 0)
            return carry

        if steps:
            jax.lax.fori_loop(0, _LAY_BY, first_moves, 0)
            if steps > 1:
                jax.lax.fori_loop(1, steps, step, 0)
            lays(steps - 1, (steps - 1) % 2)
        if full > steps * _LAY_BY:
            jax.lax.fori_loop(steps * _LAY_BY, full, leftover, 0)
        if tail:
            move(full, tail, 0)
            lay(full, tail, 0)


def _as_laid(a, tags_out: bool, interpret: bool):
    """
    ``a`` (M, n, f) in the order of its layout on the chip, ``(f, M, n)``
    with the tags outermost, else ``(M, f, n)``: a bitcast there. The
    kernel reads whole (8, 128) tiles. Compiled, a tile past the table's
    last row, and past its last machine (tags outermost) or tag (machines
    outermost), lies in the padding of the table's own tiles, which are
    (8, 128) where their sublanes number 8 or more. The interpreter's
    slices, and smaller tiles, stop at the array's end: there the table is
    padded to whole tiles first (a copy).
    """
    m, n, f = a.shape
    sublanes = m if tags_out else f
    if interpret or sublanes < _GROUP:
        rows = (0, _round_up(n, _LANES) - n)
        if tags_out:
            a = jnp.pad(a, ((0, _round_up(m, _GROUP) - m), rows, (0, 0)))
        else:
            a = jnp.pad(a, ((0, 0), rows, (0, _round_up(f, _GROUP) - f)))
    return a.transpose(2, 0, 1) if tags_out else a.transpose(0, 2, 1)


def _fleet_batches(X, y, idx, n_batches, x_dtype, interpret):
    """
    ``X[m][idx[m]]`` and ``y[m][idx[m]]`` for every machine ``m``, cut into
    ``n_batches`` batches: ``(M, n_batches, batch, fx)`` and ``(..., fy)``.
    The kernel writes ``(n_batches, M, f, batch)`` slabs, the form in which
    the step loop's products take a batch; the transposes at the two ends
    say that to XLA and move no data of their own.
    """
    n_machines, n, fx = X.shape
    fy = y.shape[2]
    n_out = idx.shape[1]
    batch = n_out // n_batches
    # a slab is a batch where a batch is whole 128-lane tiles, else a
    # machine's whole epoch, which XLA then cuts into batches
    width = batch if batch % _LANES == 0 else n_out
    n_slabs = n_out // width
    m_pad, n_pad = _round_up(n_machines, _GROUP), _round_up(n, _LANES)
    out_pad = _round_up(n_out, _LANES)
    idx = idx.astype(jnp.int32)
    if (m_pad, out_pad) != (n_machines, n_out):
        idx = jnp.pad(idx, ((0, m_pad - n_machines), (0, out_pad - n_out)))
    n_groups = m_pad // _GROUP
    n_cols = n_pad // _LANES
    tags_out = tuple(_tags_outermost(n_machines, f) for f in (fx, fy))

    def group_scratch(f, tags_outermost):
        if tags_outermost:
            shape = (n_cols * f, _GROUP, _LANES)
        else:
            shape = (_GROUP, _round_up(f, _GROUP), n_pad)
        return pltpu.VMEM(shape, jnp.float32)

    # target rows from a tile's edge where they fit, else straight after
    # the input's
    yoff = min(_round_up(fx, 8), _LANES - fy)

    def slab_spec(f):
        return pl.BlockSpec(
            (n_slabs, None, f, width),
            lambda g, j: (0, jnp.minimum(g * _GROUP + j, n_machines - 1), 0, 0),
        )

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    x_slabs, y_slabs = pl.pallas_call(
        functools.partial(
            _fetch_kernel, n_machines=n_machines, n_groups=n_groups,
            n_cols=n_cols, n_out=n_out, fx=fx, fy=fy, yoff=yoff, width=width,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_slabs, n_machines, fx, width), x_dtype),
            jax.ShapeDtypeStruct((n_slabs, n_machines, fy, width), y.dtype),
        ),
        grid_spec=pl.GridSpec(
            grid=(n_groups, _GROUP),
            in_specs=[anywhere, anywhere, anywhere],
            out_specs=[slab_spec(fx), slab_spec(fy)],
            scratch_shapes=[
                group_scratch(fx, tags_out[0]),
                group_scratch(fy, tags_out[1]),
                pltpu.VMEM((_LANES, _LANES), jnp.float32),
                pltpu.VMEM((n_pad, _LANES), jnp.float32),
                pltpu.VMEM((2 * _LAY_BY, _LANES, _LANES), jnp.float32),
                pltpu.SMEM((_GROUP * out_pad,), jnp.int32),
                pltpu.SemaphoreType.DMA((3,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(
                vmem_bytes(n, n_out, fx, fy, n_machines) + _VMEM_HEADROOM_BYTES
            ),
        ),
        interpret=interpret,
        name="epoch_fetch",
    )(
        _as_laid(X, tags_out[0], interpret),
        _as_laid(y, tags_out[1], interpret),
        idx.reshape(-1),
    )

    def batches(slabs, f):
        if n_slabs == n_batches:
            return slabs.transpose(1, 0, 3, 2)
        rows = slabs[0].reshape(n_machines, f, n_batches, batch)
        return rows.transpose(0, 2, 3, 1)

    return batches(x_slabs, fx), batches(y_slabs, fy)


def epoch_batches(
    n_batches: int,
    input_in_products: bool = False,
    interpret: Optional[bool] = None,
):
    """
    ``fetch(Xi, yi, order) -> (xb_all, yb_all)`` for ONE machine: its rows
    ``Xi[order]``, ``yi[order]`` as ``(n_batches, batch, f)`` stacks, the
    ``xs`` of the trainer's step loop. Under ``jax.vmap`` over a fleet the
    whole fleet goes through ONE kernel call (the rule below), which is how
    the trainer calls it.

    ``input_in_products``: the caller reads the input rows only as operands
    of matrix products at the default precision. Compiled on a TPU, where
    such a product takes them in bfloat16, the kernel then stores the input
    slab rounded to bfloat16, as XLA stores such an operand (half the
    bytes, the same bits to the products); interpreted on the CPU, whose
    products take float32, the slab stays float32.

    ``interpret=None`` selects from the backend: compiled Mosaic kernel on
    ``tpu``, interpreter on ``cpu``, ValueError on anything else.
    """
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())
    x_dtype = jnp.bfloat16 if input_in_products and not interpret else jnp.float32

    @jax.custom_batching.custom_vmap
    def fetch(Xi, yi, order):
        # one machine alone, outside any vmap: the rows the kernel gives
        batch = order.shape[0] // n_batches
        xb = Xi[order].reshape(n_batches, batch, -1).astype(x_dtype)
        return xb, yi[order].reshape(n_batches, batch, -1)

    @fetch.def_vmap
    def fetch_fleet(axis_size, in_batched, X, y, order):
        X, y, order = (
            a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, batched in zip((X, y, order), in_batched)
        )
        return (
            _fleet_batches(X, y, order, n_batches, x_dtype, interpret),
            (True, True),
        )

    return fetch
