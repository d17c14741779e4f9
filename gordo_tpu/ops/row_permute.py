"""
Row permutation from on-chip memory, as a Pallas TPU kernel.

``out[g, j] = table[g, idx[g, j]]`` for a stack of small tables: one grid
step per table brings the whole ``(n, f)`` table into VMEM with one
contiguous copy (the pipeline fetches the next table while this one is
permuted), copies that table's ``(n_out,)`` indices into SMEM, and moves
the rows one by one with a dynamic single-row load and a single-row store.
An XLA gather fetches every row from HBM on its own; here HBM sees two
contiguous streams and the random access stays in vector memory.

The kernel moves rows, so the table's rows have to lie on the sublane axis
(row-major, ``f`` on lanes). A table occupies ``n x round_up(f, 128) x 4``
bytes of VMEM whatever ``f`` is, so the caller packs what it can into the
128 lanes of one row (``parallel/fleet.py`` puts a machine's input and
target columns side by side) and asks :func:`serves` first.

On the CPU backend (tests, rehearsals) the kernel runs in interpret mode;
on ``tpu`` it compiles through Mosaic; any other backend is an error
(the rule of ``ops/flash_attention.py``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LANES, _interpret_for_backend, _round_up

#: rows moved per iteration of the kernel's loop: one (8, 128) output tile,
#: so that every store's sublane offset within its tile is static
_ROWS_PER_ITER = 8
#: what one kernel call may ask of the chip's vector memory (a v5e core has
#: 128 MiB): the table and the permuted rows, each double-buffered by the
#: pipeline, plus room for the compiler's own
_VMEM_BUDGET_BYTES = 96 * 1024 * 1024
_VMEM_HEADROOM_BYTES = 4 * 1024 * 1024


def vmem_bytes(n: int, n_out: int, f: int) -> int:
    """VMEM the kernel's buffers take for one ``(n, f)`` float32 table and
    ``n_out`` permuted rows: rows pad to 128 lanes, both blocks are
    double-buffered."""
    row = _round_up(f, _LANES) * 4
    return 2 * (_round_up(n, 8) + _round_up(n_out, 8)) * row


def _movable(dtype, n_out: int) -> bool:
    """What the kernel is written for: float32 rows, and a count of output
    rows that fills whole 8-row tiles."""
    return jnp.dtype(dtype) == jnp.float32 and n_out % _ROWS_PER_ITER == 0


def serves(X, y, n_out: int) -> bool:
    """
    Whether :func:`epoch_batches` can fetch ``n_out`` rows an epoch from
    tables like ``X`` and ``y`` (``(..., n, f)``; only shapes and dtypes are
    read): float32 rows in whole 8-row tiles, an input row and its target
    row side by side within the 128 lanes of one packed row, and a machine's
    packed table within the kernel's share of vector memory.
    """
    n, fx, fy = X.shape[-2], X.shape[-1], y.shape[-1]
    return (
        _movable(X.dtype, n_out)
        and _movable(y.dtype, n_out)
        and fx + fy <= _LANES
        and vmem_bytes(n, n_out, fx + fy) + _VMEM_HEADROOM_BYTES <= _VMEM_BUDGET_BYTES
    )


def _permute_kernel(idx_hbm, table_ref, out_ref, idx_smem, sem, *, n_out):
    g = pl.program_id(0)
    fetch = pltpu.make_async_copy(idx_hbm.at[g], idx_smem, sem)
    fetch.start()
    fetch.wait()

    def move_tile(t, carry):
        base = pl.multiple_of(t * _ROWS_PER_ITER, _ROWS_PER_ITER)
        for u in range(_ROWS_PER_ITER):
            row = idx_smem[0, base + u]
            out_ref[0, pl.ds(base + u, 1), :] = table_ref[0, pl.ds(row, 1), :]
        return carry

    jax.lax.fori_loop(0, n_out // _ROWS_PER_ITER, move_tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _permute_stack(table, idx, interpret):
    n_tables, n, f = table.shape
    n_out = idx.shape[1]
    limit = vmem_bytes(n, n_out, f) + _VMEM_HEADROOM_BYTES
    return pl.pallas_call(
        functools.partial(_permute_kernel, n_out=n_out),
        out_shape=jax.ShapeDtypeStruct((n_tables, n_out, f), table.dtype),
        grid_spec=pl.GridSpec(
            grid=(n_tables,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, n, f), lambda g: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, n_out, f), lambda g: (g, 0, 0)),
            scratch_shapes=[
                pltpu.SMEM((1, n_out), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=limit,
        ),
        interpret=interpret,
        name="row_permute",
    )(idx[:, None, :], table)


def permute_rows(
    table: jnp.ndarray, idx: jnp.ndarray, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """
    ``table[idx]`` for one ``(n, f)`` table and ``(n_out,)`` int32 indices,
    or row by row for a stack ``(g, n, f)`` / ``(g, n_out)``: bit for bit
    what the gather gives, for indices inside the table (a permutation, or
    one with repeats; nothing checks the range).

    ``interpret=None`` selects from the backend: compiled Mosaic kernel on
    ``tpu``, interpreter on ``cpu``, ValueError on anything else.
    """
    if not _movable(table.dtype, idx.shape[-1]):
        raise ValueError(
            f"permute_rows moves float32 rows in tiles of {_ROWS_PER_ITER}: "
            f"got {table.dtype} and {idx.shape[-1]} indices"
        )
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())
    idx = idx.astype(jnp.int32)
    if table.ndim == 2:
        return _permute_stack(table[None], idx[None], interpret)[0]
    return _permute_stack(table, idx, interpret)


# --------------------------------------------------------------------------
# a fleet's epoch of minibatches
# --------------------------------------------------------------------------

#: bytes of packed row-major tables the fleet loop takes at a time: the loop
#: walks the fleet in groups of machines small enough that XLA keeps a
#: group's packed tables, the kernel's operands among them, in vector
#: memory from the packing to the laying back, and HBM sees only the data
#: read once and the permuted rows written once. Measured on ff50.fit1000's
#: epoch program (PERF.md, PR 25; 8.4 MB a machine): groups of 1, 2, 4, 8,
#: 16, 40 and 104 machines gave epochs of 349, 343, 305, 341, 368, 406 and
#: 419 ms; from 8 machines on the compiler leaves the tables in HBM.
_GROUP_BYTES = 32 * 1024 * 1024


def _group_size(n_machines: int, n: int, n_out: int) -> int:
    per_machine = max(n, n_out) * _LANES * 4
    return max(1, min(_GROUP_BYTES // per_machine, n_machines))


def _fleet_batches(X, y, idx, n_batches, interpret):
    """
    ``X[m][idx[m]]`` and ``y[m][idx[m]]`` for every machine ``m``, cut into
    ``n_batches`` batches: ``(M, n_batches, batch, fx)`` and ``(..., fy)``.

    A stacked fleet's data lies on the chip with the rows on lanes and the
    tags outermost (the compact layout XLA:TPU gives ``f32[M, n, 50]``), so
    a group of machines at a time is packed row-major, ``[x | y | 0]`` in
    the 128 lanes of one row, permuted by the kernel, and laid back as
    ``(n_batches, f, M, batch)`` slabs, the form in which the step loop's
    products take a batch. The transposes at the two ends say that to XLA;
    they move no data of their own.
    """
    n_machines, n, fx = X.shape
    fy = y.shape[2]
    n_out = idx.shape[1]
    batch = n_out // n_batches
    group = _group_size(n_machines, n, n_out)
    n_groups = -(-n_machines // group)
    filler = jnp.zeros((group, n, _LANES - fx - fy), X.dtype)

    def slab(rows):
        f = rows.shape[2]
        return rows.reshape(group, n_batches, batch, f).transpose(1, 3, 0, 2)

    def one_group(g, slabs):
        px, py = slabs
        # the last group steps back to end at the fleet's end, and writes
        # some machines a second time with the same rows
        start = jnp.minimum(g * group, n_machines - group)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, group, 0)
        packed = jnp.concatenate([take(X), take(y), filler], axis=2)
        moved = _permute_stack(packed, take(idx), interpret)
        at = (0, 0, start, 0)
        px = jax.lax.dynamic_update_slice(px, slab(moved[:, :, :fx]), at)
        py = jax.lax.dynamic_update_slice(py, slab(moved[:, :, fx:fx + fy]), at)
        return px, py

    # the loop writes every slab, so what they hold before it is nothing's
    # value; filled from the data and not with a constant, because XLA takes
    # a constant fill out from under the scope's name and a device trace
    # then shows its time as nobody's
    unwritten = lambda f: jnp.full((n_batches, f, n_machines, batch), X[0, 0, 0])
    px, py = jax.lax.fori_loop(
        0, n_groups, one_group, (unwritten(fx), unwritten(fy))
    )
    return px.transpose(2, 0, 3, 1), py.transpose(2, 0, 3, 1)


def epoch_batches(n_batches: int, interpret: Optional[bool] = None):
    """
    ``fetch(Xi, yi, order) -> (xb_all, yb_all)`` for ONE machine: its rows
    ``Xi[order]``, ``yi[order]`` as ``(n_batches, batch, f)`` stacks, the
    ``xs`` of the trainer's step loop. Under ``jax.vmap`` over a fleet the
    whole fleet goes through :func:`permute_rows` a group of machines at a
    time (the rule below), which is how the trainer calls it.
    """
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())

    @jax.custom_batching.custom_vmap
    def fetch(Xi, yi, order):
        xb, yb = _fleet_batches(
            Xi[None], yi[None], order[None], n_batches, interpret
        )
        return xb[0], yb[0]

    @fetch.def_vmap
    def fetch_fleet(axis_size, in_batched, X, y, order):
        X, y, order = (
            a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, batched in zip((X, y, order), in_batched)
        )
        return _fleet_batches(X, y, order, n_batches, interpret), (True, True)

    return fetch
