"""
Blockwise (flash-style) attention as Pallas TPU kernels — forward AND
backward, fully tiled in BOTH sequence axes.

The dense attention path (gordo_tpu/models/specs_seq.py:dense_attention)
materializes the full (seq, seq) score matrix in HBM. Here every pass
runs on an O(block_q x block_k) tile so VMEM usage is independent of the
sequence length, with the matmuls hitting the MXU in float32 accumulation:

- forward: grid (bh, q blocks, k blocks) with FlashAttention-2 online
  softmax — running row-max / row-sum / output accumulators live in VMEM
  scratch across the (sequential) k-block axis; the final k step emits
  the output and the per-row log-sum-exp (LSE).
- backward (FlashAttention-2 decomposition): ``delta = rowsum(dO * O)``
  on the host XLA side (O(s*d)); one kernel gridded (bh, q blocks,
  k blocks) accumulates dq, another gridded (bh, k blocks, q blocks)
  accumulates dk/dv, each rebuilding its (block_q, block_k) probability
  tile as ``p = exp(scores - lse)``. Residuals are (q, k, v, out, lse) —
  O(s*d) — so training memory is O(seq) in HBM and O(1) in VMEM; neither
  a (seq, seq) tensor nor a (block, seq) strip exists in the compiled
  module (pinned by tests/test_seq_models.py).

Accumulator scratch persists across grid steps because TPU Pallas grids
execute sequentially over the innermost axis; outputs indexed by the
outer axes are written on that axis's last step.

Head_dim is padded to lane multiples (128) and seq to the block size
outside the kernels; padded key columns are masked to zero probability,
padded query rows carry zero dO/delta so they contribute nothing to dk/dv.

On the CPU backend (tests, rehearsals) the kernels run in interpret mode;
on ``tpu`` they compile through Mosaic; any other backend is an error.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# TPU lane width: scratch row-statistics are stored lane-broadcast so the
# (block_q, 1) logical vectors tile cleanly into VMEM
_LANES = 128
# Mosaic requires a block's last two dims to divide (8, 128) or equal the
# array's; per-row stats (lse, delta) therefore travel as (..., seq, 8)
# arrays — logical column 0 broadcast across 8 sublane-width lanes
_STAT_LANES = 8


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _tile_mask(shape, seq_len, causal, q_offset, k_offset):
    """Validity mask for a (q rows, k cols) score tile."""
    kpos = k_offset + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = kpos < seq_len
    if causal:
        qpos = q_offset + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        mask = jnp.logical_and(mask, kpos <= qpos)
    return mask


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, seq_len, causal, block_q, block_k, sm_scale
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, dtype=m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, dtype=l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, dtype=acc_scr.dtype)

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d_pad)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d_pad)
        v = v_ref[0].astype(jnp.float32)

        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        mask = _tile_mask(
            scores.shape, seq_len, causal, qi * block_q, ki * block_k
        )
        scores = jnp.where(mask, scores, _NEG_INF)

        # online softmax: rescale the running sums by exp(m_prev - m_new)
        m_prev = m_scr[...][:, :1]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # tiles entirely above the diagonal are fully masked: skip the MXU
        # work (roughly half the grid at long seq); init/emit still run
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _emit():
        m = m_scr[...][:, :1]
        l = l_scr[...][:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-padded rows
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            m + jnp.log(l_safe), (m.shape[0], _STAT_LANES)
        )


def _flash_forward_bhsd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """Attention over (batch*heads, seq, head_dim); returns (out, lse)."""
    bh, seq, d = q.shape
    seq_pad = _round_up(seq, math.lcm(block_q, block_k))
    d_pad = _round_up(d, 128)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, seq_pad - seq), (0, d_pad - d)))

    qp, kp, vp = pad(q), pad(k), pad(v)

    kernel = functools.partial(
        _attn_kernel,
        seq_len=seq,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        sm_scale=sm_scale,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq_pad // block_q, seq_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _STAT_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_pad, d_pad), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_pad, _STAT_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running row max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running row sum
            pltpu.VMEM((block_q, d_pad), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :seq, :d], lse[:, :, 0]


# --------------------------------------------------------------------------
# backward: dq over (q blocks, k blocks), dk/dv over (k blocks, q blocks)
# --------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scr,
    *, seq_len, causal, block_q, block_k, sm_scale
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, dtype=acc_scr.dtype)

    def _compute():
        q = q_ref[0].astype(jnp.float32)        # (block_q, d_pad)
        k = k_ref[0].astype(jnp.float32)        # (block_k, d_pad)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)      # (block_q, d_pad)
        lse = lse_ref[0][:, :1]                 # (block_q, 1) from lane pad
        delta = delta_ref[0][:, :1]

        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        mask = _tile_mask(
            scores.shape, seq_len, causal, qi * block_q, ki * block_k
        )
        p = jnp.where(mask, jnp.exp(scores - lse), 0.0)
        ds = p * (jnp.dot(do, v.T, preferred_element_type=jnp.float32) - delta)
        acc_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _emit():
        dq_ref[0] = (acc_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, seq_len, causal, block_q, block_k, sm_scale
):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dtype=dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dtype=dv_scr.dtype)

    def _compute():
        q = q_ref[0].astype(jnp.float32)        # (block_q, d_pad)
        k = k_ref[0].astype(jnp.float32)        # (block_k, d_pad)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)      # (block_q, d_pad)
        lse = lse_ref[0][:, :1]                 # (block_q, 1) from lane pad
        delta = delta_ref[0][:, :1]

        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        mask = _tile_mask(
            scores.shape, seq_len, causal, qi * block_q, ki * block_k
        )
        p = jnp.where(mask, jnp.exp(scores - lse), 0.0)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        ds = p * (jnp.dot(do, v.T, preferred_element_type=jnp.float32) - delta)
        dk_scr[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _emit():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward_bhsd(
    q, k, v, out, lse, d_out, causal, sm_scale, block_q, block_k, interpret
):
    bh, seq, d = q.shape
    seq_pad = _round_up(seq, math.lcm(block_q, block_k))
    d_pad = _round_up(d, 128)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, seq_pad - seq), (0, d_pad - d)))

    qp, kp, vp, dop = pad(q), pad(k), pad(v), pad(d_out)
    # delta_i = rowsum(dO_i * O_i); zero on padded rows by construction
    delta = jnp.sum(
        d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )

    def stat_lanes(row_stat, pad_to):
        """(bh, seq) per-row stat -> lane-broadcast (bh, seq_pad, 8)."""
        padded = jnp.pad(row_stat, ((0, 0), (0, pad_to - row_stat.shape[1])))
        return jnp.broadcast_to(
            padded[:, :, None], padded.shape + (_STAT_LANES,)
        )

    lse_p = stat_lanes(lse, seq_pad)
    delta_p = stat_lanes(delta, seq_pad)

    n_q = seq_pad // block_q
    n_k = seq_pad // block_k
    common = dict(
        seq_len=seq,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        sm_scale=sm_scale,
    )

    q_tile = lambda b, i, j: (b, i, 0)   # noqa: E731 — q-indexed tiles
    k_tile = lambda b, i, j: (b, j, 0)   # noqa: E731 — k-indexed tiles
    stat_block = (1, block_q, _STAT_LANES)  # lane-broadcast row stats

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), q_tile),     # q block
            pl.BlockSpec((1, block_k, d_pad), k_tile),     # k block
            pl.BlockSpec((1, block_k, d_pad), k_tile),     # v block
            pl.BlockSpec((1, block_q, d_pad), q_tile),     # dO block
            pl.BlockSpec(stat_block, q_tile),              # lse block
            pl.BlockSpec(stat_block, q_tile),              # delta block
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), q_tile),
        out_shape=jax.ShapeDtypeStruct((bh, seq_pad, d_pad), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    # dkv grid: k blocks outer, q blocks inner (the accumulation axis)
    kv_own = lambda b, i, j: (b, i, 0)   # noqa: E731 — this kernel's k block
    q_inner = lambda b, i, j: (b, j, 0)  # noqa: E731

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), q_inner),    # q block
            pl.BlockSpec((1, block_k, d_pad), kv_own),     # k block
            pl.BlockSpec((1, block_k, d_pad), kv_own),     # v block
            pl.BlockSpec((1, block_q, d_pad), q_inner),    # dO block
            pl.BlockSpec(stat_block, q_inner),             # lse block
            pl.BlockSpec(stat_block, q_inner),             # delta block
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d_pad), kv_own),
            pl.BlockSpec((1, block_k, d_pad), kv_own),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_pad, d_pad), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_pad, d_pad), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lse_p, delta_p)

    return dq[:, :seq, :d], dk[:, :seq, :d], dv[:, :seq, :d]


# --------------------------------------------------------------------------
# custom_vjp plumbing + public API
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhsd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _flash_forward_bhsd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return out


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward_bhsd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, block_q, block_k, interpret, residuals, d_out):
    q, k, v, out, lse = residuals
    return _flash_backward_bhsd(
        q, k, v, out, lse, d_out, causal, sm_scale, block_q, block_k, interpret
    )


_flash_attention_bhsd.defvjp(_fwd, _bwd)


def _interpret_for_backend(backend: str) -> bool:
    """
    Whether the kernels run in the Pallas interpreter on ``backend``.
    Only ``cpu`` maps to the interpreter and only ``tpu`` to the compiled
    Mosaic kernel: an unknown backend name must never fall into "not tpu,
    so interpret" — that is how a chip under another platform name would
    silently run the interpreter.
    """
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise ValueError(
        f"flash_attention has no kernel mode for backend {backend!r} "
        "(compiled on 'tpu', interpreted on 'cpu'); pass interpret= "
        "explicitly"
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """
    Flash attention over (batch, seq, heads, head_dim) tensors — drop-in for
    gordo_tpu.models.specs_seq.dense_attention, O(seq) HBM and
    O(block_q x block_k) VMEM in BOTH passes (see module docstring).

    ``interpret=None`` selects from the backend: compiled Mosaic kernel
    on ``tpu``, interpreter on ``cpu`` (so CPU test runs exercise
    identical kernel code), ValueError on anything else.
    """
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())
    batch, seq, heads, head_dim = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(batch * heads, seq, head_dim)

    out = _flash_attention_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v),
        causal, sm_scale, block_q, block_k, interpret,
    )
    return out.reshape(batch, heads, seq, head_dim).transpose(0, 2, 1, 3)
