"""
Sequence/context parallelism: attention over windows too long for one
chip's HBM, sharded across a mesh axis.

The reference never shards a sequence — long series are windowed and
resampled down to size (SURVEY.md §5 "Long-context"); this module is the
TPU-native capability that removes that ceiling for the Transformer backend
(gordo_tpu/models/specs_seq.py). Two standard strategies, both expressed
with ``shard_map`` over a named mesh axis so XLA lays the collectives on
ICI:

- **Ring attention** (``ring_attention``): K/V blocks rotate around the
  ring via ``jax.lax.ppermute`` while each device holds its Q shard fixed,
  accumulating with the online-softmax (flash) recurrence — memory per
  device is O(seq/devices), communication overlaps with the per-block
  matmuls.
- **Ulysses / all-to-all** (``ulysses_attention``): ``jax.lax.all_to_all``
  reshards from sequence-sharded to head-sharded, runs exact local
  attention over the full sequence per head group, and reshards back —
  cheaper collectives for moderate sequence lengths, requires
  ``n_heads % axis_size == 0``.

Both are numerically exact (not approximations) and differentiable —
``ppermute``/``all_to_all`` transpose cleanly, so one ``jax.grad`` over the
shard_mapped program trains through them.
"""

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


SEQ_AXIS = "seq"

_NEG_INF = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """
    Exact attention with K/V rotating around the mesh axis ring.

    Call inside ``shard_map`` with the sequence axis sharded: q, k, v are
    the local shards of shape (batch, seq_local, heads, head_dim); returns
    the local shard of the attention output. Global token positions (for
    the causal mask) are reconstructed from ``jax.lax.axis_index``.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    batch, seq_loc, heads, head_dim = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)

    q32 = q.astype(jnp.float32)
    q_pos = my_idx * seq_loc + jnp.arange(seq_loc)  # global positions of q rows

    # online-softmax accumulators
    out_acc = jnp.zeros((batch, seq_loc, heads, head_dim), dtype=jnp.float32)
    row_max = jnp.full((batch, heads, seq_loc), _NEG_INF, dtype=jnp.float32)
    row_sum = jnp.zeros((batch, heads, seq_loc), dtype=jnp.float32)

    # device j sends its current K/V block to j+1, so after i rotations the
    # local block originated on device (my_idx - i) mod axis_size
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(i, carry):
        out_acc, row_max, row_sum, k_blk, v_blk = carry
        src = (my_idx - i) % axis_size
        k_pos = src * seq_loc + jnp.arange(seq_loc)

        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)) * sm_scale
        )
        mask = jnp.ones((seq_loc, seq_loc), dtype=bool)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)

        blk_max = jnp.max(scores, axis=-1)  # (b, h, q)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(scores - new_max[..., None])
        # re-mask: exp(-1e30 - (-1e30)) == 1 for fully-masked rows
        probs = jnp.where(mask[None, None], probs, 0.0)

        new_sum = row_sum * correction + jnp.sum(probs, axis=-1)
        blk_out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_blk.astype(jnp.float32))
        out_acc = out_acc * correction.transpose(0, 2, 1)[..., None] + blk_out

        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return out_acc, new_max, new_sum, k_blk, v_blk

    carry = (out_acc, row_max, row_sum, k, v)
    # unrolled python loop: axis_size is static, and unrolling lets XLA
    # overlap each step's ppermute with the next step's matmuls
    for i in range(axis_size):
        carry = step(i, carry)
    out_acc, _, row_sum, _, _ = carry

    denom = jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]
    return (out_acc / denom).astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    attn_fn: Optional[Callable] = None,
) -> jnp.ndarray:
    """
    All-to-all (DeepSpeed-Ulysses style) sequence parallelism: reshard
    (batch, seq/N, heads, d) -> (batch, seq, heads/N, d), run exact local
    attention per head group, reshard back.
    ``attn_fn(q, k, v, causal=..., sm_scale=...)`` defaults to the dense
    XLA path (gordo_tpu.models.specs_seq.dense_attention).
    """
    if attn_fn is None:
        from gordo_tpu.models.specs_seq import dense_attention

        attn_fn = dense_attention

    axis_size = jax.lax.psum(1, axis_name)
    heads = q.shape[2]
    # static check: shard_map traces with concrete axis size
    if isinstance(axis_size, int) and heads % axis_size:
        raise ValueError(
            f"ulysses_attention needs n_heads ({heads}) divisible by the "
            f"sequence-axis size ({axis_size})"
        )

    def scatter_heads(x):
        # split heads (axis 2) across devices, gather sequence (axis 1)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def gather_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q_h, k_h, v_h = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out_h = attn_fn(q_h, k_h, v_h, causal=causal, sm_scale=sm_scale)
    return gather_heads(out_h)


SEQUENCE_IMPLS = {"ring": ring_attention, "ulysses": ulysses_attention}


def sequence_sharded_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    impl: str = "ring",
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """
    Full-array entry point: shard the sequence axis of (batch, seq, heads,
    head_dim) q/k/v over ``mesh[axis_name]`` and run the chosen
    sequence-parallel attention. seq must divide evenly by the axis size.
    """
    try:
        attn = SEQUENCE_IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"Unknown sequence-parallel impl {impl!r}; available: "
            f"{sorted(SEQUENCE_IMPLS)}"
        ) from None
    axis_size = mesh.shape[axis_name]
    if q.shape[1] % axis_size:
        raise ValueError(
            f"Sequence length {q.shape[1]} not divisible by mesh axis "
            f"{axis_name!r} size {axis_size}"
        )
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(attn, axis_name=axis_name, causal=causal, sm_scale=sm_scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
