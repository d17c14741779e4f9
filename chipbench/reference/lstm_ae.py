"""
Plain reference of the stacked-LSTM autoencoder (gordo's ``lstm_model``
factory, lstm_autoencoder.py): every LSTM layer hands its whole sequence to
the next, a dense head reads the last layer's last timestep.

Straightforward ``jax.numpy`` in the dtype it is given, no fused schedule, no
batching over machines, nothing imported from the program. Gate order
[i, f, g, o], sigmoid gates, ``tanh`` on the candidate and on the cell output,
one bias per layer (the recurrent one), zero initial state.

Parameters of ONE machine, as a flat dict (its leaves are taken in the
sorted order of their names, as ``leaf_names`` gives them):
``l<k>.wx`` (f_in, 4h), ``l<k>.wh`` (h, 4h), ``l<k>.b`` (4h,), ``head.w``
(h_last, f_out), ``head.b`` (f_out,).
"""

import jax
import jax.numpy as jnp

WINDOWED = True


def leaf_names(shapes):
    names = []
    for k in range(len(shapes["layer_dims"])):
        names += [f"l{k}.wx", f"l{k}.wh", f"l{k}.b"]
    return sorted(names + ["head.w", "head.b"])


def _orthogonal(key, rows, cols):
    """(rows, cols) with orthonormal rows, rows <= cols: QR of a normal
    (cols, rows) matrix, signs fixed so that the draw is uniform."""
    q, r = jnp.linalg.qr(jax.random.normal(key, (cols, rows), jnp.float32))
    q = q * jnp.sign(jnp.diagonal(r))[None, :]
    return q.T


def init(key, shapes):
    """Initial float32 parameters of one machine from ``key``: input and head
    kernels normal with variance 1/fan_in, recurrent kernels orthogonal,
    biases zero (what Keras and flax give such layers, up to the draw)."""
    params = {}
    f_in = shapes["n_features"]
    keys = jax.random.split(key, 2 * len(shapes["layer_dims"]) + 1)
    for k, h in enumerate(shapes["layer_dims"]):
        params[f"l{k}.wx"] = jax.random.normal(
            keys[2 * k], (f_in, 4 * h), jnp.float32
        ) / jnp.sqrt(float(f_in))
        params[f"l{k}.wh"] = _orthogonal(keys[2 * k + 1], h, 4 * h)
        params[f"l{k}.b"] = jnp.zeros((4 * h,), jnp.float32)
        f_in = h
    params["head.w"] = jax.random.normal(
        keys[-1], (f_in, shapes["n_features_out"]), jnp.float32
    ) / jnp.sqrt(float(f_in))
    params["head.b"] = jnp.zeros((shapes["n_features_out"],), jnp.float32)
    return params


def forward(params, x, shapes, unroll=1):
    """x: (batch, time, f) -> ((batch, f_out), penalty 0)."""
    dtype = x.dtype
    seq = x
    for k, h_dim in enumerate(shapes["layer_dims"]):
        wx, wh, b = params[f"l{k}.wx"], params[f"l{k}.wh"], params[f"l{k}.b"]

        def step(carry, x_t, wx=wx, wh=wh, b=b):
            c, h = carry
            gates = x_t @ wx + h @ wh + b
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (c, h), h

        zeros = jnp.zeros((seq.shape[0], h_dim), dtype)
        _, hs = jax.lax.scan(
            step, (zeros, zeros), seq.swapaxes(0, 1), unroll=unroll
        )
        seq = hs.swapaxes(0, 1)
    out = seq[:, -1, :] @ params["head.w"] + params["head.b"]
    return out, jnp.zeros((), jnp.float32)
