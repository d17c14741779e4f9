"""
Plain reference of gordo's feedforward autoencoder
(feedforward_autoencoder.py): a dense encoder/decoder stack with ``tanh``
layers and a linear output, and an l1 penalty of 1e-4 on the activations of
every encoder layer but the first (Keras ``activity_regularizer``: the sum of
|activations| over the batch, divided by the batch size).

Straightforward ``jax.numpy`` in the dtype it is given; nothing imported from
the program. Parameters of ONE machine, as a flat dict (its leaves are taken in the
sorted order of their names, as ``leaf_names`` gives them):
``d<k>.w`` (f_in, f_out), ``d<k>.b`` (f_out,), the last pair being the output
layer.
"""

import jax
import jax.numpy as jnp

WINDOWED = False
L1 = 1e-4


def _dims(shapes):
    return list(shapes["layer_dims"]) + [shapes["n_features_out"]]


def leaf_names(shapes):
    names = []
    for k in range(len(_dims(shapes))):
        names += [f"d{k}.w", f"d{k}.b"]
    return sorted(names)


def init(key, shapes):
    """Initial float32 parameters of one machine from ``key``: kernels normal
    with variance 1/fan_in, biases zero."""
    params = {}
    f_in = shapes["n_features"]
    dims = _dims(shapes)
    keys = jax.random.split(key, len(dims))
    for k, f_out in enumerate(dims):
        params[f"d{k}.w"] = jax.random.normal(
            keys[k], (f_in, f_out), jnp.float32
        ) / jnp.sqrt(float(f_in))
        params[f"d{k}.b"] = jnp.zeros((f_out,), jnp.float32)
        f_in = f_out
    return params


def forward(params, x, shapes, unroll=1):
    """x: (batch, f) -> ((batch, f_out), activity penalty)."""
    del unroll
    n_enc = shapes["n_encoding_layers"]
    penalty = jnp.zeros((), jnp.float32)
    n_hidden = len(shapes["layer_dims"])
    for k in range(n_hidden):
        x = jnp.tanh(x @ params[f"d{k}.w"] + params[f"d{k}.b"])
        if 0 < k < n_enc:
            penalty = penalty + L1 * jnp.sum(
                jnp.abs(x.astype(jnp.float32))
            ) / x.shape[0]
    out = x @ params[f"d{n_hidden}.w"] + params[f"d{n_hidden}.b"]
    return out, penalty
