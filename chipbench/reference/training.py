"""
Plain reference of what one ``fit`` call does to one machine: ``epochs``
passes over its rows in minibatches, mean-squared error (plus the model's
activity penalty) and Adam, in float32. A matrix product is ``a @ b`` at JAX's
default precision, which is what the configurations state and what
``build-fleet`` runs; what the platform makes of that default is the
compiler's choice per product and not one arithmetic that a reference could
spell out (PERF.md section 2, "The reference's precision"). Nothing is
imported from the program.

The data order is part of the job, so it is stated here and not taken from
the program: epoch ``e`` of a machine with key ``k`` visits its samples in the
order ``argsort(uniform(fold_in(k, e), (n_samples,)))`` when the fit shuffles
(row models) and in time order when it does not (windowed models). A windowed
sample is ``lookback`` consecutive rows with the last of them as its target.
A batch's loss is the mean over its real samples of the per-sample mean over
tags; the epoch's loss is the sum over all real samples divided by their
number. The last batch is filled up with masked slots.

``fault`` plants one of the faults that the benchmark's comparison has to
catch (see ``chipbench/tests``): ``half_batch`` leaves the second half of each
batch out and takes the mean over the rest.
"""

import functools
import math

import jax
import jax.numpy as jnp

ADAM = {"learning_rate": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def batch_geometry(n_rows, batch_size, lookback, windowed):
    n_samples = n_rows - lookback + 1 if windowed else n_rows
    n_batches = max(1, math.ceil(n_samples / batch_size))
    return n_samples, n_batches


def _per_sample_mse(out, target):
    return jnp.mean((out.astype(jnp.float32) - target) ** 2, axis=-1)


def _adam(params, grads, m, v, t):
    t = t + 1
    b1, b2 = ADAM["b1"], ADAM["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, a, b: p
        - ADAM["learning_rate"] * (a / c1) / (jnp.sqrt(b / c2) + ADAM["eps"]),
        params, m, v,
    )
    return params, m, v, t


def follow_machine(model, shapes, params, X, y, key, *, epochs, batch_size,
                   lookback, shuffle, fault=None):
    """
    Follow ``epochs`` epochs of one machine from ``params``. X: (n, f),
    y: (n, f_out). Returns the per-epoch losses (epochs,), the parameters
    after the last epoch, and the per-leaf norms of the first gradient.
    """
    windowed = model.WINDOWED
    lb = lookback if windowed else 1
    n_samples, n_batches = batch_geometry(len(X), batch_size, lb, windowed)
    n_pad = n_batches * batch_size
    slot_real = (jnp.arange(n_pad) < n_samples).astype(jnp.float32)
    if fault == "half_batch":
        slot_real = slot_real * (jnp.arange(n_pad) % batch_size < batch_size // 2)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")

    def objective(p, xb, yb, wb):
        out, penalty = model.forward(p, xb, shapes)
        per = _per_sample_mse(out, yb)
        loss_sum = jnp.sum(per * wb)
        return loss_sum / jnp.maximum(jnp.sum(wb), 1.0) + penalty, loss_sum

    def step(carry, batch):
        p, m, v, t = carry
        sel, wb = batch
        if windowed:
            xb = X[sel[:, None] + jnp.arange(lb)[None, :]]
            yb = y[sel + lb - 1]
        else:
            xb, yb = X[sel], y[sel]
        (_, loss_sum), grads = jax.value_and_grad(objective, has_aux=True)(
            p, xb, yb, wb
        )
        p, m, v, t = _adam(p, grads, m, v, t)
        gnorms = jnp.stack(
            [jnp.linalg.norm(g.ravel()) for g in jax.tree.leaves(grads)]
        )
        return (p, m, v, t), (loss_sum, jnp.sum(wb), gnorms)

    zeros = jax.tree.map(jnp.zeros_like, params)
    carry = (params, zeros, zeros, jnp.zeros((), jnp.int32))
    losses, grad1 = [], None
    for epoch in range(epochs):
        if shuffle:
            noise = jax.random.uniform(
                jax.random.fold_in(key, epoch), (n_samples,)
            )
            order = jnp.argsort(noise).astype(jnp.int32)
        else:
            order = jnp.arange(n_samples, dtype=jnp.int32)
        order = jnp.concatenate(
            [order, jnp.zeros(n_pad - n_samples, jnp.int32)]
        )
        carry, (loss_sums, w_sums, gnorms) = jax.lax.scan(
            step,
            carry,
            (order.reshape(n_batches, batch_size),
             slot_real.reshape(n_batches, batch_size)),
        )
        losses.append(jnp.sum(loss_sums) / jnp.maximum(jnp.sum(w_sums), 1.0))
        if grad1 is None:
            grad1 = gnorms[0]
    return jnp.stack(losses), carry[0], grad1


def shapes_key(shapes):
    """The shapes as a hashable key of a compiled reference."""
    return tuple(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in sorted(shapes.items())
    )


@functools.lru_cache(maxsize=None)
def _block_fn(model, shapes_key, epochs, batch_size, lookback, shuffle, fault):
    shapes = dict(shapes_key)

    def one(params, X, y, key):
        losses, after, grad1 = follow_machine(
            model, shapes, params, X, y, key, epochs=epochs,
            batch_size=batch_size, lookback=lookback, shuffle=shuffle, fault=fault,
        )
        delta = jnp.stack([
            jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(params))
        ])
        return losses, delta, grad1, after

    return jax.jit(jax.vmap(one))


def follow_block(model, shapes, params, X, y, keys, *, epochs, batch_size,
                 lookback, shuffle, fault=None):
    """
    The same for a block of machines (leading axis on every argument), as one
    jitted call. Returns host arrays: losses
    (block, epochs), per-leaf norms of the parameters' change (block, leaves)
    and of the first gradient (block, leaves), leaves in ``leaf_names`` order;
    and the parameters after the last epoch, left on the device.
    """
    fn = _block_fn(model, shapes_key(shapes), epochs, batch_size, lookback, shuffle, fault)
    losses, delta, grad1, after = fn(params, X, y, keys)
    return (*jax.device_get((losses, delta, grad1)), after)
