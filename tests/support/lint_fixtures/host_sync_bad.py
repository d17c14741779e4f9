"""POSITIVE fixture for host-sync: device->host round-trips inside loop
bodies — each shape stalls the asynchronous dispatch pipeline once per
iteration."""

import jax
import numpy as np

step_fn = jax.jit(lambda p, x: (p, (p * x).sum()))


def train(params, batches):
    losses = []
    for batch in batches:
        params, loss = step_fn(params, batch)
        losses.append(float(loss))  # per-epoch sync of a jitted result
    return params, losses


def busy_wait(handles):
    while handles:
        h = handles.pop()
        h.block_until_ready()  # readiness sync per iteration
        jax.device_get(h)  # transfer per iteration


def drain(params, batches):
    out = []
    for batch in batches:
        _, loss = step_fn(params, batch)
        out.append(np.asarray(step_fn(params, batch)))  # sync per iter
        out.append(loss.item())  # scalar sync per iter
    return out
