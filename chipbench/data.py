"""
Seeded inputs, made on the device: sensor series for a fleet bucket and the
machines' keys. One general generator; a configuration's ``data`` block holds
its parameters.

A machine's ``tags`` series are driven by a few slow latent oscillations of
its own (a plant's load, ambient temperature, a control loop), mixed through
a random matrix, squashed and laid into [0, 1] as a fitted ``MinMaxScaler``
leaves them, plus white sensor noise. So an autoencoder has structure to
learn, gradients are not noise, and the loss falls for many epochs. Each
machine is generated on its own inside one jitted call (``lax.map``), so the
call's temporaries are one machine's, not the bucket's.
"""

import functools
import zlib

import jax
import jax.numpy as jnp

_MASK31 = (1 << 31) - 1


def base_key(seed: int):
    """A key from any non-negative whole seed, also one past 31 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    return jax.random.fold_in(jax.random.PRNGKey(seed & _MASK31), seed >> 31)


def stream(seed: int, name: str):
    """An independent key for one named use of the seed."""
    return jax.random.fold_in(base_key(seed), zlib.crc32(name.encode()) & _MASK31)


@functools.partial(jax.jit, static_argnames=("rows", "tags", "n_latent"))
def _series(keys, rows, tags, n_latent, noise):
    t = jnp.arange(rows, dtype=jnp.float32)[:, None] / rows

    def one(key):
        k_freq, k_phase, k_mix, k_noise = jax.random.split(key, 4)
        cycles = jnp.exp(jax.random.uniform(
            k_freq, (n_latent,), minval=jnp.log(2.0), maxval=jnp.log(200.0)))
        phase = jax.random.uniform(k_phase, (n_latent,), maxval=2 * jnp.pi)
        latent = jnp.sin(2 * jnp.pi * cycles[None, :] * t + phase[None, :])
        mix = jax.random.normal(k_mix, (n_latent, tags)) / jnp.sqrt(n_latent / 2.0)
        clean = 0.5 + 0.4 * jnp.tanh(latent @ mix)
        x = clean + noise * jax.random.normal(k_noise, (rows, tags))
        return jnp.clip(x, 0.0, 1.0)

    return jax.lax.map(one, keys)


def _generate(keys, rows, tags, params):
    return _series(
        keys, rows, tags, int(params.get("n_latent", 6)),
        jnp.float32(params.get("noise", 0.05)),
    )


def fleet_series(seed: int, machines: int, rows: int, tags: int, params: dict,
                 name: str = "series"):
    """(machines, rows, tags) float32 on the default device, from the seed;
    ``name`` tells one bucket's series from another's."""
    keys = jax.random.split(stream(seed, name), machines)
    return _generate(keys, rows, tags, params)


def machine_series(seed: int, machines: int, index: int, rows: int, tags: int,
                   params: dict, name: str = "series"):
    """(rows, tags): machine ``index``'s own series of ``fleet_series``."""
    keys = jax.random.split(stream(seed, name), machines)
    return _generate(keys[index:index + 1], rows, tags, params)[0]


def machine_keys(seed: int, machines: int):
    """The machines' training keys (what ``fit`` shuffles from)."""
    return jax.random.split(stream(seed, "fit"), machines)


def init_keys(seed: int, machines: int):
    """The machines' keys for their initial parameters."""
    return jax.random.split(stream(seed, "init"), machines)
