"""
Long-context example: train a Transformer on windows sharded across the
device mesh's sequence axis (ring attention), then serve the trained
params single-device.

Run (8 virtual CPU devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python examples/long_context_training.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import numpy as np

    from gordo_tpu.parallel import LongContextTrainer, get_device_mesh
    from gordo_tpu.parallel.sequence import SEQ_AXIS

    n_devices = len(jax.devices())
    mesh = get_device_mesh(shape=(n_devices,), axis_names=(SEQ_AXIS,))
    print(f"mesh: {n_devices} devices on axis {SEQ_AXIS!r}")

    n_features, seq_len = 8, 64 * n_devices  # each device holds seq/N steps
    rng = np.random.default_rng(0)
    windows = rng.normal(size=(4, seq_len, n_features)).astype("float32")
    targets = windows[:, -1, :]  # reconstruct the final timestep

    trainer = LongContextTrainer(
        n_features=n_features, mesh=mesh, d_model=32, n_heads=4, n_layers=2
    )
    params, opt_state = trainer.init(jax.random.PRNGKey(0))
    for step in range(20):
        params, opt_state, loss = trainer.train_step(
            params, opt_state, windows, targets
        )
        if step % 5 == 0:
            print(f"step {step:2d} loss {float(loss):.4f}")

    out = trainer.predict(params, windows)  # local twin, same params
    print("single-device inference:", out.shape)


if __name__ == "__main__":
    main()
