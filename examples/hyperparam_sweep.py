"""
Hyperparameter-sweep example: N learning-rate trials trained as ONE
compiled fleet program (the TPU-native replacement for one-Katib-pod-per-
trial; see docs/parallelism.md "Hyperparameter sweeps as fleets").

Run: python examples/hyperparam_sweep.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from gordo_tpu.data import RandomDataset  # noqa: E402
from gordo_tpu.models.factories.feedforward import feedforward_hourglass  # noqa: E402
from gordo_tpu.parallel import HyperparamSweep, auto_device_mesh  # noqa: E402


def main():
    dataset = RandomDataset(
        train_start_date="2020-01-01T00:00:00+00:00",
        train_end_date="2020-01-08T00:00:00+00:00",
        tag_list=[f"tag-{i}" for i in range(6)],
        asset="example-asset",
    )
    X, y = dataset.get_data()
    print(f"data: {X.shape}")

    mesh = auto_device_mesh()
    spec = feedforward_hourglass(n_features=X.shape[1])
    sweep = HyperparamSweep(
        spec,
        {"learning_rate": list(np.logspace(-5, -1.5, 8))},
        mesh=mesh,
    )
    result = sweep.fit(np.asarray(X, dtype="float32"), epochs=20, batch_size=128)

    print("\ntrial ranking (best first):")
    for hyperparams, loss in result.ranking():
        print(f"  lr={hyperparams['learning_rate']:.2e}  final loss {loss:.5f}")
    print(f"\nbest: {result.best_hyperparams}")


if __name__ == "__main__":
    main()
