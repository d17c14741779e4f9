"""
Driver of the traffic kind ``fit_loop``: back-to-back ``FleetTrainer.fit``
calls on one stacked bucket.

Set-up builds ONE trainer the way ``build-fleet`` does (the configuration's
model definition through ``serializer.from_definition`` to the estimator's
spec), makes the bucket's data and initial parameters on the device from the
seed, and drives the trainer through its first ``fit`` call: the call that
compiles, and the one the reference follows. The window then goes on calling
that same trainer, each call from the parameters the last one returned.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare
from chipbench import data as seeded
from chipbench.drivers.shared import BaseDriver, leaf_change_norms


class Driver(BaseDriver):
    #: host spans the trace reduction looks for: the harness's own around
    #: each call first, then the program's own around each dispatch
    SPANS = ("fit_call", "train-dispatch")
    #: what the epoch program's name holds in the trace (the jitted
    #: ``vmap(machine_epoch)`` of parallel/fleet.py)
    EPOCH_PROGRAM = "machine_epoch"
    WINDOW_PROGRAMS = (EPOCH_PROGRAM,)

    def timesteps_per_call(self):
        """Sensor-timesteps one call trains: machines x real rows x tags,
        once per epoch."""
        return self.machines * self.rows * self.shapes["n_features"] * self.epochs

    def setup(self):
        marks = [("start", time.perf_counter())]
        from gordo_tpu import serializer
        from gordo_tpu.builder.fleet_build import _find_jax_estimator
        from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

        estimator = _find_jax_estimator(
            serializer.from_definition(self.model_definition())
        )
        estimator.kwargs.update(
            n_features=self.shapes["n_features"],
            n_features_out=self.shapes["n_features_out"],
        )
        spec = estimator._build_spec()
        self.shuffle = not spec.windowed
        self.trainer = FleetTrainer(spec, lookahead=0)
        marks.append(("imports+spec", time.perf_counter()))

        tags = self.shapes["n_features"]
        X = seeded.fleet_series(
            self.seed, self.machines, self.rows, tags, self.config["data"]
        )
        self.X = X
        self.data = StackedData(
            X, jnp.array(X, copy=True),
            jnp.ones((self.machines, self.rows), jnp.float32),
        )
        self.keys = seeded.machine_keys(self.seed, self.machines)
        jax.block_until_ready(self.data.y)
        marks.append(("data", time.perf_counter()))
        tree = self.adapter.to_program(
            jax.tree.map(jnp.copy, self.initial_params()), self.shapes
        )

        jax.block_until_ready(tree)
        marks.append(("init", time.perf_counter()))
        self.params, losses = self._fit(tree)
        marks.append(("first_call", time.perf_counter()))
        after = self.adapter.from_program(self.params, self.shapes)
        change = leaf_change_norms(after, self.initial_params())
        self.first_call = {
            "losses": np.asarray(losses, dtype=np.float64).T,  # (M, epochs)
            "change": np.asarray(jax.device_get(change), dtype=np.float64),
        }
        marks.append(("change_norms", time.perf_counter()))
        self.log_phases(marks)

    def _fit(self, params):
        params, losses = self.trainer.fit(
            self.data, self.keys, epochs=self.epochs,
            batch_size=self.batch_size, params=params,
        )
        jax.block_until_ready(params)
        return params, losses

    def one_call(self):
        self.params, losses = self._fit(self.params)
        bad = ~np.isfinite(np.asarray(losses)).all(axis=0)
        healthy = getattr(self.trainer, "healthy_", None)
        if healthy is not None:
            bad |= ~np.asarray(healthy, dtype=bool)
        telemetry = dict(getattr(self.trainer, "fit_telemetry_", {}) or {})
        return {"telemetry": telemetry, "failed": int(bad.sum())}

    def run_calls(self, seconds=None, max_calls=None, span=None):
        record = super().run_calls(seconds, max_calls, span)
        record["timesteps"] = len(record["calls"]) * self.timesteps_per_call()
        record["epochs_per_call"] = self.epochs
        return record

    def release(self):
        """Free what the program holds on the device, keeping only the
        benchmark's own inputs for the reference."""
        self.params = None
        self.data = None
        self.trainer = None

    def numbers(self, program, reference):
        return compare.training_numbers(program, reference)

    def reference(self, fault=None):
        """The reference's numbers for the first call, in blocks of machines."""
        from chipbench.reference import training

        block = int(self.config["reference"]["machine_block"])
        params0 = self.initial_params()
        outs = []
        for lo in range(0, self.machines, block):
            sl = slice(lo, min(lo + block, self.machines))
            outs.append(training.follow_block(
                self.model, self.shapes,
                jax.tree.map(lambda a: a[sl], params0),
                self.X[sl], self.X[sl], self.keys[sl],
                epochs=self.epochs, batch_size=self.batch_size,
                lookback=self.shapes.get("lookback", 1),
                shuffle=self.shuffle, fault=fault,
            )[:3])
        losses, change, grad1 = (np.concatenate(x).astype(np.float64) for x in zip(*outs))
        return {"losses": losses, "change": change, "grad1": grad1}
