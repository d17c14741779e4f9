"""What both kinds of driver share: the fields read from a configuration, the
seed's initial parameters, the model definition as the program gets it, the
loop of back-to-back calls, and the norms of the parameters' change."""

import contextlib
import copy
import gc
import resource
import sys
import time

import jax
import jax.numpy as jnp

from chipbench import data as seeded
from chipbench import loading


def estimator_kwargs(definition):
    """The kwargs dict of the gordo estimator inside a model definition."""
    if isinstance(definition, dict):
        for key, value in definition.items():
            if key.startswith("gordo_tpu.models.models."):
                return value
            found = estimator_kwargs(value)
            if found is not None:
                return found
    elif isinstance(definition, list):
        for item in definition:
            found = estimator_kwargs(item)
            if found is not None:
                return found
    return None


@jax.jit
def leaf_change_norms(after, before):
    """(machines, leaves): norm of each machine's change of each leaf."""
    return jnp.stack(
        [
            jnp.sqrt(jnp.sum((a - b).reshape(a.shape[0], -1) ** 2, axis=1))
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
        ],
        axis=1,
    )


def host_counters():
    """What the host did in this process so far: CPU seconds and full
    garbage collections. A call's record keeps the difference over the call,
    so that a call that stalls says whether the host was working or waiting.
    (The kernel's counts of context switches and the load average read 0 on
    the chip's machine, so they are not kept.)"""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return {
        "cpu_s": usage.ru_utime + usage.ru_stime,
        "full_gcs": gc.get_stats()[2]["collections"],
    }


class BaseDriver:
    """A driver adds ``SPANS`` (the harness's span around each call first),
    ``WINDOW_PROGRAMS`` (parts of the names of the programs its window
    runs), ``setup()``, ``one_call()`` (a call's record, with its ``failed``
    units), ``release()``, ``reference()`` and ``numbers()``."""

    def __init__(self, config, traffic, seed, dtype=None):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.shapes = config["shapes"]
        self.machines = int(config["bucket"]["machines"])
        self.rows = int(config["bucket"]["rows"])
        self.epochs = int(config["fit"]["epochs"])
        self.batch_size = int(config["fit"]["batch_size"])
        self.model = loading.kind_module("reference", config["model_kind"])
        self.adapter = loading.kind_module("adapters", config["model_kind"])
        #: the control's switch: the model definition's own ``dtype``
        self.dtype = dtype or config["dtype"]
        self.first_call = None
        self._params0 = None

    def initial_params(self):
        """The machines' initial parameters under the reference's names,
        made once from the seed and kept."""
        if self._params0 is None:
            keys = seeded.init_keys(self.seed, self.machines)
            self._params0 = jax.jit(
                jax.vmap(lambda k: self.model.init(k, self.shapes))
            )(keys)
        return self._params0

    def model_definition(self):
        """The configuration's model definition with the preset's sizes, the
        fit's epochs and batch size, and the dtype of this run."""
        definition = copy.deepcopy(self.config["model"])
        kwargs = estimator_kwargs(definition)
        kwargs.update(self.config.get("model_overrides", {}))
        kwargs.update(dtype=self.dtype, epochs=self.epochs, batch_size=self.batch_size)
        return definition

    @staticmethod
    def log_phases(marks):
        print("set-up phases:", ", ".join(
            f"{name} {t - marks[i][1]:.2f} s" for i, (name, t) in enumerate(marks[1:])
        ), file=sys.stderr, flush=True)

    def run_calls(self, seconds=None, max_calls=None, span=None):
        """Make calls back to back until ``seconds`` have passed at a call
        boundary, or ``max_calls`` calls are done. Returns the record of this
        stretch: a rate is all of its work over all of its time."""
        span = span or (lambda name: contextlib.nullcontext())
        record = {"calls": [], "units": 0, "failed": 0}
        start = time.perf_counter()
        while True:
            before, t0 = host_counters(), time.perf_counter()
            with span(self.SPANS[0]):
                call = self.one_call()
            now = time.perf_counter()
            call["seconds"] = now - t0
            after = host_counters()
            call["host"] = {k: round(after[k] - before[k], 3) for k in after}
            record["calls"].append(call)
            record["units"] += self.machines
            record["failed"] += call["failed"]
            if max_calls is not None and len(record["calls"]) >= max_calls:
                break
            if seconds is not None and now - start >= seconds:
                break
        record["elapsed_s"] = time.perf_counter() - start
        return record

    def compare(self):
        """The numbers that decide ``correct``: set-up's first call as the
        timed path made it, against the reference's."""
        return self.numbers(self.first_call, self.reference())
