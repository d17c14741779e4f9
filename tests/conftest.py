"""
Test configuration.

TPU twist on the reference's fixture spine (SURVEY.md §4): XLA-on-CPU is the
"fake backend" — tests force the CPU platform with 8 virtual devices so
multi-chip sharding logic is exercised without TPU hardware.
``JAX_PLATFORMS=cpu`` in the environment is sufficient (set before jax is
imported).
"""

import atexit
import os
import shutil
import sys
import tempfile

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the persistent compile cache lives in a session temp dir: tier-1 must
# never write XLA:CPU executables into the checkout (the utils default,
# <repo>/.jax_cache) — the checkout is what gets copied to the chip
# machine, and a CPU cache carried to another host risks SIGILL on load.
# Set before jax is imported: JAX reads the variable once, at import —
# and only then (this file runs again when a test imports tests.conftest).
if "jax" not in sys.modules:
    _session_cache_dir = tempfile.mkdtemp(prefix="gordo_tpu_test_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _session_cache_dir
    atexit.register(shutil.rmtree, _session_cache_dir, ignore_errors=True)

import pytest  # noqa: E402

# --- the lock-order sanitizer (docs/static_analysis.md) ---------------------
# GORDO_LOCK_SANITIZE=1 (`make test-sanitize`) instruments the threading
# constructors for the WHOLE run, so every tier-1 test doubles as a
# lock-discipline probe; the observed lock graph (edges, ordering
# inversions, runtime blocking-under-lock witnesses) dumps as JSON at
# session end for `gordo-tpu lockgraph`. Installed at import time —
# before test modules (and the package modules they pull in) construct
# their locks.

from gordo_tpu.analysis import lock_sanitizer  # noqa: E402

if lock_sanitizer.enabled():
    lock_sanitizer.install()


def pytest_sessionfinish(session, exitstatus):
    if lock_sanitizer.enabled() and lock_sanitizer.installed():
        path = lock_sanitizer.dump_report()
        report = lock_sanitizer.report()
        sys.stdout.write(
            f"\nlock sanitizer: {len(report['nodes'])} site(s), "
            f"{len(report['edges'])} edge(s), "
            f"{len(report['inversions'])} inversion(s), "
            f"{len(report['blocking'])} blocking event(s) -> {path}\n"
        )


@pytest.fixture
def no_persistent_compile_cache():
    """
    JAX's persistent compile cache off for one test. For tests whose
    subject is an executable's own bytes or a compile for a device that is
    not attached: an XLA:CPU executable that came from a cache HIT
    re-serializes to a payload that no longer executes, and a compile for
    a described TPU topology is written to the cache but cannot be read
    back without a chip.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def tmp_dir_session(tmp_path_factory):
    return tmp_path_factory.mktemp("gordo-tpu-session")


# --- the one-real-trained-artifact fixture spine (SURVEY.md §4) -------------

GORDO_PROJECT = "gordo-test"
GORDO_TARGETS = ["gordo-test-model"]
GORDO_SINGLE_TARGET = GORDO_TARGETS[0]
GORDO_BASE_TARGETS = ["gordo-base-model"]
GORDO_REVISION = "1573740000000"

SENSORS = [f"tag-{i}" for i in range(4)]

CONFIG_STR = f"""
machines:
  - name: {GORDO_SINGLE_TARGET}
    dataset:
      type: RandomDataset
      tags: {SENSORS}
      target_tag_list: {SENSORS}
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-03T00:00:00+00:00'
      asset: gra
    model:
      gordo_tpu.models.anomaly.DiffBasedAnomalyDetector:
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
              - sklearn.preprocessing.MinMaxScaler
              - gordo_tpu.models.AutoEncoder:
                  kind: feedforward_hourglass
                  epochs: 2
  - name: {GORDO_BASE_TARGETS[0]}
    dataset:
      type: RandomDataset
      tags: {SENSORS}
      target_tag_list: {SENSORS}
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-03T00:00:00+00:00'
      asset: gra
    model:
      gordo_tpu.models.AutoEncoder:
        kind: feedforward_hourglass
        epochs: 1
"""


@pytest.fixture(scope="session")
def trained_model_collection(tmp_path_factory):
    """
    Train the real artifacts once per session via ``local_build`` on random
    data and lay them out the way a deployment does:
    ``<root>/<project>/models/<revision>/<machine>/{model.pkl,metadata.json}``
    (reference: tests/conftest.py:141-194; layout from
    argo-workflow.yml.template:669-671).
    """
    from gordo_tpu import serializer
    from gordo_tpu.builder import local_build

    root = tmp_path_factory.mktemp("collection")
    collection_dir = root / GORDO_PROJECT / "models" / GORDO_REVISION
    for model, machine in local_build(CONFIG_STR):
        out = collection_dir / machine.name
        serializer.dump(model, out, metadata=machine.to_dict())
    return collection_dir


@pytest.fixture
def model_collection_env(trained_model_collection, monkeypatch):
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(trained_model_collection))
    return str(trained_model_collection)


@pytest.fixture
def gordo_ml_server_client(model_collection_env):
    """werkzeug test client against the real app (reference: conftest.py:202-214)."""
    from werkzeug.test import Client

    from gordo_tpu.server import build_app

    from gordo_tpu.server import utils as server_utils

    server_utils.clear_caches()
    return Client(build_app())


N_SAMPLES = 10


@pytest.fixture
def sensor_frame():
    """A small indexed frame shaped like the trained machines' inputs."""
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(1)
    index = pd.date_range("2019-01-01", periods=N_SAMPLES, freq="10min", tz="UTC")
    return pd.DataFrame(
        rng.random((N_SAMPLES, len(SENSORS))), columns=SENSORS, index=index
    )
