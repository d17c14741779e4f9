"""
Utility-layer tests (reference model: tests/gordo/util/ — disk_registry
key semantics, capture_args round-trip capture, non-ascii replacement).
"""

import pytest

from gordo_tpu.utils import disk_registry
from gordo_tpu.utils.utils import (
    capture_args,
    replace_all_non_ascii_chars_with_default,
)


def test_registry_write_get_delete(tmp_path):
    reg = tmp_path / "registry"
    assert disk_registry.get_value(reg, "missing") is None

    disk_registry.write_key(reg, "abc-123", "some/output/dir")
    assert disk_registry.get_value(reg, "abc-123") == "some/output/dir"

    # overwrite wins
    disk_registry.write_key(reg, "abc-123", "other/dir")
    assert disk_registry.get_value(reg, "abc-123") == "other/dir"

    assert disk_registry.delete_value(reg, "abc-123") is True
    assert disk_registry.get_value(reg, "abc-123") is None
    assert disk_registry.delete_value(reg, "abc-123") is False


def test_registry_nonexistent_dir_reads_none(tmp_path):
    assert disk_registry.get_value(tmp_path / "nope", "k") is None
    assert disk_registry.delete_value(tmp_path / "nope", "k") is False


@pytest.mark.parametrize("bad", ["a/b", "../x", "a b", "", "k\n", ".", ".."])
def test_registry_rejects_path_escaping_keys(bad, tmp_path):
    with pytest.raises(ValueError):
        disk_registry.write_key(tmp_path, bad, "v")


def test_registry_value_coerced_to_str(tmp_path):
    disk_registry.write_key(tmp_path, "num", 42)
    assert disk_registry.get_value(tmp_path, "num") == "42"


def test_capture_args_records_effective_config():
    class Thing:
        @capture_args
        def __init__(self, a, b=10, *args, c="x", **kwargs):
            pass

    t = Thing(1, 2, 3, c="y", extra=True)
    assert t._params == {"a": 1, "b": 2, "args": [3], "c": "y", "extra": True}

    # defaults applied when not passed
    t2 = Thing(5)
    assert t2._params["b"] == 10
    assert t2._params["c"] == "x"


def test_capture_args_used_by_dataset_roundtrip():
    from gordo_tpu.data import TimeSeriesDataset
    from gordo_tpu.data.providers import RandomDataProvider

    ds = TimeSeriesDataset(
        data_provider=RandomDataProvider(),
        train_start_date="2020-01-01T00:00:00+00:00",
        train_end_date="2020-01-02T00:00:00+00:00",
        tag_list=["tag-1"],
        asset="asset",
    )
    d = ds.to_dict()
    assert d["train_start_date"].startswith("2020-01-01")
    assert d["type"].endswith("TimeSeriesDataset")


def test_replace_non_ascii():
    assert replace_all_non_ascii_chars_with_default("abcæøå123") == "abc---123"
    assert replace_all_non_ascii_chars_with_default("åbc", "_") == "_bc"
    assert replace_all_non_ascii_chars_with_default("plain") == "plain"


def test_enable_compile_cache_env_resolution(monkeypatch):
    """One cache, placed from outside: with JAX_COMPILATION_CACHE_DIR set
    no directory is set in code (JAX read the variable itself); unset, the
    directory is the fixed in-checkout <repo>/.jax_cache — never a temp
    name."""
    import os

    import jax

    from gordo_tpu.utils import compile_cache_dir, enable_compile_cache

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    updates = []
    real_update = jax.config.update

    def recording_update(name, value):
        updates.append(name)
        real_update(name, value)

    prior_dir = jax.config.jax_compilation_cache_dir
    prior_floor = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setattr(jax.config, "update", recording_update)
    try:
        # conftest placed the session cache through the variable
        placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
        enable_compile_cache()
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates
        assert compile_cache_dir() == placed
        assert jax.config.jax_compilation_cache_dir == placed

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compile_cache()
        assert "jax_compilation_cache_dir" in updates
        assert compile_cache_dir() == os.path.join(repo_root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == compile_cache_dir()
    finally:
        real_update("jax_compilation_cache_dir", prior_dir)
        real_update("jax_persistent_cache_min_compile_time_secs", prior_floor)


def test_compile_cache_keeps_programs_apart_by_their_scope_names(tmp_path):
    """Two programs that differ only in a ``jax.named_scope`` are two cache
    entries once ``enable_compile_cache`` has run: a profiler trace of an
    executable loaded from the cache shows the names of the source that
    compiled it, so an entry of another version must not be taken for it
    (JAX's default key leaves metadata out)."""
    import os

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from gordo_tpu.utils import enable_compile_cache

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_include_metadata_in_key",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prior = {name: getattr(jax.config, name) for name in names}

    def program(scope):
        def body(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0

        return jax.jit(body)

    def entries():
        return {f for f in os.listdir(tmp_path) if f.startswith("jit_body")}

    try:
        enable_compile_cache(min_compile_seconds=0.0)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        program("fleet.gather")(jnp.ones(4)).block_until_ready()
        first = entries()
        assert len(first) == 1
        program("fleet.order")(jnp.ones(4)).block_until_ready()
        assert len(entries()) == 2
    finally:
        for name, value in prior.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()

