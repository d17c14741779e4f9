"""
``correct`` has to come out false when the timed path is broken, and true
when it is sound. Each test skips the harness's look for a chip (the tiny
preset of the cell's configuration, on whatever JAX runs on) and drives the
rest of a run: set-up with the first ``fit`` call, a short window, the
reference, the comparison against the limits of the cell's tiny preset.

Faults planted underneath the harness, in the program:
- a step that returns its state unchanged (``fit`` hands back the parameters
  it was given);
- half of the rows left out, the mean taken over the rest (``fit`` zeroes
  the weight of the second half of each machine's rows);
and the control: the program with its own lower-precision path switched on
(the model definition's ``dtype: bfloat16``).
"""

import json

import numpy as np
import pytest

from chipbench import harness, loading, run

CELLS = [w["name"] for w in loading.benchmark()["workloads"]]


def drive(cell, seed=11, **driver_options):
    result, code = harness.run_cell(
        cell, seed, seconds=0.2, trace=False, rehearse="tiny",
        driver_options=driver_options,
    )
    return result, code


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result, code = drive(cell)
    assert result["correct"] is True
    assert code == harness.EXIT_REHEARSAL
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compiles_in_window"] == 0
    assert set(result["compared"]) == set(loading.limits(cell, "tiny"))
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_is_not_correct(cell, monkeypatch):
    from gordo_tpu.parallel.fleet import FleetTrainer

    real_fit = FleetTrainer.fit

    def fit(self, data, keys, *args, params=None, **kwargs):
        import jax
        import jax.numpy as jnp

        kept = jax.tree.map(jnp.copy, params)
        _, losses = real_fit(self, data, keys, *args, params=params, **kwargs)
        return kept, losses

    monkeypatch.setattr(FleetTrainer, "fit", fit)
    result, _ = drive(cell)
    assert result["correct"] is False
    assert result["compared"]["change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out_is_not_correct(cell, monkeypatch):
    from gordo_tpu.parallel.fleet import FleetTrainer

    real_fit = FleetTrainer.fit

    def fit(self, data, keys, *args, extra_weight=None, **kwargs):
        mask = np.ones(data.sample_weight.shape, np.float32)
        mask[:, mask.shape[1] // 2:] = 0.0
        if extra_weight is not None:
            mask = mask * np.asarray(extra_weight)
        return real_fit(self, data, keys, *args, extra_weight=mask, **kwargs)

    monkeypatch.setattr(FleetTrainer, "fit", fit)
    result, _ = drive(cell)
    assert result["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell, seed):
    """At the tiny preset, under the preset's own limits (a CPU multiplies
    float32 exactly: a sound run reads under 5e-7 on every number, the
    control from 4e-6 on the first loss and from 1e-3 on the change). At the
    cell's own size the control is read by ``chipbench/control.py`` on the
    chip, under the cell's limits (PERF.md section 2)."""
    result, _ = drive(cell, seed=seed, dtype="bfloat16")
    assert result["correct"] is False
    over = [r for r in result["compared"].values() if r["value"] > 2 * r["limit"]]
    assert over


def test_a_failed_machine_is_not_correct(monkeypatch):
    from gordo_tpu.parallel.fleet import FleetTrainer

    real_fit = FleetTrainer.fit
    calls = {"n": 0}

    def fit(self, *args, **kwargs):
        params, losses = real_fit(self, *args, **kwargs)
        calls["n"] += 1
        if calls["n"] > 1:  # the first call is set-up's; break the window's
            losses = np.array(losses, copy=True)
            losses[-1, 0] = np.nan
        return params, losses

    monkeypatch.setattr(FleetTrainer, "fit", fit)
    result, _ = drive(CELLS[0])
    assert result["failed"] > 0 and result["correct"] is False


def test_compilation_inside_the_window_breaks_the_run(monkeypatch):
    import jax
    import jax.numpy as jnp
    from gordo_tpu.parallel.fleet import FleetTrainer

    real_fit = FleetTrainer.fit
    calls = {"n": 0}

    def fit(self, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            jax.jit(lambda x: x * 3 + calls["n"])(jnp.ones((7, 3))).block_until_ready()
        return real_fit(self, *args, **kwargs)

    monkeypatch.setattr(FleetTrainer, "fit", fit)
    result, code = drive(CELLS[0])
    assert result["compiles_in_window"] >= 1
    assert code == harness.EXIT_BROKEN


def test_no_chip_no_result(capsys):
    """On a platform that is not a TPU the command exits non-zero and prints
    no result line."""
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("this machine has the chip")
    with pytest.raises(SystemExit) as exit_info:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "0.1"])
    assert exit_info.value.code == harness.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""


def test_rehearsal_line_is_never_a_result(capsys):
    code = run.main(["--workload", CELLS[0], "--seed", str(2**31 + 77),
                     "--seconds", "0.1", "--rehearse", "tiny"])
    assert code == harness.EXIT_REHEARSAL
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["comparison_passed"] is True
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == jax_platform()


def jax_platform():
    import jax

    return jax.devices()[0].platform
