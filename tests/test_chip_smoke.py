"""
chip_smoke.py rehearsed on the CPU at its tiny size: the control flow, the
contract's last line, and the process model are what a CPU run can show.
That the verdict on any platform but ``tpu`` is a failure is the point —
a CPU rehearsal must never be mistaken for the chip's answer.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).parent.parent
SMOKE = REPO_ROOT / "chip_smoke.py"


def _run_smoke(out_dir, *argv, xla_flags=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=xla_flags)
    env.pop("GORDO_TPU_EVENT_LOG", None)
    return subprocess.run(
        [sys.executable, str(SMOKE), "--size", "tiny", "--out", str(out_dir),
         *argv],
        env=env, capture_output=True, text=True, timeout=600,
    )


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One tiny-size run of all five phases on the CPU backend."""
    return _run_smoke(tmp_path_factory.mktemp("smoke"))


def test_cpu_rehearsal_exits_nonzero_and_names_the_platform(rehearsal):
    assert rehearsal.returncode != 0, rehearsal.stdout
    assert (
        "JAX's platform is 'cpu' (cpu), not 'tpu'" in rehearsal.stdout
    ), rehearsal.stdout
    last = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_last_line_is_the_contracts_json_object(rehearsal):
    last = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_phases_report_what_a_cpu_can_show(rehearsal):
    """Build, serve and parity pass (the path works); device fails on the
    platform and kernel on the missing Mosaic call (the CPU interprets)."""
    out = rehearsal.stdout
    summary = next(
        line for line in out.splitlines() if line.startswith("summary: ")
    )
    assert (
        "device=FAIL build=PASS serve=PASS kernel=FAIL parity=PASS" in summary
    ), out
    assert "the compiled step's text has no tpu_custom_call" in out
    # the serving checks the verdict rests on, in words
    assert "0 program_cache_fallback" in out
    assert "bit-identical: True" in out


def test_parent_process_never_imports_jax(tmp_path):
    """The child-process model: the parent that spawns must stay off JAX
    (a parent holding the chip hangs its children on real hardware and
    passes every CPU rehearsal), and spawning with jax loaded is refused."""
    probe = (
        "import sys, runpy\n"
        f"sys.argv = [{str(SMOKE)!r}, '--size', 'tiny', '--out', "
        f"{str(tmp_path)!r}, '--chips', '4']\n"
        "try:\n"
        f"    runpy.run_path({str(SMOKE)!r}, run_name='__main__')\n"
        "except SystemExit:\n"
        "    pass\n"
        "print('JAX_IN_PARENT', 'jax' in sys.modules)\n"
    )
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = subprocess.run(
        [sys.executable, "-c", probe], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert "JAX_IN_PARENT False" in proc.stdout, proc.stdout + proc.stderr
    # ... and the four-chip comparison itself holds on 4 virtual devices
    assert (
        "comparison: sharding spans 4 devices, losses and params within "
        "tolerance" in proc.stdout
    ), proc.stdout
    lines = [
        line for line in proc.stdout.strip().splitlines()
        if not line.startswith("JAX_IN_PARENT")
    ]
    last = json.loads(lines[-1])
    assert last == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }


def test_spawning_with_jax_loaded_is_refused(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    import jax  # noqa: F401 - the suite has it loaded anyway

    with pytest.raises(RuntimeError, match="imported jax"):
        smoke.start_child(
            [sys.executable, "-c", "pass"], str(tmp_path / "child.log")
        )
