"""
Finding a cell's files by the names in ``BENCHMARK.json``: its configuration,
its traffic mix, the driver of the mix's kind, the model kind's modules, and
one reader per metric. A later PR adds files and entries; nothing here names
a cell, a configuration or a metric.
"""

import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def read_json(path):
    with open(path) as fh:
        return json.load(fh)


def benchmark():
    return read_json(ROOT / "BENCHMARK.json")


def cell(bench, name):
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise SystemExit(f"unknown --workload {name!r}; BENCHMARK.json has: {known}")


def config(bench, name, preset=None):
    """The configuration as run; ``preset`` lays a named preset of the file
    over it (the tiny sizes of the CPU rehearsal and the tests)."""
    entry = next(c for c in bench["configs"] if c["name"] == name)
    cfg = read_json(ROOT / entry["file"])
    if preset:
        cfg.update(cfg["presets"][preset])
    return cfg


def traffic(name):
    return read_json(HERE / "traffic" / f"{name}.json")


def limits(cell_name, preset=None):
    """The cell's limits: those set from readings on the chip at the cell's own
    size, or, for a rehearsal, the preset's own (a CPU multiplies float32
    exactly, so a sound run and the control both read lower there)."""
    table = read_json(HERE / "limits" / f"{cell_name}.json")
    rehearsals = table.pop("rehearsal", {})
    return rehearsals[preset] if preset else table


def peaks(device_kind):
    table = read_json(HERE / "peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"chipbench/peaks.json has no peaks for device kind {device_kind!r}"
        )
    return table[device_kind]


def kind_module(package, kind):
    """``chipbench.<package>.<kind>``: a driver, a reference, an adapter or
    a flops module."""
    return importlib.import_module(f"chipbench.{package}.{kind}")


def metric_reader(directory, name):
    """The ``read(ctx)`` of ``chipbench/<directory>/<name>.py``. Metric names
    may hold dots, so the file is loaded by path."""
    path = HERE / directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{directory}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_for(bench, section, cell_name):
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [
        m for m in bench[section]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
