"""
Bench-result consolidation (``make bench-summary``): the benchmarks/
directory has accreted 25+ ad-hoc ``results_*.json`` files with
divergent schemas — one per benchmark per PR revision. This tool folds
them into ONE ``benchmarks/trajectory.json``: per source file, the bench
name, revision tag (the ``_rNN`` filename convention), a headline metric
with units, and any knob settings the run recorded — so the performance
trajectory across PRs is one file instead of an archaeology dig, and the
autotuner's corpus reader (``gordo-tpu tune``, docs/tuning.md) ingests
the whole history through it.

    python benchmarks/consolidate.py                  # writes trajectory.json
    python benchmarks/consolidate.py --check          # print, write nothing

New bench outputs are stamped ``bench_schema_version``; the consolidator
accepts stamped and pre-stamp files alike (schema tolerance is the whole
point).
"""

import argparse
import json
import os
import re
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TRAJECTORY_SCHEMA_VERSION = 1

#: headline-metric candidates, priority order: (key, units). The first
#: key found (shallowest, then priority) names the file's headline.
HEADLINE_METRICS = (
    ("fleet_models_per_hour", "models/hour"),
    ("models_per_hour", "models/hour"),
    ("goodput_retained", "fraction"),
    ("goodput_retained_after_kill", "fraction"),
    ("scenarios_passed_fraction", "fraction"),
    ("first_predict_speedup", "x"),
    ("compile_reduction", "x"),
    ("speedup", "x"),
    ("goodput_machine_scores_per_s", "machine-scores/s"),
    ("machine_scores_per_s", "machine-scores/s"),
    ("mfu", "fraction"),
    ("p99_ms", "ms"),
    ("p95_ms", "ms"),
    ("mean_ms", "ms"),
    ("rps", "req/s"),
)

#: knob settings copied from the file's top level into the entry, so
#: trajectory.json rows remain usable tuning observations
_KNOB_KEYS = (
    "batch_wait_ms",
    "queue_limit",
    "batch_queue_limit",
    "bucket_policy",
    "workers",
    "lease_ttl",
    "lease_ttl_s",
    "hedge_ms",
)

_REVISION_RE = re.compile(r"_r(\d+)\b")


def _find_headline(document):
    """(key, value, units) for the shallowest, highest-priority headline
    metric anywhere in the document (breadth-first)."""
    queue = [document]
    while queue:
        level, queue = queue, []
        for node in level:
            if isinstance(node, dict):
                for key, units in HEADLINE_METRICS:
                    value = node.get(key)
                    if isinstance(value, (int, float)) and not isinstance(
                        value, bool
                    ):
                        return key, value, units
                queue.extend(node.values())
            elif isinstance(node, list):
                queue.extend(node)
    return None


def _bench_name(path: Path, document) -> str:
    for key in ("bench", "benchmark", "kind", "mode"):
        value = document.get(key) if isinstance(document, dict) else None
        if isinstance(value, str) and value:
            return value
    stem = path.stem
    stem = re.sub(r"^results_", "", stem)
    stem = _REVISION_RE.sub("", stem)
    return re.sub(r"_(cpu|tpu)$", "", stem) or path.stem


def _revision(path: Path) -> str:
    match = _REVISION_RE.search(path.stem)
    return f"r{int(match.group(1)):02d}" if match else ""


def consolidate(directory: Path) -> dict:
    entries = []
    patterns = ("results_*.json", "BENCH_r*.json", "MULTICHIP_r*.json")
    files = sorted(
        {p for pattern in patterns for p in directory.glob(pattern)}
    )
    for path in files:
        try:
            document = json.loads(path.read_text())
        except (OSError, ValueError) as exc:
            entries.append({"file": path.name, "error": str(exc)})
            continue
        headline = _find_headline(document)
        entry = {
            "file": path.name,
            "bench": _bench_name(path, document),
            "revision": _revision(path),
            "bench_schema_version": (
                document.get("bench_schema_version")
                if isinstance(document, dict)
                else None
            ),
        }
        if headline:
            key, value, units = headline
            entry["headline_metric"] = key
            entry["value"] = value
            entry["units"] = units
            # the metric under its OWN field name too, so a trajectory
            # row that also names a knob is a usable tuning observation
            # (the corpus walker matches signal fields by spelling)
            entry[key] = value
        if isinstance(document, dict):
            knobs = {
                key: document[key]
                for key in _KNOB_KEYS
                if isinstance(document.get(key), (int, float, str))
                and not isinstance(document.get(key), bool)
            }
            if knobs:
                entry.update(knobs)
            # benches run with --slo stamp an error-budget verdict; the
            # trajectory keeps the pass/fail + worst burn rate so a
            # regression shows up in ONE file (docs/observability.md)
            slo = document.get("slo")
            if isinstance(slo, dict) and "ok" in slo:
                entry["slo"] = {
                    "spec": slo.get("spec"),
                    "ok": slo.get("ok"),
                    "max_burn_rate": slo.get("max_burn_rate"),
                }
            # phase-ledger benches stamp where the wall time went; the
            # trajectory keeps the host/device split so a creeping host
            # seam (e.g. transform/serialize growth) trends in the same
            # file as the latencies (docs/observability.md "Time
            # attribution")
            attribution = document.get("phase_attribution")
            if isinstance(attribution, dict) and attribution.get(
                "host_fraction"
            ) is not None:
                entry["host_fraction"] = attribution["host_fraction"]
                entry["device_fraction"] = attribution.get(
                    "device_fraction"
                )
            # game-day runs stamp the composed per-scenario verdict so
            # a robustness regression (budget newly exhausted, a
            # post-condition newly failed) shows up in the SAME file
            # that trends perf (docs/robustness.md "Game days")
            scenarios = document.get("scenarios")
            if document.get("bench") == "gameday" and isinstance(
                scenarios, list
            ):
                entry["gameday"] = {
                    "ok": document.get("ok"),
                    "n_failed": document.get("n_failed"),
                    "scenarios": {
                        s.get("scenario"): {
                            "ok": s.get("ok"),
                            "max_burn_rate": (s.get("slo") or {}).get(
                                "max_burn_rate"
                            ),
                        }
                        for s in scenarios
                        if isinstance(s, dict)
                    },
                }
        entries.append(entry)
    return {
        "trajectory_schema_version": TRAJECTORY_SCHEMA_VERSION,
        "n_files": len(files),
        "entries": entries,
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    parser.add_argument(
        "--directory",
        default=os.path.dirname(os.path.abspath(__file__)),
        help="Directory holding the results_*.json files.",
    )
    parser.add_argument(
        "--output",
        default=None,
        help="Where to write trajectory.json (default: "
        "<directory>/trajectory.json).",
    )
    parser.add_argument(
        "--check",
        action="store_true",
        help="Print the trajectory without writing anything.",
    )
    args = parser.parse_args()
    directory = Path(args.directory)
    trajectory = consolidate(directory)
    rendered = json.dumps(trajectory, indent=2, sort_keys=True)
    print(rendered)
    if not args.check:
        out = Path(args.output or directory / "trajectory.json")
        from gordo_tpu.utils.atomic import atomic_write_json

        atomic_write_json(out, trajectory, indent=2, sort_keys=True)
        print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
