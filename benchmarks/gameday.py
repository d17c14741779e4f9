"""
Game-day benchmark (``make bench-gameday``, docs/robustness.md "Game
days"): run the full shipped scenario catalogue against an in-process
plane and write one results file with the composed verdict per scenario
— SLO budget burn, unstructured-error count, stream resumes, sheds
honored, fault sites fired, bit-identity. ``benchmarks/consolidate.py``
stamps the pass/fail + per-scenario burn rates into trajectory.json so
robustness regressions trend across PRs exactly like perf regressions.

    python benchmarks/gameday.py --output benchmarks/results_gameday_cpu_r19.json

CPU-runnable end to end (JAX_PLATFORMS=cpu); on TPU the same scenarios
drive the real device path.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    parser.add_argument(
        "--scenario",
        action="append",
        default=None,
        help="Scenario name (repeatable); default is the full catalogue.",
    )
    parser.add_argument("--output", default=None)
    args = parser.parse_args()

    from gordo_tpu.scenario import (
        builtin_scenarios,
        run_scenario,
        shared_gameday_collection,
    )

    shipped = builtin_scenarios()
    names = args.scenario or sorted(shipped)
    unknown = sorted(set(names) - set(shipped))
    if unknown:
        parser.error(f"unknown scenario(s) {unknown}; shipped: {sorted(shipped)}")

    workdir = tempfile.mkdtemp(prefix="gordo-gameday-bench-")
    started = time.time()
    reports = []
    try:
        print("training the gameday fleet (one-time) ...", file=sys.stderr)
        collection = shared_gameday_collection(workdir)
        for name in names:
            report = run_scenario(shipped[name], collection, workdir)
            reports.append(report)
            print(
                f"{name}: {'pass' if report['ok'] else 'FAIL'} "
                f"(burn {report['slo']['max_burn_rate']:.2f}x, "
                f"{report['wall_time_s']:.1f}s)",
                file=sys.stderr,
            )
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    failed = [r for r in reports if not r["ok"]]
    out = {
        "bench_schema_version": 1,
        "bench": "gameday",
        "n_scenarios": len(reports),
        "n_failed": len(failed),
        "ok": not failed,
        # the trajectory headline: 1.0 means the whole catalogue held
        # its budgets; anything less is a robustness regression
        "scenarios_passed_fraction": round(
            (len(reports) - len(failed)) / max(1, len(reports)), 4
        ),
        "wall_time_s": round(time.time() - started, 2),
        "scenarios": reports,
    }
    rendered = json.dumps(out, indent=2, default=str)
    print(rendered)
    if args.output:
        with open(args.output, "w") as fh:
            fh.write(rendered + "\n")
        print(f"wrote {args.output}", file=sys.stderr)
    return len(failed)


if __name__ == "__main__":
    sys.exit(main())
