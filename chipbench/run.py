"""
The benchmark's command: ``python3 chipbench/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``, from the root of a checkout. One process,
which holds the chip itself; the last line of its standard output is the
result. ``--rehearse tiny`` runs the cell's tiny preset wherever JAX runs,
prints a line without a device metric and with ``correct`` false, and exits
with a code other than 0: a rehearsal is never a result.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", default=None, metavar="PRESET")
    args = parser.parse_args(argv)

    from chipbench import harness, loading

    seconds = args.seconds
    if seconds is None:
        seconds = float(loading.benchmark()["run_seconds"])
    result, code = harness.run_cell(
        args.workload, args.seed, seconds, bool(args.trace), rehearse=args.rehearse
    )
    if code != harness.EXIT_BROKEN:
        harness.print_result(result)
    return code


if __name__ == "__main__":
    sys.exit(main())
