"""
Readings for a cell's limits (PERF.md, "How correct is decided"): in ONE
process, for each seed, the numbers that the comparison reads for

- the program as the configuration states it (the lower readings),
- the control: the program with its own lower-precision path switched on,
  the model definition's ``dtype: bfloat16`` (the upper readings),
- each fault planted in the reference put in the program's place
  (``half_batch``; a state left unchanged reads 1 and needs no run),
- on ``--highest-seeds``, the program traced under
  ``jax.default_matmul_precision("highest")``: what a program that multiplies
  more exactly than the configuration states reads against this reference
  (PERF.md section 7),

all against one run of the reference per seed, and each judged by
``compare.verdict`` under the cell's own limits, as a run of the benchmark
judges: the control and the faults have to come out as not correct. No
measured window: a training cell's readings come from set-up's first call
alone. The benchmark's own runs never run this; ``python3
chipbench/control.py --workload lstm50.fit --seeds 11,12,13 --control-seeds
3`` does, on the chip at the cell's own size or with ``--rehearse tiny``
anywhere.
"""

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAULTS = ("half_batch",)


def set_up(driver_cls, config, traffic, seed, context=None, **options):
    """Set-up's first call of a new driver, with the program's state freed."""
    driver = driver_cls(config, traffic, seed, **options)
    with context or contextlib.nullcontext():
        driver.setup()
    driver.release()
    return driver


def read_seed(config, traffic, limits, seed, with_control, with_highest):
    import jax

    from chipbench import compare, loading

    driver_cls = loading.kind_module("drivers", traffic["kind"]).Driver
    out = {"seed": seed}
    t0 = time.perf_counter()
    driver = set_up(driver_cls, config, traffic, seed)
    out["program_s"] = time.perf_counter() - t0
    readings = {"program": driver.first_call}
    if with_control:
        readings["control"] = set_up(
            driver_cls, config, traffic, seed, dtype="bfloat16"
        ).first_call
    if with_highest:
        readings["program_highest"] = set_up(
            driver_cls, config, traffic, seed,
            context=jax.default_matmul_precision("highest"),
        ).first_call
    t0 = time.perf_counter()
    reference = driver.reference()
    out["reference_s"] = time.perf_counter() - t0
    if with_control:
        for fault in FAULTS:
            readings[f"fault.{fault}"] = driver.reference(fault=fault)
    out["correct"] = {}
    for name, reading in readings.items():
        out[name] = driver.numbers(reading, reference)
        out["correct"][name] = compare.verdict(out[name], limits)[0]
    out["first_losses_machine0"] = [float(x) for x in driver.first_call["losses"][0]]
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--control-seeds", type=int, default=3,
                        help="on the first N seeds, also read the control and the faults")
    parser.add_argument("--highest-seeds", type=int, default=0,
                        help="on the first N seeds, also read the program at precision highest")
    parser.add_argument("--rehearse", default=None, metavar="PRESET")
    parser.add_argument("--out", default=None, help="also append the lines to this file")
    args = parser.parse_args(argv)

    from chipbench import harness, loading

    bench = loading.benchmark()
    cell = loading.cell(bench, args.workload)
    config = loading.config(bench, cell["config"], preset=args.rehearse)
    traffic = loading.traffic(cell["traffic"])
    limits = loading.limits(args.workload, args.rehearse)
    harness.look_for_chip(int(cell["chips"]), args.rehearse)
    from gordo_tpu.utils import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for index, seed in enumerate(seeds):
        line = json.dumps(read_seed(
            config, traffic, limits, seed,
            index < args.control_seeds, index < args.highest_seeds,
        ))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "a") as fh:
                fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
