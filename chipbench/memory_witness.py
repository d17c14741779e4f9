"""
How much of the chip's memory does a cell's running program take?
``device.memory_stats()`` on this runtime does not count a program's
temporaries, and the benchmark's ``memory_peak_bytes`` reports the program's
own ``peak_memory_in_bytes`` (``harness.memory_peak_bytes``), so this asks
the chip itself: set a cell up, lay a ballast of ``--ballast-gb`` of random
bits beside it, and make one more call. Beside a ballast that leaves room
the call runs and the ballast is intact; beside one that does not, the
runtime refuses the call for want of memory. The largest that ran and the
first refused bracket what the program really takes.

``python3 chipbench/memory_witness.py --workload lstm50.fit --ballast-gb 5,8``
on the chip; one JSON line per ballast. The benchmark's own runs never run
this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--ballast-gb", required=True, help="comma-separated, ascending")
    parser.add_argument("--rehearse", default=None, metavar="PRESET")
    args = parser.parse_args(argv)

    from chipbench import harness, loading

    bench = loading.benchmark()
    cell = loading.cell(bench, args.workload)
    config = loading.config(bench, cell["config"], preset=args.rehearse)
    traffic = loading.traffic(cell["traffic"])
    harness.look_for_chip(int(cell["chips"]), args.rehearse)
    import jax
    import jax.numpy as jnp
    from gordo_tpu.utils import enable_compile_cache

    enable_compile_cache()
    driver = loading.kind_module("drivers", traffic["kind"]).Driver(config, traffic, args.seed)
    driver.setup()
    stated = harness.window_program_peak(driver.WINDOW_PROGRAMS)
    device = jax.local_devices()[0]
    chunk_words = 1 << 26  # 256 MiB of uint32: a chunk's own making needs little room
    for gigabytes in (float(g) for g in args.ballast_gb.split(",")):
        line = {"ballast_bytes": int(gigabytes * 1e9), "window_program_peak": stated}
        ballast = []
        try:
            for index in range(line["ballast_bytes"] // (4 * chunk_words)):
                ballast.append(jax.random.bits(
                    jax.random.PRNGKey(index), (chunk_words,), jnp.uint32
                ))
            before = [int(chunk[-1]) for chunk in jax.block_until_ready(ballast)]
        except Exception as error:  # noqa: BLE001
            line["ballast"] = "could not be laid: " + " ".join(str(error).split())[:300]
            print(json.dumps(line), flush=True)
            break
        stats = device.memory_stats() or {}
        line["bytes_in_use"] = stats.get("bytes_in_use")
        line["bytes_limit"] = stats.get("bytes_limit")
        try:
            t0 = time.perf_counter()
            call = driver.one_call()
            line["call"] = "ran" if not call["failed"] else "ran, machines failed"
            line["call_s"] = round(time.perf_counter() - t0, 3)
            line["ballast_intact"] = [int(chunk[-1]) for chunk in ballast] == before
        except Exception as error:  # noqa: BLE001 - the refusal is the reading
            line["call"] = "refused"
            line["error"] = " ".join(str(error).split())[:600]
        print(json.dumps(line), flush=True)
        if line["call"] == "refused":  # a refused call may have taken the donated state
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
