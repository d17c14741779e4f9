"""The epoch program's share of its roofline: the least time the chip could
take for the traced epochs (the larger of operations / peak and compulsory
bytes / memory bandwidth, ``chipbench/flops/epoch.py``) over the program's
summed device time in the trace. Which bound holds is logged to stderr."""

import sys

from chipbench import loading, trace_reduce
from chipbench.flops import epoch


def read(ctx):
    if not ctx.get("trace") or not ctx.get("trace_window"):
        return None
    lo, hi = ctx["trace_window"]
    hint = ctx["driver"].EPOCH_PROGRAM
    seconds = sum(
        s for name, s in trace_reduce.program_seconds(ctx["trace"], lo, hi).items()
        if hint in name
    )
    if seconds <= 0:
        return None
    config = ctx["config"]
    kind = loading.kind_module("flops", config["model_kind"])
    shapes, bucket = config["shapes"], config["bucket"]
    epochs_traced = len(ctx["traced"]["calls"]) * ctx["traced"]["epochs_per_call"]
    least, bound = epoch.least_seconds(
        epoch.epoch_flops(kind, shapes, bucket["rows"], bucket["machines"]),
        epoch.epoch_bytes(kind, shapes, bucket["rows"], bucket["machines"]),
        ctx["peaks"],
    )
    print(f"epoch_program_roofline.fit: bound by {bound}, least {least:.6f} s an epoch, "
          f"{seconds / epochs_traced:.6f} s measured", file=sys.stderr)
    return 100.0 * least * epochs_traced / seconds
