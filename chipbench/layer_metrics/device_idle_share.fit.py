"""Share of the traced calls' window in which no operation ran on the device:
1 - union of device-op intervals / window, from the profiler."""

from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.idle_share_percent(ctx["device"])
