"""Device milliseconds an epoch under the optimizer step and the epoch's
non-finite guard (scopes ``fleet.optimizer`` + ``fleet.guard``), summed self
time over the traced epochs (``chipbench/scope_reduce.py``)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms_per_epoch(ctx, "fleet.optimizer", "fleet.guard")
