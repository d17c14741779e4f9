"""Device milliseconds an epoch under the trainer's batch gather (scope
``fleet.gather``: ``gather(Xi, yi, sel)`` and the batch's weights): summed
self time of the epoch program's operations whose path holds the scope, over
the traced epochs (``chipbench/scope_reduce.py``)."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms_per_epoch(ctx, "fleet.gather")
