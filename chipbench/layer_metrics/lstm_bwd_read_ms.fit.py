"""Device milliseconds an epoch under scope ``lstm.bwd.read`` of
``lstm_time_scan``'s backward loop: the step index and the one-slab read of
the step's gates behind its ``optimization_barrier``
(``chipbench/step_scopes.py``)."""

from chipbench import step_scopes


def read(ctx):
    return step_scopes.ms_per_epoch(ctx, "lstm.bwd.read/")
