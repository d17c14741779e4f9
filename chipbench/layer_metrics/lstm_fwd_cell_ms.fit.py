"""Device milliseconds an epoch under scope ``lstm.fwd.cell`` of
``lstm_time_scan``'s forward loop: the cell update and the stacked writes of
``h`` and ``c`` (``chipbench/step_scopes.py``)."""

from chipbench import step_scopes


def read(ctx):
    return step_scopes.ms_per_epoch(ctx, "lstm.fwd.cell/")
