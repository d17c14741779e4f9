"""Device milliseconds an epoch under scope ``lstm.fwd.gates`` of
``lstm_time_scan``'s forward loop: both gate products, the bias and the
stacked write of the gates (``chipbench/step_scopes.py``)."""

from chipbench import step_scopes


def read(ctx):
    return step_scopes.ms_per_epoch(ctx, "lstm.fwd.gates/")
