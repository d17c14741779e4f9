"""Device milliseconds an epoch under scope ``lstm.bwd.products`` of
``lstm_time_scan``'s backward loop: the four products, ``d_x``'s stacked
write, the bias's sum and the read of the previous ``h``
(``chipbench/step_scopes.py``)."""

from chipbench import step_scopes


def read(ctx):
    return step_scopes.ms_per_epoch(ctx, "lstm.bwd.products/")
