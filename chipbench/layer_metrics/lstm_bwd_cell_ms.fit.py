"""Device milliseconds an epoch under scope ``lstm.bwd.cell`` of
``lstm_time_scan``'s backward loop: the transposed cell update, with the
reads of the previous ``c`` and of ``d_hs`` that feed it
(``chipbench/step_scopes.py``)."""

from chipbench import step_scopes


def read(ctx):
    return step_scopes.ms_per_epoch(ctx, "lstm.bwd.cell/")
