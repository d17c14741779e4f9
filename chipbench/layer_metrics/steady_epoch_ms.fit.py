"""The fleet trainer's own steady-state epoch time
(``fit_telemetry_["steady_state_epoch_s"]``), median over the window's calls."""

import statistics


def read(ctx):
    values = [
        c["telemetry"].get("steady_state_epoch_s") for c in ctx["window"]["calls"]
    ]
    values = [v for v in values if v]
    return 1000.0 * statistics.median(values) if values else None
