"""Host seconds the fleet trainer spends issuing an epoch
(``fit_telemetry_["dispatch_overhead_s"]`` of a call over its dispatches),
median over the window's calls."""

import statistics


def read(ctx):
    values = []
    for call in ctx["window"]["calls"]:
        overhead = call["telemetry"].get("dispatch_overhead_s")
        dispatches = call["telemetry"].get("n_dispatches")
        if overhead and dispatches:
            values.append(overhead / dispatches)
    return 1000.0 * statistics.median(values) if values else None
