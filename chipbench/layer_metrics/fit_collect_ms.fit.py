"""Host milliseconds a ``fit`` call spends after its last dispatch
(``fit_telemetry_["collect_s"] + ["report_s"]``, beside the spans
``train.collect`` and ``train.report``: the bulk fetch that waits for the
device, stacking, quarantine bookkeeping, the registry and the event), median
over the window's calls."""

import statistics


def read(ctx):
    values = [
        c["telemetry"]["collect_s"] + c["telemetry"].get("report_s", 0.0)
        for c in ctx["window"]["calls"] if c["telemetry"].get("collect_s") is not None
    ]
    return 1000.0 * statistics.median(values) if values else None
