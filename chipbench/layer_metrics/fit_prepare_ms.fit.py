"""Host milliseconds a ``fit`` call spends before its first dispatch
(``fit_telemetry_["prepare_s"]``, the perf_counter pair beside the span
``train.prepare``: sharding, the effective-weights fetch, masks, the sample
cap, the programs' lookup), median over the window's calls."""

import statistics


def read(ctx):
    values = [c["telemetry"].get("prepare_s") for c in ctx["window"]["calls"]]
    values = [v for v in values if v is not None]
    return 1000.0 * statistics.median(values) if values else None
