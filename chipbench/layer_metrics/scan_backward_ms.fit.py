"""Device milliseconds an epoch in the fused layers' time scans, backward
pass: operations whose path holds ``/scan/`` under ``transpose(`` (scope
``scan.backward`` of ``chipbench/scopes.json``), summed self time over the
traced epochs."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms_per_epoch(ctx, "scan.backward")
