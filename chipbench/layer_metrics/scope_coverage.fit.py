"""Share of the epoch program's device self time, inside the traced window,
that falls under any scope of ``chipbench/scopes.json``. What is left over is
listed by operation on stderr. Nothing where the program names no scope."""

from chipbench import scope_reduce


def read(ctx):
    result = scope_reduce.for_run(ctx)
    if result is None or not result["scopes"] or result["program_self_s"] <= 0:
        return None
    return 100.0 * sum(result["scopes"].values()) / result["program_self_s"]
