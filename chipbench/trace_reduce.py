"""
From the profiler's ``.xplane.pb`` to numbers: the seconds in which an
operation ran on each device, the idle share of a window, each program's
device time, the longest idle gaps named by what the host was doing, and the
device operations that took most time. Read with ``jax.profiler.ProfileData``
alone; checked on a small recorded trace (``chipbench/tests``).

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event per operation run and ``XLA Modules`` one per
program run. Host spans (``jax.profiler.TraceAnnotation``) lie on the host
plane's thread lines, on the same clock.
"""

import glob
import gzip
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path, span_names):
    """{"devices": {plane: {"ops": [(name, start, end)], "modules": [...]}},
    "spans": [(name, start, end)]} with times in seconds."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            profile = ProfileData.from_serialized_xspace(fh.read())
    else:
        profile = ProfileData.from_file(str(path))
    wanted = set(span_names)
    out = {"devices": {}, "spans": []}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
            out["devices"][plane.name] = {
                "ops": lines.get(OPS_LINE, []),
                "modules": lines.get(MODULES_LINE, []),
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        out["spans"].append(
                            (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        )
    out["spans"].sort(key=lambda s: s[1])
    return out


def union(intervals, lo=None, hi=None):
    """Merged, sorted [(start, end)] of the intervals, clipped to [lo, hi]."""
    merged = []
    for start, end in sorted(intervals):
        if lo is not None:
            start = max(start, lo)
        if hi is not None:
            end = min(end, hi)
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def window_of(spans, name):
    """(start, end) from the first span of ``name`` to the end of the last."""
    own = [(s, e) for n, s, e in spans if n == name]
    if not own:
        return None
    return min(s for s, _ in own), max(e for _, e in own)


def _events(device):
    return device["ops"] or device["modules"]


def busy_seconds(device, lo, hi):
    return sum(b - a for a, b in union([(s, e) for _, s, e in _events(device)], lo, hi))


def device_busy(trace, lo, hi):
    """Busy seconds inside [lo, hi], averaged over the device planes."""
    devices = list(trace["devices"].values())
    if not devices:
        return None
    return sum(busy_seconds(d, lo, hi) for d in devices) / len(devices)


def idle_share_percent(device):
    """A result line's ``device`` block to the idle share of its traced
    window, in percent; nothing where no device operation was traced."""
    if "busy_s" not in device:
        return None
    return 100.0 * (1.0 - device["busy_s"] / device["window_s"])


def program_seconds(trace, lo, hi):
    """{program name: summed device seconds of its runs inside [lo, hi]},
    summed over the devices."""
    totals = {}
    for device in trace["devices"].values():
        for name, start, end in device["modules"]:
            start, end = max(start, lo), min(end, hi)
            if end > start:
                totals[name] = totals.get(name, 0.0) + (end - start)
    return totals


_OP = re.compile(r"^%?([\w.\-]+) = (?:\()?(\w+\[[\d,]*\])?.*?(?:kind=(\w+))?(?:, calls=.*)?$")


def short_op_name(name):
    """``%fusion.3 = f32[8,128]{...} fusion(...), kind=kLoop, calls=...`` ->
    ``fusion.3 f32[8,128] kLoop``: the trace names an operation by its whole
    HLO line, which is too long to keep."""
    match = _OP.match(name)
    if not match:
        return name[:80]
    return " ".join(part for part in match.groups() if part)


def top_ops(trace, lo, hi, n=10):
    """The device operations with most summed SELF time inside [lo, hi]:
    an operation that encloses others (a loop, a call) counts only what its
    children leave."""
    totals = {}
    for device in trace["devices"].values():
        events = sorted(
            ((s, -e, name) for name, s, e in device["ops"] if e > lo and s < hi)
        )
        stack = []  # [name, start, end, child_seconds]

        def close(upto):
            while stack and stack[-1][2] <= upto:
                name, s, e, child = stack.pop()
                totals[name] = totals.get(name, 0.0) + max(0.0, (e - s) - child)
                if stack:
                    stack[-1][3] += e - s

        for s, neg_e, name in events:
            close(s)
            stack.append([name, s, -neg_e, 0.0])
        close(float("inf"))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[short_op_name(name), seconds] for name, seconds in ranked]


def idle_gaps(trace, lo, hi, n=10):
    """The idle gaps of the first device inside [lo, hi], grouped by the host
    span that covers most of each (the innermost of equals), with the summed
    seconds of each group; the ``n`` largest groups."""
    devices = list(trace["devices"].values())
    if not devices:
        return []
    busy = union([(s, e) for _, s, e in _events(devices[0])], lo, hi)
    gaps, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        gaps.append((cursor, hi))
    totals = {}
    for a, b in gaps:
        best, best_cover, best_len = "no_span", 0.0, float("inf")
        for name, s, e in trace["spans"]:
            cover = min(b, e) - max(a, s)
            if cover <= 0:
                continue
            if cover > best_cover + 1e-9 or (
                abs(cover - best_cover) <= 1e-9 and e - s < best_len
            ):
                best, best_cover, best_len = name, cover, e - s
        totals[best] = totals.get(best, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]
