"""
From the profiler's ``.xplane.pb`` to the epoch program's device time BY SCOPE
NAME, and the window's idle seconds by the program's own ``train.*`` spans.

``jax.profiler.ProfileData`` (which ``trace_reduce`` reads) shows an event's
own stats only. The path that says which part of the program an operation
belongs to, e.g.
``jit(machine_epoch)/vmap()/while/body/closed_call/fleet.gather/gather``,
is a stat of the event's METADATA: the one named ``tf_op`` (looked at by hand
in ``testdata/tiny_lstm.xplane.pb.gz``, recorded on a TPU v5e; its value ends
in a ``:`` that is dropped here). Beside it the metadata carries
``program_id``, which an event of the ``XLA Modules`` line repeats in its
name, ``jit_machine_epoch(<program_id>)``: so an operation with no path at all
can still be told to belong to the epoch program. The only importable schema
for the file comes with the whole of TensorFlow, so this module decodes the
few fields it needs from the wire format itself (``XSpace.planes`` ->
``XPlane.name / lines / event_metadata / stat_metadata`` -> ``XLine.name /
timestamp_ns / events`` -> ``XEvent.metadata_id / offset_ps / duration_ps``,
``XEventMetadata.name / stats``): tsl/profiler/protobuf/xplane.proto.

Self time is counted as ``trace_reduce.top_ops`` counts it: an operation that
encloses others (a loop, a call) keeps only what its children leave. Which
scope an operation falls under is ``chipbench/scopes.json``'s to say.
"""

import gzip
import re
import sys
import traceback

from chipbench import loading, trace_reduce

_MODULE_NAME = re.compile(r"^(.*)\((\d+)\)$")


# -- the wire format -----------------------------------------------------------


def _fields(buf, pos, end):
    """(field number, wire type, value) of one message in ``buf[pos:end]``:
    an int for a varint, ``(start, end)`` for a length-delimited field, the
    raw bytes of a fixed one."""
    while pos < end:
        byte = buf[pos]
        pos += 1
        key = byte & 0x7F
        shift = 7
        while byte & 0x80:
            byte = buf[pos]
            pos += 1
            key |= (byte & 0x7F) << shift
            shift += 7
        kind = key & 7
        if kind == 0:
            byte = buf[pos]
            pos += 1
            value = byte & 0x7F
            shift = 7
            while byte & 0x80:
                byte = buf[pos]
                pos += 1
                value |= (byte & 0x7F) << shift
                shift += 7
            yield key >> 3, 0, value
        elif kind == 2:
            byte = buf[pos]
            pos += 1
            size = byte & 0x7F
            shift = 7
            while byte & 0x80:
                byte = buf[pos]
                pos += 1
                size |= (byte & 0x7F) << shift
                shift += 7
            yield key >> 3, 2, (pos, pos + size)
            pos += size
        elif kind == 1:
            yield key >> 3, 1, buf[pos:pos + 8]
            pos += 8
        elif kind == 5:
            yield key >> 3, 5, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {kind} at byte {pos}: not an xplane file")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, span of the value message) of one ``map<int64, Message>`` entry."""
    key, value = 0, None
    for number, _, got in _fields(buf, *span):
        if number == 1:
            key = got
        elif number == 2:
            value = got
    return key, value


def _stat(buf, span):
    """(metadata id, value) of one XStat; a ``ref_value`` comes back as
    ``("ref", id)`` for the caller to look up among the stat names."""
    ident, value = 0, None
    for number, _, got in _fields(buf, *span):
        if number == 1:
            ident = got
        elif number in (3, 4):
            value = got
        elif number == 5:
            value = _text(buf, got)
        elif number == 7:
            value = ("ref", got)
    return ident, value


def _event_metadata(buf, span, stat_names, wanted):
    """{"name": ..., <wanted stat name>: value} of one XEventMetadata."""
    out = {"name": ""}
    for number, _, got in _fields(buf, *span):
        if number == 2:
            out["name"] = _text(buf, got)
        elif number == 5:
            ident, value = _stat(buf, got)
            stat = stat_names.get(ident)
            if stat in wanted:
                if isinstance(value, tuple):
                    value = stat_names.get(value[1], "")
                out[stat] = value
    return out


def _line(buf, span):
    """(name, timestamp_ns, [spans of its events]) of one XLine."""
    name, timestamp_ns, events = "", 0, []
    for number, _, got in _fields(buf, *span):
        if number == 2:
            name = _text(buf, got)
        elif number == 3:
            timestamp_ns = got
        elif number == 4:
            events.append(got)
    return name, timestamp_ns, events


def _events(buf, timestamp_ns, spans):
    """[(metadata id, start s, end s)] of a line's events. A long trace holds
    a million of them, so the three varints are read in place."""
    base = timestamp_ns * 1000
    out = []
    for pos, end in spans:
        numbers = [0, 0, 0, 0]  # by field number: -, metadata_id, offset_ps, duration_ps
        while pos < end:
            key = buf[pos]
            pos += 1
            if key & 0x87:  # not a varint field with a one-byte key: stats
                if key & 0x80 or key & 7 != 2:
                    for number, kind, got in _fields(buf, pos - 1, end):
                        if kind == 0 and number < 4:
                            numbers[number] = got
                    break
                byte = buf[pos]
                pos += 1
                size = byte & 0x7F
                shift = 7
                while byte & 0x80:
                    byte = buf[pos]
                    pos += 1
                    size |= (byte & 0x7F) << shift
                    shift += 7
                pos += size
                continue
            byte = buf[pos]
            pos += 1
            value = byte & 0x7F
            shift = 7
            while byte & 0x80:
                byte = buf[pos]
                pos += 1
                value |= (byte & 0x7F) << shift
                shift += 7
            if key < 32:
                numbers[key >> 3] = value
        start = (base + numbers[2]) * 1e-12
        out.append((numbers[1], start, start + numbers[3] * 1e-12))
    return out


def read_planes(path):
    """{plane name: {"metadata": {id: {"name", "tf_op", "program_id"}},
    "lines": {line name: [(metadata id, start s, end s)]}}} of the device
    planes (their ``XLA Ops`` and ``XLA Modules`` lines) and the host planes
    (every thread's line, under the line's name and id)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as fh:
        buf = fh.read()
    planes = {}
    for number, _, plane_span in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, lines, metadata, stats = "", [], [], []
        for field, _, got in _fields(buf, *plane_span):
            if field == 2:
                name = _text(buf, got)
            elif field == 3:
                lines.append(got)
            elif field == 4:
                metadata.append(got)
            elif field == 5:
                stats.append(got)
        device = name.startswith(trace_reduce.DEVICE_PREFIX)
        if not (device or name.startswith("/host:")):
            continue
        stat_names = {}
        for span in stats:
            ident, value = _map_entry(buf, span)
            for field, _, got in _fields(buf, *value):
                if field == 2:
                    stat_names[ident] = _text(buf, got)
        wanted = ("tf_op", "program_id") if device else ()
        plane = {"metadata": {}, "lines": {}}
        for span in metadata:
            ident, value = _map_entry(buf, span)
            plane["metadata"][ident] = _event_metadata(buf, value, stat_names, wanted)
        for index, span in enumerate(lines):
            line_name, timestamp_ns, events = _line(buf, span)
            if device and line_name not in (
                trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
            ):
                continue
            key = line_name if device else f"{line_name}#{index}"
            plane["lines"][key] = _events(buf, timestamp_ns, events)
        planes[name] = plane
    return planes


# -- the reduction ---------------------------------------------------------------


def self_seconds(events, lo, hi, order=str):
    """{key: summed SELF seconds} of ``[(key, start, end)]`` events that
    touch [lo, hi], nested as ``trace_reduce.top_ops`` nests them: sorted by
    start, the longer first, then by ``order(key)`` (there, the name)."""
    totals = {}
    stack = []  # [key, start, end, child seconds]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            key, start, end, child = stack.pop()
            totals[key] = totals.get(key, 0.0) + max(0.0, (end - start) - child)
            if stack:
                stack[-1][3] += end - start

    for start, neg_end, _, key in sorted(
        (s, -e, order(key), key) for key, s, e in events if e > lo and s < hi
    ):
        close(start)
        stack.append([key, start, -neg_end, 0.0])
    close(float("inf"))
    return totals


def scope_of(path, table):
    """The first scope of ``table`` whose ``holds`` all occur in ``path`` and
    whose ``lacks`` do not; None for a path under no scope."""
    for scope in table["scopes"]:
        if all(part in path for part in scope["holds"]) and not any(
            part in path for part in scope.get("lacks", ())
        ):
            return scope["name"]
    return None


def idle_by_span(busy, spans, lo, hi):
    """{span name or "no_span": idle seconds} of [lo, hi]: every idle stretch
    is cut at the spans' edges and each piece goes to the INNERMOST span that
    covers it (the latest to start; of equals, the first to end)."""
    gaps, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        gaps.append((cursor, hi))
    totals = {}
    for a, b in gaps:
        edges = sorted({a, b, *(t for _, s, e in spans for t in (s, e) if a < t < b)})
        for left, right in zip(edges, edges[1:]):
            inner = None
            for name, s, e in spans:
                if s <= left and e >= right and (
                    inner is None or (s, -e) > (inner[1], -inner[2])
                ):
                    inner = (name, s, e)
            name = inner[0] if inner else "no_span"
            totals[name] = totals.get(name, 0.0) + (right - left)
    return totals


def reduce(path, window=None, table=None, window_span="fit_call"):
    """
    The numbers of one traced stretch. ``window`` is (lo, hi) in the trace's
    seconds; left out, it runs from the first ``window_span`` on a host
    plane to the end of the last. Returns None where the trace holds no such
    window or no device operation; else a dict:

    - ``program_self_s``: summed self time, inside the window, of the
      operations of the programs ``table["programs"]`` names;
    - ``scopes``: {scope: self seconds} of those operations, by the first
      scope of ``table`` their path holds; ``unscoped``: [(operation, path,
      seconds)] of those under none, largest first; ``by_path``: the same
      sums under each operation's path cut after the scope;
    - ``spans``: [(name, start, end)] of the host spans the table's
      prefixes select, aliases already renamed;
    - ``idle_s`` and ``idle_by_span`` of the first device inside the window.
    """
    table = table or loading.read_json(loading.HERE / "scopes.json")
    planes = read_planes(path)
    prefixes = tuple(table["span_prefixes"])
    aliases = table.get("span_aliases", {})
    spans, marks = [], []
    for name, plane in planes.items():
        if not name.startswith("/host:"):
            continue
        names = {i: m["name"] for i, m in plane["metadata"].items()}
        for events in plane["lines"].values():
            for ident, start, end in events:
                event = names.get(ident, "")
                if event == window_span:
                    marks.append((start, end))
                if event.startswith(prefixes):
                    spans.append((aliases.get(event, event), start, end))
    spans.sort(key=lambda s: s[1])
    if window is None:
        if not marks:
            return None
        window = min(s for s, _ in marks), max(e for _, e in marks)
    lo, hi = window
    devices = [
        plane for name, plane in sorted(planes.items())
        if name.startswith(trace_reduce.DEVICE_PREFIX)
    ]
    if not devices or not any(d["lines"].get(trace_reduce.OPS_LINE) for d in devices):
        return None

    scopes, by_path, unscoped, program_self = {}, {}, {}, 0.0
    for device in devices:
        programs = set()
        for ident, _, _ in device["lines"].get(trace_reduce.MODULES_LINE, ()):
            match = _MODULE_NAME.match(device["metadata"].get(ident, {}).get("name", ""))
            if match and any(part in match.group(1) for part in table["programs"]):
                programs.add(int(match.group(2)))
        ops = device["lines"].get(trace_reduce.OPS_LINE, ())
        metadata = device["metadata"]

        def name_of(ident, metadata=metadata):
            return metadata.get(ident, {}).get("name", "")

        for ident, seconds in self_seconds(ops, lo, hi, name_of).items():
            meta = metadata.get(ident, {})
            if meta.get("program_id") not in programs:
                continue
            program_self += seconds
            op_path = str(meta.get("tf_op", "")).rstrip(":")
            scope = scope_of(op_path, table)
            if scope is None:
                key = (trace_reduce.short_op_name(meta.get("name", "")), op_path)
                unscoped[key] = unscoped.get(key, 0.0) + seconds
            else:
                scopes[scope] = scopes.get(scope, 0.0) + seconds
            by_path[op_path] = by_path.get(op_path, 0.0) + seconds

    first = devices[0]["lines"]
    busy = trace_reduce.union(
        [(s, e) for _, s, e in (
            first.get(trace_reduce.OPS_LINE) or first.get(trace_reduce.MODULES_LINE, ())
        )],
        lo, hi,
    )
    idle = idle_by_span(busy, spans, lo, hi)
    return {
        "window": (lo, hi),
        "program_self_s": program_self,
        "scopes": scopes,
        "unscoped": sorted(
            ((op, p, s) for (op, p), s in unscoped.items()), key=lambda row: -row[2]
        ),
        "by_path": by_path,
        "spans": [s for s in spans if s[2] > lo and s[1] < hi],
        "idle_s": sum(idle.values()),
        "idle_by_span": idle,
    }


def log_tables(result, table, out=sys.stderr, rows=12):
    """The full tables of one traced stretch, for the run's stderr."""
    def say(*parts):
        print(*parts, file=out, flush=True)

    total = result["program_self_s"]
    say(f"scope_reduce: programs {table['programs']} self time {total:.6f} s "
        f"in window {result['window'][1] - result['window'][0]:.6f} s")
    for name, seconds in sorted(result["scopes"].items(), key=lambda kv: -kv[1]):
        say(f"  scope {name:<18} {seconds:.6f} s  {100 * seconds / max(total, 1e-12):6.2f}%")
    covered = sum(result["scopes"].values())
    say(f"  under a scope      {covered:.6f} s  {100 * covered / max(total, 1e-12):6.2f}%")
    for op, path, seconds in result["unscoped"][:rows]:
        say(f"  unscoped {seconds:.6f} s  {op}  [{path or 'no path'}]")
    say("scope_reduce: largest paths")
    for path, seconds in sorted(result["by_path"].items(), key=lambda kv: -kv[1])[:rows]:
        say(f"  {seconds:.6f} s  {path or 'no path'}")
    say(f"scope_reduce: idle {result['idle_s']:.6f} s of the window, by innermost span")
    for name, seconds in sorted(result["idle_by_span"].items(), key=lambda kv: -kv[1]):
        say(f"  idle {name:<18} {seconds:.6f} s  "
            f"{100 * seconds / max(result['idle_s'], 1e-12):6.2f}%")
    phases = sum(
        seconds for name, seconds in result["idle_by_span"].items()
        if name not in ("no_span", table.get("root_span"))
    )
    say(f"  idle under a span other than the root {table.get('root_span')}: "
        f"{100 * phases / max(result['idle_s'], 1e-12):.2f}%")
    by_name = {}
    for name, start, end in result["spans"]:
        count, seconds = by_name.get(name, (0, 0.0))
        by_name[name] = (count + 1, seconds + (end - start))
    for name, (count, seconds) in sorted(by_name.items(), key=lambda kv: -kv[1][1]):
        say(f"  span {name:<18} n={count:<3d} {seconds:.6f} s")


def for_run(ctx):
    """The reduction of a traced run's own stretch, made once per run and
    kept in ``ctx``; its tables go to stderr. None, and no exception, where
    there is nothing to read: no trace, or a program without these scopes."""
    if "scope_reduce" not in ctx:
        result = None
        if ctx.get("trace") and ctx.get("trace_window"):
            try:
                table = loading.read_json(loading.HERE / "scopes.json")
                trace_dir = loading.ROOT / "chipbench_out" / "trace" / ctx["cell"]["name"]
                result = reduce(
                    trace_reduce.find_xplane(str(trace_dir)), ctx["trace_window"], table
                )
                if result is not None:
                    log_tables(result, table)
            except Exception:  # noqa: BLE001 - a reader never breaks the run
                traceback.print_exc(file=sys.stderr)
                result = None
        ctx["scope_reduce"] = result
    return ctx["scope_reduce"]


def traced_epochs(ctx):
    return len(ctx["traced"]["calls"]) * ctx["traced"]["epochs_per_call"]


def scope_ms_per_epoch(ctx, *names):
    """Milliseconds an epoch of the traced stretch under the named scopes;
    None where the trace holds none of them."""
    result = for_run(ctx)
    if result is None or not any(name in result["scopes"] for name in names):
        return None
    seconds = sum(result["scopes"].get(name, 0.0) for name in names)
    return 1000.0 * seconds / traced_epochs(ctx)
