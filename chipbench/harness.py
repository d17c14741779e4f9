"""
One run of one cell: look for the chip, set up, measure, read the memory,
free the program, compare with the reference, print the result line.

Nothing here names a cell, a configuration, a traffic kind or a metric: each
is found by its name in ``BENCHMARK.json`` (``chipbench/loading.py``).
"""

import contextlib
import json
import os
import shutil
import sys
import time

from chipbench import compare, loading, trace_reduce

#: exit codes: 0 a result line was printed; 3 no chip (nothing printed);
#: 4 a rehearsal (a line without device metrics); 5 a run that broke its own
#: rules (a compilation inside the window)
EXIT_NO_CHIP, EXIT_REHEARSAL, EXIT_BROKEN = 3, 4, 5

_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def process_start_age():
    """Seconds since this process was created (from /proc), so that set-up
    counts the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class CompileCounter:
    """Counts programs that are compiled, or loaded from the persistent
    cache, while ``armed``: inside the measured window there may be none."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.armed and event in _COMPILE_EVENTS:
            self.count += 1

    @contextlib.contextmanager
    def window(self):
        self.armed = True
        try:
            yield
        finally:
            self.armed = False


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def look_for_chip(chips, rehearse):
    import jax

    devices = jax.devices()
    first = devices[0]
    info = {"platform": first.platform, "kind": first.device_kind, "count": len(devices)}
    if rehearse:
        return info
    if first.platform != "tpu":
        log(f"chipbench measures on a TPU; JAX found platform {first.platform!r} "
            f"({first.device_kind}). No result.")
        raise SystemExit(EXIT_NO_CHIP)
    if len(devices) < chips:
        log(f"the cell asks for {chips} chip(s); JAX found {len(devices)}. No result.")
        raise SystemExit(EXIT_NO_CHIP)
    return info


def window_program_peak(name_parts):
    """The largest ``peak_memory_in_bytes`` among the loaded programs whose
    module name holds one of ``name_parts``: the programs the window ran, as
    the driver names them (its ``WINDOW_PROGRAMS``). It is XLA's own
    statement of the most a program holds at one moment: its arguments, its
    outputs and the temporaries that are alive together, which is less than
    ``temp_size_in_bytes``, the addresses it lays out for all of them. A
    program that says no name or no size counts as 0."""
    import jax.extend

    peak = 0
    for executable in jax.extend.backend.get_backend().live_executables():
        try:
            name = executable.hlo_modules()[0].name
            if any(part in name for part in name_parts):
                stats = executable.get_compiled_memory_stats()
                log(f"window program {name}: peak_memory_in_bytes "
                    f"{stats.peak_memory_in_bytes}, temp_size_in_bytes "
                    f"{stats.temp_size_in_bytes}, argument_size_in_bytes "
                    f"{stats.argument_size_in_bytes}")
                peak = max(peak, int(stats.peak_memory_in_bytes))
        except Exception:  # noqa: BLE001
            continue
    return peak


def memory_peak_bytes(window_programs):
    """
    The peak on the fullest chip, read while the program's state is still on
    the device. ``memory_stats()`` counts the allocator's buffers; on this
    runtime a running program's temporaries are not among them, though they
    take the chip's memory while it runs (``chipbench/memory_witness.py``
    asks the chip: beside a ballast that leaves less room than the program's
    ``peak_memory_in_bytes`` the call is refused for want of memory, beside
    a smaller one it runs; PERF.md section 4). So the peak is the larger of
    the allocator's own peak and the stated peak of the largest program that
    the window ran, which holds that program's arguments and outputs, most
    of what is live; other live buffers are left out, so the figure errs low.
    Where an allocator does count temporaries its peak is the larger and is
    what is reported. Returns (peak, the allocator's own peak).
    """
    import jax

    program_peak = window_program_peak(window_programs)
    peak = stats_peak = 0
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        stats_peak = max(stats_peak, int(stats.get("peak_bytes_in_use", 0)))
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)), program_peak)
        log("memory_stats", device.id, {
            k: stats.get(k) for k in
            ("peak_bytes_in_use", "bytes_in_use", "largest_alloc_size", "bytes_limit")
        }, "window program peak", program_peak)
    return peak, stats_peak


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def traced_stretch(driver, cell_name, traffic):
    """Run the mix's ``trace_calls`` calls under the profiler and reduce the
    trace. The writing of the trace is outside every clock."""
    import jax

    trace_dir = loading.ROOT / "chipbench_out" / "trace" / cell_name
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        record = driver.run_calls(max_calls=int(traffic.get("trace_calls", 1)), span=_span)
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)), driver.SPANS)
    return record, trace


def run_cell(cell_name, seed, seconds, trace, rehearse=None, driver_options=None):
    """Returns (result dict, exit code). ``rehearse`` names a preset of the
    configuration's file and skips the look for a chip."""
    started_age = process_start_age()
    started_clock = time.perf_counter()
    bench = loading.benchmark()
    cell = loading.cell(bench, cell_name)
    config = loading.config(bench, cell["config"], preset=rehearse)
    traffic = loading.traffic(cell["traffic"])

    device = look_for_chip(int(cell["chips"]), rehearse)
    from gordo_tpu.utils import enable_compile_cache

    enable_compile_cache()
    compiles = CompileCounter()

    driver = loading.kind_module("drivers", traffic["kind"]).Driver(
        config, traffic, seed, **(driver_options or {})
    )
    driver.setup()
    setup_s = (started_age or 0.0) + (time.perf_counter() - started_clock)
    log(f"set-up {setup_s:.2f} s, of which {started_age or 0.0:.2f} s before the harness")

    traced, trace_data = None, None
    with compiles.window():
        if trace:
            traced, trace_data = traced_stretch(driver, cell_name, traffic)
        window = driver.run_calls(seconds=seconds)
    device["memory_peak_bytes"], device["allocator_peak_bytes"] = memory_peak_bytes(
        driver.WINDOW_PROGRAMS
    )
    driver.release()

    reference_start = time.perf_counter()
    numbers = driver.compare()
    log(f"reference {time.perf_counter() - reference_start:.2f} s, "
        f"window {window['elapsed_s']:.2f} s in {len(window['calls'])} calls of "
        + " ".join(f"{c['seconds']:.3f}" for c in window["calls"]) + " s")
    for index, call in enumerate(window["calls"]):
        told = {"seconds": round(call["seconds"], 4), **call["host"], **{
            k: round(v, 5) for k, v in call.get("telemetry", {}).items()
            if isinstance(v, float)
        }}
        log(f"call {index}: " + " ".join(f"{k}={v}" for k, v in told.items()))
    correct, rows = compare.verdict(numbers, loading.limits(cell_name, rehearse))
    if window["failed"]:
        correct = False

    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "driver": driver,
        "window": window, "traced": traced, "trace": trace_data,
        "setup_s": setup_s, "device": device, "chips": int(cell["chips"]),
        "compiles_in_window": compiles.count,
    }
    if trace:
        lo_hi = trace_reduce.window_of(trace_data["spans"], driver.SPANS[0])
        ctx["trace_window"] = lo_hi
        if lo_hi is not None:
            for name, secs in sorted(
                trace_reduce.program_seconds(trace_data, *lo_hi).items(),
                key=lambda kv: -kv[1],
            )[:8]:
                log(f"traced program {name}: {secs:.6f} s on the device")
            busy = trace_reduce.device_busy(trace_data, *lo_hi)
            if busy:
                device["busy_s"] = busy
                device["window_s"] = lo_hi[1] - lo_hi[0]
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    if not rehearse:
        ctx["peaks"] = loading.peaks(device["kind"])
        directory = "layer_metrics" if trace else "end_to_end"
        for metric in loading.metrics_for(bench, section, cell_name):
            value = loading.metric_reader(directory, metric["name"])(ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    result = {
        "correct": bool(correct),
        "attempted": window["units"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace and not rehearse and ctx.get("trace_window"):
        lo, hi = ctx["trace_window"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace_data, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(trace_data, lo, hi),
        }
    result["workload"] = cell_name
    result["seed"] = int(seed)
    result["window_s"] = window["elapsed_s"]
    result["compiles_in_window"] = compiles.count
    if rehearse:
        result["rehearsal"] = rehearse
    result["compared"] = {
        name: {"value": value, "limit": limit} for name, value, limit in rows
    }

    code = 0
    if compiles.count:
        log(f"{compiles.count} program(s) compiled or loaded inside the measured "
            "window: the warm-up missed a shape. No result.")
        code = EXIT_BROKEN
    elif rehearse:
        code = EXIT_REHEARSAL
    for name in numbers:
        if name not in result["compared"]:
            log(f"read {name}={numbers[name]!r}, not compared in this cell")
    for name, value, limit in rows:
        log(f"compared {name}={value!r} limit={limit!r} {'ok' if value <= limit else 'OVER'}")
    return result, code


def print_result(result):
    """The result line. A rehearsal's line says what the comparison found
    under a key of its own and carries ``correct`` false and no metric: it
    is never a result."""
    if result.get("rehearsal"):
        compared = result.pop("compared")
        result.update(
            comparison_passed=result["correct"], correct=False, metrics={},
            compared=compared,
        )
    print(json.dumps(result), flush=True)
