"""
Host/device time attribution: the phase ledger.

Server-Timing has three coarse phases (``queue``/``model_load``/
``predict``); the request hot path actually crosses seven seams — and
the float64 pandas/sklearn transform seam the dtype walk documented
(docs/serving.md "Streaming scoring") was invisible in every metric.
This module brackets the serving, streaming and routing hot paths into
ONE closed phase vocabulary:

==============  ============================================================
phase           what it covers
==============  ============================================================
``parse``       request bytes -> host arrays (JSON decode, frame assembly)
``transform``   the pandas/sklearn host seam (per-machine prefix
                transforms, float64 -> float32 cast)
``queue``       dynamic-batching wait (the existing Server-Timing phase)
``transfer``    host -> device staging (batch assembly, ``device_put``)
``device``      the compiled dispatch, bounded by the existing sanctioned
                sync points (the output fetch that materializes results)
``postprocess`` anomaly statistic / threshold math on the way out
``serialize``   response frame -> JSON bytes
==============  ============================================================

Each request/update carries a :class:`PhaseLedger`; phases are recorded
into ``gordo_phase_seconds{plane,phase}`` histograms, stamped as
attributes on the enclosing span (``server.request`` /
``stream.update``), and windowed by the rollup into
the ``host_fraction``/``device_fraction`` control signals — roadmap
direction #2's target metric (drive ``host_fraction`` toward zero).

Overhead discipline: the ledger is **always on by default** — its cost
is a ``perf_counter`` pair and a dict add per phase, measured by
:func:`measure_overhead` exactly like ``tracing.measure_overhead``.
``GORDO_PHASE_LEDGER=0`` turns it off entirely: one env dict lookup per
request, then process-wide no-op singletons (the tracing/fault-inject
house rule, call-count pinned by tests/test_attribution.py). The
sampling-profiler hook inside each bracket is a single module-global
read when ``GORDO_PROFILE_HZ`` is unset.
"""

import os
import threading
import time
import typing

from gordo_tpu.observability import sampling
from gordo_tpu.observability.registry import get_registry

LEDGER_ENV_VAR = "GORDO_PHASE_LEDGER"

#: the closed phase vocabulary (docs/observability.md "Time attribution")
PHASES: typing.Tuple[str, ...] = (
    "parse",
    "transform",
    "queue",
    "transfer",
    "device",
    "postprocess",
    "serialize",
)

#: phases whose time is host CPU (the compilation roadmap's target)
HOST_PHASES = frozenset(
    {"parse", "transform", "queue", "postprocess", "serialize"}
)
#: phases on the accelerator side of the seam
DEVICE_PHASES = frozenset({"transfer", "device"})

#: the planes a ledger can account for (the ``plane`` label's vocabulary)
PLANES: typing.Tuple[str, ...] = ("server", "stream", "router")

#: per-thread stack of active ledgers: cross-layer code (the fleet
#: scorer, the estimator hot path) attributes via
#: :func:`record_current` without threading a ledger through every
#: signature
_TLS = threading.local()


def _phase_histogram():
    return get_registry().histogram(
        "gordo_phase_seconds",
        "Per-request host/device phase attribution (the phase ledger)",
        ("plane", "phase"),
    )


def ledger_enabled() -> bool:
    """One env dict lookup: the ledger is on unless explicitly off."""
    return os.environ.get(LEDGER_ENV_VAR, "1").lower() not in (
        "0",
        "false",
        "off",
    )


# -- the no-op half (GORDO_PHASE_LEDGER=0) ---------------------------------


class _NoopContextManager:
    """Reusable disabled-path bracket: no allocation, no clock reads."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP_CM = _NoopContextManager()


class _NoopLedger:
    """The disabled-path singleton: every operation is a pass."""

    __slots__ = ()
    plane = None
    phases: typing.Dict[str, float] = {}

    def phase(self, name: str):
        return _NOOP_CM

    def add(self, name: str, seconds: float) -> None:
        pass

    def activate(self):
        return _NOOP_CM

    def finish(self, span=None, wall_s=None, record_spans=False) -> dict:
        return {}


NOOP_LEDGER = _NoopLedger()


# -- the real half ---------------------------------------------------------


class _PhaseBracket:
    """One ``with ledger.phase(name):`` bracket. Slotted and reused per
    bracket (not per ledger) — the enter/exit cost is two
    ``perf_counter`` calls, one dict add, and one module-global read
    for the profiler hook."""

    __slots__ = ("_ledger", "_name", "_start", "_prev_phase")

    def __init__(self, ledger: "PhaseLedger", name: str):
        self._ledger = ledger
        self._name = name

    def __enter__(self):
        if sampling._ACTIVE:
            self._prev_phase = sampling.current_phase()
            sampling.set_phase(self._ledger.plane, self._name)
        else:
            self._prev_phase = None
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        elapsed = time.perf_counter() - self._start
        self._ledger.add(self._name, elapsed)
        if sampling._ACTIVE:
            sampling.clear_phase(self._prev_phase)
        return False


class _Activation:
    """Pushes a ledger onto the calling thread's sink stack so
    :func:`record_current` calls from deeper layers land on it."""

    __slots__ = ("_ledger",)

    def __init__(self, ledger: "PhaseLedger"):
        self._ledger = ledger

    def __enter__(self):
        stack = getattr(_TLS, "sinks", None)
        if stack is None:
            stack = _TLS.sinks = []
        stack.append(self._ledger)
        return self._ledger

    def __exit__(self, exc_type, exc, tb):
        _TLS.sinks.pop()
        return False


class PhaseLedger:
    """Per-request/update/dispatch phase accounting for one plane.

    Create via :func:`ledger_for` (which owns the enabled check), bracket
    hot-path seams with :meth:`phase` / :meth:`add`, then :meth:`finish`
    once to observe the histograms and stamp the enclosing span.
    """

    __slots__ = ("plane", "phases", "_created")

    def __init__(self, plane: str):
        self.plane = plane
        self.phases: typing.Dict[str, float] = {}
        self._created = time.perf_counter()

    def phase(self, name: str) -> _PhaseBracket:
        """Context manager timing one phase bracket."""
        return _PhaseBracket(self, name)

    def add(self, name: str, seconds: float) -> None:
        """Fold an already-measured duration into a phase."""
        self.phases[name] = self.phases.get(name, 0.0) + float(seconds)

    def activate(self) -> _Activation:
        """Make this ledger the thread's :func:`record_current` sink for
        the ``with`` body (innermost activation wins)."""
        return _Activation(self)

    def finish(
        self,
        span=None,
        wall_s: typing.Optional[float] = None,
        record_spans: bool = False,
    ) -> dict:
        """Observe every phase into ``gordo_phase_seconds``, stamp the
        attribution summary onto ``span`` (when recording), and return
        it. ``wall_s`` (the request's measured wall time) adds the
        coverage accounting — what fraction of the wall the ledger
        explains. ``record_spans=True`` additionally persists each phase
        as a completed child span (planes whose phases do not already
        ride the Server-Timing ``record_phase`` path)."""
        if not self.phases:
            return {}
        histogram = _phase_histogram()
        host_s = device_s = 0.0
        for name, seconds in self.phases.items():
            histogram.observe(seconds, plane=self.plane, phase=name)
            if name in DEVICE_PHASES:
                device_s += seconds
            else:
                host_s += seconds
        total = host_s + device_s
        summary: typing.Dict[str, typing.Any] = {
            "plane": self.plane,
            "phases": dict(self.phases),
            "host_s": host_s,
            "device_s": device_s,
            "host_fraction": host_s / total if total else None,
            "device_fraction": device_s / total if total else None,
        }
        if wall_s is None:
            wall_s = time.perf_counter() - self._created
        summary["wall_s"] = wall_s
        summary["coverage"] = min(1.0, total / wall_s) if wall_s > 0 else None
        if span is not None and getattr(span, "recording", False):
            for name, seconds in self.phases.items():
                span.set_attribute(
                    f"phase_{name}_ms", round(seconds * 1000.0, 3)
                )
            if summary["host_fraction"] is not None:
                span.set_attribute(
                    "host_fraction", round(summary["host_fraction"], 4)
                )
                span.set_attribute(
                    "device_fraction", round(summary["device_fraction"], 4)
                )
            if summary["coverage"] is not None:
                span.set_attribute(
                    "ledger_coverage", round(summary["coverage"], 4)
                )
        if record_spans:
            from gordo_tpu.observability import tracing

            parent = span if span is not None else None
            for name, seconds in self.phases.items():
                tracing.record_span(
                    name, seconds, parent=parent, plane=self.plane
                )
        return summary


def ledger_for(plane: str):
    """A :class:`PhaseLedger` for ``plane`` — or the no-op singleton
    when ``GORDO_PHASE_LEDGER`` disables attribution (one env lookup,
    nothing else)."""
    if not ledger_enabled():
        return NOOP_LEDGER
    return PhaseLedger(plane)


def current_ledger():
    """The innermost :meth:`PhaseLedger.activate`-d ledger on this
    thread, or None."""
    stack = getattr(_TLS, "sinks", None)
    return stack[-1] if stack else None


def record_current(phase: str, seconds: float) -> bool:
    """Attribute ``seconds`` to ``phase`` on the calling thread's active
    ledger (scorer/estimator hot paths, which don't know whose request
    they serve). Returns whether a ledger was listening."""
    stack = getattr(_TLS, "sinks", None)
    if not stack:
        return False
    stack[-1].add(phase, seconds)
    return True


# -- registry-snapshot readers (benches, `profile report`, summarize) ------


def phase_totals(
    snapshot: typing.Optional[typing.Mapping[str, dict]] = None,
) -> typing.Dict[typing.Tuple[str, str], dict]:
    """``{(plane, phase): {"count", "sum"}}`` from a registry snapshot
    (default: the live process registry) — the ledger's lifetime totals,
    the shape benches stamp into ``phase_attribution`` blocks."""
    if snapshot is None:
        snapshot = get_registry().snapshot()
    dump = snapshot.get("gordo_phase_seconds") or {}
    out: typing.Dict[typing.Tuple[str, str], dict] = {}
    for series in dump.get("series") or []:
        labels = series.get("labels") or {}
        plane = labels.get("plane", "?")
        phase = labels.get("phase", "?")
        out[(plane, phase)] = {
            "count": int(series.get("count") or 0),
            "sum": float(series.get("sum") or 0.0),
        }
    return out


def split_host_device(
    totals: typing.Mapping[typing.Tuple[str, str], typing.Mapping],
) -> dict:
    """Host/device seconds and fractions over a :func:`phase_totals`
    map — the one spelling of the host-share arithmetic (rollup signals,
    bench blocks, and the cost-seam report all call this)."""
    host_s = device_s = 0.0
    for (_, phase), state in totals.items():
        seconds = float(state.get("sum") or 0.0)
        if phase in DEVICE_PHASES:
            device_s += seconds
        else:
            host_s += seconds
    total = host_s + device_s
    return {
        "host_s": round(host_s, 6),
        "device_s": round(device_s, 6),
        "host_fraction": round(host_s / total, 4) if total else None,
        "device_fraction": round(device_s / total, 4) if total else None,
    }


def phase_attribution_block(
    snapshot: typing.Optional[typing.Mapping[str, dict]] = None,
) -> dict:
    """The ``phase_attribution`` block benches stamp into their result
    JSON: per-(plane, phase) totals plus the host/device split."""
    totals = phase_totals(snapshot)
    block = {
        "phases": {
            f"{plane}/{phase}": {
                "count": state["count"],
                "sum_s": round(state["sum"], 6),
            }
            for (plane, phase), state in sorted(totals.items())
        }
    }
    block.update(split_host_device(totals))
    return block


# -- overhead --------------------------------------------------------------


def measure_overhead(samples: int = 2000) -> dict:
    """Nanoseconds per phase bracket in both regimes — disabled (the
    strict no-op) and enabled (the always-on default) — mirroring
    ``tracing.measure_overhead`` so benches report the attribution tax
    as a number. Mutates ``GORDO_PHASE_LEDGER`` while running; call
    after the measured workload has drained."""
    saved = os.environ.pop(LEDGER_ENV_VAR, None)

    def _time_loop() -> float:
        ledger = ledger_for("server")
        start = time.perf_counter()
        for _ in range(samples):
            with ledger.phase("parse"):
                pass
        return (time.perf_counter() - start) / samples * 1e9

    try:
        os.environ[LEDGER_ENV_VAR] = "0"
        disabled = _time_loop()
        os.environ.pop(LEDGER_ENV_VAR, None)
        enabled = _time_loop()
    finally:
        if saved is None:
            os.environ.pop(LEDGER_ENV_VAR, None)
        else:
            os.environ[LEDGER_ENV_VAR] = saved
    return {
        "samples": samples,
        "disabled_ns_per_phase": round(disabled, 1),
        "enabled_ns_per_phase": round(enabled, 1),
    }
