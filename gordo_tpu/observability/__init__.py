"""
Fleet-wide telemetry (SURVEY.md §5 gap; ML-goodput direction from
PAPERS.md arXiv:2502.06982): an in-process, dependency-light metrics
registry, a structured JSONL event log, device-memory watermark
sampling, and distributed tracing — the data layer every perf / memory-
modeling PR stands on.

- :mod:`registry` — thread-safe Counter/Gauge/Histogram metrics,
  snapshot-able to plain dicts (no ``prometheus_client`` dependency).
- :mod:`events` — one-JSON-line-per-event emitter (build started/
  finished, epoch, bucket flush, resume, crash context), stamped with
  the active trace context.
- :mod:`tracing` — dependency-light span layer with W3C ``traceparent``
  propagation client→server→fleet, JSONL span persistence, and
  Chrome-trace (Perfetto) export behind ``gordo-tpu trace``.
- :mod:`profiler` — ``maybe_trace``: the operator's switch
  (``GORDO_TPU_PROFILE_DIR``) that starts and stops a ``jax.profiler``
  session around a region. Spans reach any such session's timeline
  through :mod:`tracing` itself.
- :mod:`device_memory` — HBM watermark sampling via
  ``device.memory_stats()``, degrading gracefully (null bytes) on CPU.
- :mod:`prom_bridge` — optional export of the registry into a
  ``prometheus_client`` CollectorRegistry so ``/metrics`` serves it.
- :mod:`report` — telemetry-report JSON persisted next to build
  artifacts, plus the aggregation behind ``gordo-tpu telemetry
  summarize``.
- :mod:`rollup` — the plane-wide telemetry rollup: /telemetry/snapshot
  contract, registry merge (counters sum, gauges union under a
  ``replica`` label, histograms bucket-wise), poller, control signals.
- :mod:`slo` — declarative SLO specs evaluated against merged
  snapshots into error-budget + burn-rate objects.
- :mod:`attribution` — the phase ledger: per-request host/device time
  attribution into a closed phase vocabulary
  (``gordo_phase_seconds{plane,phase}``), span attribute stamping, and
  the ``host_fraction``/``device_fraction`` control-signal inputs.
- :mod:`sampling` — the opt-in wall profiler (``GORDO_PROFILE_HZ``):
  background stack sampling folded per-phase/per-module, flamegraph
  output, merged with the ledger by ``gordo-tpu profile report``.
"""

from .attribution import (
    DEVICE_PHASES,
    HOST_PHASES,
    LEDGER_ENV_VAR,
    PHASES,
    PLANES,
    PhaseLedger,
    ledger_enabled,
    ledger_for,
    phase_attribution_block,
    phase_totals,
    record_current,
    split_host_device,
)

from .device_memory import (
    device_memory_stats,
    memory_watermarks,
    save_device_memory_profile,
)
from .events import (
    EVENT_LOG_ENV_VAR,
    EVENT_LOG_MAX_MB_ENV_VAR,
    EventEmitter,
    emit_event,
    read_events,
)
from .profiler import PROFILE_DIR_ENV_VAR, maybe_trace, profile_dir
from .sampling import (
    PROFILE_HZ_ENV_VAR,
    PROFILE_OUT_ENV_VAR,
    WallSampler,
    active_sampler,
    folded_lines,
    maybe_start_from_env,
    profiler_active,
)
from .registry import (
    Counter,
    Gauge,
    Histogram,
    HistogramMergeError,
    MetricsRegistry,
    get_registry,
    histogram_quantile,
    histogram_stat,
    histogram_state,
    merge_histogram_states,
)
from .report import (
    TELEMETRY_REPORT_FILENAME,
    load_reports,
    summarize_directory,
    write_telemetry_report,
)
from .rollup import (
    SNAPSHOT_VERSION,
    RollupPoller,
    compute_signals,
    merge_snapshots,
    plane_status,
    render_prometheus_text,
    snapshot_payload,
)
from .slo import (
    SloObjective,
    SloReport,
    SloSpec,
    evaluate,
    evaluate_values,
    load_slo_spec,
    parse_slo_spec,
)
from .tracing import (
    TRACE_ID_RESPONSE_HEADER,
    TRACE_LOG_ENV_VAR,
    TRACE_SAMPLE_ENV_VAR,
    TRACEPARENT_HEADER,
    SpanContext,
    current_context,
    current_span,
    current_traceparent,
    format_traceparent,
    parse_traceparent,
    propagation_headers,
    read_spans,
    record_span,
    spans_to_chrome_trace,
    start_span,
    summarize_spans,
    trace_fields,
    tracing_enabled,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "EVENT_LOG_ENV_VAR",
    "EventEmitter",
    "emit_event",
    "read_events",
    "PROFILE_DIR_ENV_VAR",
    "maybe_trace",
    "profile_dir",
    "TRACE_ID_RESPONSE_HEADER",
    "TRACE_LOG_ENV_VAR",
    "TRACE_SAMPLE_ENV_VAR",
    "TRACEPARENT_HEADER",
    "SpanContext",
    "current_context",
    "current_span",
    "current_traceparent",
    "format_traceparent",
    "parse_traceparent",
    "propagation_headers",
    "read_spans",
    "record_span",
    "spans_to_chrome_trace",
    "start_span",
    "summarize_spans",
    "trace_fields",
    "tracing_enabled",
    "device_memory_stats",
    "memory_watermarks",
    "save_device_memory_profile",
    "TELEMETRY_REPORT_FILENAME",
    "write_telemetry_report",
    "load_reports",
    "summarize_directory",
    "EVENT_LOG_MAX_MB_ENV_VAR",
    "HistogramMergeError",
    "histogram_quantile",
    "histogram_stat",
    "histogram_state",
    "merge_histogram_states",
    "SNAPSHOT_VERSION",
    "RollupPoller",
    "compute_signals",
    "merge_snapshots",
    "plane_status",
    "render_prometheus_text",
    "snapshot_payload",
    "SloObjective",
    "SloReport",
    "SloSpec",
    "evaluate",
    "evaluate_values",
    "load_slo_spec",
    "parse_slo_spec",
    "DEVICE_PHASES",
    "HOST_PHASES",
    "LEDGER_ENV_VAR",
    "PHASES",
    "PLANES",
    "PhaseLedger",
    "ledger_enabled",
    "ledger_for",
    "phase_attribution_block",
    "phase_totals",
    "record_current",
    "split_host_device",
    "PROFILE_HZ_ENV_VAR",
    "PROFILE_OUT_ENV_VAR",
    "WallSampler",
    "active_sampler",
    "folded_lines",
    "maybe_start_from_env",
    "profiler_active",
]
