"""Near-miss fixture for span-discipline: context-managed spans,
helper-stamped events, completed-span recorders. Nothing here may flag."""

import contextlib

from gordo_tpu.observability import tracing
from gordo_tpu.observability.events import emit_event
from gordo_tpu.observability.tracing import start_span, trace_fields


def managed():
    with start_span("build.fetch", machine="m-1") as span:
        emit_event("epoch", epoch=0)  # stamped by the ambient span
        return span.trace_id


def managed_attribute_form():
    with tracing.start_span("client.request"):
        pass


def managed_multi_item(profiler):
    with profiler.maybe_trace("build"), start_span("build.fit"):
        pass


def exit_stack_entered():
    with contextlib.ExitStack() as stack:
        span = stack.enter_context(start_span("build.bucket"))
        return span


def helper_stamped_cross_thread(span):
    emit_event("build_machine_failed", machine="m-1", **trace_fields(span))


def completed_recorders(seconds):
    # record_span / record_phase persist a finished span immediately:
    # no context manager involved, not a leak
    tracing.record_span("model_load", seconds)
    return tracing.record_span("predict", seconds, machine="m-1")
