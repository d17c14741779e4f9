"""Profiler-trace hook tests (SURVEY.md §5 tracing analogue): the operator's
``maybe_trace`` switch, and the one span seam (``tracing.start_span``) that
writes every span onto the host plane of whatever ``jax.profiler`` session
is active in the process."""

import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.observability import tracing
from gordo_tpu.observability.profiler import PROFILE_DIR_ENV_VAR, maybe_trace
from gordo_tpu.observability.tracing import TRACE_LOG_ENV_VAR, start_span


@pytest.fixture(autouse=True)
def _no_span_log(monkeypatch):
    monkeypatch.delenv(TRACE_LOG_ENV_VAR, raising=False)


def host_events(trace_dir):
    """[(name, {stat: value})] of every event on the host planes of the one
    trace written under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True
    )
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((e.name, dict(e.stats)) for e in line.events)
    return events


@pytest.fixture
def caller_trace(tmp_path):
    """A profiler session the CALLER starts, as a benchmark harness or an
    operator attaching TensorBoard does: nothing of gordo_tpu opened it."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    stopped = []

    def stop():
        if not stopped:
            stopped.append(jax.profiler.stop_trace())
        return tmp_path / "trace"

    yield stop
    stop()


def test_maybe_trace_noop_when_unconfigured(monkeypatch):
    monkeypatch.delenv(PROFILE_DIR_ENV_VAR, raising=False)
    with maybe_trace("nothing"):
        pass  # must not create anything or require jax profiler state


def test_maybe_trace_writes_dump(tmp_path, monkeypatch):
    monkeypatch.setenv(PROFILE_DIR_ENV_VAR, str(tmp_path))
    with maybe_trace("unit"):
        with start_span("compute"):
            jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    dumps = [d for d in os.listdir(tmp_path) if d.startswith("unit-")]
    assert len(dumps) == 1
    # something was actually written under the dump dir, the span among it
    contents = list(os.walk(tmp_path / dumps[0]))
    assert sum(len(files) for _, _, files in contents) > 0
    assert "compute" in [name for name, _ in host_events(tmp_path / dumps[0])]


def test_span_outside_any_session_is_noop(tmp_path, monkeypatch):
    """With no profiler session and no span log, start_span is the strict
    no-op: the singleton context manager and span, no annotation built, no
    file touched."""
    monkeypatch.delenv(PROFILE_DIR_ENV_VAR, raising=False)
    monkeypatch.chdir(tmp_path)

    def explode(*args, **kwargs):
        raise AssertionError("span machinery ran with nothing to write to")

    monkeypatch.setattr(tracing, "_open_timeline_event", explode)
    monkeypatch.setattr(tracing, "_write_span", explode)
    monkeypatch.setattr(tracing, "_TimelineSpanContextManager", explode)
    assert not tracing._timeline_active()
    ran = []
    with start_span("orphan-span", machine="m") as span:
        assert span is tracing.NOOP_SPAN
        ran.append(1)
    assert ran == [1]
    assert os.listdir(tmp_path) == []


def test_span_on_worker_thread_reaches_the_callers_trace(caller_trace):
    """A span opened on a worker thread while a trace started by the CALLER
    is active lies in the written trace under its name (the old annotate
    saw only maybe_trace's own thread)."""
    seen = []

    def work():
        with start_span("build.fetch", machine="m-1") as span:
            seen.append(span)
            jnp.ones(8).block_until_ready()

    worker = threading.Thread(target=work)
    worker.start()
    worker.join()
    with start_span("build.fit"):
        pass
    names = [name for name, _ in host_events(caller_trace())]
    assert "build.fetch" in names and "build.fit" in names
    # recording is off: the body got the no-op span and nothing was logged
    assert seen == [tracing.NOOP_SPAN]


def test_recording_span_joins_its_profiler_event(caller_trace, tmp_path, monkeypatch):
    """With the span log set too, the profiler event carries the span's ids,
    so the JSONL record and the event of one span can be joined."""
    log = tmp_path / "spans.jsonl"
    monkeypatch.setenv(TRACE_LOG_ENV_VAR, str(log))
    with start_span("build.bucket") as parent:
        with start_span("build.cv", machine="m-1"):
            pass
    events = dict(host_events(caller_trace()))
    records = {r["name"]: r for r in tracing.read_spans(str(log))}
    assert events["build.cv"] == {
        "span_id": records["build.cv"]["span_id"],
        "parent_span_id": parent.span_id,
        "trace_id": parent.trace_id,
    }
    assert events["build.bucket"]["span_id"] == parent.span_id
    assert "parent_span_id" not in events["build.bucket"]  # a root


def test_dispatch_span_keeps_the_name_the_benchmark_reads(caller_trace):
    """chipbench/drivers/fit_loop.py looks for "train-dispatch": the one
    span whose timeline name differs from its catalogue name."""
    with start_span("train.dispatch", epoch=0, n_epochs=1):
        pass
    names = [name for name, _ in host_events(caller_trace())]
    assert "train-dispatch" in names and "train.dispatch" not in names


def test_maybe_trace_nested_regions(tmp_path, monkeypatch):
    """The jax profiler cannot start twice: a NESTED maybe_trace region
    degrades to a warning no-op while the outer trace survives, stops
    cleanly, and writes its dump — and a fresh trace works afterwards."""
    monkeypatch.setenv(PROFILE_DIR_ENV_VAR, str(tmp_path))
    with maybe_trace("outer"):
        with maybe_trace("inner"):
            with start_span("nested-compute"):
                jnp.dot(
                    jnp.ones((32, 32)), jnp.ones((32, 32))
                ).block_until_ready()
    dumps = os.listdir(tmp_path)
    assert any(d.startswith("outer-") for d in dumps)
    # the failed inner start must not have corrupted profiler state
    with maybe_trace("after-nested"):
        np.asarray(jnp.ones(4))
    assert any(d.startswith("after-nested-") for d in os.listdir(tmp_path))


def test_maybe_trace_start_failure_is_silent_noop(tmp_path, monkeypatch):
    """A profiler that cannot START must not break the traced workload,
    leaves no session behind for spans to write to, and writes nothing."""
    monkeypatch.setenv(PROFILE_DIR_ENV_VAR, str(tmp_path))

    def boom(*args, **kwargs):
        raise RuntimeError("profiler wedged")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    monkeypatch.setattr(tracing, "_open_timeline_event", boom)
    ran = []
    with maybe_trace("broken"):
        assert not tracing._timeline_active()
        with start_span("never-active") as span:
            assert span is tracing.NOOP_SPAN
            ran.append(1)
    assert ran == [1]
    assert os.listdir(tmp_path) == []


def test_maybe_trace_stop_failure_does_not_raise(tmp_path, monkeypatch):
    """A profiler that cannot STOP must not raise out of the region; once
    the session is really closed, spans are no-ops again."""
    monkeypatch.setenv(PROFILE_DIR_ENV_VAR, str(tmp_path))
    real_stop = jax.profiler.stop_trace

    def boom():
        raise RuntimeError("stop failed")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    try:
        with maybe_trace("stopfail"):
            np.asarray(jnp.ones(4))
            assert tracing._timeline_active()
    finally:
        # the real profiler session is still open (start succeeded, our
        # fake stop raised): close it so later tests can trace again
        monkeypatch.undo()
        try:
            real_stop()
        except Exception:
            pass
    assert not tracing._timeline_active()


class _BrokenAnnotation:
    """A profiler that says it is recording and cannot annotate."""

    @staticmethod
    def is_enabled():
        return True

    def __init__(self, name, **kwargs):
        raise RuntimeError("no annotations on this backend")


@pytest.mark.parametrize("span_log", [False, True], ids=["timeline-only", "recording"])
def test_span_survives_broken_annotation_api(tmp_path, monkeypatch, span_log):
    """With a session nominally active but TraceAnnotation unusable, the
    bracketed body still runs, and a recording span is still logged."""
    monkeypatch.setattr(tracing, "_annotation_class", _BrokenAnnotation)
    log = tmp_path / "spans.jsonl"
    if span_log:
        monkeypatch.setenv(TRACE_LOG_ENV_VAR, str(log))
    ran = []
    with start_span("unusable") as span:
        ran.append(span.recording)
    assert ran == [span_log]
    assert log.exists() == span_log
    if span_log:
        assert [r["name"] for r in tracing.read_spans(str(log))] == ["unusable"]


@pytest.mark.slow
def test_builder_traces_fit(tmp_path, monkeypatch):
    """ModelBuilder wraps fit in a trace when the env var is set."""
    import yaml

    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.machine import Machine

    monkeypatch.setenv(PROFILE_DIR_ENV_VAR, str(tmp_path))
    config = yaml.safe_load(
        """
        name: traced-machine
        dataset:
          type: RandomDataset
          tags: [tag-0, tag-1]
          train_start_date: '2019-01-01T00:00:00+00:00'
          train_end_date: '2019-01-02T00:00:00+00:00'
          asset: gra
        model:
          gordo_tpu.models.AutoEncoder: {kind: feedforward_hourglass, epochs: 1}
        project_name: test
        """
    )
    machine = Machine.from_dict(config)
    model, _ = ModelBuilder(machine).build()
    assert model is not None
    assert any(d.startswith("build-traced-machine") for d in os.listdir(tmp_path))
