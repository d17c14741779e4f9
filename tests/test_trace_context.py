"""
Distributed-tracing tests (docs/observability.md "Distributed tracing"):
the span API and its strict-no-op discipline, W3C traceparent
propagation edges (same trace id across client retries and forwarder
hops; server echo on success AND 409/503 error paths), the span-JSONL →
Chrome-trace export contract, and the end-to-end acceptance scenario —
ONE trace id threading a client retry, the server request spans, the
per-machine predict phase, and the correlated event-log records.
"""

import json
import os

import dateutil.parser
import numpy as np
import pandas as pd
import pytest
import requests

from gordo_tpu.observability import emit_event, read_events, tracing
from gordo_tpu.observability.tracing import (
    TRACE_ID_RESPONSE_HEADER,
    TRACE_LOG_ENV_VAR,
    TRACE_SAMPLE_ENV_VAR,
    TRACEPARENT_HEADER,
    format_traceparent,
    parse_traceparent,
    read_spans,
    spans_to_chrome_trace,
    start_span,
    summarize_spans,
    trace_fields,
)
from gordo_tpu.robustness import faults
from tests.conftest import GORDO_PROJECT, GORDO_TARGETS


@pytest.fixture
def span_log(tmp_path, monkeypatch):
    """Tracing ON, sampling default, spans to a fresh JSONL file."""
    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv(TRACE_LOG_ENV_VAR, str(path))
    monkeypatch.delenv(TRACE_SAMPLE_ENV_VAR, raising=False)
    return path


@pytest.fixture
def bare_server(tmp_path, monkeypatch):
    """The real app over an (empty) collection dir — enough surface for
    header-echo and span-middleware tests without trained artifacts."""
    collection = tmp_path / "rev-1"
    collection.mkdir()
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(collection))
    from gordo_tpu.server import build_app

    return build_app(), collection


# --------------------------------------------------------------------------
# span API
# --------------------------------------------------------------------------


def test_span_tree_ids_and_jsonl_roundtrip(span_log):
    with start_span("build.fleet", n_machines=2) as root:
        with start_span("build.bucket") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_span_id == root.span_id
        tracing.record_span("model_load", 0.25, machine="m-1")
    spans = {s["name"]: s for s in read_spans(span_log)}
    assert set(spans) == {"build.fleet", "build.bucket", "model_load"}
    assert spans["build.fleet"]["parent_span_id"] is None
    assert spans["build.bucket"]["parent_span_id"] == root.span_id
    assert spans["model_load"]["parent_span_id"] == root.span_id
    assert spans["build.fleet"]["attributes"] == {"n_machines": 2}
    assert spans["model_load"]["duration_ms"] == pytest.approx(250.0)
    assert all(s["trace_id"] == root.trace_id for s in spans.values())
    assert all(s["status"] == "ok" for s in spans.values())
    # children persist before parents (exit order), and durations nest
    assert (
        spans["build.bucket"]["duration_ms"]
        <= spans["build.fleet"]["duration_ms"]
    )


def test_escaping_exception_marks_span_error(span_log):
    with pytest.raises(RuntimeError):
        with start_span("build.fetch", machine="m-err"):
            raise RuntimeError("fetch broke")
    (span,) = read_spans(span_log)
    assert span["status"] == "error"
    assert "RuntimeError" in span["attributes"]["error"]


def test_disabled_is_strict_noop(monkeypatch):
    """With GORDO_TPU_TRACE_LOG unset, the span machinery NEVER runs —
    one env dict lookup, then the singleton (the GORDO_FAULT_INJECT
    discipline, call-count pinned)."""
    monkeypatch.delenv(TRACE_LOG_ENV_VAR, raising=False)

    def explode(*args, **kwargs):
        raise AssertionError("span machinery ran with tracing off")

    monkeypatch.setattr(tracing, "_begin_span", explode)
    monkeypatch.setattr(tracing, "_write_span", explode)
    with start_span("anything", machine="m") as span:
        assert span is tracing.NOOP_SPAN
        span.set_attribute("k", "v")  # all no-ops
        # nesting stays on the singleton; the contextvar is untouched
        with start_span("nested") as inner:
            assert inner is tracing.NOOP_SPAN
    assert tracing.record_span("phase", 0.1) is None
    assert tracing.current_span() is None
    assert tracing.current_context() is None
    assert tracing.current_traceparent() is None
    assert trace_fields() == {}


def test_disabled_client_and_server_paths_never_open_spans(
    monkeypatch, bare_server
):
    """The instrumented hot paths — server middleware, client request —
    stay on the no-op path end to end when tracing is off."""
    from werkzeug.test import Client as WerkzeugClient

    monkeypatch.delenv(TRACE_LOG_ENV_VAR, raising=False)

    def explode(*args, **kwargs):
        raise AssertionError("span machinery ran with tracing off")

    monkeypatch.setattr(tracing, "_begin_span", explode)
    app, _ = bare_server
    http = WerkzeugClient(app)
    resp = http.get("/healthcheck")
    assert resp.status_code == 200
    assert TRACE_ID_RESPONSE_HEADER not in resp.headers

    client, session = _client_with_canned_session(monkeypatch, fail_times=0)
    result = _send_one_batch(client)
    assert result.error_messages == []
    assert TRACEPARENT_HEADER not in session.requests[0][1].get(
        "headers", {}
    )


def test_sampling_zero_propagates_but_records_nothing(span_log, monkeypatch):
    monkeypatch.setenv(TRACE_SAMPLE_ENV_VAR, "0")
    with start_span("client.predict") as span:
        assert not span.recording
        assert span.context is not None and not span.context.sampled
        with start_span("client.request") as child:
            assert not child.recording
            assert child.trace_id == span.trace_id
        header = tracing.current_traceparent()
    assert header is not None and header.endswith("-00")
    assert not span_log.exists()
    assert trace_fields(span) == {}


def test_sampling_is_deterministic_per_trace(monkeypatch):
    """The verdict is a threshold test on the trace id, so every process
    holding the same id agrees without coordination."""
    monkeypatch.setenv(TRACE_SAMPLE_ENV_VAR, "0.5")
    sampled = {tid: tracing._sampled(tid) for tid in
               [os.urandom(16).hex() for _ in range(64)]}
    assert {True, False} == set(sampled.values())  # both verdicts occur
    for tid, verdict in sampled.items():
        assert tracing._sampled(tid) == verdict


def test_traceparent_roundtrip_and_malformed_headers():
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8, True)
    assert parse_traceparent(format_traceparent(ctx)) == ctx
    unsampled = ctx._replace(sampled=False)
    assert parse_traceparent(format_traceparent(unsampled)) == unsampled
    for bad in (
        None,
        "",
        "garbage",
        "00-short-cdcdcdcdcdcdcdcd-01",
        f"00-{'z' * 32}-{'cd' * 8}-01",  # non-hex
        f"00-{'0' * 32}-{'cd' * 8}-01",  # all-zero trace id
        f"00-{'ab' * 16}-{'0' * 16}-01",  # all-zero span id
        f"ff-{'ab' * 16}-{'cd' * 8}-01",  # forbidden version
        f"00-{'ab' * 16}-{'cd' * 8}-01-extra",  # version 00: exactly 4 fields
    ):
        assert parse_traceparent(bad) is None, bad


def test_events_stamped_with_ambient_trace(span_log, tmp_path, monkeypatch):
    event_log = tmp_path / "events.jsonl"
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_log))
    emit_event("build_started", n_machines=1)
    with start_span("build.fleet") as span:
        emit_event("bucket_flush", n_models=1)
        # the cross-thread explicit form spells identically
        emit_event("build_machine_failed", machine="m", **trace_fields(span))
    events = {e["event"]: e for e in read_events(event_log)}
    assert "trace_id" not in events["build_started"]
    assert events["bucket_flush"]["trace_id"] == span.trace_id
    assert events["bucket_flush"]["span_id"] == span.span_id
    assert events["build_machine_failed"]["trace_id"] == span.trace_id


# --------------------------------------------------------------------------
# client propagation edges
# --------------------------------------------------------------------------


def _canned_prediction_response():
    index = pd.date_range("2019-01-01", periods=5, freq="10min", tz="UTC")
    frame = pd.DataFrame(
        np.zeros((5, 2)), columns=["tag-0", "tag-1"], index=index
    )
    from gordo_tpu.server import utils as server_utils

    resp = requests.Response()
    resp.status_code = 200
    resp._content = json.dumps(
        {"data": server_utils.dataframe_to_dict(frame)}
    ).encode()
    resp.headers["content-type"] = "application/json"
    return resp


class _FlakySession:
    """POSTs fail with a connection error ``fail_times`` times, then
    return a canned prediction response. Records every POST's kwargs."""

    def __init__(self, fail_times: int):
        self.fail_times = fail_times
        self.requests = []

    def post(self, url, **kwargs):
        self.requests.append((url, kwargs))
        if len(self.requests) <= self.fail_times:
            raise requests.ConnectionError("injected wire failure")
        return _canned_prediction_response()


def _mini_machine(name="m-trace"):
    from gordo_tpu.machine import Machine

    return Machine.from_config(
        {
            "name": name,
            "dataset": {
                "type": "RandomDataset",
                "tags": ["tag-0", "tag-1"],
                "train_start_date": "2019-01-01T00:00:00+00:00",
                "train_end_date": "2019-01-02T00:00:00+00:00",
                "asset": "gra",
            },
            "model": {"sklearn.decomposition.PCA": {}},
        },
        project_name="trace-test",
    )


def _client_with_canned_session(monkeypatch, fail_times: int):
    from gordo_tpu.client import Client

    monkeypatch.setattr("gordo_tpu.client.client.sleep", lambda s: None)
    session = _FlakySession(fail_times)
    client = Client(
        project="trace-test", scheme="http", port=80, session=session,
        n_retries=2,
    )
    return client, session


def _send_one_batch(client):
    index = pd.date_range("2019-01-01", periods=8, freq="10min", tz="UTC")
    X = pd.DataFrame(
        np.zeros((8, 2)), columns=["tag-0", "tag-1"], index=index
    )
    return client._send_prediction_request(
        X,
        None,
        chunk=slice(0, 8),
        machine=_mini_machine(),
        start=index[0],
        end=index[-1],
        revision="rev-1",
    )


def test_client_retries_keep_one_trace_id(span_log, monkeypatch):
    """The acceptance edge: every retry of one batch carries the SAME
    traceparent — one flapping request is one trace, not three."""
    client, session = _client_with_canned_session(monkeypatch, fail_times=2)
    result = _send_one_batch(client)
    assert result.error_messages == []
    assert len(session.requests) == 3  # two failures + the success
    headers = [kw["headers"][TRACEPARENT_HEADER] for _, kw in session.requests]
    assert len(set(headers)) == 1
    ctx = parse_traceparent(headers[0])
    assert ctx is not None and ctx.sampled
    request_spans = [
        s for s in read_spans(span_log) if s["name"] == "client.request"
    ]
    assert len(request_spans) == 1  # one span spanning all attempts
    assert request_spans[0]["trace_id"] == ctx.trace_id
    assert request_spans[0]["span_id"] == ctx.span_id
    assert request_spans[0]["attributes"]["machine"] == "m-trace"


def test_retry_exhausted_error_names_the_trace(span_log, monkeypatch):
    client, session = _client_with_canned_session(monkeypatch, fail_times=99)
    result = _send_one_batch(client)
    assert result.predictions is None
    header_ctx = parse_traceparent(
        session.requests[0][1]["headers"][TRACEPARENT_HEADER]
    )
    assert f"trace id: {header_ctx.trace_id}" in result.error_messages[0]


def test_forwarder_hop_keeps_trace_id(span_log):
    """forwarders.py runs in-thread under the batch span: its span (and
    any influx-write failure it logs) shares the trace id."""
    from gordo_tpu.client.forwarders import ForwardPredictionsIntoInflux

    class _Writer:
        def write_points(self, **kwargs):
            pass

    forwarder = ForwardPredictionsIntoInflux(dataframe_client=_Writer())
    frame = pd.DataFrame(
        np.zeros((4, 2)),
        columns=pd.MultiIndex.from_product([["model-output"], ["t0", "t1"]]),
    )
    with start_span("client.request", machine="m-trace") as span:
        forwarder(predictions=frame, machine=_mini_machine())
    spans = {s["name"]: s for s in read_spans(span_log)}
    assert spans["client.forward"]["trace_id"] == span.trace_id
    assert spans["client.forward"]["parent_span_id"] == span.span_id


# --------------------------------------------------------------------------
# server propagation edges
# --------------------------------------------------------------------------


def test_server_echoes_incoming_trace_id_with_recording_off(
    bare_server, monkeypatch
):
    """The echo works even when server-side tracing is disabled: parsing
    the client's traceparent needs no span machinery."""
    from werkzeug.test import Client as WerkzeugClient

    monkeypatch.delenv(TRACE_LOG_ENV_VAR, raising=False)
    app, _ = bare_server
    http = WerkzeugClient(app)
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8, True)
    resp = http.get(
        "/healthcheck",
        headers={TRACEPARENT_HEADER: format_traceparent(ctx)},
    )
    assert resp.headers[TRACE_ID_RESPONSE_HEADER] == ctx.trace_id
    # no header, no tracing: nothing to echo
    resp = http.get("/healthcheck")
    assert TRACE_ID_RESPONSE_HEADER not in resp.headers


def test_probe_endpoints_echo_but_record_no_spans(span_log, bare_server):
    """/healthcheck and /metrics are span-exempt (a liveness probe every
    few seconds would drown the span log in junk traces), mirroring the
    prometheus request-counting exclusion — but a deliberately traced
    probe still gets its id echoed."""
    from werkzeug.test import Client as WerkzeugClient

    app, _ = bare_server
    http = WerkzeugClient(app)
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8, True)
    resp = http.get(
        "/healthcheck",
        headers={TRACEPARENT_HEADER: format_traceparent(ctx)},
    )
    assert resp.status_code == 200
    assert resp.headers[TRACE_ID_RESPONSE_HEADER] == ctx.trace_id
    http.get("/healthcheck")
    http.get("/metrics")  # 404 without prometheus; still exempt
    assert not span_log.exists()


def test_server_request_span_children_and_echo(span_log, bare_server):
    from werkzeug.test import Client as WerkzeugClient

    app, _ = bare_server
    http = WerkzeugClient(app)
    resp = http.get(f"/gordo/v0/{GORDO_PROJECT}/models")
    assert resp.status_code == 200
    echoed = resp.headers[TRACE_ID_RESPONSE_HEADER]
    (span,) = read_spans(span_log)
    assert span["name"] == "server.request"
    assert span["trace_id"] == echoed
    assert span["parent_span_id"] is None  # no incoming context: new root
    assert span["attributes"]["endpoint"] == "models"
    assert span["attributes"]["status_code"] == 200


def test_server_409_and_503_paths_echo_trace_id(
    span_log, bare_server, monkeypatch
):
    """The satellite contract: error responses — the PR-4 degraded-
    serving 409 and the chaos-harness 503 — carry X-Gordo-Trace-Id, so
    client-side casualties are matchable to server-side logs."""
    from werkzeug.test import Client as WerkzeugClient

    app, collection = bare_server
    (collection / "build_report.json").write_text(
        json.dumps(
            {
                "version": 1,
                "failed": [
                    {"machine": "ghost", "phase": "fetch", "error": "IOError"}
                ],
            }
        )
    )
    http = WerkzeugClient(app)
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8, True)
    header = {TRACEPARENT_HEADER: format_traceparent(ctx)}

    resp = http.post(
        f"/gordo/v0/{GORDO_PROJECT}/ghost/prediction",
        json={"X": [[0.0, 0.0]]},
        headers=header,
    )
    assert resp.status_code == 409
    assert resp.headers[TRACE_ID_RESPONSE_HEADER] == ctx.trace_id

    monkeypatch.setenv(faults.FAULT_INJECT_ENV_VAR, "serve:raise:healthy-m")
    faults.reset()
    try:
        resp = http.post(
            f"/gordo/v0/{GORDO_PROJECT}/healthy-m/prediction",
            json={"X": [[0.0, 0.0]]},
            headers=header,
        )
    finally:
        monkeypatch.delenv(faults.FAULT_INJECT_ENV_VAR)
        faults.reset()
    assert resp.status_code == 503
    assert resp.headers[TRACE_ID_RESPONSE_HEADER] == ctx.trace_id
    # both error requests joined the client's trace in the span log
    server_spans = [
        s for s in read_spans(span_log) if s["name"] == "server.request"
    ]
    assert sorted(
        s["attributes"]["status_code"] for s in server_spans
    ) == [409, 503]
    assert all(s["trace_id"] == ctx.trace_id for s in server_spans)
    assert all(s["parent_span_id"] == ctx.span_id for s in server_spans)


def test_client_409_message_carries_server_trace_id(
    span_log, bare_server, monkeypatch
):
    from tests.utils import loopback_session

    from gordo_tpu.client import Client

    app, collection = bare_server
    (collection / "build_report.json").write_text(
        json.dumps(
            {
                "version": 1,
                "quarantined": [{"machine": "m-trace", "epoch": 1}],
            }
        )
    )
    client = Client(
        project=GORDO_PROJECT, scheme="http", port=80,
        session=loopback_session(app), n_retries=0,
    )
    result = _send_one_batch(client)
    assert result.predictions is None
    request_spans = [
        s for s in read_spans(span_log) if s["name"] == "client.request"
    ]
    assert len(request_spans) == 1
    # the id in the message is the one the SERVER echoed — which is the
    # client span's own trace id, round-tripped through the wire
    assert (
        f"server trace id: {request_spans[0]['trace_id']}"
        in result.error_messages[0]
    )


# --------------------------------------------------------------------------
# export / summarize
# --------------------------------------------------------------------------


def _make_span_fixture(span_log):
    with start_span("client.predict", path="single") as root:
        with start_span("client.request", machine="m-0"):
            tracing.record_span("predict", 0.05, machine="m-0")
    with start_span("build.fleet", n_machines=1):
        pass
    return root.trace_id


def test_chrome_trace_export_schema(span_log):
    """`trace export` emits Trace Event Format JSON that summarize and a
    schema check both accept: 'X' complete events with numeric ts/dur in
    MICROseconds, one tid per trace, gordo ids under args."""
    _make_span_fixture(span_log)
    records = read_spans(span_log)
    payload = spans_to_chrome_trace(records)
    assert set(payload) == {"traceEvents", "displayTimeUnit"}
    events = payload["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == len(records)
    assert len(meta) == 2  # one thread_name row per trace
    # track labels attach: metadata rides the SAME (pid, tid) keys the
    # span slices occupy, or Perfetto labels a phantom empty track
    assert {(e["pid"], e["tid"]) for e in meta} == {
        (e["pid"], e["tid"]) for e in complete
    }
    for event in complete:
        assert set(event) >= {"name", "ph", "ts", "dur", "pid", "tid", "args"}
        assert isinstance(event["ts"], float) and isinstance(
            event["dur"], float
        )
        assert event["args"]["trace_id"] and event["args"]["span_id"]
    # microseconds: the 50ms recorded phase is 50_000us
    predict = next(e for e in complete if e["name"] == "predict")
    assert predict["dur"] == pytest.approx(50_000.0)
    tids = {e["args"]["trace_id"]: e["tid"] for e in complete}
    assert len(set(tids.values())) == 2  # distinct rows per trace
    json.loads(json.dumps(payload))  # round-trips as plain JSON


def test_trace_cli_export_and_summarize(span_log, tmp_path):
    from click.testing import CliRunner

    from gordo_tpu.cli.trace import trace_cli

    trace_id = _make_span_fixture(span_log)
    runner = CliRunner()
    out_path = tmp_path / "chrome.json"
    result = runner.invoke(
        trace_cli, ["export", str(span_log), "-o", str(out_path)]
    )
    assert result.exit_code == 0, result.output
    payload = json.loads(out_path.read_text())
    assert any(e.get("ph") == "X" for e in payload["traceEvents"])

    result = runner.invoke(trace_cli, ["summarize", str(span_log)])
    assert result.exit_code == 0, result.output
    for expected in ("client.predict", "client.request", "predict", "m-0"):
        assert expected in result.output
    assert trace_id in result.output  # critical path names the trace
    # a directory scan finds the same spans
    result = runner.invoke(trace_cli, ["summarize", str(span_log.parent)])
    assert result.exit_code == 0 and "client.request" in result.output


def test_summarize_handles_empty_and_malformed(span_log):
    assert summarize_spans([]) == "no spans"
    span_log.write_text('{"truncated junk\n')
    assert read_spans(span_log) == []


def test_summarize_tolerates_parent_cycles():
    """A merged/hand-edited span log can hold duplicate span ids whose
    parent chain loops (root -> X, X -> X); the critical-path walk must
    terminate like the rest of the reader stack tolerates malformed
    input."""

    def rec(span_id, parent, name, dur):
        return {
            "trace_id": "t" * 32,
            "span_id": span_id,
            "parent_span_id": parent,
            "name": name,
            "start_unix_ms": 0,
            "duration_ms": dur,
        }

    records = [
        rec("rr", None, "root", 9.0),
        rec("xx", "rr", "looper", 5.0),
        rec("xx", "xx", "looper", 4.0),  # duplicate id, self-parent
        rec("aa", "bb", "mutual-a", 3.0),  # parentless mutual cycle
        rec("bb", "aa", "mutual-b", 2.0),
    ]
    out = summarize_spans(records)
    assert "5 spans in 1 traces" in out
    assert "root" in out


def test_measure_overhead_reports_all_regimes(monkeypatch):
    monkeypatch.delenv(TRACE_LOG_ENV_VAR, raising=False)
    out = tracing.measure_overhead(samples=50)
    assert set(out) == {
        "samples",
        "disabled_ns_per_span",
        "profiler_only_ns_per_span",
        "sampled_out_ns_per_span",
        "enabled_ns_per_span",
    }
    assert all(v > 0 for v in out.values())
    # measuring must not leave tracing enabled behind
    assert not tracing.tracing_enabled()


# --------------------------------------------------------------------------
# end to end: the acceptance scenario
# --------------------------------------------------------------------------


def test_one_trace_id_threads_retry_server_phase_and_events(
    trained_model_collection, tmp_path, monkeypatch
):
    """ISSUE 5 acceptance: a serve-site injected fault 503s the first
    POST; the client retries and succeeds. ONE trace id demonstrably
    threads (1) the client request span covering both attempts, (2) both
    server request spans — the 503 and the 200 — as its children, (3)
    the predict phase span under the successful request, and (4) the
    fault_injected event-log record, stamped with the 503 span's ids."""
    from tests.utils import loopback_session

    from gordo_tpu.client import Client
    from gordo_tpu.data.providers import RandomDataProvider
    from gordo_tpu.server import build_app
    from gordo_tpu.server import utils as server_utils

    target = GORDO_TARGETS[0]
    span_path = tmp_path / "spans.jsonl"
    event_path = tmp_path / "events.jsonl"
    monkeypatch.setenv(TRACE_LOG_ENV_VAR, str(span_path))
    monkeypatch.delenv(TRACE_SAMPLE_ENV_VAR, raising=False)
    monkeypatch.setenv("GORDO_TPU_EVENT_LOG", str(event_path))
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(trained_model_collection))
    monkeypatch.setenv(
        faults.FAULT_INJECT_ENV_VAR, f"serve:raise:{target}@attempts:1"
    )
    faults.reset()
    server_utils.clear_caches()
    monkeypatch.setattr("gordo_tpu.client.client.sleep", lambda s: None)
    try:
        client = Client(
            project=GORDO_PROJECT, scheme="http", port=80,
            data_provider=RandomDataProvider(),
            session=loopback_session(build_app()),
            parallelism=1, n_retries=2,
        )
        start = dateutil.parser.isoparse("2019-01-01T00:00:00+00:00")
        end = dateutil.parser.isoparse("2019-01-01T04:00:00+00:00")
        ((name, frame, errors),) = client.predict(
            start, end, targets=[target]
        )
    finally:
        faults.reset()
    assert name == target and errors == [] and len(frame) > 0

    spans = read_spans(span_path)
    (client_req,) = [
        s
        for s in spans
        if s["name"] == "client.request"
        and s["attributes"].get("machine") == target
    ]
    trace_id = client_req["trace_id"]

    # client span lineage: predict -> predict_machine -> request
    (predict_root,) = [s for s in spans if s["name"] == "client.predict"]
    (per_machine,) = [
        s for s in spans if s["name"] == "client.predict_machine"
    ]
    assert predict_root["trace_id"] == trace_id
    assert per_machine["parent_span_id"] == predict_root["span_id"]
    assert client_req["parent_span_id"] == per_machine["span_id"]

    # both server attempts joined the SAME trace as children of the one
    # client.request span: first the injected 503, then the 200
    server_reqs = [
        s
        for s in spans
        if s["name"] == "server.request" and s["trace_id"] == trace_id
    ]
    assert sorted(
        s["attributes"]["status_code"] for s in server_reqs
    ) == [200, 503]
    assert all(
        s["parent_span_id"] == client_req["span_id"] for s in server_reqs
    )
    faulted = next(
        s for s in server_reqs if s["attributes"]["status_code"] == 503
    )
    served = next(
        s for s in server_reqs if s["attributes"]["status_code"] == 200
    )
    assert faulted["status"] == "error" and served["status"] == "ok"

    # the per-machine predict phase hangs under the successful request
    phase_spans = [
        s
        for s in spans
        if s["name"] in ("model_load", "predict")
        and s["trace_id"] == trace_id
    ]
    assert {s["name"] for s in phase_spans} >= {"predict"}
    assert all(
        s["parent_span_id"] == served["span_id"] for s in phase_spans
    )

    # and the event log is trace-correlated: the fault firing carries
    # the 503 request span's ids
    fault_events = [
        e for e in read_events(event_path) if e["event"] == "fault_injected"
    ]
    assert len(fault_events) == 1
    assert fault_events[0]["trace_id"] == trace_id
    assert fault_events[0]["span_id"] == faulted["span_id"]

    # discovery requests (revisions/models/metadata) were separate
    # traces: nothing else leaked into this one
    assert {s["name"] for s in spans if s["trace_id"] == trace_id} == {
        "client.predict",
        "client.predict_machine",
        "client.request",
        "server.request",
        "model_load",
        "predict",
    }
