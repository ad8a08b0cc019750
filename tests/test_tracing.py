"""Tracing satellites (ISSUE 10): the span clock, W3C traceparent
hardening, OTLP export-failure accounting, and end-to-end propagation
(REST header -> engine node spans -> remote hop; gRPC metadata
round-trip). The flight-recorder span trees themselves live in
tests/test_flight.py."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

import seldon_core_tpu.tracing as tracing
from seldon_core_tpu.metrics.registry import MetricsRegistry
from seldon_core_tpu.testing.faults import FaultClock
from seldon_core_tpu.tracing import (
    Span,
    TraceContext,
    Tracer,
    _parse_traceparent,
    current_traceparent,
    get_tracer,
    set_tracer,
    tail_thresholds,
)

TRACE_ID = "ab" * 16
SPAN_ID = "cd" * 8
VALID_TP = f"00-{TRACE_ID}-{SPAN_ID}-01"
UNSAMPLED_TP = f"00-{TRACE_ID}-{SPAN_ID}-00"


@pytest.fixture()
def fresh_tracer():
    old = get_tracer()
    t = Tracer(enabled=True)
    set_tracer(t)
    yield t
    set_tracer(old)
    tracing.anchor()  # restore the real span clock for later tests


# ---------------------------------------------------------------------------
# _parse_traceparent hardening
# ---------------------------------------------------------------------------

def test_parse_valid_sampled():
    assert _parse_traceparent(VALID_TP) == (TRACE_ID, SPAN_ID, True)


def test_parse_honors_unsampled_flag():
    assert _parse_traceparent(UNSAMPLED_TP) == (TRACE_ID, SPAN_ID, False)


def test_parse_future_version_extra_fields():
    # per W3C, unknown versions keep the first four fields' meaning
    assert _parse_traceparent(f"01-{TRACE_ID}-{SPAN_ID}-01-extrastate") == (
        TRACE_ID, SPAN_ID, True)


def test_parse_version_00_must_have_exactly_four_fields():
    # W3C trace-context §4: extra fields are only allowed for FUTURE
    # versions; a version-00 header with a fifth field is malformed
    assert _parse_traceparent(f"00-{TRACE_ID}-{SPAN_ID}-01-extra") is None


@pytest.mark.parametrize("header", [
    None,
    "",
    "garbage",
    "00-abc-def-01",                               # short fields
    f"00-{TRACE_ID}-{SPAN_ID}",                    # missing flags
    f"zz-{TRACE_ID}-{SPAN_ID}-01",                 # non-hex version
    f"ff-{TRACE_ID}-{SPAN_ID}-01",                 # forbidden version
    f"00-{'xy' * 16}-{SPAN_ID}-01",                # non-hex trace id
    f"00-{'0' * 32}-{SPAN_ID}-01",                 # all-zero trace id
    f"00-{TRACE_ID}-{'0' * 16}-01",                # all-zero span id
    f"00-{TRACE_ID[:-2]}-{SPAN_ID}-01",            # 30-hex trace id
    f"00-{TRACE_ID}-{SPAN_ID}ab-01",               # 18-hex span id
    f"00-+{TRACE_ID[:-1]}-{SPAN_ID}-01",           # int(x,16) sign tolerance
    f"00-{TRACE_ID}-{SPAN_ID}- 1",                 # whitespace in flags
    f"00- {TRACE_ID[:-1]}-{SPAN_ID}-01",           # whitespace in trace id
])
def test_parse_rejects_malformed(header):
    assert _parse_traceparent(header) is None


def test_malformed_header_starts_fresh_trace():
    ctx = TraceContext.from_traceparent("totally-not-a-traceparent",
                                        ingress="rest:/v1/generate")
    assert len(ctx.trace_id) == 32 and ctx.trace_id != TRACE_ID
    assert ctx.parent_span_id is None and ctx.sampled


def test_context_adopts_valid_header():
    ctx = TraceContext.from_traceparent(UNSAMPLED_TP, ingress="x")
    assert ctx.trace_id == TRACE_ID
    assert ctx.parent_span_id == SPAN_ID
    assert ctx.sampled is False


# ---------------------------------------------------------------------------
# Sampled-flag behavior in the tracer
# ---------------------------------------------------------------------------

def test_unsampled_span_not_recorded_and_flag_propagates(fresh_tracer):
    with fresh_tracer.span("op", traceparent=UNSAMPLED_TP) as s:
        assert s.sampled is False
        # outbound header keeps saying "don't sample" downstream
        assert s.traceparent().endswith("-00")
        assert current_traceparent() == s.traceparent()
        with fresh_tracer.span("child") as c:
            assert c.sampled is False  # inherited
    assert fresh_tracer.drain() == []


def test_sampled_span_recorded(fresh_tracer):
    with fresh_tracer.span("op", traceparent=VALID_TP) as s:
        assert s.traceparent().endswith("-01")
    spans = fresh_tracer.drain()
    assert [sp.name for sp in spans] == ["op"]
    assert spans[0].trace_id == TRACE_ID and spans[0].parent_id == SPAN_ID


# ---------------------------------------------------------------------------
# Span clock: monotonic, anchored, immune to wall steps
# ---------------------------------------------------------------------------

def test_span_duration_survives_backward_wall_step(fresh_tracer):
    """The historical bug: time.time() at both ends of a span made the
    duration negative when NTP stepped the wall clock back mid-span. The
    anchored clock's duration is purely monotonic."""
    clock = FaultClock(start=100.0)
    wall = {"t": 5_000.0}
    tracing.anchor(wall=lambda: wall["t"], mono=clock)
    with fresh_tracer.span("op") as s:
        wall["t"] -= 3600.0          # NTP steps the wall back an hour...
        clock.advance(0.25)          # ...while 250ms actually elapse
    assert s.end - s.start == pytest.approx(0.25)
    assert s.to_dict()["durationUs"] == 250_000


def test_span_absolute_time_is_anchor_plus_elapsed(fresh_tracer):
    clock = FaultClock(start=10.0)
    tracing.anchor(wall=lambda: 1_000.0, mono=clock)
    clock.advance(2.0)
    with fresh_tracer.span("op") as s:
        clock.advance(1.0)
    assert s.start == pytest.approx(1_002.0)
    assert s.end == pytest.approx(1_003.0)


def test_forward_wall_step_mid_span_also_ignored(fresh_tracer):
    clock = FaultClock(start=0.0)
    wall = {"t": 100.0}
    tracing.anchor(wall=lambda: wall["t"], mono=clock)
    with fresh_tracer.span("op") as s:
        wall["t"] += 10_000.0        # big forward step (leap smear etc.)
        clock.advance(0.5)
    assert s.end - s.start == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# OTLP export failure accounting: bounded re-enqueue, drop counter, latency
# ---------------------------------------------------------------------------

def _failing_exporter(fail_times):
    calls = []

    def exporter(spans):
        calls.append(list(spans))
        if len(calls) <= fail_times:
            raise RuntimeError("collector down")

    exporter.calls = calls
    return exporter


def test_transient_export_blip_does_not_lose_the_batch():
    tr = Tracer(enabled=True)
    tr.exporter = _failing_exporter(fail_times=1)
    with tr.span("a"):
        pass
    tr.flush()   # fails -> re-enqueued
    assert tr.spans_dropped_total == 0
    tr.flush()   # collector back -> delivered
    assert tr.spans_dropped_total == 0
    assert [s.name for s in tr.exporter.calls[1]] == ["a"]
    assert len(tr.export_stats()["export_times_s"]) == 2


def test_second_export_failure_drops_and_counts():
    tr = Tracer(enabled=True)
    tr.exporter = _failing_exporter(fail_times=10)
    with tr.span("a"):
        pass
    tr.flush()
    tr.flush()
    assert tr.spans_dropped_total == 1
    tr.flush()   # buffer empty now — nothing re-exported, nothing counted
    assert tr.spans_dropped_total == 1
    assert len(tr.exporter.calls) == 2


def test_reenqueue_respects_buffer_bound():
    tr = Tracer(enabled=True, max_buffer=2)
    tr.exporter = _failing_exporter(fail_times=10)
    spans = [Span(name=f"s{i}", trace_id=TRACE_ID, span_id=f"{i:016x}",
                  parent_id=None) for i in range(3)]
    tr.record_spans(spans)   # >= max_buffer -> auto flush -> fail
    # only max_buffer spans re-enqueue; the overflow is dropped and counted
    assert tr.spans_dropped_total == 1
    assert len(tr.drain()) == 2


def test_full_buffer_with_exporter_drops_without_inline_flush():
    """With an exporter installed, a full buffer means the collector is
    already failing: recording threads (the batcher loop!) must NEVER run
    the blocking HTTP flush inline — new spans drop and count, and the
    background flusher keeps owning the network I/O."""
    tr = Tracer(enabled=True, max_buffer=2)
    tr.exporter = _failing_exporter(fail_times=10)
    with tr.span("a"):
        pass
    with tr.span("b"):       # buffer reaches max_buffer — still no flush
        pass
    assert tr.exporter.calls == [] and tr.spans_dropped_total == 0
    extra = [Span(name=f"x{i}", trace_id=TRACE_ID, span_id=f"{i:016x}",
                  parent_id=None) for i in range(3)]
    tr.record_spans(extra)                      # full: drop, no exporter call
    with tr.span("c"):
        pass                                    # same for single spans
    assert tr.exporter.calls == []              # NO inline network attempt
    assert tr.spans_dropped_total == 4
    tr.flush()   # the background flusher's thread owns the (failing) export
    tr.flush()   # second failure drops the re-enqueued batch (bounded)
    assert len(tr.exporter.calls) == 2
    assert tr.spans_dropped_total == 6


def test_recorder_tracks_clock_reanchor():
    """A late tracing.anchor() correction (NTP fixed after boot) must reach
    the flight recorder's materialized timestamps, not just new Spans."""
    from seldon_core_tpu.runtime.flight import EV_FIRST_TOKEN, FlightRecorder
    from seldon_core_tpu.testing.faults import FaultClock

    mono = FaultClock(start=10.0)
    wall = {"t": 1_000.0}
    tracing.anchor(wall=lambda: wall["t"], mono=mono)
    try:
        fr = FlightRecorder(1)
        tr = Tracer(enabled=True)
        # the wall clock is stepped (NTP sync) and the operator re-anchors
        wall["t"] = 50_000.0
        tracing.anchor(wall=lambda: wall["t"], mono=mono)
        fr.begin(0, None, None, prompt_tokens=1)
        fr.record(0, EV_FIRST_TOKEN, tokens=1)
        fr.complete(0, "done", 1, tr)
        root = [s for s in tr.drain() if s.parent_id is None][0]
        assert root.start >= 49_000.0  # corrected epoch, not the stale one
    finally:
        tracing.anchor()


def test_sync_tracing_feeds_registry_idempotently():
    reg = MetricsRegistry(deployment="d", predictor="p")
    tr = Tracer(enabled=True)
    tr.exporter = _failing_exporter(fail_times=10)
    with tr.span("a"):
        pass
    tr.flush()
    tr.flush()               # drop 1, two export latencies observed
    tr.count_retained("tail")
    tr.count_retained("head")
    tr.count_retained("head")
    reg.sync_tracing(tr)
    reg.sync_tracing(tr)     # catch-up idiom: second sync adds nothing
    base = {"deployment_name": "d", "predictor_name": "p"}
    get = reg.registry.get_sample_value
    assert get("seldon_trace_spans_dropped_total", base) == 1
    assert get("seldon_trace_export_seconds_count", base) == 2
    assert get("seldon_llm_traces_retained_total", {**base, "mode": "tail"}) == 1
    assert get("seldon_llm_traces_retained_total", {**base, "mode": "head"}) == 2


def test_tail_thresholds_env_parsing():
    assert tail_thresholds({}) == (None, None)
    assert tail_thresholds({"TRACING_TAIL_TTFT_MS": "250"}) == (0.25, None)
    assert tail_thresholds({"TRACING_TAIL_GAP_MS": "50"}) == (None, 0.05)
    assert tail_thresholds({"TRACING_TAIL_TTFT_MS": "garbage"}) == (None, None)


# ---------------------------------------------------------------------------
# End-to-end propagation: REST -> engine node spans -> remote hop
# ---------------------------------------------------------------------------

def test_rest_header_to_engine_nodes_to_remote_hop(fresh_tracer):
    """The reference's span topology (PAPER.md §5): the inbound traceparent
    roots the server span, every graph node gets a child span, and the
    remote hop's outbound header carries the NODE span's id downstream."""
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.contracts.graph import PredictorSpec
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.transport.rest import make_engine_app

    seen = {}

    async def go():
        async def remote_predict(request):
            seen["traceparent"] = request.headers.get("traceparent")
            return web.json_response(await request.json())

        remote_app = web.Application()
        remote_app.router.add_post("/predict", remote_predict)
        async with TestClient(TestServer(remote_app)) as rc:
            spec = PredictorSpec.from_dict({
                "name": "p",
                "graph": {"name": "m", "type": "MODEL",
                          "endpoint": {"service_host": "127.0.0.1",
                                       "service_port": rc.port,
                                       "type": "REST"}},
            })
            engine = GraphEngine(spec)
            app = make_engine_app(engine)
            async with TestClient(TestServer(app)) as ec:
                resp = await ec.post("/api/v0.1/predictions",
                                     json={"data": {"ndarray": [[1.0]]}},
                                     headers={"traceparent": VALID_TP})
                assert resp.status == 200

    asyncio.run(go())
    hop = seen["traceparent"]
    assert hop is not None and hop.split("-")[1] == TRACE_ID
    spans = {s.name: s for s in fresh_tracer.drain()}
    assert "predictions" in spans and "node:m" in spans
    assert all(s.trace_id == TRACE_ID for s in spans.values())
    # parenting: ingress span under the caller's span, node under ingress,
    # and the hop's outbound header names the node span
    assert spans["predictions"].parent_id == SPAN_ID
    assert spans["node:m"].parent_id == spans["predictions"].span_id
    assert hop.split("-")[2] == spans["node:m"].span_id


def test_remote_hop_without_span_sends_no_header(fresh_tracer):
    """Outside any span (tracing idle) the remote hop must not invent a
    traceparent."""
    import socket

    from aiohttp import web

    from seldon_core_tpu.contracts.graph import Endpoint
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.runtime.remote import RemoteComponent

    seen = {}

    async def go():
        async def handler(request):
            seen["traceparent"] = request.headers.get("traceparent")
            return web.json_response(await request.json())

        app = web.Application()
        app.router.add_post("/predict", handler)
        runner = web.AppRunner(app)
        await runner.setup()
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        await web.SockSite(runner, s).start()
        comp = RemoteComponent(Endpoint(service_host="127.0.0.1",
                                        service_port=port, type="REST"))
        try:
            await comp.predict_raw(
                SeldonMessage.from_dict({"data": {"ndarray": [[1.0]]}}))
        finally:
            await comp.close()
            await runner.cleanup()

    asyncio.run(go())
    assert seen["traceparent"] is None


# ---------------------------------------------------------------------------
# gRPC metadata round-trip
# ---------------------------------------------------------------------------

class _Echo:
    def load(self):
        pass

    def predict(self, X, names, meta=None):
        return np.asarray(X)


def test_grpc_metadata_traceparent_roundtrip(fresh_tracer):
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.transport.grpc_client import call_sync
    from seldon_core_tpu.transport.grpc_server import make_component_server

    server = make_component_server(_Echo(), port=None)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        out = call_sync(
            f"127.0.0.1:{port}", "Predict",
            SeldonMessage.from_dict({"data": {"ndarray": [[1.0, 2.0]]}}),
            metadata=[("traceparent", VALID_TP)])
        assert out.to_dict()["data"]["ndarray"] == [[1.0, 2.0]]
    finally:
        server.stop(None)
    spans = [s for s in fresh_tracer.drain() if s.name == "grpc:predict"]
    assert len(spans) == 1
    assert spans[0].trace_id == TRACE_ID and spans[0].parent_id == SPAN_ID


def test_grpc_unsampled_metadata_not_recorded(fresh_tracer):
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.transport.grpc_client import call_sync
    from seldon_core_tpu.transport.grpc_server import make_component_server

    server = make_component_server(_Echo(), port=None)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        call_sync(f"127.0.0.1:{port}", "Predict",
                  SeldonMessage.from_dict({"data": {"ndarray": [[1.0]]}}),
                  metadata=[("traceparent", UNSAMPLED_TP)])
    finally:
        server.stop(None)
    assert fresh_tracer.drain() == []


# ---------------------------------------------------------------------------
# The start's span tree (tracing/start.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", [True, False])
def test_the_start_is_one_tree_under_server_start_only_when_tracing_is_on(enabled):
    from seldon_core_tpu.tracing.start import StartLedger

    old = get_tracer()
    tracer = Tracer(enabled=enabled)
    set_tracer(tracer)
    try:
        ledger = StartLedger(age_s=1.5)
        ledger.advance("construct")
        ledger.advance("listen")
        ledger.ready()
        with ledger.stage("batcher.build"):      # after /ready: a late child of the same root
            pass
        spans = {s.name: s for s in tracer.drain()}
    finally:
        set_tracer(old)
    assert ledger.stage_seconds["import"] >= 1.5    # the stages are booked either way
    if not enabled:
        assert spans == {}
        return
    assert set(spans) == {"server.start", "start.import", "start.construct", "start.listen",
                          "start.batcher.build"}
    root = spans["server.start"]
    assert root.parent_id is None and root.sampled
    assert root.end - root.start == pytest.approx(ledger.ready_s) and ledger.ready_s >= 1.5
    assert all((s.trace_id, s.parent_id) == (root.trace_id, root.span_id)
               for name, s in spans.items() if name != "server.start")
    assert spans["start.batcher.build"].start >= root.end
