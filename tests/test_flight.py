"""Flight recorder (ISSUE 10 tentpole): per-request span trees through the
serving hot path.

The contract: with the tracer enabled, every request served by the
continuous batcher yields ONE span tree rooted at the transport ingress
containing queue-wait, every prefill chunk, the handoff stages when
disaggregated, and a decode lifetime whose per-step token counts sum to
the generated length — disagg on + off, greedy + seeded — while TRACING off leaves the batcher
with no recorder and zero added work. Tail sampling retains unsampled
slow requests; /debug/timeline (REST + gRPC mirror) exposes the recent
timelines and the scaling snapshot. Runs on the virtual 8-device CPU
mesh (tests/conftest.py) for the disaggregated configs."""

from __future__ import annotations

import asyncio
import collections
import json
import socket
import threading

import pytest

import seldon_core_tpu.tracing as tracing
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.runtime.flight import (
    EV_FIRST_TOKEN,
    EV_STEP,
    FlightRecorder,
)
from seldon_core_tpu.servers.llmserver import LLMServer
from seldon_core_tpu.tracing import TraceContext, Tracer, get_tracer, set_tracer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)

PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13],
           [7], [60, 61, 62, 63, 64, 65]]


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture()
def enabled_tracer():
    old = get_tracer()
    t = Tracer(enabled=True)
    set_tracer(t)
    yield t
    set_tracer(old)
    tracing.anchor()


@pytest.fixture(scope="module")
def server():
    return make_server()


@pytest.fixture(scope="module")
def disagg_server():
    return make_server(disaggregation="remote_prefill", prefill_devices=2)


def run_batch(srv, prompts, *, n=8, seeds=None, ctxs=None, tracer=None,
              **batcher_kw):
    async def go():
        b = ContinuousBatcher(srv, **batcher_kw)
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=n,
                     seed=None if seeds is None else seeds[i],
                     trace=None if ctxs is None else ctxs[i])
            for i, p in enumerate(prompts)])
        recorder = b._flight
        await b.close()
        return outs, recorder

    return asyncio.run(go())


def _tree_for(spans, trace_id):
    """(root, children) for one trace id; asserts exactly one root."""
    mine = [s for s in spans if s.trace_id == trace_id]
    roots = [s for s in mine if s.parent_id is None
             or all(s.parent_id != o.span_id for o in mine)]
    assert len(roots) == 1, [s.name for s in mine]
    root = roots[0]
    children = [s for s in mine if s.parent_id == root.span_id]
    return root, children


# ---------------------------------------------------------------------------
# The acceptance matrix: one span tree per request, token counts exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seeded", [False, True])
def test_span_tree_per_request(server, enabled_tracer, seeded):
    seeds = [11, 22, 33, 44] if seeded else None
    ctxs = [TraceContext.from_traceparent(None, ingress="rest:/v1/generate")
            for _ in PROMPTS]
    kw = dict(max_slots=3, page_size=8, prefill_chunk=4)
    outs, recorder = run_batch(server, PROMPTS, seeds=seeds, ctxs=ctxs, **kw)
    spans = enabled_tracer.drain()
    timelines = {t["trace_id"]: t for t in recorder.timelines()}
    for i, ctx in enumerate(ctxs):
        root, children = _tree_for(spans, ctx.trace_id)
        assert root.name == "llm.request rest:/v1/generate"
        names = collections.Counter(c.name for c in children)
        assert names["queue.wait"] == 1
        assert names["llm.first_token"] == 1
        assert names["llm.decode"] == 1
        # every prefill chunk of the (4-token) chunked admission
        L = len(PROMPTS[i])
        assert names["llm.prefill_chunk"] == -(-L // 4)
        # decode lifetime: per-step token counts sum to the generated
        # length (first token + step events == credited tokens == output)
        step_tokens = sum(c.tags["tokens"] for c in children
                          if c.name == "llm.step")
        assert step_tokens + 1 == len(outs[i]) == root.tags["tokens"]
        tl = timelines[ctx.trace_id]
        assert tl["token_events_sum"] == len(outs[i])
        assert tl["status"] == "done" and tl["sampling"] == "head"
        assert tl["queue_wait_s"] >= 0.0 and tl["ttft_s"] > 0.0
        # spans nest inside the root's lifetime
        for c in children:
            assert c.start >= root.start - 1e-6
            assert c.end <= root.end + 1e-6


@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered tracing step
def test_span_tree_disaggregated(disagg_server, enabled_tracer):
    ctxs = [TraceContext.from_traceparent(None, ingress="grpc:GenerateStream")
            for _ in PROMPTS]
    outs, recorder = run_batch(disagg_server, PROMPTS, ctxs=ctxs,
                               max_slots=3, page_size=8,
                               disaggregation="remote_prefill")
    spans = enabled_tracer.drain()
    for i, ctx in enumerate(ctxs):
        root, children = _tree_for(spans, ctx.trace_id)
        assert root.name == "llm.request grpc:GenerateStream"
        names = {c.name for c in children}
        # the handoff's full stage chain joins the request's own trace
        assert {"llm.handoff_staged", "llm.handoff_compute",
                "llm.handoff_transfer", "llm.handoff_import",
                "queue.wait", "llm.first_token", "llm.decode"} <= names
        step_tokens = sum(c.tags["tokens"] for c in children
                          if c.name == "llm.step")
        assert step_tokens + 1 == len(outs[i])


def test_inbound_traceparent_roots_the_tree(server, enabled_tracer):
    parent_trace, parent_span = "ef" * 16, "12" * 8
    ctx = TraceContext.from_traceparent(
        f"00-{parent_trace}-{parent_span}-01", ingress="rest:/v1/generate")
    outs, _ = run_batch(server, [PROMPTS[0]], ctxs=[ctx], max_slots=2,
                        page_size=8)
    spans = enabled_tracer.drain()
    root, _children = _tree_for(spans, parent_trace)
    # the ingress root hangs under the CALLER's span, same trace id
    assert root.parent_id == parent_span
    assert all(s.trace_id == parent_trace for s in spans)


def test_tracing_disabled_means_no_recorder_and_no_spans(server):
    tracer = get_tracer()
    assert not tracer.enabled  # default test environment
    outs, recorder = run_batch(server, [PROMPTS[0]], max_slots=2,
                               page_size=8)
    assert recorder is None
    assert tracer.drain() == []
    assert len(outs[0]) == 8


@pytest.mark.slow  # two full batches; the claim also rides the unfiltered CI step
def test_tokens_identical_with_and_without_tracing(server, enabled_tracer):
    """The recorder observes; it must never change what is served."""
    ctxs = [TraceContext.from_traceparent(None, ingress="x")
            for _ in PROMPTS]
    traced, _ = run_batch(server, PROMPTS, ctxs=ctxs, max_slots=3,
                          page_size=8)
    enabled_tracer.drain()
    untraced, _ = run_batch(server, PROMPTS, max_slots=3,
                            page_size=8, tracing=False)
    assert traced == untraced


# ---------------------------------------------------------------------------
# Tail sampling
# ---------------------------------------------------------------------------

def test_unsampled_request_dropped_without_thresholds(server, enabled_tracer):
    ctx = TraceContext.from_traceparent(None, ingress="x")
    ctx.sampled = False
    outs, recorder = run_batch(server, [PROMPTS[0]], ctxs=[ctx],
                               max_slots=2, page_size=8)
    # no spans exported for the head-dropped request...
    assert [s for s in enabled_tracer.drain()
            if s.trace_id == ctx.trace_id] == []
    # ...but the operator-facing timeline still exists
    tl = recorder.timelines()[-1]
    assert tl["trace_id"] == ctx.trace_id and tl["sampling"] == "drop"


def test_tail_retention_overrides_head_drop(server, enabled_tracer,
                                            monkeypatch):
    """An unsampled request whose TTFT exceeds the tail threshold is
    retained anyway — the slow outliers head sampling is blind to."""
    monkeypatch.setenv("TRACING_TAIL_TTFT_MS", "0")   # everything is slow
    ctx = TraceContext.from_traceparent(None, ingress="x")
    ctx.sampled = False
    outs, recorder = run_batch(server, [PROMPTS[0]], ctxs=[ctx],
                               max_slots=2, page_size=8)
    spans = [s for s in enabled_tracer.drain() if s.trace_id == ctx.trace_id]
    assert spans, "tail sampling must retain the slow unsampled request"
    tl = recorder.timelines()[-1]
    assert tl["sampling"] == "tail"
    assert enabled_tracer.retained_total.get("tail", 0) >= 1


# ---------------------------------------------------------------------------
# Recorder unit behavior (no jax)
# ---------------------------------------------------------------------------

def _fake_clock(start=0.0):
    state = {"t": start}

    def clock():
        state["t"] += 0.001
        return state["t"]

    clock.state = state
    return clock


def test_ring_overflow_drops_oldest_and_counts():
    fr = FlightRecorder(1, ring_size=4, clock=_fake_clock())
    fr.begin(0, None, None, prompt_tokens=3)
    fr.record(0, EV_FIRST_TOKEN, tokens=1)
    for _ in range(9):
        fr.record(0, EV_STEP, tokens=1)
    tl = fr.complete(0, "done", 10)
    assert len(tl["events"]) == 4          # the ring keeps the last 4
    assert tl["events_dropped"] == 6
    assert fr.snapshot()["events_dropped_total"] == 6
    # the latency/token AUDIT signals survive eviction (segment
    # accumulators, not ring-derived): without this a long slow request
    # would lose its TTFT and dodge TTFT tail-sampling
    assert tl["ttft_s"] is not None
    assert tl["token_events_sum"] == 10
    assert tl["worst_gap_s"] is not None


def test_recorder_worst_gap_and_ttft():
    clock = _fake_clock()
    fr = FlightRecorder(1, clock=clock)
    t_submit = clock()
    fr.begin(0, None, t_submit, prompt_tokens=2)
    fr.record(0, EV_FIRST_TOKEN, tokens=1)
    clock.state["t"] += 0.200               # a 200ms stall mid-decode
    fr.record(0, EV_STEP, tokens=1)
    fr.record(0, EV_STEP, tokens=1)
    tl = fr.complete(0, "done", 3)
    assert tl["worst_gap_s"] == pytest.approx(0.201, abs=1e-3)
    assert tl["ttft_s"] > 0
    snap = fr.snapshot()
    assert snap["completed_total"] == 1
    assert snap["worst_gap_s"]["max"] == pytest.approx(0.201, abs=1e-3)


def test_recorder_complete_without_begin_is_noop():
    fr = FlightRecorder(2)
    assert fr.complete(1, "done", 5) is None
    fr.record(1, EV_STEP, tokens=1)         # no segment: silently ignored
    assert fr.timelines() == []


def test_timelines_clamps_nonpositive_n():
    """?n= comes raw off the query string: n<=0 must mean none, not the
    whole ring (items[-0:]) or an arbitrary middle slice (negative n)."""
    fr = FlightRecorder(1)
    for _ in range(3):
        fr.begin(0, None, None, prompt_tokens=1)
        fr.complete(0, "done", 1)
    assert fr.timelines(0) == []
    assert fr.timelines(-5) == []
    assert len(fr.timelines(2)) == 2
    assert len(fr.timelines(99)) == 3


# ---------------------------------------------------------------------------
# /debug/timeline: REST endpoint + gRPC mirror, SSE trace stamps
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rest_port():
    """Threaded REST app over a batched component (the serving-test idiom:
    plain urllib clients can hit it from any thread). Module-scoped with
    its own enabled tracer — one server build serves every transport test
    (tier-1 wall budget; the recorder arms at the first request's lazy
    BatcherService creation, while this tracer is current)."""
    from aiohttp import web

    from seldon_core_tpu.transport.rest import make_component_app

    old = get_tracer()
    set_tracer(Tracer(enabled=True))
    component = make_server(continuous_batching=2)
    app = make_component_app(component)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        run.port = s.getsockname()[1]
        loop.run_until_complete(web.SockSite(runner, s).start())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    yield run.port, component
    loop.call_soon_threadsafe(loop.stop)
    set_tracer(old)
    tracing.anchor()


def _post(port, path, body, timeout=120.0, headers=None, stream=False):
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    resp = urllib.request.urlopen(req, timeout=timeout)
    if stream:
        return resp
    return json.loads(resp.read())


def _get(port, path, timeout=30.0):
    import urllib.request

    resp = urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout)
    return json.loads(resp.read())


def test_rest_generate_stamps_trace_id_and_debug_timeline(rest_port):
    port, component = rest_port
    tp = f"00-{'aa' * 16}-{'bb' * 8}-01"
    out = _post(port, "/v1/generate", {"prompt": [5, 9, 17]},
                headers={"traceparent": tp})
    assert out["trace_id"] == "aa" * 16
    rep = _get(port, "/debug/timeline?n=8")
    assert rep["tracing"] is True and rep["tracer_enabled"] is True
    assert rep["timelines"], "the served request must appear"
    tl = rep["timelines"][-1]
    assert tl["trace_id"] == "aa" * 16
    assert tl["ingress"] == "rest:/v1/generate"
    assert tl["token_events_sum"] == tl["tokens"] == len(out["tokens"])
    kinds = [e["kind"] for e in tl["events"]]
    assert "first_token" in kinds and "step" in kinds
    scaling = rep["scaling"]
    assert scaling["total_slots"] == 2
    assert scaling["requests"]["completed_total"] >= 1
    assert scaling["requests"]["retained"]["head"] >= 1


def test_sse_stream_carries_trace_id(rest_port):
    port, _component = rest_port
    resp = _post(port, "/v1/generate",
                 {"prompt": [7, 8, 9], "stream": True}, stream=True)
    assert resp.headers.get("X-Trace-Id"), "stream must expose the trace id"
    trace_id = resp.headers["X-Trace-Id"]
    events = []
    for line in resp:
        line = line.decode().strip()
        if line.startswith("data: "):
            events.append(json.loads(line[len("data: "):]))
    done = events[-1]
    assert done["done"] is True
    assert done["trace_id"] == trace_id


def test_metrics_endpoint_exposes_trace_series(rest_port):
    import urllib.request

    port, _component = rest_port
    _post(port, "/v1/generate", {"prompt": [4, 5]})
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
    assert "seldon_llm_traces_retained_total" in body
    assert "seldon_trace_spans_dropped_total" in body
    assert "seldon_trace_export_seconds" in body


def test_grpc_stream_initial_metadata_carries_trace_id(rest_port):
    """The gRPC mirror of SSE's X-Trace-Id header: the id must ride the
    INITIAL metadata (available even if the stream later hangs — trailing
    metadata never arrives on a cancelled RPC) and match the done event."""
    import grpc

    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.transport import proto_convert as pc
    from seldon_core_tpu.transport.proto import prediction_pb2 as pb
    from seldon_core_tpu.transport.grpc_server import make_component_server

    _http, component = rest_port
    server = make_component_server(component, port=None)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        rpc = channel.unary_stream(
            "/seldon.protos.Model/GenerateStream",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=pb.SeldonMessage.FromString)
        call = rpc(pc.message_to_proto(SeldonMessage.from_json_data(
            {"prompt": [5, 6, 7], "max_new_tokens": 4})), timeout=120)
        md = dict(call.initial_metadata())   # blocks until headers arrive
        events = [pc.message_from_proto(m).json_data for m in call]
        done = events[-1]
        assert done["done"] is True
        assert md.get("x-trace-id") == done["trace_id"]
    finally:
        channel.close()
        server.stop(None)


def test_engine_predict_path_joins_inbound_trace(rest_port):
    """A jsonData-prompt Predict (the engine/dispatch batching path, not
    /v1/generate) carrying a traceparent must root its flight timeline in
    the CALLER's trace — the transport span is active when dispatch
    submits, so the timeline may not start a fresh 'internal' trace."""
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.runtime.batcher import get_batcher_service
    from seldon_core_tpu.transport.grpc_client import call_sync
    from seldon_core_tpu.transport.grpc_server import make_component_server

    _http, component = rest_port
    server = make_component_server(component, port=None)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    trace_id, span_id = "fe" * 16, "21" * 8
    try:
        out = call_sync(
            f"127.0.0.1:{port}", "Predict",
            SeldonMessage.from_json_data({"prompt": [9, 9, 3],
                                          "max_new_tokens": 4}),
            metadata=[("traceparent", f"00-{trace_id}-{span_id}-01")])
        assert out.json_data["tokens"][0]
    finally:
        server.stop(None)
    recorder = get_batcher_service(component).batcher._flight
    mine = [t for t in recorder.timelines() if t["trace_id"] == trace_id]
    assert mine, "dispatch-path request must join the inbound trace"
    # ingress inherits the ACTIVE transport span's name — here the gRPC
    # component server's predict handler
    assert mine[-1]["ingress"] == "grpc:predict"
    assert mine[-1]["token_events_sum"] == len(out.json_data["tokens"][0])


def test_grpc_debug_timeline_mirrors_rest(rest_port):
    """The gRPC mirror serves the SAME component (and recorder) the REST
    endpoint reads — one wire round-trip proves the rpc + payload parity."""
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.transport.grpc_client import call_sync
    from seldon_core_tpu.transport.grpc_server import make_component_server

    from seldon_core_tpu.runtime.batcher import get_batcher_service

    http_port, component = rest_port
    ctx = TraceContext.from_traceparent(None, ingress="grpc:GenerateStream")
    toks = get_batcher_service(component).submit_sync([5, 9, 17], 6,
                                                      trace=ctx)
    server = make_component_server(component, port=None)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        out = call_sync(f"127.0.0.1:{port}", "DebugTimeline",
                        SeldonMessage.from_json_data({"n": 64}))
        rep = out.json_data
        assert rep["tracing"] is True
        mine = [t for t in rep["timelines"] if t["trace_id"] == ctx.trace_id]
        assert mine and mine[-1]["token_events_sum"] == len(toks)
        assert mine[-1]["ingress"] == "grpc:GenerateStream"
        # identical schema/payload source as REST (timeline_report)
        rest_rep = _get(http_port, "/debug/timeline?n=64")
        assert rep["scaling"].keys() == rest_rep["scaling"].keys()
        assert [t["trace_id"] for t in rep["timelines"]] == \
            [t["trace_id"] for t in rest_rep["timelines"]]
    finally:
        server.stop(None)
