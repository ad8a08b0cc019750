"""Request logger service + engine pair-posting + load generator."""

import asyncio
import io
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from seldon_core_tpu.observability.request_logger import flatten_pair, make_logger_app


def call(app, path, json_body, headers=None):
    async def go():
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(path, json=json_body, headers=headers or {})
            return resp.status, await resp.json()

    return asyncio.run(go())


def test_flatten_pair_per_element():
    body = {
        "request": {"data": {"ndarray": [[1, 2], [3, 4]]}, "meta": {"puid": "abc"}},
        "response": {"data": {"ndarray": [[0.9], [0.1]]}},
    }
    rows = flatten_pair(body, {"ce-type": "seldon.message.pair"})
    assert len(rows) == 2
    assert rows[0]["request.id"] == "abc"
    assert rows[0]["request.data"] == [1, 2]
    assert rows[0]["response.data"] == [0.9]
    assert rows[1]["request.elem"] == 1


def test_flatten_tensor_and_strdata():
    body = {
        "request": {"data": {"tensor": {"shape": [2, 2], "values": [1, 2, 3, 4]}}},
        "response": {"strData": "ok"},
    }
    rows = flatten_pair(body, {})
    assert rows[0]["request.data"] == [1, 2]
    assert rows[0]["response.data"] == "ok"


def test_logger_app_writes_lines():
    out = io.StringIO()
    app = make_logger_app(out=out)
    status, body = call(
        app,
        "/",
        {"request": {"data": {"ndarray": [[1.0]]}}, "response": {"data": {"ndarray": [[2.0]]}}},
        headers={"CE-Type": "seldon.message.pair", "CE-SDep": "dep1"},
    )
    assert status == 200
    lines = [json.loads(line) for line in out.getvalue().strip().splitlines()]
    assert len(lines) == 1
    assert lines[0]["sdep"] == "dep1"
    assert lines[0]["request.data"] == [1.0]


def test_logger_app_rejects_bad_json():
    async def go():
        app = make_logger_app(out=io.StringIO())
        async with TestClient(TestServer(app)) as client:
            resp = await client.post("/", data=b"not json")
            return resp.status

    assert asyncio.run(go()) == 400


def test_engine_posts_pairs_to_logger(monkeypatch):
    """REQUEST_LOGGER_URL set on the engine -> logger receives the pair."""
    from aiohttp import web

    from seldon_core_tpu.contracts.graph import PredictorSpec
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.transport.rest import make_engine_app

    out = io.StringIO()
    received = []

    async def go():
        logger_app = make_logger_app(out=out)

        async def spy(request):
            received.append(await request.json())
            return web.json_response({"status": "ok"})

        logger_app.router.add_post("/spy", spy)
        async with TestClient(TestServer(logger_app)) as lc:
            logger_url = f"http://127.0.0.1:{lc.port}/spy"
            monkeypatch.setenv("REQUEST_LOGGER_URL", logger_url)
            engine = GraphEngine(
                PredictorSpec.from_dict(
                    {"name": "p", "graph": {"name": "m", "type": "MODEL", "implementation": "SIMPLE_MODEL"}}
                )
            )
            app = make_engine_app(engine)
            async with TestClient(TestServer(app)) as ec:
                resp = await ec.post("/api/v0.1/predictions", json={"data": {"ndarray": [[1.0]]}})
                assert resp.status == 200
            for _ in range(50):  # fire-and-forget post: wait briefly
                if received:
                    break
                await asyncio.sleep(0.05)

    asyncio.run(go())
    assert received, "logger never received the message pair"
    assert received[0]["request"]["data"]["ndarray"] == [[1.0]]
    assert received[0]["response"]["data"]["ndarray"]


def test_loadgen_rest_against_engine():
    from seldon_core_tpu.benchmarks.loadgen import default_payload_fn, run_rest_load
    from seldon_core_tpu.contracts.graph import PredictorSpec
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.transport.rest import make_engine_app

    engine = GraphEngine(
        PredictorSpec.from_dict(
            {"name": "p", "graph": {"name": "m", "type": "MODEL", "implementation": "SIMPLE_MODEL"}}
        )
    )

    async def go():
        app = make_engine_app(engine)
        async with TestClient(TestServer(app)) as client:
            url = f"http://127.0.0.1:{client.port}/api/v0.1/predictions"
            return await run_rest_load(
                url, default_payload_fn(), clients=4, duration_s=1.0, warmup_s=0.2
            )

    report = asyncio.run(go())
    assert report["requests"] > 10
    assert report["errors"] == 0
    assert report["p50_ms"] > 0
    assert report["rps"] > 10


def test_percentile_stats_empty():
    from seldon_core_tpu.benchmarks.loadgen import percentile_stats

    assert percentile_stats([]) == {}


# ---------------------------------------------------------- span export
def test_spans_to_otlp_shape():
    from seldon_core_tpu.tracing import Tracer
    from seldon_core_tpu.tracing.export import spans_to_otlp

    tracer = Tracer(enabled=True)
    with tracer.span("predictions", deployment="d1", code=200):
        with tracer.span("node.m"):
            pass
    spans = tracer.drain()
    otlp = spans_to_otlp(spans, "svc")
    scope = otlp["resourceSpans"][0]["scopeSpans"][0]
    assert {s["name"] for s in scope["spans"]} == {"predictions", "node.m"}
    child = next(s for s in scope["spans"] if s["name"] == "node.m")
    parent = next(s for s in scope["spans"] if s["name"] == "predictions")
    assert child["parentSpanId"] == parent["spanId"]
    assert child["traceId"] == parent["traceId"]
    assert int(parent["endTimeUnixNano"]) >= int(parent["startTimeUnixNano"])
    attrs = {a["key"]: a["value"] for a in parent["attributes"]}
    assert attrs["deployment"] == {"stringValue": "d1"}
    assert attrs["code"] == {"intValue": "200"}
    res_attrs = otlp["resourceSpans"][0]["resource"]["attributes"]
    assert {"key": "service.name", "value": {"stringValue": "svc"}} in res_attrs


def test_otlp_exporter_posts_to_collector():
    """Real HTTP round trip to a local OTLP sink (what Jaeger listens for on
    4318/v1/traces)."""
    import http.server
    import threading

    from seldon_core_tpu.tracing import Tracer
    from seldon_core_tpu.tracing.export import OTLPExporter

    received = {}

    class Sink(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            received["path"] = self.path
            received["body"] = json.loads(
                self.rfile.read(int(self.headers["Content-Length"]))
            )
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):  # quiet
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        tracer = Tracer(enabled=True)
        tracer.exporter = OTLPExporter(
            f"http://127.0.0.1:{srv.server_port}", service_name="svc"
        )
        with tracer.span("predictions"):
            pass
        tracer.flush()
        assert received["path"] == "/v1/traces"
        spans = received["body"]["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert spans[0]["name"] == "predictions"
    finally:
        srv.shutdown()


def test_install_from_env_wires_exporter():
    from seldon_core_tpu.tracing import Tracer
    from seldon_core_tpu.tracing.export import OTLPExporter, install_from_env

    tracer = Tracer(enabled=True)
    flusher = install_from_env(
        tracer, {"OTEL_EXPORTER_OTLP_ENDPOINT": "http://collector:4318"}
    )
    try:
        assert isinstance(tracer.exporter, OTLPExporter)
        assert tracer.exporter.url == "http://collector:4318/v1/traces"
    finally:
        if flusher:
            flusher.stop()
    # disabled tracer or missing endpoint -> no exporter
    assert install_from_env(Tracer(enabled=False),
                            {"OTEL_EXPORTER_OTLP_ENDPOINT": "x"}) is None
    assert install_from_env(Tracer(enabled=True), {}) is None


# ------------------------------------------------- the start ledger's series
def test_the_start_ledgers_three_series_are_exposed_under_the_documented_names():
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.tracing import start

    ledger = start.StartLedger(age_s=2.0)
    ledger.advance("construct")
    ledger.advance("load.weights")
    ledger.advance("load.rest")
    ledger.advance("listen")
    ledger.ready()
    # what the listeners book (tests/test_start_ledger.py drives them through JAX)
    ledger.build_seconds = {("decode_step", "trace", "0"): [3.0, 1],
                            ("decode_step", "cache_load", "0"): [2.5, 1],
                            ("paged_live_read", "trace", "1"): [0.75, 2]}
    ledger.builds = {("decode_step", "hit"): 1, ("other", "off"): 3}
    old = start.get_ledger()
    start.set_ledger(ledger)
    try:
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_start()
        text = reg.expose().decode()
    finally:
        start.set_ledger(old)
    base = {"deployment_name": "d", "predictor_name": "p"}
    get = reg.registry.get_sample_value
    assert [get("seldon_start_stage_seconds", {**base, "stage": s}) is not None
            for s in start.STAGES] == [True] * 5 + [False]      # no batcher was built
    assert get("seldon_start_stage_seconds", {**base, "stage": "import"}) >= 2.0
    assert get("seldon_program_build_seconds_total",
               {**base, "program": "decode_step", "leg": "cache_load", "nested": "0"}) == 2.5
    assert get("seldon_program_build_seconds_total",
               {**base, "program": "paged_live_read", "leg": "trace", "nested": "1"}) == 0.75
    assert get("seldon_program_builds_total", {**base, "program": "decode_step", "cache": "hit"}) == 1
    assert get("seldon_program_builds_total", {**base, "program": "other", "cache": "off"}) == 3
    for name, kind in (("seldon_start_stage_seconds", "gauge"),
                       ("seldon_program_build_seconds_total", "counter"),
                       ("seldon_program_builds_total", "counter")):
        assert f"# TYPE {name} {kind}" in text, name


# ------------------------------------------------- dashboards + alert rules
def test_analytics_artifacts_use_live_metric_names(tmp_path):
    """Rules and dashboard queries must reference metrics the registry
    actually exposes — generated-from-code, verified against /metrics."""
    import yaml

    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.observability.dashboards import write_artifacts

    reg = MetricsRegistry(deployment="d", predictor="p")
    reg.observe_api_call("predictions", "200", 0.01)
    exposed = reg.expose().decode()

    paths = write_artifacts(str(tmp_path))
    assert len(paths) == 3

    with open(tmp_path / "rules" / "seldon-alerts.yaml") as f:
        rules = yaml.safe_load(f)
    exprs = [r["expr"] for g in rules["groups"] for r in g["rules"]]
    with open(tmp_path / "predictions-dashboard.json") as f:
        dash = json.load(f)
    queries = [t["expr"] for p in dash["panels"] for t in p["targets"]]

    import re

    for expr in exprs + queries:
        for name in re.findall(r"(seldon_[a-z_]+)", expr):
            base = re.sub(r"_(bucket|sum|count|total)$", "", name)
            assert base in exposed or name in exposed, (name, expr)


def test_committed_analytics_artifacts_current(tmp_path):
    """deploy/analytics/ must equal the generator's output (no drift)."""
    import filecmp
    import os

    from seldon_core_tpu.observability.dashboards import write_artifacts

    write_artifacts(str(tmp_path))
    repo_dir = os.path.join(os.path.dirname(__file__), "..", "deploy", "analytics")
    for rel in ("prometheus-config.yaml", "predictions-dashboard.json",
                os.path.join("rules", "seldon-alerts.yaml")):
        assert filecmp.cmp(os.path.join(repo_dir, rel), tmp_path / rel, shallow=False), rel


def test_tracer_buffer_overflow_no_deadlock():
    """Filling the span buffer past max_buffer must neither deadlock on
    the tracer's own lock nor run the exporter inline from the recording
    thread (PR 10 contract: with an exporter installed, the background
    PeriodicFlusher owns the — possibly blocking — network flush, so a
    recording thread only buffers, dropping-and-counting overflow)."""
    from seldon_core_tpu.tracing import Tracer

    exported = []
    tracer = Tracer(enabled=True, max_buffer=3)
    tracer.exporter = exported.extend
    for i in range(7):
        with tracer.span(f"s{i}"):
            pass
    assert exported == []                  # no inline export while recording
    assert tracer.spans_dropped_total == 4  # overflow counted, not hidden
    tracer.flush()                          # the PeriodicFlusher's role
    assert [s.name for s in exported] == ["s0", "s1", "s2"]
