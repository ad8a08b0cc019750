"""REST transport (aiohttp).

Two route families, matching the reference:

- **Microservice routes** (`python/seldon_core/wrapper.py:37-94`): /predict,
  /transform-input, /transform-output, /route, /aggregate, /send-feedback,
  plus GET /seldon.json (OpenAPI) and /health. Serves ONE component.
- **Engine routes** (`engine/.../api/rest/RestClientController.java:76-245`):
  /api/v0.1/predictions, /api/v0.1/feedback, /ready, /live, /pause, /unpause,
  /ping, /metrics (Prometheus). Serves a whole predictor GRAPH via the
  in-process engine — the reference needs a separate JVM pod for this; here it
  is the same process, so a single-model deployment is one process total.

Request parsing accepts raw JSON bodies, form field ``json=``, and multipart
(binData/strData parts) like the reference (`python/seldon_core/flask_utils.py:
6-65`).
"""

from __future__ import annotations

import asyncio
import base64
import json
import logging
import os
import time
from typing import Any, Callable, Optional

import numpy as np
from aiohttp import web

from seldon_core_tpu.codec.framing import (
    CONTENT_TYPE_FRAME,
    decode_message,
    encode_message,
    frameable,
)
from seldon_core_tpu.components import dispatch
from seldon_core_tpu.contracts.payload import (
    Feedback,
    SeldonError,
    SeldonMessage,
    SeldonMessageList,
)
from seldon_core_tpu.metrics.registry import MetricsRegistry
from seldon_core_tpu.runtime.resilience import (
    DEADLINE_HEADER,
    AdmissionController,
    Deadline,
    ResumeMarker,
    ShedError,
    current_deadline,
    deadline_scope,
)
from seldon_core_tpu.tracing import get_tracer
from seldon_core_tpu.tracing.start import get_ledger

logger = logging.getLogger(__name__)


def deadline_from_headers(request: web.Request) -> Optional[Deadline]:
    """``Seldon-Deadline-Ms: <float>`` — the client's total budget for this
    request. Missing/garbage headers mean no deadline (the engine may still
    apply the deployment's ``seldon.io/deadline-default-ms``)."""
    raw = request.headers.get(DEADLINE_HEADER)
    if raw is None:
        return None
    try:
        ms = float(raw)
    except (TypeError, ValueError):
        return None
    if ms <= 0:
        return None
    return Deadline.from_ms(ms)


def shed_response(e: ShedError) -> web.Response:
    return web.json_response(
        {"status": e.to_status().to_dict()},
        status=503,
        headers={"Retry-After": str(max(int(e.retry_after_s), 1))},
    )


async def parse_request(request: web.Request) -> dict:
    """JSON body, ?json= query param, form json= field, or multipart parts."""
    ctype = request.content_type or ""
    if ctype.startswith("multipart/"):
        data = await request.post()
        out: dict = {}
        for key, value in data.items():
            if hasattr(value, "file"):
                raw = value.file.read()
                if key == "binData":
                    import base64

                    out[key] = base64.b64encode(raw).decode()
                elif key == "strData":
                    out[key] = raw.decode()
                else:
                    out[key] = json.loads(raw)
            else:
                out[key] = json.loads(value) if key not in ("strData",) else value
        return out
    body = await request.text()
    if ctype == "application/x-www-form-urlencoded" and body:
        from urllib.parse import parse_qs

        qs = parse_qs(body)
        if "json" in qs:
            return json.loads(qs["json"][0])
        # fall through: clients (curl -d) often send raw JSON under the
        # default form content type
    if body:
        try:
            return json.loads(body)
        except json.JSONDecodeError as e:
            raise SeldonError(f"Invalid JSON body: {e}")
    if "json" in request.query:
        return json.loads(request.query["json"])
    raise SeldonError("Empty request body")


def error_response(e: Exception) -> web.Response:
    if isinstance(e, SeldonError):
        status = e.to_status()
        code = e.status_code
    else:
        logger.exception("unhandled error")
        from seldon_core_tpu.contracts.payload import Status

        status = Status(code=500, info=str(e), reason="INTERNAL_ERROR", status="FAILURE")
        code = 500
    return web.json_response({"status": status.to_dict()}, status=code)


def _json(msg: SeldonMessage) -> web.Response:
    return web.json_response(msg.to_dict())


def _wants_frame(request: web.Request) -> bool:
    return CONTENT_TYPE_FRAME in request.headers.get("Accept", "")


def _respond(request: web.Request, msg: SeldonMessage) -> web.Response:
    """Frame the response only when the client ASKED for frames (Accept)
    and the payload actually benefits (tensor/binData); everything else —
    including every error path — stays JSON, so clients that never opted
    in see byte-identical behavior."""
    if _wants_frame(request) and frameable(msg):
        return web.Response(body=encode_message(msg, path="rest"),
                            content_type=CONTENT_TYPE_FRAME)
    return _json(msg)


async def parse_framed_message(request: web.Request) -> SeldonMessage:
    """Decode an ``application/x-seldon-frame`` request body. Frames carry
    SeldonMessage only — aggregate lists and feedback stay JSON."""
    return decode_message(await request.read(), path="rest")


# ---------------------------------------------------------------------------
# Microservice app: one component
# ---------------------------------------------------------------------------

class http_busy:
    """One synchronous stretch of the transport thread, which shares the GIL
    with the batcher's loop and its workers: ``http.<what>`` in a profiler
    trace (a prefix of its own, so no reader of the loop's ``llm.*`` spans
    takes it for a phase) and its wall added to
    ``seldon_http_busy_seconds_total{what}`` / ``seldon_http_busy_total``.
    A busy time, where ``seldon_llm_emit_delay_seconds`` is the delay the
    client feels. While a profile is being captured (and only then) the
    span carries the request's trace id, so a viewer can follow one request
    from parse to reply. Always on, like the loop's phases: an inactive
    annotation and two clock reads."""

    __slots__ = ("metrics", "what", "trace", "_ann", "_t0")
    _annotation: Any = None     # jax.profiler.TraceAnnotation, at first use

    def __init__(self, metrics: MetricsRegistry, what: str, trace: Any = None):
        self.metrics, self.what, self.trace = metrics, what, trace

    def __enter__(self) -> "http_busy":
        cls = http_busy._annotation
        if cls is None:
            from jax.profiler import TraceAnnotation

            cls = http_busy._annotation = TraceAnnotation
        self._ann = cls("http." + self.what)
        self._ann.__enter__()
        if self.trace is not None and cls.is_enabled():
            self._ann.set_metadata(trace_id=self.trace.trace_id)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.metrics.observe_http_busy(self.what, time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)


def make_profile_handler() -> Callable:
    """POST /profile?seconds=N on both apps: capture a jax.profiler trace
    (device planes + the host plane, which carries the batcher's ``llm.*``
    loop phases on the same clock — docs/observability.md "Loop phases")
    and write it under SELDON_PROFILE_DIR. Gated by that env var:
    profiling allocates and serializes device state, so it is opt-in. Only
    the process that holds the chip can trace it, hence a route."""
    state = {"active": False}

    async def profile(request: web.Request) -> web.Response:
        base = os.environ.get("SELDON_PROFILE_DIR", "")
        if not base:
            return web.json_response(
                {"status": {"code": 403, "info": "set SELDON_PROFILE_DIR to enable"}},
                status=403,
            )
        if state["active"]:
            return web.json_response(
                {"status": {"code": 409, "info": "profile already running"}}, status=409
            )
        import math

        try:
            seconds = float(request.query.get("seconds", "2"))
        except ValueError:
            seconds = 2.0
        if not (math.isfinite(seconds) and 0 < seconds <= 60):
            seconds = 2.0

        import jax

        options = jax.profiler.ProfileOptions()
        # the Python tracer slows the host it measures; TraceAnnotations
        # (level 2) are what the host plane is read for
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        out_dir = os.path.join(base, f"trace_{int(time.time())}")
        state["active"] = True
        started = False
        try:
            jax.profiler.start_trace(out_dir, profiler_options=options)
            started = True
            await asyncio.sleep(seconds)
        finally:
            state["active"] = False
            if started:
                try:
                    jax.profiler.stop_trace()
                except Exception:  # double-stop on teardown races
                    logger.exception("stop_trace failed")
        return web.json_response({"trace_dir": out_dir, "seconds": seconds})

    return profile


def make_component_app(
    component: Any,
    unit_id: str = "",
    metrics: Optional[MetricsRegistry] = None,
    admission: Optional[AdmissionController] = None,
    annotations: Optional[dict] = None,
) -> web.Application:
    app = web.Application(client_max_size=1 << 30)
    metrics = metrics or MetricsRegistry()
    admission = admission or AdmissionController.from_annotations(annotations)
    # dynamic Retry-After: shed backoff derived from the component's live
    # backlog instead of the fixed constant (docs/resilience.md)
    from seldon_core_tpu.observability.timeline import wire_retry_after

    wire_retry_after(admission, component=component)
    tracer = get_tracer()

    def handler(fn: Callable, parser: Callable, method_name: str):
        async def handle(request: web.Request) -> web.Response:
            t0 = time.perf_counter()
            try:
                await admission.acquire()
            except ShedError as e:
                metrics.observe_api_call(method_name, "503", time.perf_counter() - t0)
                return shed_response(e)
            try:
                deadline = deadline_from_headers(request)
                if request.content_type == CONTENT_TYPE_FRAME:
                    if getattr(parser, "__func__", parser) \
                            is not SeldonMessage.from_dict.__func__:
                        raise SeldonError(
                            f"{method_name} does not accept framed bodies "
                            "(frames carry SeldonMessage only)",
                            status_code=415)
                    payload = await parse_framed_message(request)
                else:
                    payload = parser(await parse_request(request))
                with deadline_scope(deadline):
                    # inbound W3C traceparent roots this request's server
                    # span in the caller's trace (sampled flag honored)
                    with tracer.span(method_name,
                                     traceparent=request.headers.get(
                                         "traceparent")):
                        result = fn(component, payload)
                        if asyncio.iscoroutine(result):
                            result = await result
                metrics.observe_api_call(method_name, "200", time.perf_counter() - t0)
                return _respond(request, result)
            except Exception as e:
                code = str(getattr(e, "status_code", 500))
                metrics.observe_api_call(method_name, code, time.perf_counter() - t0)
                return error_response(e)
            finally:
                admission.release()

        return handle

    msg = SeldonMessage.from_dict
    lst = SeldonMessageList.from_dict
    fbk = Feedback.from_dict

    def fb_with_unit(comp, f):
        return dispatch.send_feedback(comp, f, unit_id=unit_id or None)

    for path, fn, parser, name in [
        ("/predict", dispatch.predict, msg, "predict"),
        ("/api/v0.1/predictions", dispatch.predict, msg, "predict"),
        ("/transform-input", dispatch.transform_input, msg, "transform_input"),
        ("/transform-output", dispatch.transform_output, msg, "transform_output"),
        ("/route", dispatch.route, msg, "route"),
        ("/aggregate", dispatch.aggregate, lst, "aggregate"),
        ("/send-feedback", fb_with_unit, fbk, "send_feedback"),
        ("/api/v0.1/feedback", fb_with_unit, fbk, "send_feedback"),
    ]:
        h = handler(fn, parser, name)
        app.router.add_post(path, h)
        app.router.add_get(path, h)

    async def health(request):
        return web.json_response({"status": "ok"})

    async def ready(request):
        get_ledger().ready()    # the first one ends the start's `listen` stage
        return await health(request)

    async def openapi(request):
        from seldon_core_tpu.transport.openapi import wrapper_spec

        return web.json_response(wrapper_spec())

    async def prom(request):
        with http_busy(metrics, "scrape"):
            metrics.sync_resilience(admission=admission, transport="rest")
            metrics.sync_llm(component)
            metrics.sync_controlplane(component)
            metrics.sync_framing()
            metrics.sync_tracing()
            metrics.sync_start()
            return web.Response(body=metrics.expose(), content_type="text/plain")

    async def debug_timeline(request):
        """Recent per-request flight-recorder timelines + the scaling
        snapshot (docs/observability.md); mirrored by the gRPC
        ``Model/DebugTimeline`` rpc."""
        from seldon_core_tpu.observability.timeline import (
            parse_n, timeline_report)

        with http_busy(metrics, "scrape"):
            return web.json_response(
                timeline_report(component, n=parse_n(request.query.get("n"))))

    app.router.add_get("/health/status", health)
    app.router.add_get("/ready", ready)
    app.router.add_get("/live", health)
    app.router.add_get("/seldon.json", openapi)
    app.router.add_get("/metrics", prom)
    app.router.add_get("/prometheus", prom)
    app.router.add_get("/debug/timeline", debug_timeline)
    app.router.add_post("/profile", make_profile_handler())

    if hasattr(component, "generate"):
        _add_generate_routes(app, component, metrics)
    return app


def _parse_generate(request: web.Request, text: str) -> tuple:
    """The synchronous head of POST /v1/generate: the body's JSON and the
    request's identity -> (body, max_new, tenant, slo_class, adapter,
    deadline_s)."""
    body = json.loads(text)
    if not isinstance(body, dict):
        raise SeldonError("body must be a JSON object", status_code=400)
    max_new = body.get("max_new_tokens")
    # multi-tenant identity (docs/multitenancy.md): tenant + SLO
    # class ride headers (body fields win when both are present,
    # for clients that cannot set headers); the LoRA adapter name
    # is a body field like the sampling knobs. The deadline header
    # doubles as the scheduler's EDF key.
    tenant = body.get("tenant") or request.headers.get("Seldon-Tenant")
    slo_class = (body.get("slo_class")
                 or request.headers.get("Seldon-SLO-Class"))
    # a typo'd class fails loudly on EVERY path — the non-batched
    # branches (prompts batch, per-request temperature) never reach
    # the batcher's own validation
    from seldon_core_tpu.runtime.scheduler import normalize_slo_class

    try:
        normalize_slo_class(slo_class)
    except ValueError as e:
        raise SeldonError(str(e), status_code=400)
    dl = deadline_from_headers(request)
    return (body, max_new, tenant, slo_class, body.get("adapter"),
            dl.remaining_s() if dl is not None else None)


def _add_generate_routes(app: web.Application, component: Any,
                         metrics: MetricsRegistry) -> None:
    """LLM generation endpoint (POST /v1/generate). Body:
      {"prompt": str|[ids], "max_new_tokens": N, "stream": bool}  — single
          prompt; with the component's continuous_batching on, concurrent
          requests JOIN the in-flight decode batch (runtime/batcher.py)
          instead of each running a private generate(); "stream": true
          sends tokens as SSE events as they decode. "logits": true (a
          probe: plain reply, batched path only) adds the float32 logits
          each token was sampled from, one row per token, taken from the
          step programs that serve every request:
          {"shape": [n, vocab], "dtype": "float32", "base64": ...}; for a
          mixture-of-experts model also "routing", the experts every
          processed token took in every MoE layer, out of the same programs:
          {"first_token": i, "shape": [tokens, moe_layers, k], "dtype":
          "int32", "base64": ...} (a reference that follows them is held to
          the arithmetic and not to how a near-tie fell). "state": true (a
          probe likewise, of a model with mamba layers) adds the h the first
          of them holds for the sequence where the request finishes: {"layer":
          i, "tokens": n, "shape": [H, d_state, d_head], "dtype": "float32",
          "base64": ...}, a head's h transposed, after the first n tokens of
          prompt + reply; n is past the reply's last token where steps
          dispatched ahead for other requests fed tokens nobody was sent.
      {"prompts": [...], ...} — explicit batch, served by one generate().
    No reference counterpart (its servers are request/response classifiers);
    this is the BASELINE.json LLM stretch surface."""
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    async def generate(request: web.Request) -> web.Response:
        t0 = time.perf_counter()
        # request-scoped tracing (runtime/flight.py): the inbound W3C
        # traceparent (or a fresh trace) rides into the batcher, which
        # roots the request's span tree at this ingress; the trace id is
        # stamped on the response/stream so the client can correlate
        from seldon_core_tpu.tracing import ingress_trace

        trace = ingress_trace(get_tracer(),
                              request.headers.get("traceparent"),
                              "rest:/v1/generate")
        try:
            raw = await request.text()
            # http.parse: the body's JSON and the request's identity, on the
            # transport thread (the tokenizer runs where the batcher's
            # submit does, on its loop thread)
            with http_busy(metrics, "parse", trace):
                body, max_new, tenant, slo_class, adapter, deadline_s = \
                    _parse_generate(request, raw)
            if "prompts" in body:
                if adapter:
                    raise SeldonError(
                        "adapters serve through the continuous batch; use "
                        "single-prompt requests (the 'prompts' batch runs "
                        "a private base-model generate())", status_code=400)
                out = await asyncio.to_thread(
                    component.generate, body["prompts"], max_new_tokens=max_new,
                    temperature=body.get("temperature"), seed=body.get("seed"))
                metrics.observe_api_call("generate", "200", time.perf_counter() - t0)
                return web.json_response(out)
            prompt = body.get("prompt")
            if prompt is None:
                raise SeldonError("body needs 'prompt' or 'prompts'", status_code=400)
            # A per-request TEMPERATURE can't join a shared batch (the
            # batcher decodes every slot with the server's temperature), so
            # those requests get a private generate() — same output as with
            # batching disabled, never silently different. A per-request
            # SEED now joins fine: each slot carries its own device rng on
            # the exact generate(seed=...) chain (runtime/batcher.py,
            # parity-tested in tests/test_batcher_pipeline.py) — UNLESS the
            # request would not fit the fixed slot cache (truncated prompt /
            # clipped budget), where only the private per-request-sized
            # generate() can honor the seeded-reproducibility contract.
            custom_sampling = "temperature" in body
            if adapter and custom_sampling:
                raise SeldonError(
                    "per-request temperature cannot join the shared batch, "
                    "and adapters only serve through it — drop one",
                    status_code=400)
            svc = None if custom_sampling else get_batcher_service(component)
            if svc is None and adapter:
                # adapters serve ONLY through a batcher (the adapted
                # compiled programs live there); a component without
                # continuous batching still serves them via the shared
                # 1-slot streaming service
                from seldon_core_tpu.runtime.batcher import ensure_stream_service

                svc = await asyncio.to_thread(ensure_stream_service, component)
            if svc is not None and "seed" in body and not await asyncio.to_thread(
                    svc.batcher.accommodates, prompt, max_new):
                if adapter:
                    raise SeldonError(
                        "seeded adapted prompt exceeds the batcher slot "
                        "cache and would not reproduce; raise "
                        "continuous_batching_max_len", status_code=400)
                svc = None
            stream = bool(body.get("stream"))
            decode = getattr(component, "_tokenizer", None)

            info: dict = {}
            if body.get("logits"):
                if stream or svc is None \
                        or svc.batcher.spec_mode != "off":
                    raise SeldonError(
                        "'logits' is a probe of the batched path: a plain "
                        "(not streamed) request to a server with "
                        "continuous batching, no per-request temperature "
                        "and no speculation", status_code=400)
                info["logits"] = []   # the batcher appends a row per token
            if body.get("state"):
                from seldon_core_tpu.models.cache import matrix_state_layer

                if stream or svc is None or svc.batcher.spec_mode != "off" \
                        or matrix_state_layer(svc.batcher.server._cfg) is None:
                    raise SeldonError(
                        "'state' is a probe of the batched path of a model "
                        "with mamba layers: a plain (not streamed) request to "
                        "a server with continuous batching and no speculation",
                        status_code=400)
                info["state"] = {}    # the batcher fills it where the request finishes
            if not stream:
                if svc is not None:
                    toks = await svc.submit(prompt, max_new, info=info,
                                            seed=body.get("seed"),
                                            trace=trace, tenant=tenant,
                                            slo_class=slo_class,
                                            adapter=adapter,
                                            deadline_s=deadline_s)
                else:
                    out = await asyncio.to_thread(
                        component.generate, [prompt], max_new_tokens=max_new,
                        temperature=body.get("temperature"), seed=body.get("seed"))
                    metrics.observe_api_call("generate", "200",
                                             time.perf_counter() - t0)
                    resp_body = {"tokens": out["tokens"][0],
                                 "text": out["texts"][0]}
                    if trace is not None:
                        # private-generate fallback: no flight recorder ran,
                        # but the client still gets a stable correlation id
                        resp_body["trace_id"] = trace.trace_id
                    return web.json_response(resp_body)
                with http_busy(metrics, "reply", trace):
                    text = decode.decode(toks) if (decode is not None
                                                   and isinstance(prompt, str)) else None
                    metrics.observe_api_call("generate", "200", time.perf_counter() - t0)
                    out = {"tokens": toks, "text": text}
                    if trace is not None:
                        out["trace_id"] = trace.trace_id
                    if info.get("truncated_prompt"):
                        out["truncated_prompt"] = info["truncated_prompt"]
                    if info.get("logits"):
                        rows = np.stack(info["logits"]).astype("<f4")
                        out["logits"] = {
                            "shape": list(rows.shape), "dtype": "float32",
                            "base64": base64.b64encode(rows.tobytes()).decode()}
                    if info.get("routing"):
                        # an MoE model: the experts each processed token took
                        # (every token but the last one sampled), for a reference
                        # that follows the served choices
                        took = np.stack(info["routing"]).astype("<i4")
                        out["routing"] = {
                            "first_token": info["routing_start"],
                            "shape": list(took.shape), "dtype": "int32",
                            "base64": base64.b64encode(took.tobytes()).decode()}
                    if info.get("state"):
                        held = info["state"].pop("array").astype("<f4")
                        out["state"] = {
                            **info["state"], "shape": list(held.shape), "dtype": "float32",
                            "base64": base64.b64encode(held.tobytes()).decode()}
                    return web.json_response(out)

            if custom_sampling:
                raise SeldonError(
                    "streaming with per-request temperature is not "
                    "supported; set it on the server", status_code=400)
            if "seed" in body:
                # streaming has no generate() fallback, so a seeded prompt
                # that exceeds the slot cache (truncation / budget clip)
                # cannot honor the reproducibility contract — reject before
                # the SSE response starts
                from seldon_core_tpu.runtime.batcher import ensure_stream_service

                s_svc = svc if svc is not None else await asyncio.to_thread(
                    ensure_stream_service, component)
                if not await asyncio.to_thread(
                        s_svc.batcher.accommodates, prompt, max_new):
                    raise SeldonError(
                        "seeded streaming prompt exceeds the batcher slot "
                        "cache and would not reproduce generate(seed=...); "
                        "raise continuous_batching_max_len or drop stream",
                        status_code=400)
                svc = s_svc

            # SSE streaming: one event per token as the shared batch decodes
            headers = {
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache"}
            if trace is not None:
                # the stream's trace id, visible BEFORE the first token:
                # a client filing "this stream stalled" hands the operator
                # the exact /debug/timeline + Jaeger key
                headers["X-Trace-Id"] = trace.trace_id
            resp = web.StreamResponse(headers=headers)
            await resp.prepare(request)
            loop = asyncio.get_running_loop()
            q: asyncio.Queue = asyncio.Queue()

            def on_token(tok):
                # called on the batcher's worker thread at the moment the
                # token is surfaced: the stamp beside it starts the
                # transport's own clock (call_soon_threadsafe, queue, SSE
                # framing, socket write) — seldon_llm_emit_delay_seconds
                loop.call_soon_threadsafe(
                    q.put_nowait,
                    None if tok is None else (tok, time.perf_counter()))

            if svc is None:
                # no batcher configured: stream via a shared 1-slot service
                from seldon_core_tpu.runtime.batcher import ensure_stream_service

                svc = await asyncio.to_thread(ensure_stream_service, component)
            fut = asyncio.ensure_future(svc.submit(prompt, max_new,
                                                   on_token=on_token,
                                                   info=info,
                                                   seed=body.get("seed"),
                                                   trace=trace,
                                                   tenant=tenant,
                                                   slo_class=slo_class,
                                                   adapter=adapter,
                                                   deadline_s=deadline_s))
            try:
                # Wait on the queue AND the future: a submit that fails before
                # any token (closed batcher, bad prompt) never sends the None
                # sentinel, and waiting only on the queue would hang the
                # connection forever.
                async def write_tok(tok, surfaced):
                    if isinstance(tok, ResumeMarker):
                        # fleet recovery re-attached this stream after a
                        # replica death: an in-band marker, never a token
                        # (at-most-once contract, docs/resilience.md)
                        await resp.write(
                            f"data: {json.dumps({'resumed': True, 'tokens_delivered': tok.tokens_delivered})}\n\n".encode())
                        return
                    with http_busy(metrics, "sse_write"):
                        piece = (decode.decode([tok]) if decode is not None
                                 and isinstance(prompt, str) else None)
                        await resp.write(
                            f"data: {json.dumps({'token': tok, 'text': piece})}\n\n".encode())
                    metrics.observe_emit_delay(time.perf_counter() - surfaced)

                while True:
                    getter = asyncio.ensure_future(q.get())
                    done, _ = await asyncio.wait(
                        {getter, fut}, return_when=asyncio.FIRST_COMPLETED)
                    if getter in done:
                        item = getter.result()
                        if item is None:
                            break
                        await write_tok(*item)
                        continue
                    # fut resolved first. The old code took AT MOST ONE
                    # leftover token here, so tokens enqueued between the
                    # future resolving and the next loop turn were silently
                    # dropped from the stream (they only reappeared in the
                    # done event's full token list) — and cancelling the
                    # getter could swallow a token it had already claimed.
                    # Recover the getter's claim, then drain the queue FULLY
                    # (the None sentinel, if queued, still terminates).
                    getter.cancel()
                    try:
                        item = await getter
                    except asyncio.CancelledError:
                        item = False  # cancelled clean: claimed nothing
                    leftovers = [] if item is False else [item]
                    while True:
                        try:
                            leftovers.append(q.get_nowait())
                        except asyncio.QueueEmpty:
                            break
                    for item in leftovers:
                        if item is None:
                            break
                        await write_tok(*item)
                    break
                toks = await fut
                with http_busy(metrics, "reply", trace):
                    text = decode.decode(toks) if (decode is not None
                                                   and isinstance(prompt, str)) else None
                    done_evt = {"done": True, "tokens": toks, "text": text}
                    if trace is not None:
                        done_evt["trace_id"] = trace.trace_id
                    if info.get("truncated_prompt"):
                        done_evt["truncated_prompt"] = info["truncated_prompt"]
                    await resp.write(
                        f"data: {json.dumps(done_evt)}\n\n".encode())
                await resp.write_eof()
                metrics.observe_api_call("generate", "200", time.perf_counter() - t0)
                return resp
            except (ConnectionError, ConnectionResetError, asyncio.CancelledError):
                # client went away mid-stream: stop awaiting (the admitted
                # slot still decodes out its bounded max_new tokens)
                fut.cancel()
                raise
            except Exception as e:
                # response already prepared: a fresh error response can't be
                # sent; log via metrics, surface what we can, stop decoding
                fut.cancel()
                metrics.observe_api_call(
                    "generate", str(getattr(e, "status_code", 500)),
                    time.perf_counter() - t0)
                try:
                    await resp.write(
                        f"data: {json.dumps({'error': str(e)})}\n\n".encode())
                    await resp.write_eof()
                except Exception:
                    pass
                return resp
        except Exception as e:
            code = str(getattr(e, "status_code", 500))
            metrics.observe_api_call("generate", code, time.perf_counter() - t0)
            if isinstance(e, ShedError):
                # page-exhaustion sheds surface here (the batcher's own
                # 503 path): render the Retry-After header so clients see
                # the backlog-derived backoff, not just the status body
                return shed_response(e)
            return error_response(e)

    app.router.add_post("/v1/generate", generate)


# ---------------------------------------------------------------------------
# Engine app: whole predictor graph in-process
# ---------------------------------------------------------------------------

def make_engine_app(
    engine: Any,
    metrics: Optional[MetricsRegistry] = None,
    admission: Optional[AdmissionController] = None,
    annotations: Optional[dict] = None,
) -> web.Application:
    """engine: seldon_core_tpu.runtime.engine.GraphEngine (or compatible,
    e.g. the batched engine wrapper).

    ``admission`` bounds concurrent predictions (overflow sheds with 503 +
    Retry-After); defaults from annotations/env via
    AdmissionController.from_annotations — disabled unless configured."""
    app = web.Application(client_max_size=1 << 30)
    metrics = metrics or MetricsRegistry()
    admission = admission or AdmissionController.from_annotations(annotations)
    from seldon_core_tpu.observability.timeline import wire_retry_after

    wire_retry_after(admission, engine=engine)
    tracer = get_tracer()
    state = {"paused": False, "ready": True}
    app[web.AppKey("state", dict)] = state

    # request/response pair logging — the reference's stdout logging
    # (log.requests/log.responses, PredictionService.java:62-76,122-128) and
    # CloudEvents POST to the request logger (:162-191)
    log_requests = os.environ.get("SELDON_LOG_REQUESTS", "") == "1"
    log_responses = os.environ.get("SELDON_LOG_RESPONSES", "") == "1"
    logger_url = os.environ.get("REQUEST_LOGGER_URL", "")
    # strong refs so fire-and-forget log tasks can't be GC'd mid-flight
    log_tasks: set = set()
    logger_session: list = [None]  # lazily-created shared ClientSession

    async def _log_pair(req_dict, resp_dict):
        if log_requests:
            print(json.dumps({"request": req_dict}), flush=True)
        if log_responses:
            print(json.dumps({"response": resp_dict}), flush=True)
        if logger_url:
            try:
                import aiohttp

                if logger_session[0] is None or logger_session[0].closed:
                    logger_session[0] = aiohttp.ClientSession(
                        timeout=aiohttp.ClientTimeout(total=2)
                    )
                headers = {
                    "CE-Type": "seldon.message.pair",
                    "CE-Source": "seldon-engine-tpu",
                    "CE-SDep": os.environ.get("DEPLOYMENT_NAME", ""),
                    "CE-RequestId": (resp_dict.get("meta") or {}).get("puid", ""),
                }
                async with logger_session[0].post(
                    logger_url,
                    json={"request": req_dict, "response": resp_dict},
                    headers=headers,
                ) as resp:
                    await resp.read()
            except Exception as e:  # logging must never fail the request
                logging.getLogger(__name__).warning("request-logger post failed: %s", e)

    def _spawn_log(req_dict, resp_dict):
        task = asyncio.ensure_future(_log_pair(req_dict, resp_dict))
        log_tasks.add(task)
        task.add_done_callback(log_tasks.discard)

    async def predictions(request: web.Request) -> web.Response:
        if state["paused"]:
            return web.json_response(
                {"status": {"code": 503, "info": "paused", "status": "FAILURE"}}, status=503
            )
        t0 = time.perf_counter()
        try:
            # admission BEFORE parsing: shedding must stay cheap when the
            # server is already saturated
            await admission.acquire()
        except ShedError as e:
            metrics.observe_api_call("predictions", "503", time.perf_counter() - t0)
            return shed_response(e)
        try:
            deadline = deadline_from_headers(request)
            if request.content_type == CONTENT_TYPE_FRAME:
                body = None
                msg = await parse_framed_message(request)
            else:
                body = await parse_request(request)
                msg = SeldonMessage.from_dict(body)
            with deadline_scope(deadline):
                with tracer.span("predictions",
                                 traceparent=request.headers.get(
                                     "traceparent")):
                    out = await engine.predict(msg)
                d = current_deadline()
                if d is not None:
                    metrics.observe_remaining_budget(d.remaining_s())
            metrics.observe_prediction(engine, out, time.perf_counter() - t0)
            if log_requests or log_responses or logger_url:
                # framed requests have no JSON body; the logger pair pays
                # the to_dict() tax only when logging is actually on
                _spawn_log(body if body is not None else msg.to_dict(),
                           out.to_dict())
            return _respond(request, out)
        except Exception as e:
            code = getattr(e, "status_code", 500)
            if code == 504:
                metrics.observe_deadline_exceeded("rest")
            metrics.observe_api_call("predictions", str(code), time.perf_counter() - t0)
            if isinstance(e, ShedError):
                return shed_response(e)
            return error_response(e)
        finally:
            admission.release()

    async def feedback(request: web.Request) -> web.Response:
        t0 = time.perf_counter()
        try:
            body = await parse_request(request)
            fb = Feedback.from_dict(body)
            with tracer.span("feedback"):
                out = await engine.send_feedback(fb)
            metrics.observe_feedback(fb)
            metrics.observe_api_call("feedback", "200", time.perf_counter() - t0)
            return _json(out)
        except Exception as e:
            metrics.observe_api_call("feedback", str(getattr(e, "status_code", 500)), time.perf_counter() - t0)
            return error_response(e)

    async def ready(request):
        if state["ready"] and not state["paused"]:
            get_ledger().ready()    # the first one ends the start's `listen` stage
            return web.Response(text="ready")
        return web.Response(status=503, text="not ready")

    async def live(request):
        return web.Response(text="live")

    async def ping(request):
        return web.Response(text="pong")

    async def pause(request):
        state["paused"] = True
        return web.Response(text="paused")

    async def unpause(request):
        state["paused"] = False
        return web.Response(text="unpaused")

    async def prom(request):
        with http_busy(metrics, "scrape"):
            metrics.sync_resilience(engine=engine, admission=admission, transport="rest")
            for comp in getattr(engine, "_components", {}).values():
                metrics.sync_llm(comp)
            metrics.sync_controlplane(engine)
            metrics.sync_framing()
            metrics.sync_tracing()
            metrics.sync_start()
            return web.Response(body=metrics.expose(), content_type="text/plain")

    async def debug_timeline(request):
        """Per-component flight-recorder timelines + scaling snapshots for
        the whole graph (docs/observability.md)."""
        from seldon_core_tpu.observability.timeline import (
            parse_n, timeline_report)

        with http_busy(metrics, "scrape"):
            n = parse_n(request.query.get("n"))
            return web.json_response({
                name: timeline_report(comp, n=n)
                for name, comp in getattr(engine, "_components", {}).items()
            })

    async def openapi(request):
        from seldon_core_tpu.transport.openapi import engine_spec

        return web.json_response(engine_spec())

    app.router.add_post("/api/v0.1/predictions", predictions)
    app.router.add_post("/predict", predictions)
    app.router.add_post("/api/v0.1/feedback", feedback)
    app.router.add_post("/send-feedback", feedback)
    app.router.add_get("/ready", ready)
    app.router.add_get("/live", live)
    app.router.add_get("/ping", ping)
    app.router.add_post("/pause", pause)
    app.router.add_post("/unpause", unpause)
    app.router.add_get("/pause", pause)
    app.router.add_get("/unpause", unpause)
    app.router.add_get("/metrics", prom)
    app.router.add_get("/prometheus", prom)
    app.router.add_get("/seldon.json", openapi)
    app.router.add_get("/debug/timeline", debug_timeline)
    app.router.add_post("/profile", make_profile_handler())
    return app


def serve(app: web.Application, host: str = "0.0.0.0", port: int = 5000) -> None:
    web.run_app(app, host=host, port=port, print=None)
