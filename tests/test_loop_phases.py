"""The batcher loop's time budget (ISSUE 23 tentpole): every second of a loop
turn is put down to exactly one phase, by spans a profiler trace carries and
counters /metrics exports.

The contract: the phases partition the loop's wall (their sum IS the wall,
hop included); the queue wait of every admitted request is counted whether or
not TRACING is on, and TRACING off still builds no flight recorder; the SSE
writer observes one emit delay per streamed token; a jax.profiler capture
holds ``llm.turn`` with the phases inside it; the histograms are fed from
lifetime accumulators, so nothing is lost between scrapes; and none of it
reaches the compiled step programs. CPU toy model, paged layout with
multi-chunk prefill (page 8, chunk 8)."""

from __future__ import annotations

import asyncio
import glob
import json
import os
import re
import threading
import time

import pytest

from seldon_core_tpu.metrics.local import LATENCY_BUCKETS, HistogramAccumulator
from seldon_core_tpu.metrics.registry import MetricsRegistry
from seldon_core_tpu.runtime.batcher import (
    HOP_PARTS,
    LOOP_PHASES,
    BatcherService,
    ContinuousBatcher,
    LoopPhases,
)
from seldon_core_tpu.servers.llmserver import LLMServer
from seldon_core_tpu.tracing import Tracer, get_tracer, set_tracer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)

PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13, 14, 15, 16, 17, 18, 19],
           [7], [60, 61, 62, 63, 64, 65, 66, 67, 68, 69]]


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(8, 16, 32), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3, kv_page_size=8,
                prefill_chunk=8, continuous_batching=2,
                continuous_batching_max_len=64)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def server():
    return make_server()


def open_service(s: LLMServer) -> BatcherService:
    svc = BatcherService(s, max_slots=2)
    s._batcher_service = svc        # so that llm_stats sees it
    return svc


def drive(svc: BatcherService, prompts, max_new=6) -> None:
    """The prompts through the real service path, concurrently."""
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = svc.submit_sync(prompts[i], max_new)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(len(o) == max_new for o in outs)


def serve(s: LLMServer, prompts, max_new=6) -> BatcherService:
    svc = open_service(s)
    drive(svc, prompts, max_new)
    return svc


def series(text: str, name: str, label: str = "") -> float:
    found = [float(v) for n, labels, v in re.findall(
        r"^([A-Za-z_:][\w:]*)(\{[^}]*\})? (\S+)$", text, re.M)
        if n == name and label in (labels or "")]
    assert found, name
    return sum(found)


# ----------------------------------------------------------- the partition
def test_phases_partition_the_loop_wall():
    s = make_server()
    svc = open_service(s)
    t0 = time.perf_counter()            # the loop starts with the first submit
    drive(svc, PROMPTS + PROMPTS)
    time.sleep(0.6)                     # an idle wait or two belong to the wall
    svc.close()                         # and ends here
    wall = time.perf_counter() - t0
    st = s.llm_stats()
    assert set(st["loop_seconds"]) == set(LOOP_PHASES) == set(st["loop_phase_counts"])
    assert all(v >= 0.0 for v in st["loop_seconds"].values())
    assert st["loop_seconds"]["hop"] >= 0.0 and st["loop_phase_counts"]["hop"] == st["loop_turns"]
    assert sum(st["loop_seconds"].values()) == pytest.approx(wall, rel=0.02)
    # every kind of work this traffic does was put down to its phase
    for phase in ("admit", "dispatch", "prefill", "first_token_wait", "first_token",
                  "drain_wait", "emit", "idle"):
        assert st["loop_phase_counts"][phase] > 0 and st["loop_seconds"][phase] > 0.0, phase
    assert st["loop_phase_counts"]["first_token"] == len(PROMPTS) * 2
    assert st["loop_phase_counts"]["first_token_wait"] == len(PROMPTS) * 2
    # one read a first token, waited for or not
    assert set(st["first_token_reads"]) == {"yes", "no"}
    assert sum(st["first_token_reads"].values()) == len(PROMPTS) * 2
    assert st["loop_phase_counts"]["drain_wait"] == st["loop_phase_counts"]["emit"]
    # two slots, six tokens a request: the occupancy integral is positive and
    # cannot pass slots x wall
    assert 0.0 < st["slot_seconds"] <= 2 * wall


def test_nested_phase_time_is_taken_out_of_the_outer_phase():
    phases = LoopPhases()
    phases.turn(1)
    with phases.phase("emit") as outer:
        time.sleep(0.01)
        with phases.phase("drain_wait") as inner:
            time.sleep(0.02)
        assert inner.t1 >= inner.t0 and inner.seconds == inner.t1 - inner.t0
    time.sleep(0.005)
    phases.end_turn(1)
    st = phases.stats()
    assert st["loop_seconds"]["drain_wait"] == pytest.approx(inner.seconds)
    assert st["loop_seconds"]["emit"] == pytest.approx(outer.seconds - inner.seconds)
    assert st["loop_seconds"]["hop"] >= 0.005
    assert st["loop_turns"] == 1 and st["slot_seconds"] == pytest.approx(
        sum(st["loop_seconds"].values()))


def test_dispatch_and_sync_histograms_share_the_phase_clocks(server):
    server.llm_stats()      # drain what an earlier service left in the windows
    svc = serve(server, PROMPTS[:2])
    try:
        before = server.llm_stats()
        assert before["decode_dispatch_times_s"] and before["decode_sync_times_s"]
        # one clock pair a site: the samples are the phases' own seconds
        assert sum(before["decode_sync_times_s"]) == pytest.approx(
            before["loop_seconds"]["drain_wait"], rel=1e-6)
        assert sum(before["decode_dispatch_times_s"]) <= before["loop_seconds"]["dispatch"] * (1 + 1e-6)
    finally:
        svc.close()


# -------------------------------------------------------------- queue wait
@pytest.mark.parametrize("tracing_on", [False, True])
def test_queue_wait_counts_every_admission(tracing_on):
    old = get_tracer()
    set_tracer(Tracer(enabled=tracing_on))
    try:
        s = make_server()
        svc = serve(s, PROMPTS)
        try:
            assert (svc.batcher._flight is not None) == tracing_on
            reg = MetricsRegistry(deployment="d", predictor="p")
            reg.sync_llm(s)
            text = reg.expose().decode()
            assert series(text, "seldon_llm_queue_wait_seconds_count") == len(PROMPTS)
            assert series(text, "seldon_llm_queue_wait_seconds_sum") >= 0.0
            assert series(text, "seldon_llm_ttft_seconds_count") == len(PROMPTS)
            assert series(text, "seldon_llm_first_token_reads_total") == len(PROMPTS)
            assert series(text, "seldon_llm_first_token_reads_total", 'ready="no"') \
                + series(text, "seldon_llm_first_token_reads_total", 'ready="yes"') == len(PROMPTS)
            assert series(text, "seldon_llm_loop_turns_total") > 0
            assert series(text, "seldon_llm_slots_active") == 0
            assert series(text, "seldon_llm_slot_seconds_total") > 0.0
        finally:
            svc.close()
    finally:
        set_tracer(old)


def test_tracing_off_leaves_no_flight_recorder(server):
    old = get_tracer()
    set_tracer(Tracer(enabled=False))
    try:
        b = ContinuousBatcher(server, max_slots=2, max_len=64)
        assert b._flight is None
        assert isinstance(b._phases, LoopPhases)      # the budget is always on
    finally:
        set_tracer(old)


# ------------------------------------------------- lossless histograms
def test_histograms_catch_up_by_difference_and_lose_nothing(server):
    svc = serve(server, PROMPTS, max_new=8)
    try:
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(server)
        first = reg.expose().decode()
        gaps = series(first, "seldon_llm_inter_token_seconds_count")
        steps = series(first, "seldon_llm_decode_step_seconds_count")
        assert gaps == server._hists["inter_token_s"].count >= len(PROMPTS) * 7
        assert steps == server._hists["decode_step_s"].count > 0
        assert series(first, "seldon_llm_decode_host_lag_steps_count") == \
            server._hists["decode_host_lag_steps"].count
        # a second scrape with no new work changes nothing; the raw windows
        # llm_stats drains are not what the histograms are fed from
        reg.sync_llm(server)
        again = reg.expose().decode()
        assert series(again, "seldon_llm_inter_token_seconds_count") == gaps
        assert series(again, "seldon_llm_inter_token_seconds_sum") == pytest.approx(
            server._hists["inter_token_s"].sum)
        # more observations than any bounded window holds, between two scrapes
        for _ in range(20000):
            server.observe("inter_token_s", 0.003)
        reg.sync_llm(server)
        assert series(reg.expose().decode(),
                      "seldon_llm_inter_token_seconds_count") == gaps + 20000
        assert len(server._inter_token_times) <= 8192
    finally:
        svc.close()


def test_accumulator_buckets_are_the_prometheus_buckets():
    from prometheus_client import CollectorRegistry, Histogram

    values = [0.0, 0.0005, 0.00051, 0.0049, 0.005, 0.3, 5.0, 7.0]
    acc = HistogramAccumulator(LATENCY_BUCKETS)
    ref = Histogram("x", "x", buckets=LATENCY_BUCKETS, registry=CollectorRegistry())
    for v in values:
        acc.observe(v)
        ref.observe(v)
    acc.observe(0.02, weight=3)
    for _ in range(3):
        ref.observe(0.02)
    assert acc.counts == [int(b.get()) for b in ref._buckets]
    assert acc.sum == pytest.approx(ref._sum.get()) and acc.count == len(values) + 3


# -------------------------------------------------------------- emit delay
def test_emit_delay_counts_every_streamed_token():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.transport.rest import make_component_app

    s = make_server()
    reg = MetricsRegistry(deployment="d", predictor="p")
    seen = []
    observe = reg.observe_emit_delay

    def recording(seconds):
        seen.append(seconds)
        observe(seconds)

    reg.observe_emit_delay = recording
    app = make_component_app(s, metrics=reg)

    async def go():
        async with TestClient(TestServer(app)) as client:
            tokens = 0
            for prompt in (PROMPTS[0], PROMPTS[3]):
                resp = await client.post("/v1/generate", json={
                    "prompt": prompt, "stream": True, "max_new_tokens": 6})
                assert resp.status == 200
                async for line in resp.content:
                    if line.startswith(b"data: ") and "token" in json.loads(line[6:]):
                        tokens += 1
            return tokens, await (await client.get("/metrics")).text()

    try:
        tokens, text = asyncio.run(go())
    finally:
        svc = getattr(s, "_batcher_service", None)
        if svc is not None:
            svc.close()
    assert tokens == 12 == len(seen)
    assert all(v >= 0.0 for v in seen)
    assert series(text, "seldon_llm_emit_delay_seconds_count") == tokens
    assert series(text, "seldon_llm_emit_delay_seconds_sum") == pytest.approx(sum(seen))


def test_profile_route_is_on_the_component_app_and_shared_with_the_engine(tmp_path, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.transport import rest

    s = make_server()
    app = rest.make_component_app(s)

    async def go():
        async with TestClient(TestServer(app)) as client:
            monkeypatch.delenv("SELDON_PROFILE_DIR", raising=False)
            refused = await client.post("/profile")
            monkeypatch.setenv("SELDON_PROFILE_DIR", str(tmp_path))
            taken = await client.post("/profile", params={"seconds": "0.2"})
            return refused.status, taken.status, await taken.json()

    refused, taken, body = asyncio.run(go())
    assert refused == 403 and taken == 200
    assert glob.glob(os.path.join(body["trace_dir"], "**", "*.xplane.pb"), recursive=True)


# --------------------------------------------------- spans in the trace
def test_profiler_capture_holds_turn_with_phases_nested(tmp_path, server):
    import jax
    from jax.profiler import ProfileData

    svc = serve(server, PROMPTS[:1])        # compiled and warm before the capture
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t_end = time.perf_counter() + 0.5
        while time.perf_counter() < t_end:
            svc.submit_sync(PROMPTS[1], 6)
    finally:
        jax.profiler.stop_trace()
        svc.close()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[-1]
    events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events if e.name.startswith("llm.")]
    names = {n for _s, _e, n in events}
    assert {"llm.turn", "llm.dispatch", "llm.drain_wait", "llm.emit", "llm.prefill",
            "llm.first_token_wait", "llm.first_token", "llm.admit"} <= names
    turns = [(s, e) for s, e, n in events if n == "llm.turn"]

    def inside_a_turn(name):
        return [any(t0 <= s and e <= t1 for t0, t1 in turns)
                for s, e, n in events if n == name]

    # nested in time (the turn is the loop thread's, the phases its workers')
    for name in ("llm.dispatch", "llm.drain_wait", "llm.first_token_wait"):
        inside = inside_a_turn(name)
        # a phase cut by the capture's edges may miss its turn; the rest nest
        assert sum(inside) >= len(inside) - 2 > 0, name
    emits = [(s, e) for s, e, n in events if n == "llm.emit"]
    waits = [(s, e) for s, e, n in events if n == "llm.drain_wait"]
    assert sum(any(s0 <= s and e <= e0 for s0, e0 in emits) for s, e in waits) >= len(waits) - 1


# ------------------------------------------ nothing reaches the programs
def test_step_programs_lower_identically_inside_and_outside_a_phase(server):
    """TraceAnnotations are host-side only: the decode step and the prefill
    chunk lower to the same text whether or not a loop phase (and a profiler
    capture's worth of annotation) is open around the lowering — no scope
    name, no metadata, no compile-cache key moves (the hlolint contracts of
    tests/test_hlolint.py stay as they are)."""
    import jax.numpy as jnp
    import numpy as np

    b = ContinuousBatcher(server, max_slots=2, max_len=64)
    decode = server._get_decode_step_paged(b.S, b.n_pages, 1)
    chunk = server._get_prefill_chunk(8, b.n_pages)
    bt_row = jnp.asarray(np.zeros((1, b.n_pages), np.int32))
    toks = jnp.asarray(np.zeros((1, 8), np.int32))

    def lowered():
        return (
            decode.lower(server._params, b._caches, b._last_tok, b._next_pos,
                         b._keys, b._temp, b._block_tables).as_text(),
            chunk.lower(server._params, b._caches, bt_row, toks, toks,
                        jnp.asarray(7, jnp.int32)).as_text())

    outside = lowered()
    b._phases.turn(0)
    with b._phases.phase("dispatch"), b._phases.phase("prefill"):
        inside = lowered()
    b._phases.end_turn(0)
    assert inside == outside
    assert "llm." not in outside[0] and "llm." not in outside[1]


# ------------------------------------------- parts: a second level (ISSUE 33)
class FakeClock:
    """An injected clock: every read is what the test last set."""

    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def clocked_phases():
    phases, clock = LoopPhases(), FakeClock()
    phases._clock = clock
    return phases, clock


@pytest.mark.parametrize("with_parts", [False, True])
def test_a_part_takes_nothing_from_its_phase(with_parts):
    phases, clock = clocked_phases()
    phases.turn(1)
    clock.now += 1.0                            # the loop's own code
    with phases.phase("dispatch") as ph:
        clock.now += 0.25                       # the phase's own head
        if with_parts:
            with phases.part("pages"):
                clock.now += 0.5
            with phases.part("call"):
                clock.now += 2.0
        else:
            clock.now += 2.5
        clock.now += 0.125
    clock.now += 0.5
    phases.end_turn(1)
    st = phases.stats()
    # the phase's seconds are its wall whether or not parts were opened in it
    assert ph.seconds == st["loop_seconds"]["dispatch"] == 2.875
    assert st["loop_seconds"]["hop"] == 1.5
    assert sum(st["loop_seconds"].values()) == 4.375 == st["slot_seconds"]
    named = {k: v for k, v in st["loop_part_seconds"].items() if not k.startswith("hop.")}
    assert named == ({"dispatch.pages": 0.5, "dispatch.call": 2.0} if with_parts else {})
    assert st["loop_part_seconds"]["hop.loop"] == 1.5 == st["loop_seconds"]["hop"]
    assert st["loop_part_counts"]["hop.loop"] == 1


def test_parts_nest_among_themselves_and_a_phase_without_parts_reports_none():
    phases, clock = clocked_phases()
    phases.turn(2)
    with phases.phase("emit"):
        with phases.phase("drain_wait"):
            clock.now += 3.0                    # no part opened in this one
        with phases.part("slots"):
            clock.now += 1.0
            for _ in range(2):
                with phases.part("finish"):
                    clock.now += 0.25
    phases.end_turn(2)
    st = phases.stats()
    assert st["loop_seconds"]["drain_wait"] == 3.0 and st["loop_seconds"]["emit"] == 1.5
    # emit.finish comes out of emit.slots, as drain_wait comes out of emit
    assert st["loop_part_seconds"]["emit.slots"] == 1.0
    assert st["loop_part_seconds"]["emit.finish"] == 0.5
    assert st["loop_part_counts"]["emit.slots"] == 1 and st["loop_part_counts"]["emit.finish"] == 2
    assert not [k for k in st["loop_part_seconds"] if k.startswith("drain_wait.")]
    assert set(st["loop_part_counts"]) == set(st["loop_part_seconds"])


def test_a_handoffs_four_stamps_name_every_piece_of_the_turn():
    """Under the injected clock: submission, the worker's entry and exit and
    resumption cut a hand-off into wake_worker, the phases the worker opened,
    the worker's own Python outside them, and wake_loop."""
    phases, clock = clocked_phases()

    def work():
        clock.now += 0.5            # the worker's own Python, before its first phase
        with phases.phase("dispatch"):
            clock.now += 2.0
        clock.now += 0.25           # ... and between two phases
        with phases.phase("emit"):
            clock.now += 1.0
        return "done"

    async def go():
        phases.turn(1)
        clock.now += 1.0                        # hop.loop before the hand-off
        hop = phases.handoff(work)
        got = await asyncio.to_thread(hop)
        hop.resumed()
        clock.now += 0.125                      # hop.loop after it
        phases.end_turn(1)
        return got

    assert asyncio.run(go()) == "done"
    st = phases.stats()
    parts = st["loop_part_seconds"]
    assert st["loop_handoffs"] == 1 == st["loop_part_counts"]["hop.wake_worker"]
    assert parts["hop.wake_worker"] == 0.0 and parts["hop.wake_loop"] == 0.0
    assert parts["hop.worker"] == pytest.approx(0.75)     # 0.5 + 0.25 outside the phases
    assert parts["hop.loop"] == pytest.approx(1.125)
    assert st["loop_part_counts"]["hop.loop"] == 2
    assert st["loop_seconds"]["dispatch"] == 2.0 and st["loop_seconds"]["emit"] == 1.0
    assert sum(parts[k] for k in HOP_PARTS) == pytest.approx(st["loop_seconds"]["hop"]) \
        == pytest.approx(1.875)


def test_hop_is_its_four_measured_parts_over_200_turns():
    s = make_server()
    svc = open_service(s)
    try:
        while svc.batcher._phases.turns < 200:
            drive(svc, PROMPTS)
    finally:
        svc.close()
    st = s.llm_stats()
    parts, counts = st["loop_part_seconds"], st["loop_part_counts"]
    assert st["loop_turns"] >= 200
    assert all(parts[k] > 0.0 for k in HOP_PARTS)
    # no piece of a turn is still unnamed: the four parts ARE hop
    assert sum(parts[k] for k in HOP_PARTS) == pytest.approx(st["loop_seconds"]["hop"], rel=0.01)
    # one of each leg a hand-off, one stretch of the loop's own between them
    assert counts["hop.wake_worker"] == counts["hop.wake_loop"] == counts["hop.worker"] \
        == st["loop_handoffs"] > st["loop_turns"]
    assert counts["hop.loop"] == st["loop_handoffs"] + st["loop_turns"]
    # the phases' own budget is what it was: the parts stand inside it
    for part, phase in (("dispatch.pages", "dispatch"), ("dispatch.call", "dispatch"),
                        ("dispatch.book", "dispatch"), ("drain_wait.asides", "drain_wait"),
                        ("emit.slots", "emit"), ("emit.finish", "emit"),
                        ("prefill.build", "prefill"), ("prefill.call", "prefill"),
                        ("prefill.activate", "prefill")):
        assert 0.0 < parts[part] <= st["loop_seconds"][phase] * (1 + 1e-9), part
    assert sum(parts[p] for p in ("dispatch.pages", "dispatch.call", "dispatch.book")) \
        <= st["loop_seconds"]["dispatch"]
    assert counts["dispatch.call"] == st["loop_phase_counts"]["dispatch"]
    assert counts["drain_wait.asides"] == st["loop_phase_counts"]["drain_wait"]
    assert counts["prefill.build"] == counts["prefill.call"] == st["loop_phase_counts"]["prefill"]
    # one activation and one finish a request (six tokens each: none ends in first_token)
    assert counts["prefill.activate"] == counts["emit.finish"] \
        == st["loop_phase_counts"]["first_token"]
    # no part inside the phases whose idle time the benchmark reads by name
    assert not [k for k in parts if k.split(".")[0] in ("admit", "first_token_wait", "first_token")]


def test_parts_and_handoffs_are_exported_under_the_documented_names(server):
    svc = serve(server, PROMPTS)
    try:
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(server)
        text = reg.expose().decode()
        st = server.llm_stats()
    finally:
        svc.close()
    for part in ("dispatch.pages", "dispatch.call", "dispatch.book", "drain_wait.asides",
                 "emit.slots", "emit.finish", "prefill.build", "prefill.call",
                 "prefill.activate") + HOP_PARTS:
        label = f'part="{part}"'
        assert series(text, "seldon_llm_loop_part_seconds_total", label) \
            == pytest.approx(st["loop_part_seconds"][part], rel=0.5), part
        assert series(text, "seldon_llm_loop_part_total", label) > 0
    assert series(text, "seldon_llm_loop_handoffs_total") > series(text, "seldon_llm_loop_turns_total")
    # a second scrape catches up by difference, and the phases' series are untouched
    reg.sync_llm(server)
    again = reg.expose().decode()
    assert series(again, "seldon_llm_loop_part_total", 'part="dispatch.call"') \
        == series(again, "seldon_llm_loop_phase_total", 'phase="dispatch"')
    assert series(again, "seldon_llm_loop_seconds_total") == pytest.approx(
        sum(server.llm_stats()["loop_seconds"].values()))


# ----------------------------------------- the transport thread's busy time
def test_http_busy_counts_once_per_request_sse_event_reply_and_scrape():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.transport.rest import make_component_app

    s = make_server()
    reg = MetricsRegistry(deployment="d", predictor="p")
    app = make_component_app(s, metrics=reg)

    async def go():
        async with TestClient(TestServer(app)) as client:
            first = await (await client.get("/metrics")).text()
            for prompt in (PROMPTS[0], PROMPTS[3]):
                resp = await client.post("/v1/generate", json={
                    "prompt": prompt, "stream": True, "max_new_tokens": 6})
                assert resp.status == 200
                await resp.read()
            plain = await client.post("/v1/generate", json={"prompt": PROMPTS[2],
                                                            "max_new_tokens": 5})
            assert plain.status == 200 and len((await plain.json())["tokens"]) == 5
            bad = await client.post("/v1/generate", data=b"[1, 2]")
            assert bad.status == 400
            assert (await client.get("/debug/timeline")).status == 200
            return first, await (await client.get("/metrics")).text()

    try:
        first, text = asyncio.run(go())
    finally:
        svc = getattr(s, "_batcher_service", None)
        if svc is not None:
            svc.close()
    # counted where the stretch ends: the first scrape's own text does not hold it
    assert "seldon_http_busy_total{" not in first
    for what, n in (("parse", 4), ("sse_write", 12), ("reply", 3), ("scrape", 2)):
        label = f'what="{what}"'
        assert series(text, "seldon_http_busy_total", label) == n, what
        assert series(text, "seldon_http_busy_seconds_total", label) > 0.0, what
    # busy times of one thread: together they cannot pass the run's wall
    assert series(text, "seldon_http_busy_seconds_total") < 60.0
    assert series(text, "seldon_llm_emit_delay_seconds_count") == 12


def test_profiler_capture_holds_parts_hop_legs_and_http_spans_with_the_trace_id(tmp_path):
    """A capture's host plane carries the second level inside the first, the
    hop's legs as spans of their own (entered on one thread, left on another)
    and the transport's ``http.*`` spans; parse and reply carry the request's
    trace id while a capture runs."""
    import jax
    from aiohttp.test_utils import TestClient, TestServer
    from jax.profiler import ProfileData

    from seldon_core_tpu.transport.rest import make_component_app

    old = get_tracer()
    set_tracer(Tracer(enabled=True))
    s = make_server()
    app = make_component_app(s, metrics=MetricsRegistry(deployment="d", predictor="p"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2

    async def go():
        async with TestClient(TestServer(app)) as client:
            body = {"prompt": PROMPTS[1], "max_new_tokens": 6}
            await client.post("/v1/generate", json=body)       # compiled and warm
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                plain = await (await client.post("/v1/generate", json=body)).json()
                resp = await client.post("/v1/generate", json={**body, "stream": True})
                await resp.read()
                await client.get("/metrics")
            finally:
                jax.profiler.stop_trace()
            return plain["trace_id"], resp.headers["X-Trace-Id"]

    try:
        plain_id, stream_id = asyncio.run(go())
    finally:
        set_tracer(old)
        svc = getattr(s, "_batcher_service", None)
        if svc is not None:
            svc.close()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[-1]
    events = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith(("llm.", "http."))]
    names = {n for _s, _e, n, _st in events}
    assert {"llm.dispatch.pages", "llm.dispatch.call", "llm.dispatch.book",
            "llm.drain_wait.asides", "llm.emit.slots", "llm.emit.finish", "llm.prefill.build",
            "llm.prefill.call", "llm.prefill.activate", "llm.hop.loop", "llm.hop.wake_worker",
            "llm.hop.wake_loop", "http.parse", "http.sse_write", "http.reply",
            "http.scrape"} <= names
    assert not [n for n in names if n.startswith(("llm.admit.", "llm.first_token"))
                and n.count(".") > 1]

    def spans(name):
        return [(s0, e0) for s0, e0, n, _st in events if n == name]

    def nested(inner, outer):
        got = [any(o0 <= s0 and e0 <= o1 for o0, o1 in spans(outer)) for s0, e0 in spans(inner)]
        return sum(got), len(got)

    # a part lies inside its phase; the legs and the loop's own stretch inside a turn
    # (one cut by the capture's edge may miss its parent)
    for inner, outer in (("llm.dispatch.call", "llm.dispatch"), ("llm.emit.slots", "llm.emit"),
                         ("llm.drain_wait.asides", "llm.drain_wait"),
                         ("llm.prefill.call", "llm.prefill"), ("llm.hop.loop", "llm.turn"),
                         ("llm.hop.wake_worker", "llm.turn"), ("llm.hop.wake_loop", "llm.turn")):
        inside, n = nested(inner, outer)
        assert inside >= n - 2 > 0, (inner, inside, n)
    # a leg ends where the worker's phase begins: wake_worker never overlaps a phase
    phase_spans = sorted(spans("llm.dispatch") + spans("llm.emit") + spans("llm.prefill"))
    for s0, e0 in spans("llm.hop.wake_worker"):
        assert not any(p0 < e0 and s0 < p1 for p0, p1 in phase_spans)
    ids = {what: {st.get("trace_id") for _s, _e, n, st in events if n == what}
           for what in ("http.parse", "http.reply", "http.sse_write", "http.scrape")}
    assert ids["http.parse"] == ids["http.reply"] == {plain_id, stream_id}
    assert ids["http.sse_write"] == {None} == ids["http.scrape"]
