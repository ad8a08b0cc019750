"""SLO-aware weighted-fair scheduling (ISSUE 15 tentpole,
runtime/scheduler.py): admission ordering, quotas, preemption, tenant
accounting, header threading, and the deterministic SLO-isolation
scenario the bench's phase L measures under wall-clock load.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.runtime.batcher import BatcherService, ContinuousBatcher
from seldon_core_tpu.runtime.resilience import ShedError
from seldon_core_tpu.runtime.scheduler import (
    BATCH,
    INTERACTIVE,
    PendingRequest,
    WeightedFairScheduler,
    normalize_slo_class,
)
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1,),
                temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


_SHARED = {}


def shared_server() -> LLMServer:
    """One default-kwargs server for the batcher-integration tests that
    only READ server config (each private LLMServer.load() + program
    compile costs seconds against the tier-1 870s budget; sharing also
    shares the per-server jit caches across same-shape batchers). Tests
    that mutate server-level state (llm_stats TTFT drains, quota knobs)
    keep their own make_server()."""
    if "s" not in _SHARED:
        _SHARED["s"] = make_server()
    return _SHARED["s"]


def req(tenant="", cls=INTERACTIVE, deadline=None, seq_ids=(1,)):
    return PendingRequest(ids=list(seq_ids), max_new=4, fut=None,
                          tenant=tenant, slo_class=cls, deadline_t=deadline)


def drain_order(s):
    out = []
    while len(s):
        r = s.next_request()
        s.commit(r)
        out.append(r)
    return out


# ---------------------------------------------------------------------------
# pure scheduler semantics
# ---------------------------------------------------------------------------

def test_normalize_slo_class():
    assert normalize_slo_class(None) == INTERACTIVE
    assert normalize_slo_class("") == INTERACTIVE
    assert normalize_slo_class("Batch") == BATCH
    assert normalize_slo_class("throughput") == BATCH
    with pytest.raises(ValueError):
        normalize_slo_class("gold")


def test_interactive_jumps_a_batch_flood():
    s = WeightedFairScheduler()
    flood = [req("bulk", BATCH) for _ in range(12)]
    for r in flood:
        assert s.push(r)
    late = req("chat", INTERACTIVE)
    s.push(late)
    assert drain_order(s)[0] is late


def test_class_weights_hold_the_admission_ratio():
    """4:1 default — of any 10 picks with both queues backlogged, 8 are
    interactive; neither class ever starves."""
    s = WeightedFairScheduler()
    for _ in range(40):
        s.push(req("a", INTERACTIVE))
        s.push(req("b", BATCH))
    picks = [r.slo_class for r in drain_order(s)[:20]]
    assert picks.count(INTERACTIVE) == 16
    assert picks.count(BATCH) == 4
    # custom weights flip the ratio
    s2 = WeightedFairScheduler(class_weights={INTERACTIVE: 1, BATCH: 1})
    for _ in range(10):
        s2.push(req("a", INTERACTIVE))
        s2.push(req("b", BATCH))
    picks2 = [r.slo_class for r in drain_order(s2)[:10]]
    assert picks2.count(INTERACTIVE) == 5


def test_tenant_weights_within_a_class():
    s = WeightedFairScheduler(tenant_weights={"gold": 3.0, "iron": 1.0})
    for _ in range(20):
        s.push(req("gold", BATCH))
        s.push(req("iron", BATCH))
    picks = [r.tenant for r in drain_order(s)[:8]]
    assert picks.count("gold") == 6 and picks.count("iron") == 2


def test_idle_class_banks_no_credit():
    """A class that sat empty must not monopolize on return: after 100
    interactive-only admissions, a fresh batch arrival does not get 100
    back-pay picks."""
    s = WeightedFairScheduler()
    for _ in range(100):
        s.push(req("a", INTERACTIVE))
    for _ in range(100):
        s.commit(s.next_request())
    for _ in range(10):
        s.push(req("a", INTERACTIVE))
        s.push(req("b", BATCH))
    picks = [r.slo_class for r in drain_order(s)[:10]]
    assert picks.count(BATCH) <= 3  # ~1 in 5, not a monopoly


def test_deadline_edf_within_tenant():
    s = WeightedFairScheduler()
    r_none = req("t", INTERACTIVE)
    r_late = req("t", INTERACTIVE, deadline=9.0)
    r_soon = req("t", INTERACTIVE, deadline=1.0)
    for r in (r_none, r_late, r_soon):
        s.push(r)
    assert [r is x for r, x in zip(drain_order(s),
                                   (r_soon, r_late, r_none))] == [True] * 3


def test_quota_sheds_and_counts():
    s = WeightedFairScheduler(tenant_quota=2,
                              tenant_quotas={"vip": 4})
    assert all(s.push(req("noisy", BATCH)) for _ in range(2))
    assert not s.push(req("noisy", BATCH))           # over global quota
    assert all(s.push(req("vip", BATCH)) for _ in range(4))
    assert not s.push(req("vip", BATCH))             # over its override
    rows = {(r["tenant"], r["slo_class"]): r for r in s.counters()}
    assert rows[("noisy", BATCH)]["shed"] == 1
    assert rows[("vip", BATCH)]["shed"] == 1
    assert rows[("noisy", BATCH)]["queued"] == 2


def test_tenant_cardinality_bounded_by_overflow_bucket():
    """The tenant header is client-controlled: past MAX_TENANT_SERIES
    distinct tallies, unseen tenants fold into the shared overflow
    bucket, so a cardinality flood cannot grow the tally map (or the
    Prometheus series counters() feeds) without bound — and emptied
    per-tenant queues prune their heap/virtual-time map entries."""
    from seldon_core_tpu.runtime.scheduler import (MAX_TENANT_SERIES,
                                                   OVERFLOW_TENANT)

    s = WeightedFairScheduler()
    n = MAX_TENANT_SERIES + 50
    reqs = [req(f"flood-{i}", BATCH) for i in range(n)]
    for r in reqs:
        assert s.push(r)
    rows = {r["tenant"] for r in s.counters()}
    assert len(rows) <= MAX_TENANT_SERIES + 1
    assert OVERFLOW_TENANT in rows
    over = [r for r in s.counters() if r["tenant"] == OVERFLOW_TENANT]
    assert over[0]["queued"] == 50                 # the folded tail
    # known tenants (configured or seen before the cap) keep their own row
    assert "flood-0" in rows
    # draining everything prunes the per-tenant queue/vt maps entirely
    while True:
        nxt = s.next_request()
        if nxt is None:
            break
        s.commit(nxt)
    assert len(s) == 0
    assert s._queues == {} and s._tenant_vt == {}


def test_requeue_restores_position_and_marks_preempted():
    s = WeightedFairScheduler()
    first = req("t", BATCH)
    second = req("t", BATCH)
    s.push(first)
    s.push(second)
    s.commit(first)  # staged...
    s.push(first, requeue=True)  # ...then preempted back
    assert first.preempted is True
    # original seq: it re-enters AHEAD of second
    assert drain_order(s)[0] is first
    rows = {(r["tenant"], r["slo_class"]): r for r in s.counters()}
    assert rows[("t", BATCH)]["preempted"] == 1


def test_commit_by_identity_survives_interleaved_push():
    """The peek-try-commit idiom: a push landing between peek and commit
    (same loop, different coroutine) must not make commit remove the
    wrong request."""
    s = WeightedFairScheduler()
    a = req("t", INTERACTIVE)
    s.push(a)
    peeked = s.next_request()
    assert peeked is a
    b = req("t", INTERACTIVE, deadline=0.1)  # jumps ahead of a
    s.push(b)
    s.commit(a)                               # still removes a, not b
    assert s.next_request() is b


def test_drain_all_returns_everything_in_seq_order():
    s = WeightedFairScheduler()
    rs = [req("x", BATCH), req("y", INTERACTIVE), req("x", INTERACTIVE)]
    for r in rs:
        s.push(r)
    drained = s.drain_all()
    assert drained == sorted(drained, key=lambda r: r.seq)
    assert len(drained) == 3 and len(s) == 0
    assert s.depths() == {INTERACTIVE: 0, BATCH: 0}


# ---------------------------------------------------------------------------
# batcher integration
# ---------------------------------------------------------------------------

def test_interactive_preempts_staged_batch_prefill_never_active():
    """The preemption contract: with the only slot held by a STAGED
    batch-class chunked prefill, an interactive arrival preempts it
    (the batch request requeues, finishes later, is preempted at most
    once); an ACTIVE slot is never preempted."""
    s = shared_server()
    long_prompt = list(np.random.default_rng(0).integers(1, 90, size=14))

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=48, len_buckets=(16,),
                              page_size=4, prefill_chunk=2)
        batch_fut = asyncio.ensure_future(
            b.submit(long_prompt, max_new_tokens=4, tenant="bulk",
                     slo_class="batch"))
        # wait until the batch job is STAGED (slot reserved, prefilling)
        for _ in range(400):
            if b._prefill is not None:
                break
            await asyncio.sleep(0.002)
        assert b._prefill is not None
        inter = await b.submit([3, 5], max_new_tokens=3, tenant="chat",
                               slo_class="interactive")
        batch_out = await batch_fut
        ctrs = {(r["tenant"], r["slo_class"]): r
                for r in b._pending.counters()}
        await b.close()
        return inter, batch_out, ctrs

    inter, batch_out, ctrs = asyncio.run(go())
    assert len(inter) == 3
    assert len(batch_out) == 4                      # preempted, not dropped
    assert ctrs[("bulk", "batch")]["preempted"] == 1
    assert ctrs[("bulk", "batch")]["admitted"] >= 1
    assert ctrs[("chat", "interactive")]["admitted"] == 1


def test_batch_outputs_unchanged_by_preemption():
    """A preempted batch request re-prefills and generates the IDENTICAL
    tokens it would have unpreempted — preemption moves time, never
    content."""
    s = shared_server()
    prompt = [7, 11, 13, 17, 19, 23, 29, 31, 37, 41, 43, 47]

    async def once(preempt: bool):
        b = ContinuousBatcher(s, max_slots=1, max_len=48, len_buckets=(16,),
                              page_size=4, prefill_chunk=2)
        fut = asyncio.ensure_future(
            b.submit(prompt, max_new_tokens=5, slo_class="batch"))
        if preempt:
            for _ in range(400):
                if b._prefill is not None:
                    break
                await asyncio.sleep(0.002)
            await b.submit([2, 4], max_new_tokens=2,
                           slo_class="interactive")
        out = await fut
        await b.close()
        return out

    plain = asyncio.run(once(False))
    preempted = asyncio.run(once(True))
    assert plain == preempted


def test_per_class_ttft_and_tenant_tokens_flow_metrics():
    """The whole flow: batcher tallies -> llm_stats -> sync_llm ->
    Prometheus text. llm_stats' TTFT drain is one-shot (scrape
    semantics), so the direct-surface asserts read the FIRST scrape and
    the /metrics text a second scrape fed by fresh requests."""
    s = make_server(continuous_batching=2)

    async def go():
        from seldon_core_tpu.metrics.registry import MetricsRegistry

        b = ContinuousBatcher(s, max_slots=2, max_len=40, len_buckets=(8,),
                              page_size=8)
        s._batcher_service = type("Svc", (), {"batcher": b})()
        try:
            await b.submit([5, 9], max_new_tokens=4, tenant="acme",
                           slo_class="batch")
            await b.submit([5, 9], max_new_tokens=4, tenant="chat")
            stats = s.llm_stats()
            # second round feeds the REGISTRY scrape (the first drained
            # the per-class TTFT deque, as any scrape does)
            await b.submit([5, 9], max_new_tokens=4, tenant="acme",
                           slo_class="batch")
            await b.submit([5, 9], max_new_tokens=4, tenant="chat")
            m = MetricsRegistry(deployment="d", predictor="p")
            m.sync_llm(s)
            text = m.expose().decode()
        finally:
            await b.close()
            del s._batcher_service
        return stats, text

    stats, text = asyncio.run(go())
    rows = {(r["tenant"], r["slo_class"]): r
            for r in stats["tenant_counters"]}
    assert rows[("acme", "batch")]["tokens"] == 4
    assert rows[("chat", "interactive")]["tokens"] == 4
    classes = [c for c, _ in stats["ttft_by_class"]]
    assert sorted(classes) == ["batch", "interactive"]
    assert 'seldon_tenant_tokens_total{' in text
    assert 'tenant="acme"' in text
    assert 'seldon_llm_tenant_ttft_seconds_bucket' in text
    assert 'slo_class="interactive"' in text and 'slo_class="batch"' in text


def test_quota_shed_is_503_with_retry_after():
    s = make_server(tenant_quota=1)

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=40, len_buckets=(8,),
                              page_size=8)
        futs = [asyncio.ensure_future(
            b.submit([5, 9], max_new_tokens=4, tenant="noisy",
                     slo_class="batch")) for _ in range(5)]
        done = await asyncio.gather(*futs, return_exceptions=True)
        ctrs = {(r["tenant"], r["slo_class"]): r
                for r in b._pending.counters()}
        await b.close()
        return done, ctrs

    done, ctrs = asyncio.run(go())
    sheds = [d for d in done if isinstance(d, ShedError)]
    assert sheds, "over-quota submits must shed"
    assert all(d.status_code == 503 and d.retry_after_s >= 1.0
               for d in sheds)
    assert ctrs[("noisy", "batch")]["shed"] == len(sheds)


def test_scaling_snapshot_reports_queue_by_class():
    from seldon_core_tpu.observability.timeline import scaling_snapshot

    s = shared_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=40, len_buckets=(8,),
                              page_size=8)
        for r in [PendingRequest(ids=[1], max_new=1, fut=None,
                                 slo_class=cls)
                  for cls in (INTERACTIVE, INTERACTIVE, BATCH)]:
            b._pending.push(r)
        snap = scaling_snapshot(object(), batcher=b)
        for r in b._pending.drain_all():
            pass
        await b.close()
        return snap

    snap = asyncio.run(go())
    assert snap["queue_by_class"] == {INTERACTIVE: 2, BATCH: 1}
    assert snap["queue_depth"] == 3


def test_flight_timeline_carries_tenant_tags():
    s = shared_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=40, len_buckets=(8,),
                              page_size=8, tracing=True)
        await b.submit([5, 9, 2], max_new_tokens=3, tenant="acme",
                       slo_class="batch")
        await b.submit([5, 9, 2], max_new_tokens=3)
        tls = b._flight.timelines(4)
        await b.close()
        return tls

    tls = asyncio.run(go())
    tagged = [t for t in tls if "request_tags" in t]
    assert len(tagged) == 1
    assert tagged[0]["request_tags"] == {
        "tenant": "acme", "slo_class": "batch", "adapter_id": 0}


# ---------------------------------------------------------------------------
# transport threading (headers -> submit)
# ---------------------------------------------------------------------------

def test_rest_headers_thread_into_scheduler():
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.transport.rest import make_component_app

    s = make_server(continuous_batching=2, tenant_quota=0)
    app = make_component_app(s)

    async def go():
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/v1/generate",
                json={"prompt": [5, 9, 2], "max_new_tokens": 3},
                headers={"Seldon-Tenant": "acme",
                         "Seldon-SLO-Class": "batch"})
            assert resp.status == 200
            body = await resp.json()
            assert len(body["tokens"]) == 3
            # unknown class -> 400, not a silent default
            resp = await client.post(
                "/v1/generate", json={"prompt": [5], "max_new_tokens": 2},
                headers={"Seldon-SLO-Class": "gold"})
            assert resp.status == 400
            # ...including on the NON-batched branch (per-request
            # temperature routes around the batcher and its validation)
            resp = await client.post(
                "/v1/generate",
                json={"prompt": [5], "max_new_tokens": 2,
                      "temperature": 0.7},
                headers={"Seldon-SLO-Class": "gold"})
            assert resp.status == 400
            # unknown adapter -> 400
            resp = await client.post(
                "/v1/generate",
                json={"prompt": [5], "max_new_tokens": 2,
                      "adapter": "ghost"})
            assert resp.status == 400
        svc = s._batcher_service
        rows = {(r["tenant"], r["slo_class"]): r
                for r in svc.batcher._pending.counters()}
        assert rows[("acme", "batch")]["admitted"] == 1
        svc.close()

    asyncio.run(go())


def test_slo_isolation_under_deterministic_load():
    """The SLO-isolation acceptance shape, deterministically: a
    batch-class tenant floods a 2-slot batcher; interactive requests
    submitted after the flood still admit within the first
    weighted-fair wave (their queue position, not wall clock, is the
    deterministic proxy phase L measures as TTFT p95), and the batch
    tenant still finishes everything (no starvation)."""
    s = shared_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=40, len_buckets=(8,),
                              page_size=8)
        flood = [asyncio.ensure_future(
            b.submit([9, 9, 9], max_new_tokens=6, tenant="bulk",
                     slo_class="batch")) for _ in range(8)]
        await asyncio.sleep(0)  # flood queued first
        inter = [asyncio.ensure_future(
            b.submit([3, 5, 7], max_new_tokens=3, tenant="chat",
                     slo_class="interactive")) for _ in range(2)]
        inter_out = await asyncio.gather(*inter)
        # when the LAST interactive token lands, most of the flood must
        # still be queued/in-flight — interactive did not wait it out
        pending_batch = sum(1 for f in flood if not f.done())
        flood_out = await asyncio.gather(*flood)
        ctrs = {(r["tenant"], r["slo_class"]): r
                for r in b._pending.counters()}
        await b.close()
        return inter_out, flood_out, pending_batch, ctrs

    inter_out, flood_out, pending_batch, ctrs = asyncio.run(go())
    assert all(len(t) == 3 for t in inter_out)
    assert all(len(t) == 6 for t in flood_out)      # zero starvation
    assert pending_batch >= 4, (
        "interactive completed while most of the batch flood was still "
        "queued — isolation held")
    assert ctrs[("chat", "interactive")]["admitted"] == 2
    assert ctrs[("bulk", "batch")]["admitted"] == 8
