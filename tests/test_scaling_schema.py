"""Pinned scaling-snapshot schema (ISSUE 14 satellite): every field the
autoscaler consumes — names, types, quantile keys — asserted against the
REAL producer (observability/timeline.py over a live batcher + flight
recorder), so a timeline refactor cannot silently starve the controller.
Plus the dynamic Retry-After derivation that rides the same snapshot."""

from __future__ import annotations

import asyncio

import pytest

from seldon_core_tpu.controlplane.autoscaler import ReplicaSignals
from seldon_core_tpu.observability.timeline import (
    retry_after_hint,
    scaling_snapshot,
)
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)

# The controller's consumption contract.  Changing this set is an API
# break for controlplane/autoscaler.py: update ReplicaSignals.from_scaling
# and docs/control-plane.md in the same PR.
PINNED_FIELDS = {
    "active_slots": int,
    "total_slots": int,
    "queue_depth": int,
    "steps_in_flight": int,
    "page_pressure": float,
    "page_sheds_total": int,
    "handoff_queue_depth": int,
    "draining": bool,
    # fleet fault tolerance (ISSUE 16): True once the fleet quarantined
    # this replica after an unplanned death — the autoscaler's replace
    # signal (a solo component is never ejected)
    "ejected": bool,
    "prefill_devices": int,
    "decode_devices": int,
    # multi-tenant (ISSUE 15): queued admissions per SLO class — the
    # weighted-fair scheduler's split of queue_depth
    "queue_by_class": dict,
}
PINNED_REQUEST_BLOCKS = ("ttft_s", "queue_wait_s", "worst_gap_s")
PINNED_QUANTILE_KEYS = {"p50", "p95", "max"}


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1,),
                temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def live_snapshot():
    """A snapshot from the real pipeline: paged batcher, flight recorder
    on, one request served."""
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    s = make_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=40, len_buckets=(8,),
                              page_size=8, tracing=True)
        await b.submit([5, 9, 17], max_new_tokens=4)
        snap = scaling_snapshot(object(), batcher=b, recorder=b._flight)
        await b.close()
        return snap

    return asyncio.run(go())


def test_snapshot_field_names_and_types_are_pinned(live_snapshot):
    snap = live_snapshot
    assert set(snap) == set(PINNED_FIELDS) | {"requests"}, (
        "scaling_snapshot schema drifted — the autoscaler consumes every "
        "pinned field; update ReplicaSignals.from_scaling and this pin "
        "together")
    for field, typ in PINNED_FIELDS.items():
        if typ is float:
            assert isinstance(snap[field], (int, float)), field
        else:
            assert isinstance(snap[field], typ), field


def test_request_quantile_blocks_are_pinned(live_snapshot):
    req = live_snapshot["requests"]
    assert {"completed_total", "retained", "events_dropped_total",
            *PINNED_REQUEST_BLOCKS} <= set(req)
    for block in PINNED_REQUEST_BLOCKS:
        assert set(req[block]) == PINNED_QUANTILE_KEYS, block
        for v in req[block].values():
            assert v is None or isinstance(v, (int, float))
    assert req["completed_total"] == 1


def test_controller_parser_consumes_the_pinned_snapshot(live_snapshot):
    """The other half of the contract: the autoscaler's parser reads the
    real snapshot without defaulting anything away."""
    parsed = ReplicaSignals.from_scaling(live_snapshot)
    assert parsed.total_slots == live_snapshot["total_slots"] == 2
    assert parsed.queue_depth == live_snapshot["queue_depth"]
    assert parsed.page_pressure == live_snapshot["page_pressure"]
    assert parsed.draining is False
    # the recorder ran, so the latency quantiles are REAL numbers
    assert parsed.ttft_p95_s is not None and parsed.ttft_p95_s >= 0
    assert parsed.queue_wait_p95_s is not None
    # a snapshot without the requests block (tracing off) parses too,
    # with the latency terms disarmed
    bare = {k: v for k, v in live_snapshot.items() if k != "requests"}
    assert ReplicaSignals.from_scaling(bare).ttft_p95_s is None


def test_componentless_snapshot_keeps_the_schema():
    """The endpoint never 500s on configuration: a component with no
    batcher still reports the full pinned field set (zeros)."""
    snap = scaling_snapshot(object())
    assert set(snap) == set(PINNED_FIELDS)
    assert snap["total_slots"] == 0 and snap["draining"] is False


def _queue_dummy_requests(batcher, n):
    """Park n inert requests in the weighted-fair scheduler (the loop
    never runs: nothing admits them) so backlog-derived hints have a
    queue to measure."""
    from seldon_core_tpu.runtime.scheduler import PendingRequest

    reqs = [PendingRequest(ids=[1], max_new=1, fut=None) for _ in range(n)]
    for r in reqs:
        assert batcher._pending.push(r)
    return reqs


# ------------------------------------------------- dynamic Retry-After
def test_retry_after_hint_scales_with_backlog():
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    s = make_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=40, len_buckets=(8,),
                              page_size=8)
        idle = b.retry_after_hint()
        # 8 queued requests over 2 slots = 4 drain waves ahead (the loop
        # never ran: no submit ever started it, so poking the scheduler
        # is race-free)
        reqs = _queue_dummy_requests(b, 8)
        loaded = b.retry_after_hint()
        for r in reqs:
            b._pending.remove(r)
        await b.close()
        return idle, loaded

    idle, loaded = asyncio.run(go())
    assert idle == 1.0               # base: no backlog
    assert loaded == 4.0             # base x ceil(8/2) drain waves
    assert loaded <= 30.0            # clamped


def test_retry_after_hint_component_fallback():
    class Bare:
        pass

    assert retry_after_hint(Bare(), 2.5) == 2.5  # no batcher: constant


def test_shed_error_carries_the_dynamic_hint():
    """The admission path's ShedError is refined through retry_after_fn
    OUTSIDE the lock — clients back off proportionally to the spike."""
    from seldon_core_tpu.runtime.resilience import (
        AdmissionController, ShedError)

    adm = AdmissionController(max_inflight=1, max_queue=0,
                              retry_after_fn=lambda: 7.5)
    adm.acquire_sync()  # take the only slot
    with pytest.raises(ShedError) as e:
        adm.acquire_sync()
    assert e.value.retry_after_s == 7.5
    adm.release()
    # a failing hint falls back to the configured constant
    def boom():
        raise RuntimeError("no snapshot")

    adm2 = AdmissionController(max_inflight=1, max_queue=0,
                               retry_after_s=3.0, retry_after_fn=boom)
    adm2.acquire_sync()
    with pytest.raises(ShedError) as e:
        adm2.acquire_sync()
    assert e.value.retry_after_s == 3.0


def test_batcher_page_shed_uses_the_hint():
    """The batcher's own exhaustion sheds derive Retry-After from the
    live backlog too (not the fixed constant)."""
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    s = make_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=40, len_buckets=(8,),
                              page_size=8)
        reqs = _queue_dummy_requests(b, 8)
        err = b._shed_error("test")
        for r in reqs:
            b._pending.remove(r)
        await b.close()
        return err

    err = asyncio.run(go())
    assert err.retry_after_s == 4.0  # backlog-derived, not DEFAULT(1)
