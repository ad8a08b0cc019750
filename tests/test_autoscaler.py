"""Signal-driven autoscaler (ISSUE 14 tentpole): pure decision functions,
elastic ReplicaSet membership with no-drop draining, the deterministic
load-spike scenario (spike -> scale-up -> fault-injected canary ->
rollback -> quiesce -> scale-down, all on FaultClock — zero time.sleep),
and the disagg prefill:decode rebalance with bit-exact generation across
the move."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.controlplane.autoscaler import (
    HOLD,
    REBALANCE,
    SCALE_DOWN,
    SCALE_UP,
    Autoscaler,
    AutoscalerConfig,
    ControllerState,
    Decision,
    ReplicaSignals,
    decide_rebalance,
    decide_scale,
)
from seldon_core_tpu.runtime.engine import ReplicaSet, replica_load
from seldon_core_tpu.testing.faults import FaultClock


def sig(**kw) -> ReplicaSignals:
    return ReplicaSignals.from_scaling(kw)


# ------------------------------------------------------ decision function
def test_scale_up_needs_stability_window():
    cfg = AutoscalerConfig(up_queue_per_slot=1.0, up_stable_ticks=3,
                           cooldown_s=0.0)
    st = ControllerState()
    hot = [sig(queue_depth=8, total_slots=2)]
    d, st = decide_scale(hot, cfg, st, 0.0, 1)
    assert d.action == HOLD
    d, st = decide_scale(hot, cfg, st, 1.0, 1)
    assert d.action == HOLD
    d, st = decide_scale(hot, cfg, st, 2.0, 1)
    assert d.action == SCALE_UP and d.target == 2
    # a cold tick resets the streak
    st2 = ControllerState(over_ticks=2)
    d, st2 = decide_scale([sig(queue_depth=0, total_slots=2)], cfg, st2,
                          3.0, 1)
    assert d.action == HOLD and st2.over_ticks == 0


def test_cooldown_and_max_replicas_bound_scale_up():
    cfg = AutoscalerConfig(up_queue_per_slot=1.0, up_stable_ticks=1,
                           cooldown_s=10.0, max_replicas=2)
    hot = [sig(queue_depth=8, total_slots=2)]
    d, st = decide_scale(hot, cfg, ControllerState(), 0.0, 1)
    assert d.action == SCALE_UP
    d, st = decide_scale(hot, cfg, st, 5.0, 2)   # inside cooldown
    assert d.action == HOLD
    d, st = decide_scale(hot, cfg, st, 20.0, 2)  # at the ceiling
    assert d.action == HOLD


def test_page_pressure_and_ttft_trigger_scale_up():
    cfg = AutoscalerConfig(up_queue_per_slot=100.0, up_page_pressure=0.8,
                           up_stable_ticks=1, cooldown_s=0.0)
    d, _ = decide_scale([sig(page_pressure=0.9)], cfg, ControllerState(),
                        0.0, 1)
    assert d.action == SCALE_UP and "pages" in d.reason
    cfg = AutoscalerConfig(up_queue_per_slot=100.0, up_ttft_p95_s=0.2,
                           up_stable_ticks=1, cooldown_s=0.0)
    slow = [sig(requests={"ttft_s": {"p50": 0.1, "p95": 0.5, "max": 1.0}})]
    d, _ = decide_scale(slow, cfg, ControllerState(), 0.0, 1)
    assert d.action == SCALE_UP
    # no recorder (tracing off): the latency term simply never fires
    d, _ = decide_scale([sig()], cfg, ControllerState(), 0.0, 1)
    assert d.action == HOLD


def test_scale_down_floor_and_stability():
    cfg = AutoscalerConfig(down_queue_per_slot=0.25, down_stable_ticks=2,
                           cooldown_s=0.0, min_replicas=1)
    idle = [sig(queue_depth=0, total_slots=4),
            sig(queue_depth=0, total_slots=4)]
    d, st = decide_scale(idle, cfg, ControllerState(), 0.0, 2)
    assert d.action == HOLD
    d, st = decide_scale(idle, cfg, st, 1.0, 2)
    assert d.action == SCALE_DOWN and d.target == 1
    # at the floor nothing drains
    d2, _ = decide_scale(idle, cfg, ControllerState(under_ticks=5), 2.0, 1)
    assert d2.action == HOLD


def test_draining_replicas_do_not_mask_survivor_overload():
    """A draining replica's emptying queue must not average away the
    survivors' overload — pressure is computed over non-draining members
    only."""
    cfg = AutoscalerConfig(up_queue_per_slot=1.0, up_stable_ticks=1,
                           cooldown_s=0.0, max_replicas=4)
    mixed = [sig(queue_depth=8, total_slots=2),
             sig(queue_depth=0, total_slots=2, draining=True)]
    d, _ = decide_scale(mixed, cfg, ControllerState(), 0.0, 2, n_draining=1)
    assert d.action == SCALE_UP
    assert d.target == 2  # serving (2-1=1) + 1


def test_rebalance_decision_moves_split_both_ways():
    cfg = AutoscalerConfig(rebalance=True, rebalance_backlog_high=1.0,
                           rebalance_stable_ticks=2,
                           rebalance_cooldown_s=0.0,
                           min_prefill_devices=1, min_decode_devices=1)
    long_mix = [sig(handoff_queue_depth=4, prefill_devices=2,
                    decode_devices=6)]
    st = ControllerState()
    d, st = decide_rebalance(long_mix, cfg, st, 0.0)
    assert d.action == HOLD
    d, st = decide_rebalance(long_mix, cfg, st, 1.0)
    assert d.action == REBALANCE and d.target == 3  # decode -> prefill
    short_mix = [sig(handoff_queue_depth=0, queue_depth=0,
                     prefill_devices=3, decode_devices=5)]
    st = ControllerState()
    d, st = decide_rebalance(short_mix, cfg, st, 2.0)
    d, st = decide_rebalance(short_mix, cfg, st, 3.0)
    assert d.action == REBALANCE and d.target == 2  # prefill -> decode
    # floors hold
    floor = [sig(handoff_queue_depth=0, prefill_devices=1,
                 decode_devices=7)]
    st = ControllerState(short_ticks=5)
    d, _ = decide_rebalance(floor, cfg, st, 4.0)
    assert d.action == HOLD
    # non-disagg fleets never rebalance
    d, _ = decide_rebalance([sig()], cfg, ControllerState(), 5.0)
    assert d.action == HOLD


def test_rebalance_cooldown():
    cfg = AutoscalerConfig(rebalance=True, rebalance_backlog_high=1.0,
                           rebalance_stable_ticks=1,
                           rebalance_cooldown_s=10.0)
    long_mix = [sig(handoff_queue_depth=4, prefill_devices=2,
                    decode_devices=6)]
    d, st = decide_rebalance(long_mix, cfg, ControllerState(), 0.0)
    assert d.action == REBALANCE
    d, _ = decide_rebalance(long_mix, cfg, st, 5.0)
    assert d.action == HOLD and "cooldown" in d.reason


# ------------------------------------------------- elastic ReplicaSet
class StubReplica:
    def __init__(self, name="r"):
        self.name = name
        self.loaded = False
        self.draining = False
        self._idle = True

    def load(self):
        self.loaded = True

    def drain(self):
        self.draining = True

    def is_idle(self):
        return self._idle

    def predict(self, X, names, meta=None):
        return X


def test_replica_set_add_drain_collect_cycle():
    r1, r2 = StubReplica("r1"), StubReplica("r2")
    rs = ReplicaSet([r1])
    rs.add_replica(r2)
    assert r2.loaded
    assert len(rs.members()) == 2

    drained = rs.drain_replica()
    assert drained is r2 and r2.draining  # newest drains first
    assert rs.draining_members() == [r2]
    # fleet dispatch never targets a draining replica
    assert all(rs.pick() is r1 for _ in range(5))

    r2._idle = False  # still holding work: stays attached
    assert rs.collect_drained() == []
    assert len(rs.members()) == 2
    r2._idle = True   # quiesced: two consecutive idle sweeps detach
    assert rs.collect_drained() == []   # grace sweep (first idle sighting)
    assert rs.collect_drained() == [r2]
    assert rs.members() == [r1]
    assert rs.draining_members() == []


def test_collect_grace_resets_on_late_work():
    """The dispatch-race guard: a replica that goes busy again between
    idle sightings restarts its grace — detach needs two CONSECUTIVE
    idle sweeps, so a submit landing after the first sighting can never
    be closed under."""
    r1, r2 = StubReplica("r1"), StubReplica("r2")
    rs = ReplicaSet([r1, r2])
    rs.drain_replica(r2)
    assert rs.collect_drained() == []   # idle sighting 1
    r2._idle = False                    # late-dispatched work arrives
    assert rs.collect_drained() == []   # grace reset
    r2._idle = True
    assert rs.collect_drained() == []   # idle sighting 1 (again)
    assert rs.collect_drained() == [r2]


def test_last_serving_replica_never_drains():
    r1 = StubReplica("r1")
    rs = ReplicaSet([r1])
    assert rs.drain_replica() is None
    r2 = StubReplica("r2")
    rs.add_replica(r2)
    assert rs.drain_replica() is r2
    assert rs.drain_replica() is None  # r1 is now the last serving one


def test_all_draining_fallback_still_serves():
    r1, r2 = StubReplica("r1"), StubReplica("r2")
    rs = ReplicaSet([r1, r2])
    rs.drain_replica(r1)
    rs.drain_replica(r2)  # refused: r2 is the last serving replica
    assert rs.draining_members() == [r1]
    assert rs.pick() is r2


# -------------------------------------------------- controller end-to-end
def make_loop(snapshots, *, cfg=None, clock=None, factory=None):
    """An Autoscaler over stub replicas with a synthetic snapshot feed:
    ``snapshots`` maps replica name -> scaling dict (mutate it between
    ticks to script the load curve)."""
    r1 = StubReplica("r1")
    rs = ReplicaSet([r1])
    made = []

    def default_factory():
        r = StubReplica(f"r{len(made) + 2}")
        made.append(r)
        return r

    auto = Autoscaler(
        rs,
        config=cfg or AutoscalerConfig(
            min_replicas=1, max_replicas=3, up_queue_per_slot=1.0,
            up_stable_ticks=2, down_queue_per_slot=0.25,
            down_stable_ticks=2, cooldown_s=5.0),
        replica_factory=factory or default_factory,
        clock=clock or FaultClock(),
        snapshot_fn=lambda r: dict(snapshots.get(r.name, {})),
    )
    return auto, rs, made


def test_tick_scales_up_then_drains_down_on_scripted_load():
    clock = FaultClock()
    snapshots = {"r1": {"queue_depth": 8, "total_slots": 2}}
    auto, rs, made = make_loop(snapshots, clock=clock)

    assert auto.tick().action == HOLD          # tick 1: streak building
    clock.advance(1.0)
    assert auto.tick().action == SCALE_UP      # tick 2: actuated
    assert len(rs.members()) == 2 and made[0] in rs.members()

    # load vanishes; cooldown then two calm ticks drain the new replica
    snapshots["r1"] = {"queue_depth": 0, "total_slots": 2}
    clock.advance(6.0)
    auto.tick()
    clock.advance(1.0)
    d = auto.tick()
    assert d.action == SCALE_DOWN
    assert made[0].draining  # the batcher-level drain hook fired
    assert rs.draining_members() == [made[0]]
    # two consecutive idle sweeps (the dispatch-race grace) detach it
    clock.advance(1.0)
    auto.tick()
    clock.advance(1.0)
    auto.tick()
    assert made[0] not in rs.members()
    assert len(rs.members()) == 1
    stats = auto.autoscaler_stats()
    assert stats["autoscaler_scale_ups_total"] == 1
    assert stats["autoscaler_scale_downs_total"] == 1
    assert stats["autoscaler_collected_total"] == 1


def test_draining_replica_with_work_is_not_collected():
    clock = FaultClock()
    snapshots = {"r1": {"queue_depth": 0, "total_slots": 2}}
    auto, rs, made = make_loop(snapshots, clock=clock)
    busy = StubReplica("busy")
    busy._idle = False
    rs.add_replica(busy)
    rs.drain_replica(busy)
    for _ in range(3):
        clock.advance(1.0)
        auto.tick()
    assert busy in rs.members()  # never detached while holding work
    busy._idle = True
    auto.tick()   # idle sighting 1 (grace)
    auto.tick()   # idle sighting 2: detach
    assert busy not in rs.members()


def test_run_forever_on_injected_clock_and_sleep():
    """The production loop runs entirely on the injected pair: sleeping
    advances the FaultClock, so N loop passes take zero wall time."""
    clock = FaultClock()
    snapshots = {"r1": {"queue_depth": 8, "total_slots": 2}}
    auto, rs, _ = make_loop(
        snapshots, clock=clock,
        cfg=AutoscalerConfig(
            min_replicas=1, max_replicas=2, up_queue_per_slot=1.0,
            up_stable_ticks=2, cooldown_s=5.0))
    passes = []

    def sleep(s):
        clock.advance(s)
        passes.append(s)
        if len(passes) >= 4:
            auto.stop()

    auto.run_forever(sleep=sleep)
    assert len(passes) == 4
    assert len(rs.members()) == 2  # the scripted spike scaled it up
    assert auto.autoscaler_stats()["autoscaler_ticks_total"] == 4


def test_rebalance_actuator_reaches_the_batcher():
    class FakeBatcher:
        def __init__(self):
            self._remote = object()
            self.calls = []

        def rebalance_disagg(self, n):
            self.calls.append(n)
            return True

    class FakeSvc:
        def __init__(self):
            self.batcher = FakeBatcher()

    r1 = StubReplica("r1")
    r1._batcher_service = FakeSvc()
    rs = ReplicaSet([r1])
    auto = Autoscaler(
        rs,
        config=AutoscalerConfig(
            rebalance=True, rebalance_backlog_high=1.0,
            rebalance_stable_ticks=1, rebalance_cooldown_s=0.0,
            up_queue_per_slot=1e9),
        clock=FaultClock(),
        snapshot_fn=lambda r: {"handoff_queue_depth": 4,
                               "prefill_devices": 2, "decode_devices": 6},
    )
    auto.tick()
    assert r1._batcher_service.batcher.calls == [3]
    assert auto.autoscaler_stats()["autoscaler_rebalances_total"] == 1


# =====================================================================
# The ISSUE 14 headline: deterministic load-spike scenario on real LLM
# replicas — spike -> scale-up -> fault-injected canary -> rollback ->
# quiesce -> scale-down — with zero dropped or failed client requests
# and zero time.sleep anywhere.
# =====================================================================
KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)


def tiny_server(**extra):
    from seldon_core_tpu.servers.llmserver import LLMServer

    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1,),
                temperature=0.0, eos_id=-1, seed=3, continuous_batching=2,
                kv_page_size=8)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


def test_load_spike_scale_up_canary_rollback_scale_down():
    from seldon_core_tpu.analytics.canary import ROLLED_BACK, CanaryRouter
    from seldon_core_tpu.contracts.graph import PredictorSpec
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.observability.timeline import scaling_snapshot
    from seldon_core_tpu.runtime.batcher import get_batcher_service
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.runtime.resilience import ResilienceConfig
    from seldon_core_tpu.testing.faults import (FaultSchedule,
                                                FaultyComponent)
    from tests.test_canary import Echo

    clock = FaultClock()
    s1 = tiny_server()
    svc1 = get_batcher_service(s1)
    rs = ReplicaSet([s1])
    auto = Autoscaler(
        rs,
        config=AutoscalerConfig(
            min_replicas=1, max_replicas=2, up_queue_per_slot=1.0,
            up_stable_ticks=2, down_queue_per_slot=0.6,
            down_stable_ticks=2, cooldown_s=5.0),
        replica_factory=tiny_server,
        clock=clock,
        snapshot_fn=scaling_snapshot,
    )

    # --- phase 1: synthetic spike -> scale-up -------------------------
    # 8 one-slot-pair generations of 16 tokens each: hundreds of compiled
    # decode dispatches stand between submission and an empty queue, so
    # the controller's first ticks observe real queue pressure — no sleep
    # needed to "catch" the spike.
    prompts = [[5, 9, 17], [40, 3, 22], [7, 7], [60, 61, 62],
               [1, 2, 3], [9], [33, 44], [8, 8, 8]]
    futs = [svc1.submit_stream(p, max_new_tokens=16) for p in prompts]
    # submit_stream schedules onto the batcher's loop thread; wait (a
    # bounded state poll, not a timed sleep) until the spike is REGISTERED
    # — then the queue stays pressured for hundreds of compiled decode
    # dispatches, so the controller's instant ticks observe it reliably
    for _ in range(2_000_000):
        snap = scaling_snapshot(s1)
        if snap["queue_depth"] + snap["active_slots"] >= 4:
            break
    else:
        raise AssertionError("spike never reached the batcher queue")
    scaled = False
    for _ in range(4):
        d = auto.tick()
        clock.advance(1.0)
        if d.action == SCALE_UP:
            scaled = True
            break
    assert scaled, "a queued spike must scale the fleet up"
    assert len(rs.members()) == 2
    results = [f.result(timeout=120) for f in futs]
    assert all(len(r) == 16 for r in results)  # zero dropped by scale-up

    # --- phase 2: fault-injected canary -> automatic rollback ---------
    router = CanaryRouter(fraction=0.25, min_samples=4, eval_every=4)
    slow = FaultyComponent(FaultSchedule.always_ok(latency_s=0.5),
                           clock=clock)
    graph = {"name": "cr", "type": "ROUTER", "children": [
        {"name": "base", "type": "MODEL"},
        {"name": "cand", "type": "MODEL"}]}
    engine = GraphEngine(
        PredictorSpec.from_dict({"name": "p", "graph": graph}),
        components={"cr": router, "base": Echo(), "cand": slow},
        resilience=ResilienceConfig(clock=clock))
    req = SeldonMessage.from_dict(
        {"data": {"tensor": {"shape": [1, 1], "values": [1.0]}}})
    served = 0
    for _ in range(40):
        out = asyncio.run(engine.predict(req))
        assert out.data is not None
        served += 1
        if router.phase == ROLLED_BACK:
            break
    assert router.phase == ROLLED_BACK
    for _ in range(8):  # post-rollback traffic: all baseline, all served
        out = asyncio.run(engine.predict(req))
        assert out.meta.routing["cr"] == 0
        served += 1
    assert served >= 12  # zero failed requests attributable to rollback

    # --- phase 3: quiesce -> scale-down drains without dropping -------
    s2 = rs.members()[1]
    svc2 = get_batcher_service(s2)
    # one request lands on the replica about to drain: the drain must let
    # it finish, and detach only after
    straggler = svc2.submit_stream([11, 12, 13], max_new_tokens=16)
    clock.advance(6.0)  # cooldown from the scale-up
    drained = None
    for _ in range(6):
        d = auto.tick()
        clock.advance(1.0)
        if d.action == SCALE_DOWN:
            drained = rs.draining_members()[0]
            break
    assert drained is s2, "the newest replica drains first"
    assert svc2.batcher.draining
    toks = straggler.result(timeout=120)
    assert len(toks) == 16  # the in-flight request survived the drain
    for _ in range(4):
        auto.tick()
        clock.advance(1.0)
        if len(rs.members()) == 1:
            break
    assert rs.members() == [s1]  # drained replica detached once idle
    stats = auto.autoscaler_stats()
    assert stats["autoscaler_scale_ups_total"] == 1
    assert stats["autoscaler_scale_downs_total"] == 1
    assert stats["autoscaler_collected_total"] == 1
    svc1.close()


# =====================================================================
# Disagg rebalance: the split moves, generation stays bit-exact
# =====================================================================
def disagg_server(**extra):
    from seldon_core_tpu.servers.llmserver import LLMServer

    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3,
                disaggregation="remote_prefill", prefill_devices=2)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33], [7],
           [60, 61, 62, 63, 64, 65]]


def test_rebalance_moves_split_and_generation_stays_bit_exact():
    """The ISSUE 14 disagg acceptance bar: shifting the prompt mix moves
    the prefill:decode device split (here actuated directly, decision
    covered above), requests staged on the OUTGOING pool still deliver
    through the shared TransferQueue, and every token matches the
    single-slice baseline — before, across, and after the rebalance."""
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    s = disagg_server()
    kw = dict(max_slots=3, max_len=40, len_buckets=(8,), page_size=8)

    async def baseline():
        b = ContinuousBatcher(s, disaggregation="off", **kw)
        outs = await asyncio.gather(
            *[b.submit(p, max_new_tokens=8) for p in PROMPTS + PROMPTS])
        await b.close()
        return outs

    async def rebalanced():
        b = ContinuousBatcher(s, **kw)
        assert len(b.disagg_mesh.prefill_devices) == 2
        # first wave staged, THEN the split moves: jobs on the outgoing
        # pool drain into the shared queue during the swap
        first = [asyncio.ensure_future(b.submit(p, max_new_tokens=8))
                 for p in PROMPTS]
        assert b.rebalance_disagg(3)
        assert len(b.disagg_mesh.prefill_devices) == 3
        out1 = await asyncio.gather(*first)
        second = await asyncio.gather(
            *[b.submit(p, max_new_tokens=8) for p in PROMPTS])
        stats = b.handoff_stats()
        await b.close()
        return out1 + second, stats

    base = asyncio.run(baseline())
    moved, stats = asyncio.run(rebalanced())
    assert moved == base  # bit-exact across the rebalance
    assert stats["handoffs_total"] == 2 * len(PROMPTS)
    assert stats["handoff_queue_depth"] == 0


def test_rebalance_rejects_infeasible_splits():
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    s = disagg_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=40, len_buckets=(8,),
                              page_size=8)
        assert not b.rebalance_disagg(0)    # no prefill slice
        assert not b.rebalance_disagg(2)    # already there
        assert not b.rebalance_disagg(8)    # no decode devices left
        assert len(b.disagg_mesh.prefill_devices) == 2
        await b.close()

    asyncio.run(go())

    # non-disagg batchers refuse outright
    s2 = tiny_server()

    async def off():
        b = ContinuousBatcher(s2, max_slots=1, max_len=40, len_buckets=(8,))
        assert not b.rebalance_disagg(2)
        await b.close()

    asyncio.run(off())


# ------------------------------------------------------------- metrics
def test_sync_controlplane_exposes_loop_series():
    """The control loop's own observability: autoscaler tallies, canary
    phase/rollbacks and shadow divergence all land in /metrics through
    sync_controlplane (names enforced round-trip by graftlint's
    metrics-drift checker)."""
    from seldon_core_tpu.analytics.canary import CanaryRouter, ShadowNode
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from tests.test_canary import Doubler, Echo

    clock = FaultClock()
    snapshots = {"r1": {"queue_depth": 8, "total_slots": 2}}
    auto, rs, _ = make_loop(snapshots, clock=clock)
    auto.tick()
    clock.advance(1.0)
    auto.tick()  # second hot tick scales up

    reg = MetricsRegistry(deployment="d", predictor="p")
    reg.sync_controlplane(auto)
    router = CanaryRouter(fraction=0.5, min_samples=1000)
    router.name = "cr"
    router.rollback("test")
    reg.sync_controlplane(router)
    shadow = ShadowNode(Echo(), Doubler(), mirror_fraction=1.0,
                        clock=FaultClock())
    shadow.name = "sh"
    shadow.predict(np.array([[1.0]]), ["a"])
    reg.sync_controlplane(shadow)
    reg.sync_controlplane(None)  # no-op, never raises

    text = reg.expose().decode()
    assert 'seldon_autoscaler_replicas{deployment_name="d"' in text
    assert 'seldon_autoscaler_scale_events_total{action="scale_up"' in text
    assert 'seldon_canary_phase{' in text and 'node="cr"' in text
    assert 'seldon_canary_rollbacks_total{' in text
    assert 'seldon_shadow_divergences_total{' in text
    # counter catch-up is idempotent across scrapes
    reg.sync_controlplane(auto)
    assert ('seldon_autoscaler_scale_events_total{action="scale_up",'
            in reg.expose().decode().replace(
                'deployment_name="d",predictor_name="p",', ''))


def test_service_level_inflight_closes_the_drain_blind_window():
    """Review regression (the headline test's flake): a request handed to
    BatcherService via run_coroutine_threadsafe exists in NO batcher
    structure until the loop thread runs the submit coroutine — is_idle()
    must count it from the instant submit_stream returns, or
    collect_drained could close a batcher holding a live request."""
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    s = tiny_server()
    svc = get_batcher_service(s)
    assert svc.is_idle()
    fut = svc.submit_stream([5, 9, 17], max_new_tokens=8)
    # no sleep, no loop-thread handshake: the service-level counter makes
    # the request visible IMMEDIATELY
    assert not svc.is_idle()
    assert len(fut.result(timeout=120)) == 8
    # settled future -> the counter drains; the batcher quiesces shortly
    # after (bounded state poll, not a timed sleep)
    for _ in range(2_000_000):
        if svc.is_idle():
            break
    assert svc.is_idle()
    assert svc.submitted == 1
    svc.close()


def test_scale_up_mid_drain_resumes_the_warm_replica():
    """Review regression: a spike returning before a drain finishes must
    CANCEL the drain (warm replica, hot caches) instead of cold-building
    a new one through the factory."""
    r1, r2 = StubReplica("r1"), StubReplica("r2")
    r2.resumed = False
    r2.resume = lambda: setattr(r2, "resumed", True)
    rs = ReplicaSet([r1, r2])
    rs.drain_replica(r2)
    assert rs.draining_members() == [r2]

    built = []
    auto = Autoscaler(
        rs,
        config=AutoscalerConfig(min_replicas=1, max_replicas=3,
                                up_queue_per_slot=1.0, up_stable_ticks=1,
                                cooldown_s=0.0),
        replica_factory=lambda: built.append(StubReplica("cold")) or built[-1],
        clock=FaultClock(),
        snapshot_fn=lambda r: {"queue_depth": 8, "total_slots": 2},
    )
    auto.tick()
    assert rs.draining_members() == []      # drain cancelled
    assert r2.resumed                       # batcher-level resume fired
    assert built == []                      # no cold replica built
    assert r2 in rs.members() and len(rs.members()) == 2
    # the next over tick, with nobody draining, builds cold as before
    auto.tick()
    assert len(built) == 1 and built[0] in rs.members()


def test_scale_tallies_count_applied_actions_not_decisions():
    """Review regression: an unactuatable decision (no factory) must not
    tick the scale-event counters while the fleet never moves — the
    metric's help string says 'actions applied'."""
    auto = Autoscaler(
        ReplicaSet([StubReplica("r1")]),
        config=AutoscalerConfig(min_replicas=1, max_replicas=3,
                                up_queue_per_slot=1.0, up_stable_ticks=1,
                                cooldown_s=0.0),
        replica_factory=None,  # scale-up decided but unactuatable
        clock=FaultClock(),
        snapshot_fn=lambda r: {"queue_depth": 8, "total_slots": 2},
    )
    for _ in range(3):
        assert auto.tick().action == SCALE_UP  # decided every tick...
    stats = auto.autoscaler_stats()
    assert stats["autoscaler_scale_ups_total"] == 0  # ...applied never
    assert stats["autoscaler_replicas"] == 1


def test_concurrent_collect_sweeps_cannot_collapse_the_grace():
    """Review regression: overlapping collect sweeps must not count as
    two consecutive idle sightings (which would detach with zero real
    grace) — a sweep in progress makes concurrent callers no-ops."""
    import threading

    r1, r2 = StubReplica("r1"), StubReplica("r2")
    rs = ReplicaSet([r1, r2])
    rs.drain_replica(r2)

    entered = threading.Event()
    release = threading.Event()
    real_idle = r2.is_idle

    def gated_idle():
        entered.set()
        release.wait(10)
        return real_idle()

    r2.is_idle = gated_idle
    results = {}
    t = threading.Thread(
        target=lambda: results.setdefault("first", rs.collect_drained()))
    t.start()
    entered.wait(10)                      # sweep 1 is mid-flight
    assert rs.collect_drained() == []     # concurrent sweep: no-op
    release.set()
    t.join(10)
    assert results["first"] == []         # sweep 1 was the grace sighting
    r2.is_idle = real_idle
    assert rs.collect_drained() == [r2]   # second REAL sweep detaches
