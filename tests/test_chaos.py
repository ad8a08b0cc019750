"""Fleet fault tolerance chaos harness (ISSUE 16 tentpole proof).

Headline: a streaming batch spread across a 3-replica fleet; deterministic
chaos injection kills the busiest replica's batcher loop mid-decode; every
client still receives the BIT-EXACT token sequence of an unfaulted run
(greedy and seeded sampling), with zero duplicate
tokens, the corpse ejected from dispatch, the recovery visible in the
fleet metrics, and the autoscaler replacing the dead replica on its next
tick. Everything is event-driven — zero ``time.sleep`` in this file: kills
trigger on delivered-token events (testing/faults.py BatcherKiller) and
breaker/probe windows elapse on a FaultClock.

The stub-service tests underneath pin the recovery protocol itself
(journal, ResumeMarker placement, at-most-once, retry budget, ejection by
consecutive dispatch failures) without jax, so they run in milliseconds
and fail with exact diffs when the protocol drifts.
"""

from __future__ import annotations

import asyncio
import types

import pytest

from seldon_core_tpu.contracts.payload import SeldonError
from seldon_core_tpu.runtime.batcher import (
    ContinuousBatcher,
    ensure_stream_service,
)
from seldon_core_tpu.runtime.engine import ReplicaSet
from seldon_core_tpu.runtime.resilience import (
    ResumeMarker,
    RetryBudget,
    ShedError,
)
from seldon_core_tpu.servers.llmserver import LLMServer
from seldon_core_tpu.testing.faults import (
    BatcherKiller,
    DispatchFailer,
    FaultClock,
    FaultSchedule,
    HandoffPoisoner,
)

pytestmark = pytest.mark.leakcheck  # conftest leak canary (ISSUE 19)

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)


def make_server(**extra) -> LLMServer:
    # len bucket 48 leaves room for RESUMED prompts (original prompt +
    # the generated prefix re-admitted after a kill)
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=24, len_buckets=(16, 48), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3,
                continuous_batching=3, continuous_batching_max_len=64)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


def _close_fleet(fleet):
    for r in fleet.members():
        svc = getattr(r, "_batcher_service", None)
        if svc is not None:
            try:
                svc.close()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# headline: kill the busiest replica mid-decode, streams stay bit-exact
# ---------------------------------------------------------------------------

PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13],
           [7], [60, 61, 62, 63, 64, 65], [1, 2, 3, 4, 5]]
SEEDS = [101, 102, 103, 104, 105]
N_NEW = 24


class _CountingFactory:
    """Autoscaler replacement factory: hands out inert warm stubs and
    counts them (the replace SIGNAL is under test, not server builds)."""

    def __init__(self):
        self.built = 0

    def __call__(self):
        self.built += 1

        class _Stub:
            def load(self):
                pass

        return _Stub()


# tier-1 870s budget: one rep — seeded, the densest cell (page
# accounting + rng-chain resume in one run); greedy rides CI's
# pinned unfiltered chaos step
@pytest.mark.parametrize("temperature", [
    pytest.param(0.0, marks=pytest.mark.slow),
    0.8,
], ids=["greedy", "seeded"])
def test_kill_busiest_replica_mid_decode_streams_stay_bit_exact(temperature):
    extra = dict(temperature=temperature, kv_page_size=8)
    if temperature > 0:
        extra.update(top_k=20)
    reps = [make_server(**extra) for _ in range(3)]

    # the unfaulted truth, per request: batched continuous serving is
    # bit-exact against generate() by the repo's standing invariant, so
    # solo generate() IS the unfaulted fleet run
    expected = [reps[0].generate([p], max_new_tokens=N_NEW,
                                 seed=SEEDS[i])["tokens"][0]
                for i, p in enumerate(PROMPTS)]

    fleet = ReplicaSet(reps)
    # no half-open probes mid-test: the corpse must stay quarantined so
    # the ejection/replace assertions are deterministic (reinstatement
    # has its own FaultClock-driven test below)
    fleet.reinstate_after_s = 3600.0
    # worst case every job lands on the victim: 5 recoveries, while the
    # default budget (0.2 x 5 + 3) grants 4 — exhaustion is a separate
    # test, not noise in this one
    fleet.retry_budget = RetryBudget(ratio=1.0, min_retries=16)

    streams = [[] for _ in PROMPTS]
    markers = [[] for _ in PROMPTS]

    def mk_on_token(i):
        def cb(tok):
            if tok is None:
                return
            if isinstance(tok, ResumeMarker):
                markers[i].append(tok)
                return
            streams[i].append(int(tok))
        return cb

    # the kill point is a PREDICATE evaluated inside the batcher loops'
    # own turns, not a wall-clock guess from the test thread (this tiny
    # model can finish a whole batch between two Python statements): the
    # killer arms once every client is mid-stream (>= 2 tokens), at which
    # moment the most recently armed stream still owes ~22 tokens — so
    # the busiest loop is provably alive to take the bullet on its very
    # next turn
    batchers = [ensure_stream_service(r).batcher for r in reps]
    killer = BatcherKiller(
        trigger=lambda b: all(len(s) >= 2 for s in streams),
        busiest=True).install(*batchers)

    futs = [fleet.submit_stream(p, N_NEW, seed=SEEDS[i],
                                on_token=mk_on_token(i))
            for i, p in enumerate(PROMPTS)]
    outs = [f.result(timeout=300) for f in futs]
    try:
        assert killer.kills == 1 and killer.killed is not None
        victim = reps[batchers.index(killer.killed)]

        # every client: the bit-exact unfaulted sequence, streamed AND
        # returned, no duplicates, no holes
        for i in range(len(PROMPTS)):
            assert outs[i] == expected[i], f"request {i} diverged"
            assert streams[i] == expected[i], f"stream {i} diverged"
            assert len(streams[i]) == N_NEW

        # the corpse left dispatch and stayed out (probe window is huge)
        assert victim in fleet.ejected_members()
        assert victim not in fleet._dispatchable()

        # recovery is visible: at least one mid-stream resume happened,
        # each announced to its client exactly once via ResumeMarker
        n_markers = sum(len(m) for m in markers)
        stats = fleet.llm_stats()
        assert stats["fleet_ejections_total"] == 1
        assert stats["fleet_resumes_total"] >= 1
        assert stats["fleet_resumes_total"] == n_markers
        assert stats["fleet_resumed_tokens_total"] == sum(
            m.tokens_delivered for ms in markers for m in ms)
        assert stats["fleet_resume_journal_depth"] == 0  # all settled
        assert stats["fleet_retry_budget_exhausted_total"] == 0

        # the counters flow llm_stats -> sync_llm -> /metrics
        from seldon_core_tpu.metrics.registry import MetricsRegistry

        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(fleet)
        text = reg.expose().decode()
        for name in ("seldon_fleet_ejections_total",
                     "seldon_fleet_resumes_total",
                     "seldon_fleet_resumed_tokens_total",
                     "seldon_fleet_reinstatements_total",
                     "seldon_fleet_retry_budget_exhausted_total",
                     "seldon_fleet_resume_journal_depth"):
            assert name in text, name

        # the autoscaler reads the ejection as a replace signal on its
        # very next tick (no stability window)
        from seldon_core_tpu.controlplane.autoscaler import (
            SCALE_UP, Autoscaler, AutoscalerConfig)

        factory = _CountingFactory()
        auto = Autoscaler(
            fleet,
            config=AutoscalerConfig(min_replicas=1, max_replicas=4,
                                    up_stable_ticks=99, cooldown_s=0.0),
            replica_factory=factory)
        sigs = auto.signals()
        assert sum(1 for s in sigs if s.ejected) == 1
        decision = auto.tick()
        assert decision.action == SCALE_UP
        assert "ejected" in decision.reason
        assert factory.built == 1
        assert len(fleet.members()) == 4  # corpse + 2 survivors + spare
    finally:
        _close_fleet(fleet)


# ---------------------------------------------------------------------------
# reinstatement: half-open probe on the FaultClock, zero sleeps
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered chaos
# step (half-open breaker mechanics also stay tier-1 via the resilience
# suite's clock-driven breaker tests)
def test_ejected_replica_reinstates_through_halfopen_probe():
    """Kill one of two replicas; it is ejected and traffic fails over.
    Advance the FaultClock past the probe window: the next dispatch
    probes the corpse, whose restarted batcher loop answers — the fleet
    reinstates it and counts the reinstatement."""
    r1, r2 = make_server(max_new_tokens=6), make_server(max_new_tokens=6)
    clk = FaultClock()
    fleet = ReplicaSet([r1, r2])
    fleet.clock = clk
    fleet.heartbeat_timeout_s = 0  # batcher heartbeats are wall-clock;
    # death detection here rides the crashed flag alone
    fleet.retry_budget = RetryBudget(clock=clk)

    expected = r1.generate([[5, 9, 17]], max_new_tokens=6)["tokens"][0]
    killer = BatcherKiller().install(
        ensure_stream_service(r1).batcher)  # fires on r1's first turn
    try:
        out = fleet.submit_sync([5, 9, 17], 6)
        assert out == expected  # pre-first-token failover to r2
        assert killer.kills == 1
        assert r1 in fleet.ejected_members()
        assert fleet.llm_stats()["fleet_ejections_total"] == 1

        # inside the quarantine window nothing probes the corpse
        out = fleet.submit_sync([5, 9, 17], 6)
        assert out == expected and r1 in fleet.ejected_members()

        clk.advance(fleet.reinstate_after_s + 0.1)
        # the probe dispatch restarts the dead loop (the killer is
        # one-shot and disarmed), serves bit-exact, and reinstates
        out = fleet.submit_sync([5, 9, 17], 6)
        assert out == expected
        assert fleet.ejected_members() == []
        stats = fleet.llm_stats()
        assert stats["fleet_reinstatements_total"] == 1
    finally:
        _close_fleet(fleet)


# ---------------------------------------------------------------------------
# poisoned handoff (ISSUE 16 satellite): one bad handoff must fail ONE
# request, never the batch. Pre-fix, the import exception propagated
# through _consume_handoffs into the batcher loop: the crash handler
# failed EVERY in-flight request and the replica read as dead — this test
# failed on that shape before the containment landed in runtime/batcher.py.
# ---------------------------------------------------------------------------

def test_poisoned_handoff_fails_one_request_not_the_batch():
    s = make_server(disaggregation="remote_prefill", prefill_devices=2,
                    max_new_tokens=4)
    expected = s.generate([[5, 9, 17]], max_new_tokens=4)["tokens"][0]

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=32, len_buckets=(8,),
                              page_size=8, disaggregation="remote_prefill")
        HandoffPoisoner(b, first_n=1)
        with pytest.raises(Exception):
            await b.submit([40, 3, 22, 8], max_new_tokens=4)
        # the batch survived: the loop never crashed, pages came back,
        # and the NEXT request serves bit-exact
        assert b.crashed is None
        ok = await b.submit([5, 9, 17], max_new_tokens=4)
        pages_ok = b.page_stats()["kv_pages_in_use"] == 0
        await b.close()
        return ok, pages_ok

    ok, pages_ok = asyncio.run(go())
    assert ok == expected
    assert pages_ok


# ---------------------------------------------------------------------------
# protocol-level tests on scripted stub services (no jax, milliseconds)
# ---------------------------------------------------------------------------

class _StubBatcher:
    def __init__(self):
        self._pending = []
        self._slots = []
        # replica_load's page-pressure read: an empty pool
        self._allocator = types.SimpleNamespace(stats=lambda: (0, 0, 0))
        self.crashed = None
        self._task = None
        self.heartbeat = 0.0

    def accommodates(self, prompt, max_new_tokens=None):
        return True


class _ScriptedService:
    """A BatcherService double whose submit_sync runs a per-call script:
    ``script(i, prompt, max_new, on_token, seed, resume_tokens)`` returns
    the token list or raises. Records every call."""

    def __init__(self, script):
        self.script = script
        self.batcher = _StubBatcher()
        self.calls = []

    def submit_sync(self, prompt, max_new_tokens=None, timeout_s=600.0,
                    info=None, seed=None, trace=None, tenant=None,
                    slo_class=None, adapter=None, deadline_s=None,
                    on_token=None, resume_tokens=0):
        i = len(self.calls)
        self.calls.append(dict(prompt=list(prompt), max_new=max_new_tokens,
                               seed=seed, resume_tokens=resume_tokens))
        return self.script(i, list(prompt), max_new_tokens, on_token,
                           seed, resume_tokens)


class _StubReplica:
    def __init__(self, script):
        self._batcher_service = _ScriptedService(script)

    @property
    def svc(self):
        return self._batcher_service


FULL = [10, 11, 12, 13, 14, 15, 16, 17]
PROMPT = [1, 2, 3]


def _dying_replica(n_tokens):
    """A replica that streams ``n_tokens`` of FULL then dies like a
    crashed batcher: every in-flight on_token gets the terminal None from
    the crash handler, the crashed flag goes up, the dispatch raises."""
    holder = {}

    def script(i, prompt, max_new, on_token, seed, resume_tokens):
        for t in FULL[:n_tokens]:
            on_token(t)
        holder["r"].svc.batcher.crashed = RuntimeError("loop died")
        if on_token is not None:
            on_token(None)  # the crash handler's unblock, pre-terminal
        raise SeldonError("batcher loop died", status_code=503,
                          reason="INJECTED_FAULT")

    holder["r"] = _StubReplica(script)
    return holder["r"]


def _resuming_replica(expect_resume):
    def script(i, prompt, max_new, on_token, seed, resume_tokens):
        assert resume_tokens == expect_resume
        assert prompt == PROMPT + FULL[:expect_resume]
        assert max_new == len(FULL) - expect_resume
        out = FULL[expect_resume:]
        for t in out:
            on_token(t)
        return out

    return _StubReplica(script)


def test_mid_stream_resume_is_bit_exact_and_at_most_once():
    """The recovery contract, end to end: tokens journaled before
    delivery, the survivor re-admitted with prompt+prefix and the right
    rng fast-forward count, exactly one ResumeMarker at the seam, no
    token delivered twice, exactly one terminal None (the fleet's)."""
    a, b = _dying_replica(3), _resuming_replica(3)
    fleet = ReplicaSet([a, b])
    stream = []
    out = fleet.submit_sync(PROMPT, len(FULL), seed=77,
                            on_token=stream.append)
    assert out == FULL
    # stream shape: 3 tokens, the seam marker, 5 tokens, terminal None —
    # the dead replica's crash-handler None was swallowed by the fleet
    assert stream[:3] == FULL[:3]
    assert isinstance(stream[3], ResumeMarker)
    assert stream[3].tokens_delivered == 3
    assert stream[4:9] == FULL[3:]
    assert stream[9] is None and len(stream) == 10
    assert a.svc.calls[0]["resume_tokens"] == 0
    assert b.svc.calls[0]["resume_tokens"] == 3
    assert b.svc.calls[0]["seed"] == 77  # the SAME pinned chain
    assert a._batcher_service is not None
    assert fleet._resumes_total == 1
    assert fleet._resumed_tokens_total == 3
    assert fleet.retry_budget.snapshot()["retries_in_window"] == 1
    assert a in fleet.ejected_members()  # crashed flag -> ejected


def test_nonstreaming_caller_never_observes_the_failure():
    a, b = _dying_replica(2), _resuming_replica(2)
    fleet = ReplicaSet([a, b])
    assert fleet.submit_sync(PROMPT, len(FULL), seed=5) == FULL


def test_unseeded_request_gets_a_pinned_resumable_seed():
    a, b = _dying_replica(4), _StubReplica(None)

    def script(i, prompt, max_new, on_token, seed, resume_tokens):
        assert resume_tokens == 4 and seed is not None
        out = FULL[4:]
        for t in out:
            on_token(t)
        return out

    b._batcher_service.script = script
    fleet = ReplicaSet([a, b])
    out = fleet.submit_sync(PROMPT, len(FULL))  # no seed from the caller
    assert out == FULL
    # both dispatches saw the SAME fleet-pinned seed
    assert a.svc.calls[0]["seed"] == b.svc.calls[0]["seed"] is not None


def test_retry_budget_exhaustion_sheds_503_with_retry_after():
    """Correlated-failure storms shed honestly (ISSUE 16 acceptance):
    with the budget dry, a recovery is refused with 503 + Retry-After
    and the sibling is never loaded with the retry."""
    a, b = _dying_replica(2), _resuming_replica(2)
    fleet = ReplicaSet([a, b])
    fleet.retry_budget = RetryBudget(ratio=0.0, min_retries=0)
    with pytest.raises(ShedError) as e:
        fleet.submit_sync(PROMPT, len(FULL), seed=9)
    assert e.value.status_code == 503
    assert e.value.retry_after_s == fleet.reinstate_after_s
    assert "retry budget" in str(e.value)
    assert b.svc.calls == []  # the storm was not amplified
    assert fleet._resumes_total == 0
    assert fleet.retry_budget.snapshot()["exhausted_total"] == 1
    assert fleet.llm_stats() == {}  # stubs carry no llm_stats


def test_consecutive_dispatch_failures_eject_through_the_breaker():
    """No crash flag, no heartbeat staleness — just a replica whose
    dispatches keep failing (testing/faults.py DispatchFailer): three
    consecutive infrastructure failures open its breaker and quarantine
    it; traffic converges on the sibling."""
    ok_tokens = [5, 6]

    def serve(i, prompt, max_new, on_token, seed, resume_tokens):
        return list(ok_tokens)

    a, b = _StubReplica(serve), _StubReplica(serve)
    failer = DispatchFailer(a.svc, FaultSchedule.always_fail())
    fleet = ReplicaSet([a, b])
    out = fleet.submit_sync(PROMPT, 2, seed=1)
    assert out == ok_tokens
    assert failer.calls == 3  # threshold dispatches, then quarantine
    assert a in fleet.ejected_members()
    assert fleet._ejections_total == 1
    assert b.svc.calls and b.svc.calls[0]["resume_tokens"] == 0


def test_nonrecoverable_errors_pass_through_without_failover():
    """Backpressure and client errors are the caller's to see: a shed
    from a loaded replica must NOT eject it or retry elsewhere."""
    def shedding(i, prompt, max_new, on_token, seed, resume_tokens):
        raise ShedError("queue full", retry_after_s=2.0)

    def never(i, prompt, max_new, on_token, seed, resume_tokens):
        raise AssertionError("sibling must not be tried")

    a, b = _StubReplica(shedding), _StubReplica(never)
    fleet = ReplicaSet([a, b])
    with pytest.raises(ShedError) as e:
        fleet.submit_sync(PROMPT, 4, seed=1)
    assert e.value.retry_after_s == 2.0  # the replica's OWN hint
    assert fleet.ejected_members() == []
    assert b.svc.calls == []


def test_mid_stream_failure_without_token_journal_is_honest():
    """A string prompt no replica can tokenize has no token-granular
    journal; once tokens flowed, recovery would risk duplicates — the
    fleet raises instead of guessing."""
    def die_mid(i, prompt, max_new, on_token, seed, resume_tokens):
        on_token(99)
        raise SeldonError("died", status_code=503)

    a, b = _StubReplica(die_mid), _StubReplica(die_mid)
    fleet = ReplicaSet([a, b])
    with pytest.raises(SeldonError):
        fleet.submit_sync("untokenizable prompt", 4, seed=1,
                          on_token=lambda t: None)
    assert len(a.svc.calls) + len(b.svc.calls) == 1  # no blind retry


# ---------------------------------------------------------------------------
# pre-first-token generate() failover (ISSUE 16 satellite)
# ---------------------------------------------------------------------------

class _GenReplica:
    def __init__(self, fail_with=None):
        self.fail_with = fail_with
        self.calls = 0

    def load(self):
        pass

    def generate(self, prompts, *a, **kw):
        self.calls += 1
        if self.fail_with is not None:
            raise self.fail_with
        return {"tokens": [[1, 2, 3]]}


def test_generate_fails_over_once_pre_first_token():
    bad, good = _GenReplica(RuntimeError("device wedged")), _GenReplica()
    fleet = ReplicaSet([bad, good])
    out = fleet.generate([[7, 8]], max_new_tokens=3)
    assert out["tokens"] == [[1, 2, 3]]
    assert bad.calls == 1 and good.calls == 1  # exactly one failover
    assert fleet.retry_budget.snapshot()["retries_in_window"] == 1


def test_generate_failover_draws_from_the_budget():
    bad, good = _GenReplica(RuntimeError("device wedged")), _GenReplica()
    fleet = ReplicaSet([bad, good])
    fleet.retry_budget = RetryBudget(ratio=0.0, min_retries=0)
    with pytest.raises(ShedError) as e:
        fleet.generate([[7, 8]], max_new_tokens=3)
    assert e.value.status_code == 503 and e.value.retry_after_s > 0
    assert good.calls == 0  # refused, not amplified


def test_generate_client_errors_do_not_fail_over():
    bad, good = _GenReplica(ValueError("bad prompt")), _GenReplica()
    fleet = ReplicaSet([bad, good])
    with pytest.raises(ValueError):
        fleet.generate([[7, 8]], max_new_tokens=3)
    assert good.calls == 0
