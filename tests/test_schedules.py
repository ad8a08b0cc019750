"""Deterministic-interleaving tests: the dynamic half of racelint.

Every static race finding from the PR 6 burn-down ships with either a
replayable failing schedule here (bug reconstructed -> schedule found ->
fix proven) or a reasoned waiver in the lint layer. The harness
(seldon_core_tpu/testing/schedules.py) runs REAL classes — the fixed
AdmissionController / CircuitBreaker below are the production objects,
not doubles; only the PRE-fix variants are reconstructions (the same
idiom tests/test_graftlint.py uses for its historical bugs).

Tier-1 and jax-free: the resilience state machines are pure Python.
"""

from __future__ import annotations

import sys
import threading

import pytest

from seldon_core_tpu.runtime.resilience import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    AdmissionController,
    CircuitBreaker,
    ShedError,
)
from seldon_core_tpu.testing.faults import FaultClock
from seldon_core_tpu.testing.schedules import (
    DeterministicScheduler,
    ScheduleDivergence,
    find_race,
    run_schedule,
)

pytestmark = pytest.mark.faults  # CI's must-run resilience tier

STALL = 0.03  # tests stage small scenarios; fast stall detection keeps
              # lock-heavy exploration cheap


# ---------------------------------------------------------------------------
# harness mechanics
# ---------------------------------------------------------------------------


class _Counter:
    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1


def _two_bumps(sched):
    c = _Counter()
    sched.spawn(c.bump, name="a")
    sched.spawn(c.bump, name="b")
    return c


@pytest.mark.xfail(
    sys.version_info[:2] == (3, 12), strict=False,
    reason="CPython 3.12 instruments per-opcode trace events at "
           "sys.settrace() time and only once some frame has set "
           "f_trace_opcodes; the harness sets it from inside its trace "
           "function, so the FIRST opcode-granularity run of a process "
           "degrades to line granularity and cannot find this race (every "
           "later run can — test_replay_is_deterministic below). Arming "
           "before the first settrace was tried in PR 21 and segfaults "
           "3.12.12 once the deadlock test has left its threads parked, "
           "so the harness is left as it is. Must RUN, not skip: it is "
           "what arms the interpreter for the tests after it.")
def test_opcode_exploration_finds_lost_update():
    """x += 1 from two threads: line-level preemption cannot interleave
    inside the statement, opcode-level must."""
    bad = find_race(_two_bumps, lambda c: c.n == 2,
                    granularity="opcode", max_schedules=100, stall_s=STALL)
    assert bad is not None
    shared, rec, _ = run_schedule(_two_bumps, schedule=bad.to_list(),
                                  granularity="opcode", stall_s=STALL)
    assert shared.n == 1  # the lost update, replayed


def test_replay_is_deterministic():
    bad = find_race(_two_bumps, lambda c: c.n == 2,
                    granularity="opcode", max_schedules=100, stall_s=STALL)
    assert bad is not None
    runs = []
    for _ in range(3):
        shared, rec, _ = run_schedule(_two_bumps, schedule=bad.to_list(),
                                      granularity="opcode", stall_s=STALL)
        runs.append((shared.n, tuple(rec.choices)))
    assert runs[0] == runs[1] == runs[2]
    assert runs[0][0] == 1


def test_locked_counter_survives_same_exploration():
    class Locked(_Counter):
        def __init__(self):
            super().__init__()
            self._lock = threading.Lock()

        def bump(self):
            with self._lock:
                self.n += 1

    def scenario(sched):
        c = Locked()
        sched.spawn(c.bump, name="a")
        sched.spawn(c.bump, name="b")
        return c

    assert find_race(scenario, lambda c: c.n == 2, granularity="opcode",
                     max_schedules=60, stall_s=STALL) is None


def test_divergent_replay_raises():
    bad = find_race(_two_bumps, lambda c: c.n == 2,
                    granularity="opcode", max_schedules=100, stall_s=STALL)
    assert bad is not None
    wrong = ["zz"] + bad.to_list()
    with pytest.raises(ScheduleDivergence):
        run_schedule(_two_bumps, schedule=wrong, granularity="opcode",
                     stall_s=STALL)


def test_deadlock_detected_from_lock_order_inversion():
    """The dynamic proof of racelint's lock-order-inversion rule: AB vs BA
    acquisition deadlocks under some schedule, and the harness finds and
    names it instead of hanging."""

    class Inverted:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

        def ab(self):
            with self.a:
                with self.b:
                    pass

        def ba(self):
            with self.b:
                with self.a:
                    pass

    def scenario(sched):
        o = Inverted()
        sched.spawn(o.ab, name="ab")
        sched.spawn(o.ba, name="ba")
        return o

    found = find_race(scenario, lambda o: True, granularity="line",
                      max_schedules=100, stall_s=STALL)
    assert found is not None and found.deadlocked


def test_seeded_schedules_are_reproducible():
    rec1 = run_schedule(_two_bumps, seed=7, granularity="opcode",
                        stall_s=STALL)[1]
    rec2 = run_schedule(_two_bumps, seed=7, granularity="opcode",
                        stall_s=STALL)[1]
    assert rec1.choices == rec2.choices


def test_scheduler_integrates_fault_clock():
    """The virtual scheduler owns a FaultClock; timed state machines under
    test advance on it deterministically — no wall-clock sleeps."""
    clock = FaultClock()
    breaker = CircuitBreaker("n", failure_threshold=1, reset_timeout_s=5.0,
                             clock=clock)

    def fail_then_recover(sched_clock):
        breaker.record_failure()          # -> OPEN
        assert breaker.allow() is False   # still open at t
        sched_clock.advance(5.0)
        assert breaker.allow() is True    # half-open probe granted
        breaker.record_success()          # -> CLOSED

    sched = DeterministicScheduler(clock=clock, stall_s=STALL)
    sched.spawn(fail_then_recover, sched.clock, name="t")
    sched.run()
    assert not sched.errors()
    assert breaker.state == CLOSED
    assert breaker.transitions[OPEN] == 1
    assert breaker.transitions[HALF_OPEN] == 1


# ---------------------------------------------------------------------------
# the PR 6 burn-down races, reconstructed pre-fix and proven post-fix
# ---------------------------------------------------------------------------


class PreFixShedAdmission(AdmissionController):
    """Reconstruction of the pre-PR-6 AdmissionController bug: on the
    acquire_sync timeout path where a grant raced the timeout, the code
    ran ``self.release()`` then ``raise self._shed()`` with NO lock held —
    so the ``shed_total += 1`` inside _shed could interleave with any
    other shed and lose updates (racelint: unguarded-shared-state)."""

    def timeout_tail(self):
        self.release()
        return self._shed()  # pre-fix: called with no lock held


def _prefix_shed_scenario(sched):
    adm = PreFixShedAdmission(max_inflight=1, max_queue=0)
    adm.acquire_sync()  # occupy the slot so sheds are live accounting
    sched.spawn(adm.timeout_tail, name="w0")
    sched.spawn(adm.timeout_tail, name="w1")
    return adm


def test_prefix_shed_lost_update_found_and_replayable():
    """The acceptance race: exploration finds a schedule where two
    concurrent pre-fix sheds record only one, and the recorded schedule
    replays the corruption deterministically."""
    bad = find_race(_prefix_shed_scenario, lambda adm: adm.shed_total == 2,
                    granularity="opcode", max_schedules=150, stall_s=STALL)
    assert bad is not None, "pre-fix _shed must lose an update under some schedule"
    for _ in range(2):
        adm, rec, sched = run_schedule(
            _prefix_shed_scenario, schedule=bad.to_list(),
            granularity="opcode", stall_s=STALL)
        assert not sched.errors()
        assert adm.shed_total == 1  # two sheds, one counted: the bug


def _fixed_shed_scenario(sched):
    # the REAL class, through the REAL overloaded-acquire path: slot
    # taken, queue disabled -> both callers shed immediately
    adm = AdmissionController(max_inflight=1, max_queue=0)
    adm.acquire_sync()

    def caller():
        with pytest.raises(ShedError):
            adm.acquire_sync()

    sched.spawn(caller, name="w0")
    sched.spawn(caller, name="w1")
    return adm


def test_fixed_shed_survives_exploration():
    assert find_race(_fixed_shed_scenario, lambda adm: adm.shed_total == 2,
                     granularity="opcode", max_schedules=80,
                     stall_s=STALL) is None


def test_fixed_timeout_path_sheds_consistently():
    """The exact code path of the historical bug (acquire_sync timeout with
    waiters queued), post-fix, under exploration: every shed is counted
    and the waiter queue drains."""

    def scenario(sched):
        adm = AdmissionController(max_inflight=1, max_queue=2)
        adm.acquire_sync()

        def waiter():
            with pytest.raises(ShedError):
                adm.acquire_sync(timeout_s=0)  # enqueue, expire, shed

        sched.spawn(waiter, name="w0")
        sched.spawn(waiter, name="w1")
        return adm

    def ok(adm):
        return (adm.shed_total == 2 and adm.queue_depth() == 0
                and adm.inflight == 1)

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=60, stall_s=STALL) is None


class PreFixStatsCounter:
    """Reconstruction of the pre-PR-6 BatcherService.submitted bug: the
    per-request counter bumped from the REST loop and the gRPC worker
    threads with no lock (the fix guards it with _stats_lock)."""

    def __init__(self):
        self.submitted = 0

    def submit_sync(self):
        self.submitted += 1

    def submit(self):
        self.submitted += 1


def test_prefix_batcher_counter_races_and_fix_holds():
    def buggy(sched):
        svc = PreFixStatsCounter()
        sched.spawn(svc.submit_sync, name="grpc")
        sched.spawn(svc.submit, name="rest")
        return svc

    bad = find_race(buggy, lambda s: s.submitted == 2,
                    granularity="opcode", max_schedules=100, stall_s=STALL)
    assert bad is not None
    svc, _, _ = run_schedule(buggy, schedule=bad.to_list(),
                             granularity="opcode", stall_s=STALL)
    assert svc.submitted == 1

    class Fixed(PreFixStatsCounter):
        def __init__(self):
            super().__init__()
            self._stats_lock = threading.Lock()

        def submit_sync(self):
            with self._stats_lock:
                self.submitted += 1

        submit = submit_sync

    def fixed(sched):
        svc = Fixed()
        sched.spawn(svc.submit_sync, name="grpc")
        sched.spawn(svc.submit, name="rest")
        return svc

    assert find_race(fixed, lambda s: s.submitted == 2,
                     granularity="opcode", max_schedules=60,
                     stall_s=STALL) is None


# ---------------------------------------------------------------------------
# CircuitBreaker state machine under adversarial schedules
# ---------------------------------------------------------------------------


def test_breaker_transitions_consistent_under_exploration():
    """Two threads race record_failure around the threshold: whatever the
    interleaving, the breaker must end OPEN exactly once, with the
    failure counter reset — no double-open, no lost transition."""

    def scenario(sched):
        b = CircuitBreaker("n", failure_threshold=2, reset_timeout_s=30.0)

        def hammer():
            b.record_failure()
            b.record_failure()

        sched.spawn(hammer, name="f0")
        sched.spawn(hammer, name="f1")
        return b

    def ok(b):
        # post-OPEN failures legitimately re-count toward the next
        # threshold; the invariant is exactly-one OPEN transition
        return b.state == OPEN and b.transitions[OPEN] == 1

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=80, stall_s=STALL) is None


def test_page_allocator_unlocked_reconstruction_double_allocates():
    """Reconstruction of the bug the PageAllocator's lock exists to
    prevent: a check-then-act free-list pop with no lock hands the SAME
    page to two concurrent admissions under some interleaving — found by
    exploration, replayed deterministically."""

    class UnlockedAllocator:
        def __init__(self, n):
            self._free = list(range(n))

        def alloc_one(self):
            if self._free:                    # check
                page = self._free[-1]          # ...then act: read
                self._free = self._free[:-1]   # ...and pop, not atomic
                return page
            return None

    def scenario(sched):
        a = UnlockedAllocator(4)
        grants = []
        a._grants = grants
        sched.spawn(lambda: grants.append(a.alloc_one()), name="admit0")
        sched.spawn(lambda: grants.append(a.alloc_one()), name="admit1")
        return a

    def ok(a):
        g = a._grants
        return len(g) == 2 and g[0] != g[1] and len(a._free) == 2

    bad = find_race(scenario, ok, granularity="line",
                    max_schedules=150, stall_s=STALL)
    assert bad is not None, "unlocked pop must double-allocate under some schedule"
    a, _, sched = run_schedule(scenario, schedule=bad.to_list(),
                               granularity="line", stall_s=STALL)
    assert not sched.errors()
    g = a._grants
    # the corruption, replayed: same page granted twice and/or a page leaked
    assert g[0] == g[1] or len(a._free) != 2


def test_refcount_unlocked_reconstruction_double_frees():
    """Reconstruction of the bug the refcounted allocator's lock exists
    to prevent (ISSUE 12): two concurrent unlocked releases of a shared
    page (trie unpin racing slot release) both read refcount 2, both
    write 1 — the page never frees (leak) — or interleave into a
    double-append onto the free list (the double-allocation corruption).
    Found by opcode exploration, replayed deterministically."""

    class UnlockedRefcounts:
        def __init__(self):
            self._refs = {5: 2}          # one page, trie + one pin
            self._free = []

        def release(self, p):
            rc = self._refs[p]           # read
            if rc > 1:
                self._refs[p] = rc - 1   # ...modify-write, not atomic
            else:
                del self._refs[p]
                self._free.append(p)

    def scenario(sched):
        a = UnlockedRefcounts()
        sched.spawn(lambda: a.release(5), name="unpin")
        sched.spawn(lambda: a.release(5), name="release")
        return a

    def ok(a):
        # both refs dropped: the page must be free exactly once
        return a._free == [5] and 5 not in a._refs

    bad = find_race(scenario, ok, granularity="opcode",
                    max_schedules=200, stall_s=STALL)
    assert bad is not None, "unlocked refcount RMW must lose a release"
    a, _, sched = run_schedule(scenario, schedule=bad.to_list(),
                               granularity="opcode", stall_s=STALL)
    assert not sched.errors()
    # the corruption, replayed: leaked (never freed) or double-freed
    assert a._free != [5] or 5 in a._refs


def test_real_allocator_retain_free_exact_under_exploration():
    """The REAL refcounted PageAllocator: a retain/free pin cycle racing
    the owner's final free can never leak the page, free it twice (the
    ValueError would surface as a scheduler error), or leave a stale
    refcount — whatever the interleaving."""
    from seldon_core_tpu.runtime.batcher import PageAllocator

    def scenario(sched):
        a = PageAllocator(total_pages=8, page_size=16)
        page = a.alloc(1)[0]             # owner's reference
        a.retain([page])                 # the trie's pin
        a._page = page

        def unpin():
            a.free([a._page])

        def owner_free():
            a.free([a._page])

        sched.spawn(unpin, name="unpin")
        sched.spawn(owner_free, name="owner")
        return a

    def ok(a):
        return a.refs_of(a._page) == 0 and a.stats()[1] == 0

    assert find_race(scenario, ok, granularity="opcode",
                     max_schedules=120, stall_s=STALL) is None


def test_page_allocator_concurrent_admit_free_exact():
    """The REAL allocator (runtime/batcher.py) under exploration: two
    admit/free cycles racing a third concurrent admission can never
    double-allocate (overlapping grants stay disjoint — a duplicate would
    also trip the double-free ValueError) or leak (in_use returns to the
    held allocation only)."""
    from seldon_core_tpu.runtime.batcher import PageAllocator

    def scenario(sched):
        a = PageAllocator(total_pages=8, page_size=16)  # 6 usable
        held = a.alloc(2)                # a standing tenant
        assert held is not None
        a._held = held
        grants = []
        a._grants = grants

        def admit_free(n):
            pages = a.alloc(n)
            if pages is not None:
                # overlap with the standing tenant is the corruption the
                # lock prevents; record before freeing
                grants.append(list(pages))
                a.free(pages)

        sched.spawn(admit_free, 2, name="admit0")
        sched.spawn(admit_free, 2, name="admit1")
        return a

    def ok(a):
        total, in_use, _ = a.stats()
        if (total, in_use) != (8, 2):
            return False            # leak or lost free
        held = set(a._held)
        return all(held.isdisjoint(g) and len(set(g)) == len(g)
                   for g in a._grants)

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=60, stall_s=STALL) is None


def test_page_allocator_exhaustion_exactly_one_grant():
    """All-or-nothing under contention: two concurrent alloc(4) against 6
    usable pages — exactly one wins, whatever the interleaving, and the
    loser's None never corrupts accounting."""
    from seldon_core_tpu.runtime.batcher import PageAllocator

    def scenario(sched):
        a = PageAllocator(total_pages=8, page_size=16)
        grants = []
        a._grants = grants
        sched.spawn(lambda: grants.append(a.alloc(4)), name="big0")
        sched.spawn(lambda: grants.append(a.alloc(4)), name="big1")
        return a

    def ok(a):
        wins = [g for g in a._grants if g is not None]
        return (len(a._grants) == 2 and len(wins) == 1
                and a.stats()[1] == 4)

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=60, stall_s=STALL) is None


def test_two_page_classes_window_release_exact_under_exploration():
    """Two page classes (ISSUE 49): the full class keeps a slot's pages while it
    lives, the WINDOW class gives the page behind each slot's window back and
    books the next one, call after call, on the loop's worker threads while a
    /metrics scrape reads both gauges. Whatever the interleaving: every page is
    given back exactly once (a second free raises, and so does a free of a page
    handed to two), the fully provisioned window class never runs out although
    a page one slot gave back may be the other's next, the gauges stay within
    each class's pool, and after the release both classes are empty."""
    from seldon_core_tpu.runtime.batcher import PageAllocator

    def scenario(sched):
        full = PageAllocator(total_pages=8, page_size=4)      # 6 usable
        window = PageAllocator(total_pages=6, page_size=4)    # 2 slots x 2 pages
        log = {"errors": [], "released": {"a": 0, "b": 0}, "seen": []}

        def slot(name):
            try:
                kept = full.alloc(3)                 # admission: the whole prompt's
                held = window.alloc(2)               # ... and a window's worth
                for _ in range(3):                   # three calls, each a page on
                    window.free([held.pop(0)])       # the page behind the window
                    log["released"][name] += 1
                    held += window.alloc(1)          # the page the call's rows reach
                    assert len(set(held)) == 2
                window.free(held)
                full.free(kept)
            except Exception as exc:     # a double free, an exhausted class
                log["errors"].append((name, repr(exc)))

        def scrape():
            log["seen"].append((full.stats()[1], window.stats()[1]))

        sched.spawn(lambda: slot("a"), name="slot-a")
        sched.spawn(lambda: slot("b"), name="slot-b")
        sched.spawn(scrape, name="scrape")
        return full, window, log

    def ok(state):
        full, window, log = state
        return (not log["errors"] and log["released"] == {"a": 3, "b": 3}
                and full.stats()[1] == 0 and window.stats()[1] == 0
                and all(f <= 6 and w <= 4 for f, w in log["seen"]))

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=80, stall_s=STALL) is None


def test_breaker_single_probe_under_exploration():
    """Half-open must admit exactly one probe no matter how allow() calls
    interleave (the _probe_inflight slot)."""
    clock = FaultClock()

    def scenario(sched):
        b = CircuitBreaker("n", failure_threshold=1, reset_timeout_s=1.0,
                           clock=clock)
        b.record_failure()      # OPEN at t
        clock.advance(1.0)      # eligible for half-open
        results = []
        b._results = results    # carried for the invariant

        def prober():
            results.append(b.allow())

        sched.spawn(prober, name="p0")
        sched.spawn(prober, name="p1")
        return b

    def ok(b):
        return sorted(b._results) == [False, True] and b.state == HALF_OPEN

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=80, stall_s=STALL) is None


# ---------------------------------------------------------------------------
# speculative decoding (PR 8): the acceptance-rate controller and the
# variable-advance slot bookkeeping
# ---------------------------------------------------------------------------


def test_prefix_spec_controller_unlocked_observe_races():
    """Reconstruction of the bug SpecController._lock exists to prevent:
    observe() runs on the batcher's drain worker thread while a /metrics
    scrape snapshots on a transport thread and dispatch reads cap() — an
    unlocked EMA/total update is a read-modify-write that loses
    observations under some interleaving. Found by opcode exploration,
    replayed deterministically; the REAL (locked) controller survives the
    identical scenario below."""
    from seldon_core_tpu.runtime.spec import SpecController

    class Unlocked(SpecController):
        def observe(self, slot, accepted_drafts, offered, tokens):
            self._forwards_total += 1
            self._tokens_total += int(tokens)
            self._accepted_total += int(accepted_drafts)
            self._drafted_total += int(offered)
            self._steps[slot] += 1
            if offered > 0:
                r = accepted_drafts / float(offered)
                self._rate[slot] += self.ALPHA * (r - self._rate[slot])

    def scenario(sched):
        c = Unlocked(slots=2, k=4)
        sched.spawn(lambda: c.observe(0, 3, 4, 4), name="drain0")
        sched.spawn(lambda: c.observe(0, 1, 4, 2), name="drain1")
        return c

    def ok(c):
        return (c._accepted_total == 4 and c._drafted_total == 8
                and c._forwards_total == 2 and c._tokens_total == 6)

    bad = find_race(scenario, ok, granularity="opcode",
                    max_schedules=200, stall_s=STALL)
    assert bad is not None, "unlocked observe must lose an update"
    c, _, _ = run_schedule(scenario, schedule=bad.to_list(),
                           granularity="opcode", stall_s=STALL)
    assert not ok(c)  # the lost observation, replayed


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_spec_controller_totals_exact_under_exploration():
    """The REAL SpecController (runtime/spec.py) under the threads that
    actually share it: two drain observations racing a dispatch cap()
    read and a /metrics snapshot — lifetime totals must come out exact
    and the cap must be a legal depth whatever the interleaving."""
    from seldon_core_tpu.runtime.spec import SpecController

    def scenario(sched):
        c = SpecController(slots=2, k=4)
        caps = []
        c._caps = caps
        sched.spawn(lambda: c.observe(0, 3, 4, 4), name="drain0")
        sched.spawn(lambda: c.observe(0, 1, 4, 2), name="drain1")
        sched.spawn(lambda: caps.append(c.cap(0)), name="dispatch")
        sched.spawn(c.snapshot, name="scrape")
        return c

    def ok(c):
        s = c.snapshot()
        return (s["spec_accepted_drafts_total"] == 4
                and s["spec_drafted_total"] == 8
                and s["spec_slot_steps_total"] == 2
                and s["spec_tokens_total"] == 6
                and all(x in (1, 2, 4) for x in c._caps))

    assert find_race(scenario, ok, granularity="opcode",
                     max_schedules=200, stall_s=STALL) is None


@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered racelint proofs step
def test_spec_controller_concurrent_reset_never_corrupts():
    """Admission racing drain: reset(slot) (new occupant) interleaving
    with observe() for the OLD occupant's final verify step must leave
    the per-slot EMA in a sane state — either the fresh 1.0 or a single
    EMA step from it — and never corrupt the lifetime totals."""
    from seldon_core_tpu.runtime.spec import SpecController

    def scenario(sched):
        c = SpecController(slots=1, k=4)
        sched.spawn(lambda: c.observe(0, 0, 4, 1), name="drain")
        sched.spawn(lambda: c.reset(0), name="admit")
        return c

    def ok(c):
        s = c.snapshot()
        # the observation is never lost from the totals, and the EMA is
        # one of the two orderings' legal values (reset-last -> 1.0;
        # observe-last -> one EMA step down from 1.0)
        return (s["spec_slot_steps_total"] == 1
                and s["spec_drafted_total"] == 4
                and c._rate[0] in (1.0, 1.0 - c.ALPHA))

    assert find_race(scenario, ok, granularity="opcode",
                     max_schedules=200, stall_s=STALL) is None


class _SpecSlotBook:
    """The batcher's variable-advance slot bookkeeping shape (PR 8):
    _dispatch_spec books the PESSIMISTIC cap+1 into disp_new with a
    (slot, gen) snapshot, _credit_spec reconciles to the device's actual
    advance and credits tokens under the gen mask, and admission
    releases + reoccupies the slot bumping gen. The event loop
    serializes these on one thread in production — the lock models that
    serialization — so the defense PROVEN here is the gen mask itself:
    a drain whose dispatch snapshot predates a re-admission must never
    touch the new occupant's counters (masked=False reconstructs the
    corruption a maskless drain would cause)."""

    def __init__(self, masked: bool = True):
        self._lock = threading.Lock()   # stands in for the event loop
        self.masked = masked
        self.gen = 0
        self.active = True
        self.n_new = 0
        self.disp_new = 0

    def dispatch(self, cap):
        with self._lock:
            booked = cap + 1
            self.disp_new += booked
            return (self.gen, booked)

    def drain(self, snap, adv):
        gen, booked = snap
        with self._lock:
            if self.masked and (not self.active or self.gen != gen):
                return  # stale step for a replaced occupant: masked
            self.disp_new -= booked - adv
            self.n_new += adv

    def readmit(self):
        with self._lock:
            self.active = False       # release the old occupant...
            self.gen += 1             # ...and admit a new one
            self.n_new = 0
            self.disp_new = 0
            self.active = True


def test_spec_variable_advance_gen_mask_protects_counters():
    """ISSUE 8: concurrent admit + variable-advance bookkeeping cannot
    corrupt per-slot generation counters. A verify step is in flight
    (booked cap+1=5) when its slot is re-admitted; whatever order the
    drain (actual advance 3) and the re-admission land in, the NEW
    occupant's counters must be exactly zero. Without the gen mask,
    exploration finds the order where the stale drain credits the new
    occupant — replayed deterministically."""

    def scenario_of(masked):
        def scenario(sched):
            s = _SpecSlotBook(masked=masked)
            snap = s.dispatch(4)        # one verify step in flight
            sched.spawn(lambda: s.drain(snap, 3), name="drain")
            sched.spawn(s.readmit, name="admit")
            return s

        return scenario

    def ok(s):
        return s.n_new == 0 and s.disp_new == 0

    bad = find_race(scenario_of(False), ok, granularity="line",
                    max_schedules=60, stall_s=STALL)
    assert bad is not None, "maskless drain must corrupt under some order"
    s, _, _ = run_schedule(scenario_of(False), schedule=bad.to_list(),
                           granularity="line", stall_s=STALL)
    assert s.n_new != 0 or s.disp_new != 0  # the corruption, replayed

    assert find_race(scenario_of(True), ok, granularity="line",
                     max_schedules=60, stall_s=STALL) is None


# ---------------------------------------------------------------------------
# disaggregated prefill handoff (PR 9): the TransferQueue's exactly-once
# delivery/cancellation protocol (runtime/disagg.py)
# ---------------------------------------------------------------------------


class UnlockedTransferQueue:
    """Reconstruction of the bug TransferQueue._lock exists to prevent: the
    SAME state machine with every check-then-act transition unlocked. The
    contenders are real: a prefill-worker thread publishes (put) while the
    batcher loop consumes (pop) or sheds (cancel). Without the lock, pop
    racing cancel hands the SAME handoff to both sides (the consumer's slot
    owns the pages AND the canceller frees them — a double free), and two
    workers' puts can lose a publication outright."""

    def __init__(self):
        self._state = {}
        self._ready = []

    def register(self, job_id):
        self._state[job_id] = "staged"

    def put(self, h):
        st = self._state.get(h.job_id)        # check...
        if st == "cancelled":
            del self._state[h.job_id]
            return False
        self._state[h.job_id] = "ready"       # ...then act
        self._ready = self._ready + [h]       # read-copy-write, not atomic
        return True

    def pop(self):
        if not self._ready:                   # check...
            return None
        h = self._ready[0]                    # ...read...
        self._ready = self._ready[1:]         # ...then act
        self._state.pop(h.job_id, None)
        return h

    def cancel(self, job_id):
        st = self._state.get(job_id)          # check...
        if st == "ready":
            found = None
            for i, h in enumerate(self._ready):
                if h.job_id == job_id:
                    found = h
                    self._ready = self._ready[:i] + self._ready[i + 1:]
                    break
            self._state.pop(job_id, None)     # ...then act
            return found
        if st == "staged":
            self._state[job_id] = "cancelled"
        return None


def _handoff(job_id):
    from seldon_core_tpu.runtime.disagg import Handoff

    return Handoff(job_id, staged=f"kv{job_id}")


def test_prefix_transfer_queue_pop_cancel_double_delivers():
    """The double-free shape: one READY handoff, the batcher loop pops it
    while a shed cancels it. Unlocked, some interleaving hands the handoff
    to BOTH (slot owns the pages AND the canceller frees them) — found by
    exploration and replayed; the real class never can (below)."""

    def scenario(sched):
        q = UnlockedTransferQueue()
        q.register(1)
        q.put(_handoff(1))
        got = []
        q._got = got
        sched.spawn(lambda: got.append(q.pop()), name="loop")
        sched.spawn(lambda: got.append(q.cancel(1)), name="shed")
        return q

    def ok(q):
        return sum(1 for h in q._got if h is not None) == 1

    bad = find_race(scenario, ok, granularity="line",
                    max_schedules=150, stall_s=STALL)
    assert bad is not None, "unlocked pop/cancel must double-deliver"
    q, _, sched = run_schedule(scenario, schedule=bad.to_list(),
                               granularity="line", stall_s=STALL)
    # the corruption, replayed — either shape is the missing lock's fault:
    # both sides got the SAME handoff (double free), or pop crashed on the
    # list cancel emptied between its check and its read
    winners = [h for h in q._got if h is not None]
    if sched.errors():
        assert isinstance(sched.errors()["loop"], IndexError)
    else:
        assert len(winners) == 2 and winners[0] is winners[1]


def test_prefix_transfer_queue_concurrent_puts_lose_a_handoff():
    """Two prefill workers publish concurrently: the unlocked read-copy-
    write of the ready list loses one handoff under some interleaving — a
    request whose prefill finished but whose future never resolves."""

    def scenario(sched):
        q = UnlockedTransferQueue()
        q.register(1)
        q.register(2)
        sched.spawn(lambda: q.put(_handoff(1)), name="worker0")
        sched.spawn(lambda: q.put(_handoff(2)), name="worker1")
        return q

    def ok(q):
        return len(q._ready) == 2

    # the read-copy-write lives on one line: line-level preemption cannot
    # interleave inside it, opcode-level must (the _two_bumps idiom)
    bad = find_race(scenario, ok, granularity="opcode",
                    max_schedules=200, stall_s=STALL)
    assert bad is not None, "unlocked put must lose a handoff"
    q, _, _ = run_schedule(scenario, schedule=bad.to_list(),
                           granularity="opcode", stall_s=STALL)
    assert len(q._ready) == 1         # the lost handoff, replayed


def test_transfer_queue_pop_cancel_exactly_once_under_exploration():
    """The REAL TransferQueue (runtime/disagg.py) under the double-free
    scenario: whatever the interleaving, exactly ONE of pop/cancel gets the
    handoff, so the pages have exactly one owner-who-frees."""
    from seldon_core_tpu.runtime.disagg import TransferQueue

    def scenario(sched):
        q = TransferQueue()
        q.register(1)
        q.put(_handoff(1))
        got = []
        q._got = got
        sched.spawn(lambda: got.append(q.pop()), name="loop")
        sched.spawn(lambda: got.append(q.cancel(1)), name="shed")
        return q

    def ok(q):
        return (sum(1 for h in q._got if h is not None) == 1
                and q.depth() == 0 and q.ready_depth() == 0)

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=100, stall_s=STALL) is None


def test_transfer_queue_put_cancel_shed_frees_exactly_once():
    """A shed racing the worker's publish (the tests/test_disagg.py
    protocol, explored): whichever order lands, the SHED path frees the
    decode-side pages exactly once — either it takes the READY handoff out
    of the queue, or the worker's later put is refused — and nothing stays
    deliverable afterward."""
    from seldon_core_tpu.runtime.disagg import TransferQueue

    def scenario(sched):
        q = TransferQueue()
        q.register(1)
        frees = []
        q._frees = frees

        def worker():
            q.put(_handoff(1))

        def shed():
            # the batcher's _shed_remote_job contract: BOTH cancel outcomes
            # free here (READY -> the returned handoff's pages; STAGED ->
            # the pages now, the late put is refused)
            q.cancel(1)
            frees.append(1)

        sched.spawn(worker, name="worker")
        sched.spawn(shed, name="shed")
        return q

    def ok(q):
        return (len(q._frees) == 1 and q.pop() is None
                and q.depth() == 0 and q.ready_depth() == 0)

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=100, stall_s=STALL) is None


def test_transfer_queue_two_workers_publish_both_under_exploration():
    """Two real workers publishing while the loop pops: both handoffs are
    delivered exactly once each, in some order, and the counters are
    exact — no lost publication, no double pop."""
    from seldon_core_tpu.runtime.disagg import TransferQueue

    def scenario(sched):
        q = TransferQueue()
        q.register(1)
        q.register(2)
        got = []
        q._got = got
        sched.spawn(lambda: q.put(_handoff(1)), name="worker0")
        sched.spawn(lambda: q.put(_handoff(2)), name="worker1")
        sched.spawn(lambda: got.extend([q.pop(), q.pop()]), name="loop")
        return q

    def ok(q):
        delivered = [h.job_id for h in q._got if h is not None]
        while True:  # the loop may have raced ahead of the puts
            h = q.pop()
            if h is None:
                break
            delivered.append(h.job_id)
        return sorted(delivered) == [1, 2] and q.handoffs_total == 2

    assert find_race(scenario, ok, granularity="line",
                     max_schedules=100, stall_s=STALL) is None


# ---------------------------------------------------------------------------
# flight recorder (PR 10): completion-ring discipline
# ---------------------------------------------------------------------------
# The recorder's per-slot rings are single-writer by contract (only the
# batcher loop's serialized offload context touches them); the ONLY
# cross-thread surface is the completed-timeline ring + aggregates, written
# once per request under the lock. These tests prove both halves: the
# unlocked reconstruction of the completion aggregates loses updates under
# a found schedule, and the real class keeps exact totals under the same
# exploration budget — including with a concurrent /debug/timeline reader.


class _UnlockedCompletionAggregates:
    """Reconstruction of FlightRecorder.complete's aggregate updates
    WITHOUT self._lock: completed_total and the retained tally are plain
    read-modify-writes, so two concurrent completions can lose one."""

    def __init__(self):
        self.completed_total = 0
        self.retained = {"head": 0}

    def complete(self):
        self.retained["head"] = self.retained["head"] + 1
        self.completed_total = self.completed_total + 1


def _unlocked_completions(sched):
    r = _UnlockedCompletionAggregates()
    sched.spawn(r.complete, name="a")
    sched.spawn(r.complete, name="b")
    return r


def test_unlocked_completion_aggregates_lose_updates():
    bad = find_race(
        _unlocked_completions,
        lambda r: r.completed_total == 2 and r.retained["head"] == 2,
        granularity="opcode", max_schedules=150, stall_s=STALL)
    assert bad is not None, "unlocked completion RMW must lose an update"
    r, _, sched = run_schedule(_unlocked_completions, schedule=bad.to_list(),
                               granularity="opcode", stall_s=STALL)
    assert not sched.errors()
    assert r.completed_total == 1 or r.retained["head"] == 1  # replayed


def _real_recorder_scenario(sched):
    from seldon_core_tpu.runtime.flight import EV_STEP, FlightRecorder

    fr = FlightRecorder(2, keep=8)
    for slot in (0, 1):
        fr.begin(slot, None, None, prompt_tokens=3)
        fr.record(slot, EV_STEP, tokens=1)
    reads = []
    fr._reads = reads
    sched.spawn(lambda: fr.complete(0, "done", 1), name="complete0")
    sched.spawn(lambda: fr.complete(1, "done", 1), name="complete1")
    # a /debug/timeline + scaling scrape racing both completions
    sched.spawn(lambda: reads.append((fr.timelines(), fr.snapshot())),
                name="reader")
    return fr


def test_flight_recorder_completions_exact_under_exploration():
    def ok(fr):
        snap = fr.snapshot()
        if not (snap["completed_total"] == 2
                and snap["retained"]["head"] == 2
                and len(fr.timelines()) == 2):
            return False
        # the racing reader saw some consistent prefix, never corruption:
        # timelines() ran before snapshot() (two lock acquisitions — a
        # completion may land between them), so its count can only trail
        # the later total, and every timeline it saw is fully formed
        timelines, mid = fr._reads[0]
        return (len(timelines) <= mid["completed_total"] <= 2
                and all(t["status"] == "done" and t["tokens"] == 1
                        for t in timelines))

    assert find_race(_real_recorder_scenario, ok, granularity="line",
                     max_schedules=120, stall_s=STALL) is None


# ---------------------------------------------------------------------------
# PR 14: elastic-control-loop controller state (controlplane/autoscaler.py)
# — the controller thread's tick() races the /metrics scrape's
# autoscaler_stats() and a second (admin-triggered) tick; the decision
# functions are pure, so the ONLY shared state is the tally/history block
# the lock guards.  The reconstruction below drops that lock and loses a
# tick under a found opcode schedule; the real Autoscaler survives the
# same concurrent shape.
# ---------------------------------------------------------------------------


def test_prefix_autoscaler_tick_tally_lost_update():
    """Reconstruction of the bug Autoscaler._lock exists to prevent: two
    concurrent control passes (the run_forever thread and an admin
    trigger) bump the tick/scale tallies with unlocked read-modify-writes
    — an interleaving loses a scale-up, so /metrics under-reports the
    actions actually applied.  Found by opcode exploration, replayed
    deterministically."""

    class UnlockedTallies:
        # the tally block of Autoscaler.tick(), lock dropped
        def __init__(self):
            self._ticks_total = 0
            self._scale_ups_total = 0

        def note_tick(self, scaled_up):
            self._ticks_total += 1
            if scaled_up:
                self._scale_ups_total += 1

    def scenario(sched):
        t = UnlockedTallies()
        sched.spawn(lambda: t.note_tick(True), name="loop-tick")
        sched.spawn(lambda: t.note_tick(True), name="admin-tick")
        return t

    def ok(t):
        return t._ticks_total == 2 and t._scale_ups_total == 2

    bad = find_race(scenario, ok, granularity="opcode",
                    max_schedules=200, stall_s=STALL)
    assert bad is not None, "unlocked tick tallies must lose an update"
    t, _, _ = run_schedule(scenario, schedule=bad.to_list(),
                           granularity="opcode", stall_s=STALL)
    assert not ok(t)  # the lost tick, replayed


class _SchedStubReplica:
    def __init__(self):
        self.draining = False

    def load(self):
        pass

    def drain(self):
        self.draining = True

    def is_idle(self):
        return False  # never collected mid-scenario: membership is stable


def _real_autoscaler_scenario(sched):
    """The REAL Autoscaler under the threads that actually share it: two
    concurrent ticks (run_forever + admin trigger) over an overloaded
    snapshot, racing a /metrics scrape.  Config makes every tick decide
    scale-up (stability window 1, cooldown 0, clock pinned)."""
    from seldon_core_tpu.controlplane.autoscaler import (
        Autoscaler, AutoscalerConfig)
    from seldon_core_tpu.runtime.engine import ReplicaSet

    rs = ReplicaSet([_SchedStubReplica()])
    auto = Autoscaler(
        rs,
        config=AutoscalerConfig(
            min_replicas=1, max_replicas=8, up_queue_per_slot=1.0,
            up_stable_ticks=1, cooldown_s=0.0),
        replica_factory=_SchedStubReplica,
        clock=lambda: 100.0,
        snapshot_fn=lambda r: {"queue_depth": 8, "total_slots": 2},
    )
    auto._rs = rs
    sched.spawn(auto.tick, name="loop-tick")
    sched.spawn(auto.tick, name="admin-tick")
    sched.spawn(auto.autoscaler_stats, name="scrape")
    return auto


@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered racelint
# proofs step (the registry/scheduler/allocator real-class explorations
# keep this harness tier-1)
def test_real_autoscaler_tallies_exact_under_exploration():
    """Both ticks decide scale-up; whatever the interleaving, the tallies
    come out exact, the fleet grows by exactly two replicas, and the
    racing scrape never observes corruption (tick counter can only be
    0..2)."""

    def ok(auto):
        stats = auto.autoscaler_stats()
        return (stats["autoscaler_ticks_total"] == 2
                and stats["autoscaler_scale_ups_total"] == 2
                and len(auto._rs.members()) == 3)

    assert find_race(_real_autoscaler_scenario, ok, granularity="line",
                     max_schedules=80, stall_s=STALL) is None


def _replica_set_membership_scenario(sched):
    """Controller-vs-serving interleaving: the autoscaler's actuators
    (add_replica / drain_replica / collect sweep) race live dispatch
    (pick) on the fleet."""
    from seldon_core_tpu.runtime.engine import ReplicaSet

    r1, r2 = _SchedStubReplica(), _SchedStubReplica()
    rs = ReplicaSet([r1, r2])
    picks = []
    rs._picks = picks
    sched.spawn(lambda: rs.add_replica(_SchedStubReplica()),
                name="scale-up")
    sched.spawn(rs.drain_replica, name="scale-down")
    sched.spawn(lambda: picks.append(rs.pick()), name="dispatch")
    sched.spawn(rs.collect_drained, name="sweep")
    return rs


def test_replica_set_membership_safe_under_exploration():
    """Whatever order the actuators and dispatch interleave in: dispatch
    always lands on an attached replica, exactly one replica ends up
    draining (none were idle, so none detached), and membership is
    consistent."""

    def ok(rs):
        members = rs.members()
        draining = rs.draining_members()
        return (len(members) == 3
                and len(draining) == 1
                and all(d in members for d in draining)
                and len(rs._picks) == 1
                and rs._picks[0] in members)

    assert find_race(_replica_set_membership_scenario, ok,
                     granularity="line", max_schedules=100,
                     stall_s=STALL) is None


# ---------------------------------------------------------------------------
# fleet fault tolerance (ISSUE 16): the resume-journal and health-model
# discipline under interleaving
# ---------------------------------------------------------------------------


class UnlockedResumeJournal:
    """Reconstruction of the race ``ResumeJournal``'s lock exists to
    prevent (runtime/resilience.py): batcher worker
    threads journal each delivered token (append + delivered-count RMW)
    while the retry loop snapshots the prefix to re-admit. Unlocked, the
    count RMW loses an update against a concurrent append — the journal
    then claims fewer tokens DELIVERED than it holds, so a resume
    fast-forwards the rng chain by the wrong split count and replays a
    token the client already has: the exact duplicate-delivery the
    at-most-once contract (tests/test_chaos.py) forbids."""

    def __init__(self):
        self.tokens = []
        self.delivered = 0

    def append(self, tok):
        self.tokens.append(tok)
        self.delivered = self.delivered + 1   # pre-fix: unlocked RMW


def _unlocked_journal_scenario(sched):
    j = UnlockedResumeJournal()
    sched.spawn(lambda: j.append(11), name="worker-a")
    sched.spawn(lambda: j.append(12), name="worker-b")
    return j


def test_resume_journal_unlocked_reconstruction_desyncs_the_count():
    """Opcode exploration finds the lost delivered-count update; the
    exact schedule replays deterministically to a journal whose token
    list and rng fast-forward count disagree."""

    def ok(j):
        return j.delivered == len(j.tokens) == 2

    bad = find_race(_unlocked_journal_scenario, ok, granularity="opcode",
                    max_schedules=200, stall_s=STALL)
    assert bad is not None, \
        "the unlocked journal must desync count from tokens"
    j, _, sched = run_schedule(_unlocked_journal_scenario,
                               schedule=bad.to_list(),
                               granularity="opcode", stall_s=STALL)
    assert not sched.errors()
    # the corruption, replayed: two tokens delivered, one counted — a
    # resume would fast-forward one split and re-send token two
    assert len(j.tokens) == 2 and j.delivered == 1


def _fleet_fault_scenario(sched):
    """The REAL ReplicaSet under the threads fleet fault tolerance adds:
    a dispatch failure ejecting a replica (quarantine) races live
    dispatch (pick), the autoscaler's undrain actuator, and the resume
    journal's locked append/snapshot pair (batcher worker vs retry
    loop)."""
    from seldon_core_tpu.runtime.engine import ReplicaSet, _ResumeEntry

    r1, r2, r3 = (_SchedStubReplica(), _SchedStubReplica(),
                  _SchedStubReplica())
    rs = ReplicaSet([r1, r2, r3])
    rs.drain_replica(r3)  # pre-staged: the undrain actuator's target
    entry = _ResumeEntry([1, 2], 8, seed=5, tenant=None, slo_class=None,
                         adapter=None)
    jid = rs._journal.record(entry)
    picks = []
    snap = {}
    rs._picks, rs._snap, rs._victim, rs._jid = picks, snap, r2, jid

    def eject_dead():
        # the dispatch-failure path: force the breaker open, quarantine
        rs._breaker_for(r2).trip()
        rs._eject(r2)

    def journal_worker():
        rs._journal.append(jid, 7)

    def retry_reader():
        snap["tokens"] = rs._journal.delivered(jid)

    sched.spawn(eject_dead, name="eject")
    sched.spawn(lambda: picks.append(rs.pick()), name="dispatch")
    sched.spawn(rs.undrain_replica, name="undrain")
    sched.spawn(journal_worker, name="journal-append")
    sched.spawn(retry_reader, name="resume-snapshot")
    return rs


def test_real_fleet_fault_paths_exact_under_exploration():
    """Whatever order ejection, dispatch, undrain and the journal pair
    interleave in: membership stays consistent (the corpse quarantined,
    the drain cancelled, dispatch never lands on a detached replica) and
    the journal snapshot is always a clean prefix — never a torn read."""

    def ok(rs):
        toks = rs._journal.delivered(rs._jid)
        return (len(rs.members()) == 3
                and rs.ejected_members() == [rs._victim]
                and rs.draining_members() == []
                and len(rs._picks) == 1
                and rs._picks[0] in rs.members()
                and toks == [7]
                and rs._snap["tokens"] in ([], [7]))

    assert find_race(_fleet_fault_scenario, ok, granularity="line",
                     max_schedules=100, stall_s=STALL) is None


# ---------------------------------------------------------------------------
# adapter registry + weighted-fair scheduler (ISSUE 15): the multi-tenant
# refcount and tally discipline under interleaving
# ---------------------------------------------------------------------------


class UnlockedAdapterRefcounts:
    """Reconstruction of the race the AdapterRegistry's lock exists to
    prevent (ISSUE 15): pin() is a liveness-check-then-increment and
    evict() a refcount-check-then-free; with no lock the two interleave
    into evict freeing a row a live slot just pinned — exactly the
    freed-while-referenced corruption the acceptance bar forbids (the
    slot's next dispatch would gather a row a later load may repopulate
    with ANOTHER tenant's weights)."""

    def __init__(self):
        self._pins = {3: 0}        # one loaded adapter, row 3, unpinned
        self._freed = []

    def pin(self, row):
        if row in self._pins:          # liveness check (the real _by_row get)
            n = self._pins.get(row, 0)  # ...then the increment — not atomic
            self._pins[row] = n + 1
            return True
        return False               # raced an evict: fail loudly

    def evict(self, row):
        if self._pins.get(row, 0) == 0:   # refcount check
            self._pins.pop(row, None)      # ...then the free
            self._freed.append(row)
            return True
        return False


def _unlocked_adapter_scenario(sched):
    r = UnlockedAdapterRefcounts()
    out = {}
    r._out = out
    sched.spawn(lambda: out.__setitem__("pinned", r.pin(3)),
                name="slot-pin")
    sched.spawn(lambda: out.__setitem__("evicted", r.evict(3)),
                name="evict")
    return r


def test_adapter_refcount_unlocked_reconstruction_frees_pinned_row():
    """Opcode exploration finds the pin-lost-to-evict update; the exact
    schedule replays deterministically to the same corruption."""

    def ok(r):
        # the invariant evict exists to hold: a freed row is never pinned
        return not (r._freed and r._pins.get(3, 0) > 0)

    bad = find_race(_unlocked_adapter_scenario, ok, granularity="opcode",
                    max_schedules=200, stall_s=STALL)
    assert bad is not None, \
        "unlocked pin/evict must free a pinned row under some schedule"
    r, _, sched = run_schedule(_unlocked_adapter_scenario,
                               schedule=bad.to_list(),
                               granularity="opcode", stall_s=STALL)
    assert not sched.errors()
    # the corruption, replayed: BOTH calls reported success — the slot
    # believes it holds a pin on a row eviction just freed
    assert r._out["pinned"] and r._out["evicted"]
    assert r._freed and r._pins.get(3, 0) > 0


def _tiny_registry():
    from seldon_core_tpu.models.transformer import TransformerConfig
    from seldon_core_tpu.runtime.adapters import AdapterRegistry

    cfg = TransformerConfig(vocab_size=16, dim=8, n_layers=1, n_heads=2,
                            n_kv_heads=2, ffn_dim=8, max_seq_len=16,
                            tie_embeddings=True)
    return AdapterRegistry(cfg, rank=1, max_adapters=3)


def test_real_registry_load_evict_pin_exact_under_exploration():
    """The REAL AdapterRegistry (runtime/adapters.py): a slot pin racing
    an evict racing a concurrent load can never end freed-while-pinned —
    either the pin won (adapter stays, exactly one reference) or the
    evict won (row freed, the pin failed LOUDLY with KeyError) — and the
    racing load always lands. Line granularity: the registry's jitted
    row writes dispatch real arrays, prewarmed below so exploration
    replays cached executables, not compiles."""
    # prewarm the process-shared jitted row write + zeros-init compiles
    warm = _tiny_registry()
    warm.load("w", {})
    warm.evict("w")

    def scenario(sched):
        reg = _tiny_registry()
        reg.load("a", {})
        out = {}
        reg._out = out

        def slot_pin():
            try:
                reg.pin(reg.resolve("a"))
                out["pinned"] = True
            except KeyError:
                out["pinned"] = False  # raced the evict: failed loudly

        sched.spawn(slot_pin, name="slot-pin")
        sched.spawn(lambda: out.__setitem__("evicted", reg.evict("a")),
                    name="evict")
        sched.spawn(lambda: reg.load("b", {}), name="load")
        return reg

    def ok(reg):
        out = reg._out
        names = reg.names()
        if "b" not in names:           # the concurrent load always lands
            return False
        if out["evicted"]:
            # freed: the pin must NOT believe it holds a reference
            return not out["pinned"] and "a" not in names
        # not freed: the pin holds exactly one live reference
        return out["pinned"] and reg.refs_of("a") == 1

    # 25 schedules: the jitted row writes make each schedule ~10x a
    # pure-python one against the tier-1 870 s budget; the CHEAP
    # reconstruction above explores 200
    assert find_race(scenario, ok, granularity="line",
                     max_schedules=25, stall_s=STALL) is None


def _wfq_tally_scenario(sched):
    from seldon_core_tpu.runtime.scheduler import (PendingRequest,
                                                   WeightedFairScheduler)

    s = WeightedFairScheduler()
    reqs = [PendingRequest(ids=[1], max_new=1, fut=None, tenant="t",
                           slo_class="batch") for _ in range(2)]
    s.push(reqs[0])
    s._reqs = reqs
    sched.spawn(lambda: s.push(reqs[1]), name="submit")
    sched.spawn(lambda: s.commit(reqs[0]), name="admit")
    sched.spawn(lambda: s.count_shed("t", "batch"), name="page-shed")
    sched.spawn(s.counters, name="scrape")
    return s


def test_real_wfq_scheduler_tallies_exact_under_exploration():
    """The REAL WeightedFairScheduler: a submit push racing the admission
    commit racing a post-admission shed racing a /metrics scrape keeps
    every tally exact — one admitted, one shed, one still queued —
    whatever the interleaving (the unlocked reconstruction is the
    racelint fixture pair in tests/test_racelint.py)."""

    def ok(s):
        (row,) = [r for r in s.counters()
                  if r["tenant"] == "t" and r["slo_class"] == "batch"]
        return (row["admitted"] == 1 and row["shed"] == 1
                and row["queued"] == 1 and len(s) == 1)

    assert find_race(_wfq_tally_scenario, ok, granularity="opcode",
                     max_schedules=80, stall_s=STALL) is None
