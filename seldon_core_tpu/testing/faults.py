"""Deterministic fault injection for resilience tests.

Everything here is seeded or explicitly scheduled — no wall-clock randomness
and no real sleeps. Latency is injected by advancing a :class:`FaultClock`
(the same clock object handed to Deadline/CircuitBreaker), so a test can
"burn" 200ms of budget in zero wall time and still observe exact
deadline-exceeded and breaker open/half-open/recovery transitions.

Typical wiring::

    clock = FaultClock()
    schedule = FaultSchedule.flaps("EEEEEO")        # 5 errors then ok
    comp = FaultyComponent(schedule, clock=clock)
    engine = GraphEngine(spec, components={"m": comp},
                         resilience=ResilienceConfig(breaker_failures=5,
                                                     breaker_reset_s=1.0,
                                                     clock=clock))
    # ... drive predict(), advance clock, assert breaker transitions

Schedules are per-call: call i consults ``schedule[i]`` (the last entry
repeats once the schedule is exhausted, so a finite schedule describes an
infinite behavior).
"""

from __future__ import annotations

import random
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from seldon_core_tpu.components.component import SeldonComponent
from seldon_core_tpu.contracts.payload import SeldonError


class FaultClock:
    """A manually-advanced monotonic clock. Pass the instance anywhere a
    ``clock`` callable is expected (Deadline, CircuitBreaker,
    ResilienceConfig) — calling it returns the current fake time."""

    def __init__(self, start: float = 1000.0):
        self.t = float(start)

    def __call__(self) -> float:
        return self.t

    def now(self) -> float:
        return self.t

    def advance(self, seconds: float) -> float:
        if seconds < 0:
            raise ValueError("clocks only move forward")
        self.t += seconds
        return self.t


@dataclass
class FaultSpec:
    """Behavior of one call: optional injected latency (FaultClock seconds),
    then either success or a raised error."""

    latency_s: float = 0.0
    error: Optional[BaseException] = None

    @classmethod
    def ok(cls, latency_s: float = 0.0) -> "FaultSpec":
        return cls(latency_s=latency_s)

    @classmethod
    def fail(cls, message: str = "injected fault", status_code: int = 503,
             latency_s: float = 0.0) -> "FaultSpec":
        return cls(
            latency_s=latency_s,
            error=SeldonError(message, status_code=status_code, reason="INJECTED_FAULT"),
        )


class FaultSchedule:
    """A deterministic per-call schedule of FaultSpecs. Indexing past the end
    repeats the final entry."""

    def __init__(self, specs: Sequence[FaultSpec]):
        if not specs:
            raise ValueError("schedule needs at least one entry")
        self.specs: List[FaultSpec] = list(specs)

    def __getitem__(self, i: int) -> FaultSpec:
        return self.specs[min(i, len(self.specs) - 1)]

    def __len__(self) -> int:
        return len(self.specs)

    # -- constructors ---------------------------------------------------
    @classmethod
    def always_ok(cls, latency_s: float = 0.0) -> "FaultSchedule":
        return cls([FaultSpec.ok(latency_s)])

    @classmethod
    def always_fail(cls, status_code: int = 503) -> "FaultSchedule":
        return cls([FaultSpec.fail(status_code=status_code)])

    @classmethod
    def flaps(cls, pattern: str, latency_s: float = 0.0,
              status_code: int = 503) -> "FaultSchedule":
        """``pattern``: one char per call — 'E' error, 'O' ok. E.g.
        ``"EEEEEO"`` fails five calls then succeeds forever (final entry
        repeats)."""
        specs = []
        for ch in pattern:
            if ch in ("E", "e", "F", "f"):
                specs.append(FaultSpec.fail(status_code=status_code, latency_s=latency_s))
            elif ch in ("O", "o", ".", "S", "s"):
                specs.append(FaultSpec.ok(latency_s))
            else:
                raise ValueError(f"unknown flap char {ch!r} (use E/O)")
        return cls(specs)

    @classmethod
    def seeded(
        cls,
        seed: int,
        n: int,
        error_rate: float = 0.0,
        latency_s: float = 0.0,
        latency_jitter_s: float = 0.0,
        status_code: int = 503,
    ) -> "FaultSchedule":
        """n entries drawn from random.Random(seed): same seed, same
        schedule, forever — CI-stable chaos."""
        rng = random.Random(seed)
        specs = []
        for _ in range(n):
            lat = latency_s + (rng.random() * latency_jitter_s if latency_jitter_s else 0.0)
            if rng.random() < error_rate:
                specs.append(FaultSpec.fail(status_code=status_code, latency_s=lat))
            else:
                specs.append(FaultSpec.ok(lat))
        return cls(specs)


class FaultyComponent(SeldonComponent):
    """A graph node with scripted behavior.

    Wraps an ``inner`` component (default: echo) and, per call, advances the
    attached FaultClock by the scheduled latency then raises the scheduled
    error or delegates. ``is_async=True`` (the default) makes the engine
    treat it like a remote/async node — the class the resilience layer wraps
    with breakers. ``calls`` records every invocation so tests can prove a
    short-circuited node never executed.
    """

    def __init__(
        self,
        schedule: Optional[FaultSchedule] = None,
        clock: Optional[FaultClock] = None,
        inner: Optional[SeldonComponent] = None,
        is_async: bool = True,
        name: str = "faulty",
    ):
        super().__init__()
        self.schedule = schedule or FaultSchedule.always_ok()
        self.clock = clock
        self.inner = inner
        self.is_async = is_async
        self.name = name
        self.calls = 0
        self.on_call: Optional[Callable[[int, FaultSpec], None]] = None

    # -- fault application ---------------------------------------------
    def _apply(self) -> None:
        spec = self.schedule[self.calls]
        self.calls += 1
        if self.on_call is not None:
            self.on_call(self.calls - 1, spec)
        if spec.latency_s and self.clock is not None:
            self.clock.advance(spec.latency_s)
        if spec.error is not None:
            raise spec.error

    def _delegate(self, method: str, X, names, meta=None):
        self._apply()
        if self.inner is not None:
            fn = getattr(self.inner, method, None)
            if fn is not None:
                return fn(X, names, meta=meta)
        return X

    # -- component surface (async: the engine's breaker-wrapped class) --
    async def predict(self, X, names, meta=None):
        return self._delegate("predict", X, names, meta)

    async def transform_input(self, X, names, meta=None):
        return self._delegate("transform_input", X, names, meta)

    async def transform_output(self, X, names, meta=None):
        return self._delegate("transform_output", X, names, meta)

    async def route(self, X, names):
        self._apply()
        if self.inner is not None and hasattr(self.inner, "route"):
            return self.inner.route(X, names)
        return 0

    async def aggregate(self, Xs, names):
        self._apply()
        if self.inner is not None and hasattr(self.inner, "aggregate"):
            return self.inner.aggregate(Xs, names)
        return np.mean([np.asarray(x) for x in Xs], axis=0)


def inject_faults(
    component: SeldonComponent,
    schedule: FaultSchedule,
    clock: Optional[FaultClock] = None,
) -> FaultyComponent:
    """Wrap an existing component with a fault schedule (its methods run only
    when the scheduled call succeeds)."""
    return FaultyComponent(schedule=schedule, clock=clock, inner=component)


# ---------------------------------------------------------------------------
# Fleet chaos (ISSUE 16): deterministic batcher-level crash injection.
#
# ContinuousBatcher calls its ``_chaos`` hook at the top of every loop turn
# with itself as the argument; a raising hook is indistinguishable from a
# device fault mid-step — the crash handler fails every in-flight slot and
# the loop dies, exactly the unplanned death the fleet's health model must
# catch. No sleeps anywhere: triggers are explicit (a threading.Event the
# test sets, or any predicate over batcher state), so the kill lands
# mid-decode by construction rather than by timing luck.


class BatcherKiller:
    """A one-shot batcher-loop assassin, installable as ``batcher._chaos``
    on any number of batchers at once.

    The kill fires on the first loop turn where ``trigger`` is truthy (an
    ``threading.Event`` works directly — so does any zero-arg callable or
    a predicate taking the batcher). With ``busiest=True`` and several
    installed batchers, only the batcher holding the most active slots at
    trigger time dies — "kill the busiest replica mid-decode" without
    guessing which replica the router chose. One shot: after the kill the
    hook disarms everywhere, so the fleet's half-open re-probe (which
    restarts the very same loop) finds a healthy batcher.
    """

    def __init__(self, trigger: Optional[Any] = None, busiest: bool = False,
                 message: str = "chaos: batcher loop killed"):
        self.trigger = trigger
        self.busiest = busiest
        self.message = message
        self._armed = True
        self._lock = threading.Lock()
        self._installed: List[Any] = []
        self.kills = 0
        self.killed: Optional[Any] = None  # the batcher that died

    def install(self, *batchers: Any) -> "BatcherKiller":
        """Attach to each batcher's ``_chaos`` hook; returns self."""
        for b in batchers:
            b._chaos = self
            self._installed.append(b)
        return self

    def _triggered(self, batcher: Any) -> bool:
        t = self.trigger
        if t is None:
            return True
        if hasattr(t, "is_set"):
            return bool(t.is_set())
        try:
            return bool(t(batcher))
        except TypeError:
            return bool(t())

    @staticmethod
    def _active_slots(batcher: Any) -> int:
        return sum(1 for s in batcher._slots if s.active)

    def __call__(self, batcher: Any) -> None:
        # each batcher loop runs on its own event-loop thread: the disarm
        # is a check-then-set race between victims, so it sits under a lock
        with self._lock:
            if not self._armed or not self._triggered(batcher):
                return
            if self.busiest:
                mine = self._active_slots(batcher)
                peak = max((self._active_slots(b) for b in self._installed),
                           default=0)
                if mine == 0 or mine < peak:
                    return  # a busier sibling will take the bullet
            self._armed = False
            self.kills += 1
            self.killed = batcher
        raise SeldonError(self.message, status_code=503,
                          reason="INJECTED_FAULT")


class HandoffPoisoner:
    """Corrupts the staged KV of finished remote prefills so the decode
    side's import raises — the "poisoned handoff" fault class.

    Wraps every PrefillWorker's ``_prefill_one``: the prefill itself runs
    and publishes normally, but the handoff arrives READY with ``staged``
    replaced by an unimportable payload (a bare string has no pages to
    tree-import, so the import raises inside ``_consume_handoffs``). Poisons the first ``first_n`` handoffs, then
    passes everything through untouched — one bad handoff amid good ones,
    the shape the batcher's containment must survive.

    Network transport (``handoff_transport="network"``): the poison moves
    to the WIRE — ``_frame_handoff``'s framed bytes are truncated inside
    the tensor region, so the decode host's HandoffReceiver hits the
    frame codec's bounds check (metadata — and so the job_id — stays
    parseable, by the frame's meta-before-payload layout) and resolves
    the job with an error handoff. Same containment contract, proven one
    layer deeper."""

    def __init__(self, batcher: Any, first_n: int = 1,
                 poison: Any = "poisoned-kv-payload"):
        self.first_n = int(first_n)
        self.poison = poison
        self.poisoned = 0
        self._lock = threading.Lock()
        if getattr(batcher, "_remote", None) is None:
            raise ValueError("HandoffPoisoner needs a disaggregated batcher")
        for worker in batcher._remote.workers:
            if getattr(worker, "transport", "device") == "network":
                real_frame = worker._frame_handoff

                def poisoned_frame(h, _real=real_frame):
                    payload = _real(h)
                    with self._lock:
                        if self.poisoned < self.first_n:
                            self.poisoned += 1
                            payload = payload[:-16]
                    return payload

                worker._frame_handoff = poisoned_frame
                continue
            real = worker._prefill_one

            def poisoned_prefill(req, _real=real):
                h = _real(req)
                with self._lock:
                    if self.poisoned < self.first_n:
                        self.poisoned += 1
                        h.staged = self.poison
                return h

            worker._prefill_one = poisoned_prefill


class LeakSweep:
    """Error-path leak harness (ISSUE 19): one-shot fault injection at
    every registered acquire/commit boundary of a live batcher, plus a
    zero-residue probe over every refcounted resource the runtime owns.

    The static half of PR 19 (``tools/leaklint``) proves each acquire
    site pairs with a release on every CFG path; this is the dynamic
    half — it makes those paths actually EXECUTE. For each boundary the
    harness arms a deterministic one-shot fault, the test drives one
    request through it (which fails with a contained error — the server
    must keep serving), and ``assert_clean`` then checks that every
    counter an unwind path is responsible for is back to zero: pages
    held by slots, elevated trie pins, adapter pins, staged remote
    jobs, undelivered handoffs, resume-journal entries.

    Boundaries map 1:1 onto the leaklint effect registry
    (``tools/leaklint/effects.py``):

    ========================  =============================================
    boundary                  injected fault (one-shot)
    ========================  =============================================
    ``adapter-pin``           ``AdapterRegistry.resolve_and_pin`` raises
                              KeyError at submit — the 400 path must drop
                              nothing (no pin was taken under the raise).
    ``page-alloc``            ``_alloc_pages`` returns None while armed —
                              admission exhaustion; the unwind must drop
                              the ``match_and_pin`` prefix pins (the PR 7 /
                              PR 15 leak class).
    ``radix-cow``             only the FIRST ``_alloc_pages`` call fails —
                              the cow-drop retry path runs and the request
                              SUCCEEDS; the dropped cow-source pin must be
                              freed exactly once (the PR 12 leak class).
    ``prefill-stage``         ``PrefillWorker._prefill_one`` raises — the
                              worker publishes an error handoff and the
                              decode side releases the staged slot+pages.
    ``handoff-import``        staged KV replaced with an unimportable
                              payload — ``_consume_handoffs`` containment
                              releases slot, suffix pages, prefix pins.
    ``journal-record``        ``ResumeJournal.record`` raises — the fleet
                              submit fails before any entry exists; depth
                              stays zero (the PR 16 leak class).
    ========================  =============================================

    ``boundaries()`` returns the subset applicable to the batcher's
    configuration (radix? adapters? disaggregated? fleet engine?),
    so one parametrized test sweeps every configuration without dead arms.
    """

    POISON = "leaksweep-poisoned-kv"

    def __init__(self, batcher: Any, engine: Any = None):
        self.batcher = batcher
        self.engine = engine
        self.fired = 0
        self._lock = threading.Lock()
        self._shots = 0
        self._restore: List[Any] = []  # (obj, attr, original)

    # -- boundary catalog ----------------------------------------------
    def boundaries(self) -> List[str]:
        b, out = self.batcher, []
        if getattr(b, "_adapters", None) is not None:
            out.append("adapter-pin")
        out.append("page-alloc")
        if getattr(b, "_radix", None) is not None:
            out.append("radix-cow")
        if getattr(b, "_remote", None) is not None:
            out.append("prefill-stage")
            out.append("handoff-import")
        if self.engine is not None and getattr(self.engine, "_journal",
                                               None) is not None:
            out.append("journal-record")
        return out

    # -- one-shot plumbing ---------------------------------------------
    def _take_shot(self) -> bool:
        with self._lock:
            if self._shots <= 0:
                return False
            self._shots -= 1
            self.fired += 1
            return True

    def _wrap(self, obj: Any, attr: str, wrapper: Callable) -> None:
        original = getattr(obj, attr)
        setattr(obj, attr, wrapper(original))
        self._restore.append((obj, attr, original))

    def disarm(self) -> None:
        """Restore every wrapped method (idempotent)."""
        while self._restore:
            obj, attr, original = self._restore.pop()
            setattr(obj, attr, original)
        with self._lock:
            self._shots = 0

    def arm(self, boundary: str, shots: int = 1) -> "LeakSweep":
        """Install the one-shot fault for ``boundary``; returns self."""
        if boundary not in self.boundaries():
            raise ValueError(
                f"boundary {boundary!r} not applicable here "
                f"(have: {self.boundaries()})")
        self.disarm()
        with self._lock:
            self._shots = int(shots)
        getattr(self, "_arm_" + boundary.replace("-", "_"))()
        return self

    def _arm_adapter_pin(self) -> None:
        reg = self.batcher._adapters

        def wrapper(real):
            def resolve_and_pin(name):
                if name and self._take_shot():
                    raise KeyError(
                        f"leaksweep: injected adapter fault for {name!r}")
                return real(name)
            return resolve_and_pin

        self._wrap(reg, "resolve_and_pin", wrapper)

    def _arm_page_alloc(self) -> None:
        # while armed EVERY _alloc_pages call fails: the admission must
        # take its exhaustion unwind (shed or park), not the trie-evict
        # relief retry. Shots gate how many admissions see exhaustion.
        def wrapper(real):
            def _alloc_pages(n):
                if self._take_shot():
                    return None
                return real(n)
            return _alloc_pages

        self._wrap(self.batcher, "_alloc_pages", wrapper)

    def _arm_radix_cow(self) -> None:
        # identical injection point, but the driver arms exactly ONE shot
        # and sends a partial-block prefix continuation: the first
        # (cow-inclusive) allocation fails, the cow pin is dropped, and
        # the retry allocation succeeds — the admission completes.
        self._arm_page_alloc()

    def _arm_prefill_stage(self) -> None:
        from seldon_core_tpu.contracts.payload import SeldonError as _Err

        for worker in self.batcher._remote.workers:
            def wrapper(real):
                def _prefill_one(req):
                    if self._take_shot():
                        raise _Err("leaksweep: injected prefill fault",
                                   status_code=503, reason="INJECTED_FAULT")
                    return real(req)
                return _prefill_one

            self._wrap(worker, "_prefill_one", wrapper)

    def _arm_handoff_import(self) -> None:
        for worker in self.batcher._remote.workers:
            def wrapper(real):
                def _prefill_one(req):
                    h = real(req)
                    if self._take_shot():
                        h.staged = self.POISON
                    return h
                return _prefill_one

            self._wrap(worker, "_prefill_one", wrapper)

    def _arm_journal_record(self) -> None:
        from seldon_core_tpu.contracts.payload import SeldonError as _Err

        journal = self.engine._journal

        def wrapper(real):
            def record(entry):
                if self._take_shot():
                    raise _Err("leaksweep: injected journal fault",
                               status_code=503, reason="INJECTED_FAULT")
                return real(entry)
            return record

        self._wrap(journal, "record", wrapper)

    # -- residue probe --------------------------------------------------
    def residue(self) -> dict:
        """Every refcount the unwind paths are responsible for, as a
        dict that must be ALL ZEROS at idle. Cached trie blocks are a
        cache, not a leak — ``slot_pages`` subtracts them, and a leaked
        PIN shows up as ``shared_pins`` (a cached page with refcount
        still > 1 while no slot references it)."""
        b = self.batcher
        out = {}
        _, in_use, _ = b._allocator.stats()
        cached = 0
        shared_pins = 0
        if b._radix is not None:
            rs = b._radix.stats()
            cached = rs["prefix_cached_blocks"]
            shared_pins = rs["prefix_shared_pages"]
        out["slot_pages"] = in_use - cached
        out["shared_pins"] = shared_pins
        if getattr(b, "_adapters", None) is not None:
            out["adapter_pins"] = sum(
                b._adapters.stats()["adapter_pins"].values())
        if getattr(b, "_remote", None) is not None:
            out["staged_jobs"] = len(b._remote_jobs)
            out["ready_handoffs"] = b._transfer.ready_depth()
        if self.engine is not None and getattr(self.engine, "_journal",
                                               None) is not None:
            out["journal_depth"] = self.engine._journal.depth()
        return out

    def assert_clean(self, context: str = "") -> None:
        leaks = {k: v for k, v in self.residue().items() if v != 0}
        if leaks:
            where = f" after {context}" if context else ""
            raise AssertionError(f"leak residue{where}: {leaks}")

    # -- the sweep ------------------------------------------------------
    def sweep(self, drive: Callable[[str], None],
              boundaries: Optional[Sequence[str]] = None) -> List[str]:
        """Arm each boundary in turn, let ``drive(boundary)`` push one
        request through the fault, then disarm and assert zero residue.
        Returns the boundaries actually swept (whose fault FIRED — a
        boundary the drive never reached raises, so a sweep cannot
        silently skip a layer)."""
        swept = []
        for boundary in (boundaries or self.boundaries()):
            before = self.fired
            self.arm(boundary)
            try:
                drive(boundary)
            finally:
                self.disarm()
            if self.fired == before:
                raise AssertionError(
                    f"leaksweep: fault at {boundary!r} never fired — "
                    f"the drive did not reach this boundary")
            self.assert_clean(context=boundary)
            swept.append(boundary)
        return swept


class DispatchFailer:
    """Scripted dispatch-level failure for a replica's BatcherService:
    wraps ``submit_sync`` so call *i* consults ``schedule[i]`` before
    delegating — the repeated-failure shape that trips the fleet's
    per-replica breaker (consecutive dispatch failures) without ever
    touching the batcher loop. Latency entries advance the FaultClock, so
    breaker reset windows can elapse in zero wall time."""

    def __init__(self, service: Any, schedule: FaultSchedule,
                 clock: Optional[FaultClock] = None):
        self.schedule = schedule
        self.clock = clock
        self.calls = 0
        self._real = service.submit_sync
        self._lock = threading.Lock()
        service.submit_sync = self._submit_sync

    def _submit_sync(self, *args, **kwargs):
        with self._lock:
            spec = self.schedule[self.calls]
            self.calls += 1
        if spec.latency_s and self.clock is not None:
            self.clock.advance(spec.latency_s)
        if spec.error is not None:
            raise spec.error
        return self._real(*args, **kwargs)
