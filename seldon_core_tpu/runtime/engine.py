"""In-process inference-graph engine.

Implements the reference orchestrator's graph semantics
(`engine/src/main/java/io/seldon/engine/predictors/PredictiveUnitBean.java:81-237`):

    per node: transformInput -> route (-1 = all children) -> children ->
              aggregate -> transformOutput
    meta: merge tags, accumulate metrics, record routing + requestPath
    feedback: deliver to node, then replay only down the routed branch

with two deliberate architecture changes:

1. **One process, zero hops.** The reference pays a network round-trip and an
   ndarray<->proto codec per node (`service/InternalPredictionService.java:
   354-443`). Here every in-process node is a direct call; only nodes with an
   explicit ``endpoint`` go over the network (runtime.remote).
2. **Whole-graph XLA fusion.** Router-free subgraphs whose components expose
   ``jax_fn()`` are composed into a single jitted function at build time, so a
   MODEL->COMBINER fan-out executes as one fused XLA program on TPU rather
   than N async futures (`PredictiveUnitBean.java:167-177`'s thread pool).

The engine also builds graph state ONCE at startup — the reference rebuilds it
per request (`service/PredictionService.java:113`), which SURVEY.md flags as a
hot-path cost to avoid.
"""

from __future__ import annotations

import asyncio
import inspect
import logging
import secrets
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Dict, List, Optional, Tuple

import numpy as np

from seldon_core_tpu.components import dispatch
from seldon_core_tpu.components.builtin import make_builtin
from seldon_core_tpu.components.component import SeldonComponent
from seldon_core_tpu.contracts.graph import (
    PredictiveUnit,
    PredictorSpec,
    UnitImplementation,
    UnitMethod,
    UnitType,
)
from seldon_core_tpu.contracts.payload import (
    Feedback,
    Meta,
    SeldonError,
    SeldonMessage,
    SeldonMessageList,
)
from seldon_core_tpu.runtime.resilience import (
    BreakerOpen,
    CircuitBreaker,
    Deadline,
    ResilienceConfig,
    ResumeJournal,
    ResumeMarker,
    RetryBudget,
    ShedError,
    current_deadline,
    deadline_scope,
    failure_counts_for_breaker,
)
from seldon_core_tpu.tracing import get_tracer

logger = logging.getLogger(__name__)

TAG_PARTIAL_RESPONSE = "seldon.io/partial-response"
TAG_DROPPED_BRANCHES = "seldon.io/dropped-branches"
TAG_REROUTED = "seldon.io/rerouted"

ComponentFactory = Callable[[PredictiveUnit], SeldonComponent]


class _Suspended(Exception):
    """A graph coroutine suspended on real async work despite
    has_async_nodes=False — the detection heuristic missed an async
    component (e.g. a sync method returning an awaitable, or a callable
    object with async __call__). Callers degrade to the event-loop path."""


def _drive_sync(coro):
    """Run a coroutine that never truly suspends (fully-local graph: every
    await is another such coroutine) to completion without an event loop.
    One send() reaches the first real suspension point — which must not
    exist — or StopIteration with the result."""
    try:
        coro.send(None)
    except StopIteration as stop:
        return stop.value
    coro.close()
    raise _Suspended()


def _is_async_component(comp) -> bool:
    """Does this component's execution leave the process or suspend for real
    (remote endpoint, is_async marker, or any `async def` method)?"""
    if comp is None:
        return False
    from seldon_core_tpu.runtime.remote import RemoteComponent

    if isinstance(comp, RemoteComponent) or getattr(comp, "is_async", False):
        return True
    # _call also supports plain `async def` methods (awaitable
    # results) without the is_async marker — those suspend for real
    for name in ("predict", "transform_input", "transform_output",
                 "route", "aggregate", "send_feedback",
                 "predict_raw", "transform_input_raw",
                 "transform_output_raw", "route_raw",
                 "aggregate_raw", "send_feedback_raw"):
        meth = getattr(comp, name, None)
        if meth is not None and inspect.iscoroutinefunction(meth):
            return True
    return False


def make_puid() -> str:
    """Request id: 26 base32-ish chars, the entropy class of the reference's
    SecureRandom 130-bit id (`service/PredictionService.java:77-83`)."""
    return secrets.token_hex(16)


def replica_load(component: Any) -> Tuple[float, float]:
    """Load score for least-loaded replica dispatch, from the signals the
    serving stack already exports (no new instrumentation): primary = the
    work queued ahead of a new request (admission backlog + occupied
    batcher slots — staged prefill handoffs count HERE, through the
    prefilling slot each remote admission holds until commit/shed, so
    they are not tallied twice off the TransferQueue), secondary = KV
    page-pool pressure (in-use fraction — the shed-proximity signal).
    Components without a batcher score (0, 0): an idle plain component
    is as good a target as an idle LLM replica."""
    svc = getattr(component, "_batcher_service", None)
    if svc is None:
        return (0.0, 0.0)
    b = svc.batcher
    queued = len(b._pending) + sum(
        1 for s in b._slots if s.active or s.prefilling)
    from seldon_core_tpu.models.cache import RESERVED_PAGES

    total, in_use, _ = b._allocator.stats()
    usable = max(total - RESERVED_PAGES, 1)
    return (float(queued), in_use / usable)


class _ResumeEntry:
    """One fleet-dispatched generation's recovery record
    (docs/resilience.md "Fleet fault tolerance"): everything needed to
    re-admit it bit-exactly on a surviving replica — identity
    (tenant/SLO class/adapter), the pinned seed, the tokenized prompt,
    and the tokens DELIVERED so far (``len(tokens)`` is also the
    rng-split count to fast-forward by: the chain consumes exactly one
    split per emitted token). Appends happen on batcher worker threads
    while the fleet's retry loop reads — every access goes through
    ``ResumeJournal`` (runtime/resilience.py), which owns the lock."""

    __slots__ = ("prompt_ids", "max_new", "seed", "tenant", "slo_class",
                 "adapter", "tokens")

    def __init__(self, prompt_ids, max_new, seed, tenant, slo_class,
                 adapter):
        self.prompt_ids = prompt_ids
        self.max_new = int(max_new)
        self.seed = seed
        self.tenant = tenant
        self.slo_class = slo_class
        self.adapter = adapter
        self.tokens: List[int] = []


class ReplicaSet(SeldonComponent):
    """N identical component replicas behind least-loaded dispatch — the
    in-process analog of the reference's HPA-scaled Deployment fronted by
    the engine's service (PAPER.md layer map). A predictor unit whose
    registered component is a LIST resolves to one of these: each
    predict/generate picks the replica with the least queued work
    (``replica_load`` — admission queue depth, slot occupancy, staged
    prefill handoffs, page-pool pressure), lowest index breaking ties so
    dispatch is deterministic under equal load. With
    ``disaggregation="remote_prefill"`` replicas, this is the "N decode
    replicas + M prefill workers behind one predictor" topology
    (docs/performance.md "Disaggregated serving").

    Elastic membership (docs/control-plane.md): the autoscaler
    (controlplane/autoscaler.py) grows the set with ``add_replica`` and
    shrinks it with ``drain_replica`` -> ``collect_drained``.  Draining
    is the no-drop half of scale-down: a draining replica leaves the
    dispatch pool IMMEDIATELY (no new fleet traffic), keeps serving its
    queued and in-flight requests to completion, and is detached only
    once provably idle — a scale decision can therefore never fail a
    live request.  Membership mutates under ``self._lock`` (the
    autoscaler thread races transport dispatch threads); dispatch works
    on a locked snapshot so a mid-pick mutation can never index past the
    list.

    Fault tolerance (docs/resilience.md "Fleet fault tolerance"): the
    fleet also survives UNPLANNED departure. ``check_health`` ejects a
    replica whose batcher loop crashed or stopped heartbeating
    (quarantine — distinct from drain: a crashed batcher cannot drain),
    half-open breaker probes reinstate it once it answers again, and the
    per-request resume journal lets every in-flight generation on the
    corpse re-admit on a surviving replica with its rng chain
    fast-forwarded — the client's token sequence is bit-exact vs an
    unfaulted run, with at-most-once delivery. Recoveries draw from a
    RetryBudget so a correlated failure storm sheds honestly instead of
    amplifying fleet load."""

    # transports' service discovery (runtime/batcher.py
    # get_batcher_service): the fleet IS the batcher service — it fans
    # submits across replicas and must never be wrapped in its own batcher
    is_fleet = True

    def __init__(self, replicas: List[SeldonComponent]):
        if not replicas:
            raise SeldonError("ReplicaSet needs >= 1 replica", status_code=500)
        self.replicas = list(replicas)
        self._draining: List[SeldonComponent] = []
        # replicas observed idle on the PREVIOUS collect sweep (by id):
        # detach needs two consecutive idle observations — see
        # collect_drained for the dispatch race this grace absorbs
        self._idle_once: set = set()
        self._lock = threading.Lock()
        # one collect sweep at a time (non-blocking): concurrent sweeps
        # (run_forever tick racing an admin tick) would otherwise count
        # as two consecutive idle sightings microseconds apart —
        # collapsing the grace — and double-close the detached batcher
        self._collect_guard = threading.Lock()
        # -- fleet health (ejection / reinstatement) --------------------
        # injectable clock: chaos tests drive staleness and breaker reset
        # windows from a FaultClock instead of wall time
        self.clock: Callable[[], float] = time.monotonic
        # a batcher whose loop has not stamped its heartbeat for this long
        # (while its task claims to be running) counts as wedged; generous
        # because a first-compile device step legitimately blocks the loop
        self.heartbeat_timeout_s: float = 30.0
        # how long an ejected replica sits out before a half-open probe
        # may try to reinstate it
        self.reinstate_after_s: float = 5.0
        self._health: Dict[int, CircuitBreaker] = {}  # id(replica) -> breaker
        self._ejected: List[SeldonComponent] = []
        self._ejections_total = 0
        self._reinstatements_total = 0
        self._resumes_total = 0
        self._resumed_tokens_total = 0
        # -- deterministic request recovery -----------------------------
        # resume journal: every fleet-dispatched generation in flight,
        # at token granularity (appended from batcher worker threads,
        # read by the retry loop — all locking inside ResumeJournal)
        self._journal = ResumeJournal()
        self.retry_budget = RetryBudget(clock=self.clock)
        self._dispatch_pool = None  # lazy: gRPC submit_stream executor

    # -- membership (autoscaler actuator surface) -----------------------
    def members(self) -> List[SeldonComponent]:
        """Snapshot of every attached replica, draining included (their
        metrics/stats still aggregate until detach)."""
        with self._lock:
            return list(self.replicas)

    def draining_members(self) -> List[SeldonComponent]:
        with self._lock:
            return list(self._draining)

    def _dispatchable(self) -> List[SeldonComponent]:
        """The replicas fleet dispatch may target: everyone not draining
        and not ejected — or, if that empties the pool (a config error
        the autoscaler's min_replicas floor prevents, or a total-fleet
        crash), progressively weaker fallbacks, because black-holing
        traffic is strictly worse than touching a draining replica (and
        submitting to a crashed batcher restarts its loop — the built-in
        half-open probe)."""
        with self._lock:
            live = [r for r in self.replicas
                    if r not in self._draining and r not in self._ejected]
            if live:
                return live
            live = [r for r in self.replicas if r not in self._ejected]
            return live or list(self.replicas)

    def add_replica(self, replica: SeldonComponent) -> None:
        """Attach (and load) one replica; it becomes dispatchable
        immediately."""
        if hasattr(replica, "load"):
            replica.load()
        with self._lock:
            self.replicas.append(replica)

    def drain_replica(self, replica: Optional[SeldonComponent] = None
                      ) -> Optional[SeldonComponent]:
        """Begin draining ``replica`` (default: the newest non-draining
        one — LIFO mirrors the page-shed victim order: the newest member
        has the coldest caches).  Returns the replica now draining, or
        None when nothing is eligible (a lone serving replica never
        drains).  The replica's own ``drain()`` hook (BatcherService /
        ContinuousBatcher) is informed so its admission surface reports
        the state, but its in-flight work keeps running untouched."""
        with self._lock:
            # ejected replicas are not drain candidates: a crashed batcher
            # cannot run the drain protocol (quarantine != drain) — the
            # autoscaler replaces them instead (docs/control-plane.md)
            candidates = [r for r in self.replicas
                          if r not in self._draining
                          and r not in self._ejected]
            if len(candidates) <= 1:
                return None  # the last serving replica never drains
            if replica is None:
                replica = candidates[-1]
            elif replica not in candidates:
                return None
            self._draining.append(replica)
        hook = self._replica_hook(replica, "drain")
        if hook is not None:
            hook()
        return replica

    def undrain_replica(self) -> Optional[SeldonComponent]:
        """Cancel the newest drain (the autoscaler's scale-up-mid-drain
        path): the still-warm replica rejoins dispatch — loaded params,
        hot KV/prefix caches — instead of a cold factory build.  Returns
        the resumed replica, or None when nothing is draining."""
        with self._lock:
            if not self._draining:
                return None
            replica = self._draining.pop()
            self._idle_once.discard(id(replica))
        hook = self._replica_hook(replica, "resume")
        if hook is not None:
            hook()
        return replica

    # -- health model (ejection / reinstatement) ------------------------
    def ejected_members(self) -> List[SeldonComponent]:
        with self._lock:
            return list(self._ejected)

    def _breaker_for(self, replica: SeldonComponent) -> CircuitBreaker:
        """The replica's health breaker (created on first use). Ejected ==
        breaker not CLOSED; reinstatement rides the breaker's half-open
        probe machinery. Breaker methods are never called under
        ``self._lock`` (each breaker has its own lock — a fixed
        fleet-lock-then-breaker-lock order would invert against the
        metrics scrape reading breaker state)."""
        rid = id(replica)
        with self._lock:
            br = self._health.get(rid)
            if br is None:
                br = CircuitBreaker(
                    f"replica-{rid:x}", failure_threshold=3,
                    reset_timeout_s=self.reinstate_after_s,
                    clock=self.clock)
                self._health[rid] = br
        return br

    def _eject(self, replica: SeldonComponent) -> bool:
        with self._lock:
            if replica in self.replicas and replica not in self._ejected:
                self._ejected.append(replica)
                self._ejections_total += 1
                return True
        return False

    def check_health(self) -> List[SeldonComponent]:
        """Eject every replica observed dead: batcher loop crashed
        (terminal exception parked in ``batcher.crashed``) or wedged (its
        task claims to run but the heartbeat the loop stamps every turn
        has gone stale on the fleet clock). Called by the autoscaler tick
        and by fleet dispatch after any failure, so a corpse leaves the
        dispatch pool within one loop turn of dying. Returns the replicas
        ejected by THIS sweep."""
        with self._lock:
            candidates = [r for r in self.replicas
                          if r not in self._ejected]
        dead = []
        for r in candidates:
            svc = getattr(r, "_batcher_service", None)
            if svc is None:
                continue
            b = svc.batcher
            if getattr(b, "crashed", None) is not None:
                dead.append(r)
                continue
            task = getattr(b, "_task", None)
            hb = getattr(b, "heartbeat", None)
            if (task is not None and not task.done() and hb is not None
                    and self.heartbeat_timeout_s > 0
                    and self.clock() - hb > self.heartbeat_timeout_s):
                dead.append(r)
        out = []
        for r in dead:
            self._breaker_for(r).trip()  # observed dead: force-open
            if self._eject(r):
                logger.warning("ejecting dead replica from fleet dispatch")
                out.append(r)
        return out

    def _record_dispatch_success(self, replica: SeldonComponent) -> None:
        """A dispatch answered: close the breaker and, if the replica was
        serving an ejection probe, reinstate it into the pool."""
        self._breaker_for(replica).record_success()
        with self._lock:
            if replica in self._ejected:
                self._ejected.remove(replica)
                self._reinstatements_total += 1

    def _record_dispatch_failure(self, replica: SeldonComponent) -> None:
        """An infrastructure failure from a dispatch: count it on the
        breaker (consecutive failures open it; a failed half-open probe
        re-opens it) and quarantine once the breaker leaves CLOSED."""
        br = self._breaker_for(replica)
        br.record_failure()
        if br.state_code() != 0:  # no longer CLOSED -> quarantine
            self._eject(replica)

    @staticmethod
    def _recoverable(exc: BaseException) -> bool:
        """Which dispatch failures fleet recovery may retry on a sibling:
        infrastructure deaths only. Backpressure (ShedError/BreakerOpen)
        passes through honestly — retrying a shed amplifies exactly the
        load that caused it; client errors (4xx), cancellations and
        timeouts (the original may still be running — a retry would
        double-deliver) are the caller's to see."""
        import concurrent.futures

        if isinstance(exc, (ShedError, BreakerOpen)):
            return False
        if isinstance(exc, (asyncio.CancelledError,
                            concurrent.futures.CancelledError,
                            TimeoutError)):
            return False
        if isinstance(exc, SeldonError):
            return exc.status_code >= 500
        if isinstance(exc, (ValueError, TypeError, KeyError)):
            return False
        return True

    @staticmethod
    def _replica_hook(replica: SeldonComponent, name: str):
        """The replica's drain/is_idle surface: on the component itself,
        else on its batcher service (LLM replicas keep their serving
        state there)."""
        hook = getattr(replica, name, None)
        if hook is not None:
            return hook
        svc = getattr(replica, "_batcher_service", None)
        return getattr(svc, name, None) if svc is not None else None

    def collect_drained(self) -> List[SeldonComponent]:
        """Detach every draining replica that has gone idle (its own
        ``is_idle()`` when exposed, else a zeroed ``replica_load``) and
        close its batcher service.  Replicas still holding work stay
        attached and keep serving it — this is the "let in-flight slots
        finish, then detach" half of the drain contract.

        Detach needs TWO consecutive idle sweeps plus an idle re-check
        after removal (with reattach on failure): a dispatcher that
        picked this replica just before the drain could submit after a
        single idle observation, and closing under it would fail a live
        request.  The grace bounds the remaining exposure to a pick held
        across two full autoscaler ticks — and even that tail is
        retryable, not fatal (a closed batcher sheds 503+Retry-After
        back through routing).  One sweep runs at a time (concurrent
        callers return [] immediately): overlapping sweeps would count
        two "consecutive" sightings in one instant and detach twice."""
        if not self._collect_guard.acquire(blocking=False):
            return []
        try:
            return self._collect_locked()
        finally:
            self._collect_guard.release()

    def _collect_locked(self) -> List[SeldonComponent]:
        with self._lock:
            draining = list(self._draining)
        done = []
        for r in draining:
            idle_fn = self._replica_hook(r, "is_idle")

            def idle() -> bool:
                return idle_fn() if idle_fn is not None else \
                    replica_load(r) == (0.0, 0.0)

            if not idle():
                with self._lock:
                    self._idle_once.discard(id(r))
                continue
            with self._lock:
                if id(r) not in self._idle_once:
                    self._idle_once.add(id(r))  # first sighting: grace
                    first_sighting = True
                else:
                    first_sighting = False
            if first_sighting:
                continue
            with self._lock:
                if r in self.replicas:
                    self.replicas.remove(r)
                if r in self._draining:
                    self._draining.remove(r)
            if not idle():
                # a submit landed between the sweep check and removal:
                # reattach and try again next tick — never close under it
                with self._lock:
                    self.replicas.append(r)
                    self._draining.append(r)
                    self._idle_once.discard(id(r))
                continue
            with self._lock:
                self._idle_once.discard(id(r))
            svc = getattr(r, "_batcher_service", None)
            if svc is not None:
                try:
                    svc.close()
                except Exception:  # detaching must not fail the tick
                    logger.exception("closing drained replica's batcher")
            done.append(r)
        return done

    def load(self) -> None:
        for r in self.members():
            if hasattr(r, "load"):
                r.load()

    def pick(self) -> SeldonComponent:
        """The least-loaded dispatchable replica right now (scores re-read
        per call — the signals mutate under their own locks on the
        serving path)."""
        reps = self._dispatchable()
        best, best_score = reps[0], replica_load(reps[0])
        for r in reps[1:]:
            score = replica_load(r)
            if score < best_score:
                best, best_score = r, score
        return best

    def pick_for(self, prompt: Any) -> SeldonComponent:
        """Prefix-aware dispatch for chat traffic: the replica whose radix
        prefix cache (runtime/radix.py) already holds the LONGEST cached
        prefix of ``prompt`` wins — a hit there costs block-table entries
        while any other replica recomputes the whole prefill — with
        least-loaded as tiebreak and as fallback when nobody caches
        anything (``prefix_match_len`` is an O(prompt) host-side probe
        under the replica's own locks: cheap enough to run per dispatch).
        Lowest index breaks full ties so routing stays deterministic."""
        reps = self._dispatchable()
        prompt = self._encode_once(prompt, reps)
        best, best_key = None, None
        for i, r in enumerate(reps):
            match = 0
            probe = getattr(r, "prefix_match_len", None)
            if probe is not None and prompt is not None:
                match = int(probe(prompt))
            key = (-match, replica_load(r), i)
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _encode_once(self, prompt: Any,
                     reps: Optional[List[SeldonComponent]] = None):
        """Tokenize a string prompt ONCE before fanning the probe out —
        per-replica `prefix_match_len(str)` would re-encode a growing
        chat transcript N times per dispatch (replicas share the
        tokenizer config by construction; a replica without one just
        gets the raw prompt)."""
        if not isinstance(prompt, str):
            return prompt
        for r in (reps if reps is not None else self.members()):
            tok = getattr(r, "_tokenizer", None)
            if tok is not None:
                return tok.encode(prompt)
        return prompt

    def loads(self) -> List[Tuple[float, float]]:
        return [replica_load(r) for r in self.members()]

    def prefix_match_len(self, prompt: Any) -> int:
        """Fleet-level probe: the best cached-prefix length any replica
        offers (lets ReplicaSets nest / upstream routers see the fleet's
        coverage as one number)."""
        reps = self.members()
        prompt = self._encode_once(prompt, reps)
        out = 0
        for r in reps:
            probe = getattr(r, "prefix_match_len", None)
            if probe is not None:
                out = max(out, int(probe(prompt)))
        return out

    # -- fleet batcher-service protocol ---------------------------------
    # The transports reach LLM serving through get_batcher_service /
    # ensure_stream_service (runtime/batcher.py), which short-circuit to
    # the fleet itself: submit/submit_sync/submit_stream here mirror
    # BatcherService's surface but fan across replicas with journaled
    # deterministic recovery (docs/resilience.md "Fleet fault tolerance").

    @property
    def batcher(self):
        """Transports call ``svc.batcher.accommodates`` — the fleet
        answers for itself."""
        return self

    def accommodates(self, prompt: Any,
                     max_new_tokens: Optional[int] = None) -> bool:
        """Delegates to one dispatchable replica's batcher (replicas are
        identical by construction, so one answer speaks for the set)."""
        from seldon_core_tpu.runtime.batcher import ensure_stream_service

        for r in self._dispatchable():
            if hasattr(r, "generate"):
                return ensure_stream_service(r).batcher.accommodates(
                    prompt, max_new_tokens)
        return False

    async def submit(self, prompt: Any, max_new_tokens: Optional[int] = None,
                     on_token: Optional[Any] = None,
                     info: Optional[dict] = None,
                     seed: Optional[int] = None,
                     trace: Optional[Any] = None,
                     tenant: Optional[str] = None,
                     slo_class: Optional[str] = None,
                     adapter: Optional[str] = None,
                     deadline_s: Optional[float] = None,
                     resume_tokens: int = 0) -> List[int]:
        return await asyncio.to_thread(
            self._fleet_submit_blocking, prompt, max_new_tokens, on_token,
            info, seed, trace, tenant, slo_class, adapter, deadline_s)

    def submit_sync(self, prompt: Any, max_new_tokens: Optional[int] = None,
                    timeout_s: float = 600.0,
                    info: Optional[dict] = None,
                    seed: Optional[int] = None,
                    trace: Optional[Any] = None,
                    tenant: Optional[str] = None,
                    slo_class: Optional[str] = None,
                    adapter: Optional[str] = None,
                    deadline_s: Optional[float] = None,
                    on_token: Optional[Any] = None,
                    resume_tokens: int = 0) -> List[int]:
        return self._fleet_submit_blocking(
            prompt, max_new_tokens, on_token, info, seed, trace, tenant,
            slo_class, adapter, deadline_s, timeout_s=timeout_s)

    def submit_stream(self, prompt: Any,
                      max_new_tokens: Optional[int] = None,
                      on_token: Optional[Any] = None,
                      info: Optional[dict] = None,
                      seed: Optional[int] = None,
                      trace: Optional[Any] = None,
                      tenant: Optional[str] = None,
                      slo_class: Optional[str] = None,
                      adapter: Optional[str] = None,
                      deadline_s: Optional[float] = None,
                      resume_tokens: int = 0):
        """Streaming submit from a sync thread (the gRPC servicer):
        returns a concurrent.futures.Future of the final token list while
        ``on_token`` pumps — same contract as BatcherService."""
        with self._lock:
            pool = self._dispatch_pool
            if pool is None:
                from concurrent.futures import ThreadPoolExecutor

                pool = ThreadPoolExecutor(
                    max_workers=32, thread_name_prefix="fleet-dispatch")
                self._dispatch_pool = pool
        return pool.submit(
            self._fleet_submit_blocking, prompt, max_new_tokens, on_token,
            info, seed, trace, tenant, slo_class, adapter, deadline_s)

    def _pick_with_probe(self, prompt: Any
                         ) -> Tuple[SeldonComponent, bool]:
        """Dispatch target for one attempt: an ejected replica whose
        breaker grants a half-open probe slot wins (reinstatement rides
        real traffic — the retry loop absorbs a failed probe), otherwise
        prefix-aware least-loaded routing over the healthy pool."""
        with self._lock:
            ejected = list(self._ejected)
        for r in ejected:
            if self._breaker_for(r).allow():
                return r, True
        return self.pick_for(prompt), False

    def _fleet_submit_blocking(self, prompt: Any,
                               max_new_tokens: Optional[int] = None,
                               on_token: Optional[Any] = None,
                               info: Optional[dict] = None,
                               seed: Optional[int] = None,
                               trace: Optional[Any] = None,
                               tenant: Optional[str] = None,
                               slo_class: Optional[str] = None,
                               adapter: Optional[str] = None,
                               deadline_s: Optional[float] = None,
                               timeout_s: float = 600.0) -> List[int]:
        """One fleet generation, end to end: journal it, dispatch to the
        best replica, and on an infrastructure death resume the
        interrupted chain bit-exactly on a survivor.

        Determinism: an unseeded request gets a journaled random seed
        BEFORE first dispatch, so greedy and sampled generations alike
        live on one pinned rng chain that a resume can fast-forward
        (batcher._sample_first, on the device). The ``ResumeJournal`` records each token
        under its lock BEFORE forwarding it to the client, so a resume
        skips exactly the delivered prefix — at-most-once delivery, never
        a duplicate. The batcher's crash handler fires ``on_token(None)``
        at its victims; the wrapper swallows it (the fleet owns the
        terminal None) so a streaming client survives the failover
        without observing a premature end-of-stream."""
        from seldon_core_tpu.runtime.batcher import ensure_stream_service

        self.check_health()
        self.retry_budget.note_request()
        reps = self._dispatchable()
        ids = self._encode_once(prompt, reps)
        can_resume = not isinstance(ids, str)
        prompt_ids = (list(int(t) for t in np.asarray(ids).ravel())
                      if can_resume else ids)
        if max_new_tokens is None:
            for r in reps:
                mn = getattr(r, "max_new_tokens", None)
                if mn is not None:
                    max_new_tokens = int(mn)
                    break
        orig_max_new = int(max_new_tokens or 16)
        if seed is None:
            # pin the chain so a resume can replay it (greedy output is
            # seed-independent; unseeded SAMPLED fleet output was random
            # anyway — now it is random-but-resumable)
            seed = secrets.randbits(31)
        entry = _ResumeEntry(prompt_ids, orig_max_new, seed,
                             tenant, slo_class, adapter)
        jid = self._journal.record(entry)

        def wrapped(tok):
            if tok is None:
                return  # crash-handler unblock: the fleet owns the real one
            if isinstance(tok, ResumeMarker):
                if on_token is not None:
                    on_token(tok)
                return
            self._journal.append(jid, int(tok))
            if on_token is not None:
                on_token(tok)

        try:
            while True:
                done = self._journal.delivered(jid)
                n = len(done)
                if n >= orig_max_new:
                    return done  # the crash raced completion
                if n > 0:
                    submit_ids = prompt_ids + done
                    remaining = orig_max_new - n
                else:
                    submit_ids, remaining = prompt_ids, orig_max_new
                replica, probing = self._pick_with_probe(submit_ids)
                if n > 0:
                    self._note_resume(n, trace)
                    wrapped(ResumeMarker(n))
                try:
                    svc = ensure_stream_service(replica)
                    toks = svc.submit_sync(
                        submit_ids, remaining, timeout_s=timeout_s,
                        info=info, seed=seed, trace=trace, tenant=tenant,
                        slo_class=slo_class, adapter=adapter,
                        deadline_s=deadline_s, on_token=wrapped,
                        resume_tokens=n)
                except BaseException as e:
                    if probing:
                        self._breaker_for(replica).release_probe()
                    if not self._recoverable(e):
                        raise
                    self._record_dispatch_failure(replica)
                    self.check_health()  # a crash ejects before the retry
                    delivered = len(self._journal.delivered(jid))
                    if delivered > 0 and not can_resume:
                        raise  # mid-stream, no token-level journal: honest
                    if not self.retry_budget.take():
                        raise ShedError(
                            "fleet retry budget exhausted (correlated "
                            "failures); request not recovered",
                            retry_after_s=self.reinstate_after_s)
                    continue
                self._record_dispatch_success(replica)
                # the replica's returned segment is authoritative for the
                # tail (on_token elides EOS; the result never does)
                return done + [int(t) for t in toks]
        finally:
            self._journal.discard(jid)
            if on_token is not None:
                try:
                    on_token(None)
                except Exception:
                    pass

    def _note_resume(self, tokens_delivered: int,
                     trace: Optional[Any]) -> None:
        """Count + trace one mid-stream recovery (``llm.resume`` span)."""
        with self._lock:
            self._resumes_total += 1
            self._resumed_tokens_total += tokens_delivered
        tp = None
        if trace is not None and getattr(trace, "trace_id", None):
            span_id = getattr(trace, "parent_span_id", None) or "0" * 16
            flag = "01" if getattr(trace, "sampled", True) else "00"
            tp = f"00-{trace.trace_id}-{span_id}-{flag}"
        with get_tracer().span("llm.resume", traceparent=tp,
                               tokens_delivered=tokens_delivered):
            pass

    # the component surface delegates to the chosen replica; generate is
    # included so LLM graph nodes (and their transports) route too
    def predict(self, X, names, meta=None):
        return self.pick().predict(X, names, meta)

    def generate(self, prompts=None, *a, **kw):
        # route on the FIRST prompt's cached-prefix coverage (single-
        # prompt requests are the chat shape prefix routing exists for;
        # multi-prompt batches still benefit from the first's locality)
        probe = None
        if prompts is not None and len(prompts) > 0:
            probe = prompts[0]
        self.retry_budget.note_request()
        replica = self.pick() if probe is None else self.pick_for(probe)
        try:
            out = replica.generate(prompts, *a, **kw)
        except Exception as e:
            # pre-first-token failover (ISSUE 16 satellite): generate()
            # had not delivered anything, so retrying the WHOLE call on a
            # healthy sibling is idempotent by construction — once, and
            # only from the bounded retry budget
            if not self._recoverable(e):
                raise
            self._record_dispatch_failure(replica)
            self.check_health()
            siblings = [r for r in self._dispatchable() if r is not replica]
            if not siblings:
                raise
            if not self.retry_budget.try_spend():
                raise ShedError(
                    "fleet retry budget exhausted (correlated failures); "
                    "generate not failed over",
                    retry_after_s=self.reinstate_after_s)
            alt = min(siblings, key=replica_load)
            out = alt.generate(prompts, *a, **kw)
            self._record_dispatch_success(alt)
            return out
        self._record_dispatch_success(replica)
        return out

    def tags(self) -> Dict[str, Any]:
        from seldon_core_tpu.components.component import client_custom_tags

        reps = self.members()
        out: Dict[str, Any] = {"replicas": len(reps)}
        for i, r in enumerate(reps):
            for k, v in client_custom_tags(r).items():
                out[f"replica_{i}_{k}"] = v
        return out

    def llm_stats(self) -> Dict[str, Any]:
        """Aggregated snapshot for /metrics: numeric gauges/counters sum
        (inside dicts too: per-phase tallies, histogram buckets), drained
        lists concatenate (each replica's deques drain exactly once, same
        as solo), strings/configs come from replica 0."""
        stats_list = [r.llm_stats() for r in self.members()
                      if hasattr(r, "llm_stats")]
        if not stats_list:
            return {}
        fractions = ("kv_occupancy", "kv_page_fragmentation",
                     "spec_accept_rate", "spec_tokens_per_forward",
                     "spec_draft_overhead_fraction")

        def add(cur, v):
            if isinstance(v, list) and isinstance(cur, list):
                return cur + v
            if isinstance(v, dict) and isinstance(cur, dict):
                # per-phase tallies and histogram buckets: key by key
                out = dict(cur)
                for k, x in v.items():
                    out[k] = add(out[k], x) if k in out else x
                return out
            if isinstance(v, (int, float)) and isinstance(
                    cur, (int, float)) and not isinstance(v, bool):
                return cur + v
            return cur

        merged = dict(stats_list[0])
        for stats in stats_list[1:]:
            for k, v in stats.items():
                if k in merged:
                    merged[k] = add(merged[k], v)
        for k in fractions:  # fractions average; sums would exceed 1.0
            if isinstance(merged.get(k), (int, float)):
                merged[k] = merged[k] / len(stats_list)
        # fleet-level fault-tolerance tallies (ours, not the replicas'):
        # stamped AFTER the merge so a replica key can never shadow them
        with self._lock:
            merged["fleet_ejections_total"] = self._ejections_total
            merged["fleet_reinstatements_total"] = self._reinstatements_total
            merged["fleet_resumes_total"] = self._resumes_total
            merged["fleet_resumed_tokens_total"] = self._resumed_tokens_total
        merged["fleet_resume_journal_depth"] = self._journal.depth()
        merged["fleet_retry_budget_exhausted_total"] = (
            self.retry_budget.snapshot()["exhausted_total"])
        return merged


@dataclass
class UnitState:
    """Built (static) state for one graph node: resolved component + children.

    Equivalent of `engine/.../PredictiveUnitState.java:37-125`, constructed
    once at engine build, never per request.
    """

    name: str
    unit: PredictiveUnit
    component: Optional[SeldonComponent]
    children: List["UnitState"] = field(default_factory=list)
    image: str = ""
    # Per-node circuit breaker; built only for remote/async nodes (local
    # in-process calls cannot flake independently of the server itself).
    breaker: Optional[CircuitBreaker] = None
    # Set when this node's entire subtree fused into one jitted callable.
    fused_fn: Optional[Callable[[Any], Any]] = None
    # All units covered by fused_fn, and the component whose class_names/
    # encoding rules own the final payload (the last node in unfused flow).
    fused_units: List["UnitState"] = field(default_factory=list)
    fused_owner: Optional[SeldonComponent] = None

    @property
    def methods(self) -> List[UnitMethod]:
        return self.unit.resolved_methods()

    def has_method(self, m: UnitMethod) -> bool:
        return m in self.methods


class PredictorState:
    """Immutable built graph for one predictor."""

    def __init__(self, spec: PredictorSpec, root: UnitState):
        self.spec = spec
        self.root = root

    def walk(self):
        stack = [self.root]
        while stack:
            s = stack.pop()
            yield s
            stack.extend(s.children)

    def unit_by_name(self, name: str) -> Optional[UnitState]:
        for s in self.walk():
            if s.name == name:
                return s
        return None


class GraphEngine:
    """Builds and executes a predictor graph.

    components: name -> live SeldonComponent for in-process user nodes.
    factory: fallback resolver for units this engine cannot resolve itself
             (used by servers/ to wire prepackaged servers from modelUri).
    """

    def __init__(
        self,
        spec: PredictorSpec,
        components: Optional[Dict[str, SeldonComponent]] = None,
        factory: Optional[ComponentFactory] = None,
        fuse: bool = True,
        remote_client: Optional[Any] = None,
        annotations: Optional[Dict[str, str]] = None,
        resilience: Optional[ResilienceConfig] = None,
    ):
        self.spec = spec
        self._components = dict(components or {})
        self._factory = factory
        self._fuse = fuse
        self._remote_client = remote_client
        # deployment annotations tune the remote-node client (retry counts,
        # connect/read deadlines — the reference's per-deployment flags)
        self._annotations = dict(annotations or {})
        self.resilience = resilience or ResilienceConfig.from_annotations(self._annotations)
        self.state = self._build(spec)
        if fuse:
            self._try_fuse(self.state.root)
        # A graph whose every node is local+synchronous never truly suspends:
        # predict()/send_feedback() coroutines run to completion without an
        # event loop (the only awaits are child coroutines and — avoided
        # below for this case — asyncio.gather). The IPC drain uses this to
        # execute plane-3 frames inline on its own thread, skipping the
        # event-loop hop entirely.
        self.has_async_nodes = any(
            _is_async_component(s.component) for s in self.state.walk()
        )
        # Breakers wrap remote/async node calls only: a purely local call
        # cannot fail independently of this process, so a breaker there would
        # just add lock traffic to the fused hot path.
        for s in self.state.walk():
            if _is_async_component(s.component):
                s.breaker = self.resilience.make_breaker(s.name)

    def breakers(self) -> List[Tuple[str, CircuitBreaker]]:
        """(node name, breaker) for every breaker-wrapped node, stable order
        — the metrics scrape walks this to publish state gauges."""
        out = [(s.name, s.breaker) for s in self.state.walk() if s.breaker is not None]
        return sorted(out, key=lambda kv: kv[0])

    # ------------------------------------------------------------------
    # Build
    # ------------------------------------------------------------------
    def _build(self, spec: PredictorSpec) -> PredictorState:
        root = self._build_unit(spec.graph)
        return PredictorState(spec, root)

    def _build_unit(self, unit: PredictiveUnit) -> UnitState:
        component = self._resolve(unit)
        image = type(component).__name__ if component is not None else (
            f"{unit.endpoint.service_host}:{unit.endpoint.service_port}" if unit.endpoint else ""
        )
        state = UnitState(
            name=unit.name,
            unit=unit,
            component=component,
            children=[self._build_unit(c) for c in unit.children],
            image=image,
        )
        return state

    def _resolve(self, unit: PredictiveUnit) -> Optional[SeldonComponent]:
        if unit.name in self._components:
            comp = self._components[unit.name]
            if isinstance(comp, (list, tuple)):
                # a list of components registers N replicas behind
                # least-loaded dispatch; cache the wrapper so repeated
                # builds (and the metrics scrape walking _components)
                # see ONE ReplicaSet, not one per resolve
                comp = ReplicaSet(list(comp))
                self._components[unit.name] = comp
        elif unit.implementation is not None and unit.implementation not in (
            UnitImplementation.UNKNOWN_IMPLEMENTATION,
        ):
            comp = self._make_implementation(unit)
        elif unit.endpoint is not None and unit.endpoint.service_host:
            from seldon_core_tpu.runtime.remote import RemoteComponent

            comp = RemoteComponent(
                unit.endpoint, client=self._remote_client,
                annotations=self._annotations or None,
            )
        elif self._factory is not None:
            comp = self._factory(unit)
        else:
            raise SeldonError(
                f"Cannot resolve component for unit {unit.name!r}: no registered component, "
                f"implementation, or endpoint",
                reason="BAD_GRAPH",
                status_code=500,
            )
        if comp is not None and hasattr(comp, "load"):
            comp.load()
        return comp

    def _make_implementation(self, unit: PredictiveUnit) -> SeldonComponent:
        impl = unit.implementation
        params = unit.parameters_dict()
        try:
            return make_builtin(impl, params)
        except ValueError:
            pass
        from seldon_core_tpu.servers import make_prepackaged_server

        return make_prepackaged_server(impl, unit.model_uri, params)

    # ------------------------------------------------------------------
    # Whole-graph XLA fusion
    # ------------------------------------------------------------------
    def _try_fuse(self, state: UnitState):
        """Bottom-up: if this node and all children are pure jax fns (and no
        routing decision is needed), produce one jitted callable for the
        subtree. Returns (fn, covered_units, owner) or None. Falls back
        silently; correctness never depends on fusion."""
        child_results = [self._try_fuse(c) for c in state.children]

        fusible = (
            state.component is not None
            and not state.has_method(UnitMethod.ROUTE)
            and all(r is not None for r in child_results)
        )
        if not fusible:
            return None
        pair = state.component.jax_fn() if hasattr(state.component, "jax_fn") else None
        if pair is None:
            return None
        fn, params = pair

        is_combiner = state.has_method(UnitMethod.AGGREGATE)
        if is_combiner and not state.children:
            # A leaf combiner aggregates a singleton list of the request (the
            # unfused path's behavior); fusing fn(x) directly would instead
            # reduce over the batch dim. Leave it to the host path.
            return None
        if state.children and not is_combiner and len(state.children) > 1:
            return None  # multiple children need a combiner to merge

        import jax
        import jax.numpy as jnp

        if not state.children:
            covered = [state]
            owner = state.component

            def subtree(x, _fn=fn, _p=params):
                return _fn(_p, x)
        elif is_combiner:
            children = [r[0] for r in child_results]
            covered = [state] + [u for r in child_results for u in r[1]]
            owner = state.component  # combiner constructs the merged response

            def subtree(x, _fn=fn, _p=params, _children=children):
                outs = [c(x) for c in _children]
                return _fn(_p, jnp.stack(outs))
        else:
            # transformer/model with a single child: this node transforms the
            # input, the child consumes it and owns the response.
            child, child_units, child_owner = child_results[0]
            covered = [state] + child_units
            owner = child_owner

            def subtree(x, _fn=fn, _p=params, _child=child):
                return _child(_fn(_p, x))

        # Only install a fused executor for MULTI-node subtrees: fusing a lone
        # leaf adds a per-request jit dispatch (and, on this harness, a device
        # round trip) without merging anything — components run their own
        # compiled path (e.g. JAXServer) or host path (stubs) when unfused.
        # The (fn, covered, owner) return still flows upward so a parent can
        # fuse this leaf into a larger program.
        if len(covered) >= 2:
            state.fused_fn = jax.jit(subtree)
            state.fused_units = covered
            state.fused_owner = owner
            logger.info("fused %d-unit subtree at %s into one XLA computation", len(covered), state.name)
        return subtree, covered, owner

    # ------------------------------------------------------------------
    # Predict
    # ------------------------------------------------------------------
    async def predict(
        self, request: SeldonMessage, deadline: Optional[Deadline] = None
    ) -> SeldonMessage:
        if not request.meta.puid:
            request.meta.puid = make_puid()
        puid = request.meta.puid
        # Deadline resolution: explicit arg > transport-set contextvar >
        # deployment default annotation. The scope re-publishes it on the
        # contextvar so remote hops see the budget regardless of which path
        # delivered it.
        if deadline is None:
            deadline = current_deadline()
        if deadline is None and self.resilience.default_deadline_ms:
            deadline = Deadline.from_ms(
                self.resilience.default_deadline_ms, clock=self.resilience.clock
            )
        with deadline_scope(deadline):
            response = await self._get_output(self.state.root, request)
        response.meta.puid = puid
        return response

    def predict_sync(self, request: SeldonMessage) -> SeldonMessage:
        if self.has_async_nodes:
            return asyncio.run(self.predict(request))
        try:
            return _drive_sync(self.predict(request))
        except _Suspended:
            self._degrade_to_async("predict")
            return asyncio.run(self.predict(request))

    def send_feedback_sync(self, feedback: "Feedback") -> SeldonMessage:
        if self.has_async_nodes:
            return asyncio.run(self.send_feedback(feedback))
        try:
            return _drive_sync(self.send_feedback(feedback))
        except _Suspended:
            self._degrade_to_async("send_feedback")
            return asyncio.run(self.send_feedback(feedback))

    def _degrade_to_async(self, op: str) -> None:
        """Async-detection miss (a component's sync method returned an
        awaitable, or an async __call__ object slipped past the
        iscoroutinefunction check): flip the graph to the event-loop path
        permanently so this and every later request runs there instead of
        500ing.

        Caveat, by design: the aborted inline attempt already executed every
        node UPSTREAM of the suspension point, and the retry re-executes
        them — for this one degraded request, side-effectful upstream
        components (feedback counters, external calls) fire twice. The
        alternative (500 after the same partial execution, every request)
        is strictly worse; the log below makes the one-time re-execution
        auditable."""
        logger.warning(
            "graph suspended on real async work during sync %s despite "
            "has_async_nodes=False; degrading to the event-loop path. "
            "Nodes upstream of the suspension re-execute for this request "
            "(side effects may fire twice, once).", op)
        self.has_async_nodes = True

    async def _get_output(self, state: UnitState, message: SeldonMessage) -> SeldonMessage:
        # Budget check BEFORE executing this node: an exhausted deadline
        # short-circuits the remaining subtree with 504 instead of doing work
        # the client has already given up on.
        deadline = current_deadline()
        if deadline is not None:
            deadline.check(f"node {state.name}")

        # Fused fast path: the whole subtree is one XLA call. Meta parity with
        # the unfused flow: every covered unit contributes its requestPath
        # entry and tags/metrics; the flow-final component owns the payload
        # encoding and class_names.
        if state.fused_fn is not None and message.which == "data" and message.data is not None:
            arr = message.data.to_numpy()
            out = state.fused_fn(np.asarray(arr, dtype=np.float32) if arr.dtype != np.float32 else arr)
            resp = dispatch.construct_response(state.fused_owner or state.component, False, message, out)
            self._merge_meta(resp, message.meta)
            from seldon_core_tpu.codec.response import response_meta

            for unit in state.fused_units:
                if unit.component is not state.fused_owner:
                    self._merge_meta(resp, response_meta(unit.component, None))
                self._record_path(resp, unit)
            return resp

        # 1. transformInput (for MODEL this is predict — the reference maps
        #    MODEL.transformInput to the predict method,
        #    `PredictorConfigBean.java:30-107`).
        if state.has_method(UnitMethod.TRANSFORM_INPUT):
            if state.unit.type == UnitType.MODEL:
                transformed = await self._call(dispatch.predict, state, message)
            else:
                transformed = await self._call(dispatch.transform_input, state, message)
            self._merge_meta(transformed, message.meta)
        else:
            transformed = message

        # 2. route
        branch = -1
        if state.has_method(UnitMethod.ROUTE) and state.children:
            route_msg = await self._call(dispatch.route, state, transformed)
            branch = dispatch.extract_route(route_msg)
            if branch >= len(state.children):
                raise SeldonError(
                    f"Router {state.name} returned branch {branch} but unit has "
                    f"{len(state.children)} children",
                    status_code=500,
                    reason="BAD_ROUTING",
                )
            if branch >= 0:
                # graceful degradation: reroute away from a branch whose
                # subtree has an open breaker, onto the healthiest sibling
                healthy = self._healthy_branch(state, branch)
                if healthy != branch:
                    logger.warning(
                        "router %s: branch %d unavailable (breaker open), rerouting to %d",
                        state.name, branch, healthy,
                    )
                    rerouted = dict(transformed.meta.tags.get(TAG_REROUTED) or {})
                    rerouted[state.name] = {"from": branch, "to": healthy}
                    transformed.meta.tags[TAG_REROUTED] = rerouted
                    branch = healthy
            transformed.meta.routing[state.name] = branch
            self._merge_meta(transformed, route_msg.meta, routing_only_tags=True)

        # 3. children
        dropped_branches: List[str] = []
        if state.children:
            if branch == -1:
                allow_partial = (
                    self.resilience.allow_partial
                    and state.has_method(UnitMethod.AGGREGATE)
                    and len(state.children) > 1
                )
                if self.has_async_nodes:
                    results = await asyncio.gather(
                        *[self._get_output(c, transformed) for c in state.children],
                        return_exceptions=allow_partial,
                    )
                else:
                    # local components are synchronous: gather buys no
                    # concurrency here, only Task/loop overhead — and
                    # avoiding it keeps the whole coroutine loop-free so
                    # predict_sync can drive it without an event loop
                    results = []
                    for c in state.children:
                        if not allow_partial:
                            results.append(await self._get_output(c, transformed))
                            continue
                        try:
                            results.append(await self._get_output(c, transformed))
                        except SeldonError as e:
                            results.append(e)
                child_outputs = []
                for child, r in zip(state.children, results):
                    if isinstance(r, BaseException):
                        # allow-partial drops only branches rejected by an
                        # open breaker; real execution failures still fail
                        # the request (partial data, yes — silent data loss
                        # from crashing nodes, no)
                        if isinstance(r, BreakerOpen):
                            dropped_branches.append(child.name)
                            continue
                        raise r
                    child_outputs.append(r)
                if state.children and not child_outputs and dropped_branches:
                    raise SeldonError(
                        f"combiner {state.name}: every branch dropped by open "
                        f"circuit breakers ({', '.join(dropped_branches)})",
                        status_code=503,
                        reason="CIRCUIT_OPEN",
                    )
            else:
                # Routed-branch outcome observation: routers exposing
                # ``observe_outcome(branch, latency_s, error)`` (the canary
                # router, analytics/canary.py) see every routed request's
                # subtree wall + error on the engine's INJECTABLE clock —
                # which is what makes SLO comparison deterministic under
                # FaultClock (tests/test_canary.py). Absent the hook this
                # is one getattr per routed request.
                observe = getattr(state.component, "observe_outcome", None)
                if observe is None:
                    child_outputs = [await self._get_output(
                        state.children[branch], transformed)]
                else:
                    t0 = self.resilience.clock()
                    try:
                        child_outputs = [await self._get_output(
                            state.children[branch], transformed)]
                    except asyncio.CancelledError:
                        # client disconnect says nothing about the branch
                        # (the breaker rule, failure_counts_for_breaker):
                        # a disconnect burst during a canary must not
                        # land spurious errors in the candidate's small
                        # window and roll back a healthy candidate
                        raise
                    except BaseException:
                        self._observe_routed(
                            observe, branch, self.resilience.clock() - t0,
                            True)
                        raise
                    self._observe_routed(
                        observe, branch, self.resilience.clock() - t0, False)
        else:
            child_outputs = []

        # 4. aggregate / merge
        if state.has_method(UnitMethod.AGGREGATE):
            if not child_outputs:
                child_outputs = [transformed]
            merged = await self._call(
                dispatch.aggregate, state, SeldonMessageList(messages=list(child_outputs))
            )
            for co in child_outputs:
                self._merge_meta(merged, co.meta)
            if dropped_branches:
                merged.meta.tags[TAG_PARTIAL_RESPONSE] = True
                merged.meta.tags[TAG_DROPPED_BRANCHES] = list(dropped_branches)
        elif len(child_outputs) == 1:
            merged = child_outputs[0]
        elif len(child_outputs) > 1:
            raise SeldonError(
                f"Unit {state.name} has {len(child_outputs)} child outputs but no "
                f"COMBINER to aggregate them",
                status_code=500,
                reason="BAD_GRAPH",
            )
        else:
            merged = transformed

        # 5. transformOutput
        if state.has_method(UnitMethod.TRANSFORM_OUTPUT):
            out = await self._call(dispatch.transform_output, state, merged)
            self._merge_meta(out, merged.meta)
        else:
            out = merged

        self._record_path(out, state)
        return out

    @staticmethod
    def _observe_routed(observe, branch: int, latency_s: float,
                        error: bool) -> None:
        """Feed a routed request's outcome to the router's observation
        hook; observability must never fail the data path."""
        try:
            observe(branch, latency_s, error=error)
        except Exception:
            logger.exception("router observe_outcome hook failed")

    @staticmethod
    def _subtree_available(state: UnitState) -> bool:
        """Non-mutating: is every breaker-wrapped node in this subtree
        currently accepting calls? Routers peek at this before committing a
        request to a branch."""
        stack = [state]
        while stack:
            s = stack.pop()
            if s.breaker is not None and not s.breaker.available():
                return False
            stack.extend(s.children)
        return True

    def _healthy_branch(self, state: UnitState, branch: int) -> int:
        """The routed branch if its subtree is healthy, else the lowest-index
        sibling with no open breakers. All-unhealthy keeps the original
        routing decision (it then fails with CIRCUIT_OPEN, which is the
        honest answer)."""
        if self._subtree_available(state.children[branch]):
            return branch
        for i, child in enumerate(state.children):
            if i != branch and self._subtree_available(child):
                return i
        return branch

    async def _call(self, fn: Callable, state: UnitState, message: Any) -> SeldonMessage:
        comp = state.component
        if comp is None:
            raise SeldonError(f"Unit {state.name} has no component", status_code=500)
        breaker = state.breaker
        if breaker is not None and not breaker.allow():
            raise BreakerOpen(state.name, breaker.retry_in_s())
        # per-node child span (the reference's engine->graph-node topology,
        # PAPER.md §5): parented to the transport's server span via the
        # tracer's contextvar, so a remote node's outbound traceparent
        # (runtime/remote.py) carries this node's span id downstream. A
        # disabled tracer yields None immediately — no per-node cost.
        with get_tracer().span(f"node:{state.name}",
                               method=getattr(fn, "__name__", "")):
            try:
                if getattr(comp, "is_async", False):
                    result = await fn(comp, message)
                else:
                    result = fn(comp, message)
                    if inspect.isawaitable(result):
                        result = await result
            except BaseException as e:
                # Every outcome must resolve a half-open probe, or the breaker
                # wedges with its one probe slot held forever. Counting failures
                # re-open; cancellation judges nothing (release the slot); any
                # other error means the node RESPONDED (4xx and kin) — healthy.
                if breaker is not None:
                    if failure_counts_for_breaker(e):
                        breaker.record_failure()
                    elif isinstance(e, asyncio.CancelledError):
                        breaker.release_probe()
                    else:
                        breaker.record_success()
                raise
        if breaker is not None:
            breaker.record_success()
        return result

    @staticmethod
    def _merge_meta(target: SeldonMessage, source: Meta, routing_only_tags: bool = False) -> None:
        """Merge request/previous meta into a node response, per the reference's
        mergeMeta (`PredictiveUnitBean.java:350-366`): tags union (response
        wins), routing/requestPath union, metrics append."""
        merged_tags = dict(source.tags)
        merged_tags.update(target.meta.tags)
        target.meta.tags = merged_tags
        for k, v in source.routing.items():
            target.meta.routing.setdefault(k, v)
        for k, v in source.request_path.items():
            target.meta.request_path.setdefault(k, v)
        if not routing_only_tags:
            existing = {id(m) for m in target.meta.metrics}
            for m in source.metrics:
                if id(m) not in existing:
                    target.meta.metrics.append(m)
        if source.puid and not target.meta.puid:
            target.meta.puid = source.puid

    @staticmethod
    def _record_path(msg: SeldonMessage, state: UnitState) -> None:
        msg.meta.request_path[state.name] = state.image

    # ------------------------------------------------------------------
    # Feedback
    # ------------------------------------------------------------------
    async def send_feedback(self, feedback: Feedback) -> SeldonMessage:
        return await self._feedback(self.state.root, feedback)

    async def _feedback(self, state: UnitState, feedback: Feedback) -> SeldonMessage:
        # Deliver to this unit if it handles feedback.
        if state.has_method(UnitMethod.SEND_FEEDBACK) and state.component is not None:
            comp = state.component
            if getattr(comp, "is_async", False):
                await dispatch.send_feedback(comp, feedback, unit_id=state.name)
            else:
                result = dispatch.send_feedback(comp, feedback, unit_id=state.name)
                if inspect.isawaitable(result):
                    await result

        # Replay down the routed branch only (`PredictiveUnitBean.java:210-218`).
        if state.children:
            routing = {}
            if feedback.response is not None:
                routing = feedback.response.meta.routing
            branch = routing.get(state.name, -1)
            if branch == -1:
                if self.has_async_nodes:
                    await asyncio.gather(
                        *[self._feedback(c, feedback) for c in state.children])
                else:
                    for c in state.children:
                        await self._feedback(c, feedback)
            elif 0 <= branch < len(state.children):
                await self._feedback(state.children[branch], feedback)
            else:
                raise SeldonError(
                    f"Feedback routing for {state.name} names branch {branch} outside "
                    f"{len(state.children)} children",
                    reason="BAD_ROUTING",
                )
        return SeldonMessage()
