"""Per-slot flight recorder: request-scoped timelines through the serving
hot path.

The aggregate histograms (TTFT, inter-token gap, handoff wall — PR 9) say
*that* tail latency exists; they cannot say why *this* request saw a 200 ms
inter-token gap. The flight recorder answers that: every slot carries a
fixed-size ring of timestamped lifecycle events — admission (with queue
wait), each prefill chunk, disaggregated-handoff stages, every drained
decode step with its token count and speculative accept count, page-grow
stalls, sheds, EOS — written by the batcher at points that ALREADY touch
host state, and materialized into one span tree per request at completion
(fed to the Tracer/OTLP exporter, surfaced at ``/debug/timeline``).

Concurrency discipline (racelint-modeled; proven under deterministic
interleaving in tests/test_schedules.py):

- The per-slot segments and their event rings are SINGLE-WRITER: only the
  batcher loop's serialized offload context (the same context that owns all
  slot bookkeeping) calls ``begin``/``record``/``extend``/``complete``.
  No lock is acquired on the decode dispatch/drain path — the recorder adds
  appends, never synchronization, which is what keeps enabled-tracing
  throughput within the bench guard (benchmarks/llm_batch_bench.py
  ``--tracing``).
- Prefill-slice worker threads never touch a slot ring. They stamp their
  events into the ``Handoff`` record BEFORE publishing it through the
  TransferQueue (ownership transfers under the queue's lock, exactly-once),
  and the batcher copies them in at consume time via ``extend``.
- Only the completed-timeline ring and the scaling aggregates cross
  threads (``/debug/timeline`` + ``/metrics`` readers); they are guarded by
  ``self._lock``, acquired once per REQUEST at completion — never per
  decode step.

Zero work when disabled: the batcher holds ``_flight = None`` unless the
tracer is enabled, every hook is a None check, and no compiled program
changes either way (hlolint contracts are identical with TRACING=0/1).
"""

from __future__ import annotations

import random
import secrets
import threading
import time
from collections import deque
from typing import Any, List, Optional

from seldon_core_tpu.tracing import Span, TraceContext, Tracer, now as wall_now

# event kinds (timeline "kind" field / span names); slot reservation and
# queue wait are segment FIELDS (begin()), not ring events
EV_PREFILL_CHUNK = "prefill_chunk"  # one chunked-prefill dispatch
EV_PREFIX_HIT = "prefix_hit"        # radix prefix-cache hit: tokens served
#                                     from shared pages (fields: tokens
#                                     matched, blocks = block-table entries
#                                     written instead of prefilled) —
#                                     materializes as the llm.prefix_hit
#                                     span child with the matched-block count
EV_FIRST_TOKEN = "first_token"      # commit: prefill-sampled token surfaced
EV_STEP = "step"                    # drained decode step credited to a slot
EV_PAGE_GROW = "page_grow"          # mid-decode page allocation (stall risk)
EV_HANDOFF_STAGED = "handoff_staged"        # remote job staged (disagg)
EV_HANDOFF_COMPUTE = "handoff_compute"      # prefill-slice forward (worker)
EV_HANDOFF_TRANSFER = "handoff_transfer"    # device-to-device KV move
EV_HANDOFF_IMPORT = "handoff_import"        # decode-side page import
EV_SHED = "shed"                    # request shed (503 + Retry-After)
EV_RESUME = "resume"                # fleet recovery: re-admitted with N
                                    # already-delivered tokens (the rng
                                    # chain fast-forwarded past them)

DEFAULT_RING = 512   # events per in-flight request (~max_new steps + admission)
DEFAULT_KEEP = 64    # completed timelines retained for /debug/timeline


class _Segment:
    """One request's in-flight recording: its trace identity and the event
    ring. ``total`` counts every append so ring overflow is observable
    (``events_dropped`` = total - len(ring)). The latency/token signals
    (``t_first``, ``worst_gap``, ``tokens``) accumulate HERE at record
    time, not from the ring at materialization: a generation longer than
    the ring evicts its early events, and deriving TTFT from the ring
    would silently disable TTFT tail-sampling (and undercount tokens) for
    exactly the long slow requests the recorder exists to explain."""

    __slots__ = ("trace", "t_submit", "t_begin", "prompt_tokens", "ring",
                 "total", "t_first", "last_surface", "worst_gap", "tokens",
                 "tags")

    def __init__(self, trace: TraceContext, t_submit: Optional[float],
                 t_begin: float, prompt_tokens: int, ring_size: int,
                 tags: Optional[dict] = None):
        # request identity tags (tenant / slo_class / adapter_id for
        # multi-tenant serving): merged into the materialized timeline
        # and the root span's tags
        self.tags = tags
        self.trace = trace
        self.t_submit = t_submit if t_submit is not None else t_begin
        self.t_begin = t_begin
        self.prompt_tokens = prompt_tokens
        self.ring: Any = deque(maxlen=ring_size)
        self.total = 0
        self.t_first: Optional[float] = None
        self.last_surface: Optional[float] = None
        self.worst_gap: Optional[float] = None
        self.tokens = 0


class FlightRecorder:
    """See module docstring. ``clock`` must match the batcher's timestamp
    source (``time.perf_counter`` — submit()'s ``t_arrival`` and the
    in-flight records' ``t_dispatch`` are drawn from it); materialization
    converts to wall time through the anchor pair captured at init."""

    def __init__(self, n_slots: int, ring_size: int = DEFAULT_RING,
                 keep: int = DEFAULT_KEEP,
                 tail_ttft_s: Optional[float] = None,
                 tail_gap_s: Optional[float] = None,
                 clock=time.perf_counter):
        self.n_slots = int(n_slots)
        self.ring_size = int(ring_size)
        self.tail_ttft_s = tail_ttft_s
        self.tail_gap_s = tail_gap_s
        self._clock = clock
        # perf-counter -> wall anchor (tracing.now() is the wall source so
        # exported spans and Span() timestamps share one clock discipline).
        # REFRESHED at every materialization (_reanchor) rather than frozen
        # at init: a deployment that fixes NTP late and calls
        # tracing.anchor() must see its correction in flight-recorder
        # timestamps too, or node spans and request trees in the same
        # trace would disagree by the whole correction.
        self._wall0 = wall_now()
        self._perf0 = clock()
        self._segs: List[Optional[_Segment]] = [None] * self.n_slots
        # cross-thread surface: completed timelines + scaling aggregates,
        # written once per request under the lock, read by /debug/timeline
        # and /metrics scrape threads
        self._lock = threading.Lock()
        self._completed: Any = deque(maxlen=int(keep))
        self.completed_total = 0
        self.retained = {"head": 0, "tail": 0, "drop": 0}
        self.events_dropped_total = 0
        self._ttft: Any = deque(maxlen=256)
        self._queue_wait: Any = deque(maxlen=256)
        self._worst_gap: Any = deque(maxlen=256)
        # Span-id source for materialization: a PRNG seeded ONCE from the
        # system entropy pool instead of secrets.token_hex per id — a
        # request tree is ~40 ids and each token_hex is a urandom syscall,
        # which alone busts the <=2% tracing-overhead budget at toy decode
        # step times. Ids need uniqueness, not crypto strength; used only
        # from the single-writer materialization context.
        self._id_rng = random.Random(secrets.randbits(64))

    def _span_id(self) -> str:
        return f"{self._id_rng.getrandbits(64):016x}"

    def _trace_id(self) -> str:
        return f"{self._id_rng.getrandbits(128):032x}"

    # -- single-writer side (batcher loop context only) -----------------
    def begin(self, slot: int, trace: Optional[TraceContext],
              t_submit: Optional[float], prompt_tokens: int,
              tags: Optional[dict] = None) -> None:
        """Start recording a request at the moment its slot is chosen.
        ``trace`` may be None (an untraced submit while the recorder runs
        for others) — the segment still records, rooted at a fresh trace
        id, so /debug/timeline sees every request. ``tags`` (optional
        request identity: tenant / slo_class / adapter_id) ride the
        timeline dict and the root span."""
        if trace is None:
            trace = TraceContext(trace_id=self._trace_id(),
                                 sampled=True, ingress="internal")
        self._segs[slot] = _Segment(trace, t_submit, self._clock(),
                                    prompt_tokens, self.ring_size,
                                    tags=tags)

    def record(self, slot: int, kind: str, **fields: Any) -> Optional[dict]:
        """Returns the event's own fields (None with no segment open): the
        single writer may add to them what it learns later, before the
        segment completes (a chunk's routing tallies, known at the
        first-token sync)."""
        seg = self._segs[slot]
        if seg is None:
            return None
        seg.total += 1
        t = self._clock()
        if kind == EV_FIRST_TOKEN or kind == EV_STEP:
            seg.tokens += int(fields.get("tokens", 0))
            if seg.t_first is None and kind == EV_FIRST_TOKEN:
                seg.t_first = t
            if seg.last_surface is not None:
                gap = t - seg.last_surface
                if seg.worst_gap is None or gap > seg.worst_gap:
                    seg.worst_gap = gap
            seg.last_surface = t
        seg.ring.append((t, kind, fields))
        return fields

    def extend(self, slot: int, events) -> None:
        """Copy worker-stamped events (Handoff.events: (t, kind, fields)
        tuples on this process's perf_counter clock) into the slot ring —
        the batcher-side half of the single-writer handoff."""
        seg = self._segs[slot]
        if seg is None:
            return
        for t, kind, fields in events:
            seg.total += 1
            seg.ring.append((t, kind, fields))

    def complete(self, slot: int, status: str, tokens: int,
                 tracer: Optional[Tracer] = None) -> Optional[dict]:
        """Materialize the slot's segment into a timeline dict + span tree:
        decide retention (head flag, else tail thresholds), feed retained
        trees to the tracer, publish the timeline for /debug/timeline, and
        clear the segment. The ONLY lock acquisition in the recorder's
        write path — once per request."""
        seg = self._segs[slot]
        if seg is None:
            return None
        self._segs[slot] = None
        self._reanchor()
        t_end = self._clock()
        events = list(seg.ring)
        timeline = self._materialize(seg, events, slot, status, tokens, t_end)
        mode = timeline["sampling"]
        if tracer is not None and tracer.enabled and mode != "drop":
            tracer.record_spans(self._spans(seg, events, timeline, t_end))
            tracer.count_retained(mode)
        dropped = seg.total - len(events)
        with self._lock:
            self._completed.append(timeline)
            self.completed_total += 1
            self.retained[mode] = self.retained.get(mode, 0) + 1
            self.events_dropped_total += dropped
            if timeline["ttft_s"] is not None:
                self._ttft.append(timeline["ttft_s"])
            self._queue_wait.append(timeline["queue_wait_s"])
            if timeline["worst_gap_s"] is not None:
                self._worst_gap.append(timeline["worst_gap_s"])
        return timeline

    # -- materialization -------------------------------------------------
    def _reanchor(self) -> None:
        """Refresh the perf->wall mapping through tracing.now()'s CURRENT
        anchor (single-writer context; called once per materialization so
        every timestamp of one request tree shares one mapping)."""
        self._wall0 = wall_now()
        self._perf0 = self._clock()

    def _wall(self, t: float) -> float:
        return self._wall0 + (t - self._perf0)

    def _materialize(self, seg: _Segment, events, slot: int, status: str,
                     tokens: int, t_end: float) -> dict:
        # latency/token signals come from the SEGMENT accumulators (record
        # time), never the ring: eviction must not erase TTFT or tokens
        ttft = (seg.t_first - seg.t_submit) if seg.t_first is not None else None
        worst_gap = seg.worst_gap
        step_tokens = seg.tokens
        if seg.trace.sampled:
            mode = "head"
        elif (self.tail_ttft_s is not None and ttft is not None
                and ttft > self.tail_ttft_s) or \
             (self.tail_gap_s is not None and worst_gap is not None
                and worst_gap > self.tail_gap_s):
            mode = "tail"
        else:
            mode = "drop"
        return {
            "trace_id": seg.trace.trace_id,
            "ingress": seg.trace.ingress,
            "slot": slot,
            "status": status,
            **({"request_tags": dict(seg.tags)} if seg.tags else {}),
            "sampling": mode,
            "t_submit_wall": self._wall(seg.t_submit),
            "queue_wait_s": seg.t_begin - seg.t_submit,
            "ttft_s": ttft,
            "worst_gap_s": worst_gap,
            "total_s": t_end - seg.t_submit,
            "prompt_tokens": seg.prompt_tokens,
            "tokens": tokens,
            "token_events_sum": step_tokens,
            "events_dropped": seg.total - len(events),
            "events": [self._event_dict(seg, t, kind, fields)
                       for t, kind, fields in events],
        }

    @staticmethod
    def _event_dict(seg: _Segment, t: float, kind: str, fields: dict) -> dict:
        out = {"t_s": round(t - seg.t_submit, 6), "kind": kind}
        for k, v in fields.items():
            if k == "t_dispatch":
                # raw perf-counter stamps mean nothing to a client —
                # render submit-relative like t_s
                out["t_dispatch_s"] = round(float(v) - seg.t_submit, 6)
            else:
                out[k] = v
        return out

    def _spans(self, seg: _Segment, events, timeline: dict,
               t_end: float) -> List[Span]:
        """The request's span tree: one root at the transport ingress, a
        queue-wait child, one child per recorded lifecycle event (decode
        steps span dispatch -> drain). Tail-retained trees flip sampled on
        so the exporter ships them despite the head decision."""
        trace = seg.trace
        # Tail-retained trees detach from the caller's span: head sampling
        # DROPPED the in-process server/node spans (they were unsampled),
        # so parenting under trace.parent_span_id would reference a span
        # the collector never receives — a broken fragment for exactly the
        # slow requests tail sampling exists to keep. The trace id still
        # joins the caller's trace; the would-be parent rides as a tag.
        head = timeline["sampling"] == "head"
        root_tags_extra = {}
        if not head and trace.parent_span_id:
            root_tags_extra["caller_span_id"] = trace.parent_span_id
        root = Span(
            name=f"llm.request {trace.ingress}".strip(),
            trace_id=trace.trace_id, span_id=self._span_id(),
            parent_id=trace.parent_span_id if head else None,
            start=self._wall(seg.t_submit), end=self._wall(t_end),
            tags={
                "slot": timeline["slot"], "status": timeline["status"],
                "tokens": timeline["tokens"],
                "prompt_tokens": timeline["prompt_tokens"],
                "sampling": timeline["sampling"],
                "ttft_ms": round((timeline["ttft_s"] or 0.0) * 1e3, 3),
                "worst_gap_ms": round((timeline["worst_gap_s"] or 0.0) * 1e3, 3),
                "events_dropped": timeline["events_dropped"],
                **(seg.tags or {}),
                **root_tags_extra,
            })
        spans = [root]
        spans.append(Span(
            name="queue.wait", trace_id=trace.trace_id,
            span_id=self._span_id(), parent_id=root.span_id,
            start=self._wall(seg.t_submit), end=self._wall(seg.t_begin),
            tags={}))
        decode_start = None
        for t, kind, fields in events:
            wall_t = self._wall(t)
            # duration-bearing events span [t - dur, t]; instants are points
            dur = float(fields.get("dur_s", 0.0) or 0.0)
            start = wall_t - dur
            if kind == EV_STEP and "t_dispatch" in fields:
                start = self._wall(float(fields["t_dispatch"]))
            if kind == EV_FIRST_TOKEN and decode_start is None:
                decode_start = t
            tags = {k: v for k, v in fields.items()
                    if k not in ("dur_s", "t_dispatch")}
            spans.append(Span(
                name=f"llm.{kind}", trace_id=trace.trace_id,
                span_id=self._span_id(), parent_id=root.span_id,
                start=start, end=wall_t, tags=tags))
        if decode_start is not None:
            spans.append(Span(
                name="llm.decode", trace_id=trace.trace_id,
                span_id=self._span_id(), parent_id=root.span_id,
                start=self._wall(decode_start), end=self._wall(t_end),
                tags={"tokens": timeline["tokens"]}))
        for s in spans:
            s.sampled = True  # retention already decided (head or tail)
        return spans

    # -- cross-thread read side ------------------------------------------
    def timelines(self, n: int = DEFAULT_KEEP) -> List[dict]:
        """The ``n`` most recent completed request timelines, newest last
        (n <= 0 means none — reachable from the raw ?n= query param, where
        an unclamped -0/-k slice would return everything/an odd middle
        cut)."""
        n = int(n)
        if n <= 0:
            return []
        with self._lock:
            items = list(self._completed)
        return items[-n:]

    def snapshot(self) -> dict:
        """The aggregated scaling-signal snapshot (ROADMAP item 4's input):
        per-request latency signals reduced to the quantiles a controller
        steers by, plus the retention/drop tallies."""

        def stats(values) -> dict:
            if not values:
                return {"p50": None, "p95": None, "max": None}
            vs = sorted(values)
            return {
                "p50": vs[len(vs) // 2],
                "p95": vs[min(int(len(vs) * 0.95), len(vs) - 1)],
                "max": vs[-1],
            }

        with self._lock:
            return {
                "completed_total": self.completed_total,
                "retained": dict(self.retained),
                "events_dropped_total": self.events_dropped_total,
                "ttft_s": stats(list(self._ttft)),
                "queue_wait_s": stats(list(self._queue_wait)),
                "worst_gap_s": stats(list(self._worst_gap)),
            }
