"""Continuous batching for LLM decode.

The engine-side request batcher of the BASELINE.json north star ("the
orchestrator's gRPC request batcher shards inference-graph traffic across a
v5e slice"), specialised for autoregressive decode: requests join and leave a
fixed pool of cache slots *between decode steps*, so one compiled decode
program serves overlapping requests at arbitrary arrival times — no
head-of-line blocking on the longest generation, no recompilation.

Design (all shapes static):
- ONE cache tree lives on the device (models/cache.py owns its form): for
  every attention layer a global pool of fixed-size pages shared by all S
  slots through a device-resident block table [S, n_pages], and for every
  state layer (conv, linear attention) a fixed block a slot, no pages;
- admission: the prompt is prefilled in fixed-size chunks (one compiled
  program a chunk size) that write STRAIGHT into the slot's own pages and its
  state block, interleaved with decode steps; nothing is copied afterwards;
- every step runs ONE jitted decode over all S slots, each at its own
  position; a slot nobody holds rides along with a block-table row of
  TRASH_PAGE, so what it writes lands on the trash page and it reads nothing;
- completion: EOS or per-request max_new_tokens frees the slot and its pages
  (a page's positions are reset when it is handed out again).

The positions cached beside every row (PAD_POS = empty) are what makes the
mixed-occupancy batch exact: a slot attends only to rows its own block table
names and whose position is real and not after the query's.

Pipelined decode (PR 3): the decode loop is device-resident. Per-slot token,
position and rng-key state live in device arrays threaded through the
compiled step (``LLMServer._get_decode_step``), so dispatching step N+1
never waits for step N's tokens to land in Python. The host runs one step
(or more) BEHIND the device: a consumer drains the oldest in-flight step's
token array and does all bookkeeping there — EOS detection, ``n_new``
accounting, ``on_token`` streaming, ``_finish``, admissions.

EOS semantics under the lag: the device may run up to ``pipeline_depth``
run-ahead steps past a sequence's EOS before the host sees it. Those
trailing tokens are masked by a per-slot generation counter (a slot freed
and re-admitted between dispatch and drain fails the ``gen`` check), and the
trailing KV writes land in pages whose positions are reset (``reset_pages``)
before their next owner reads them, or on the trash page —
the lag can only cost wasted compute, never wrong output
(tests/test_batcher_pipeline.py holds token parity against ``generate()``).
The masking is advance-agnostic: a dispatched step may land 1 token
(plain decode), a fixed K (fused scan) or a data-dependent 1..K+1
(speculative verify, below) — in every case the drain credits tokens to a
slot only while ``(slot, gen)`` still matches the dispatch-time snapshot
and the slot still has budget, so trailing tokens of ANY width for a
finished or replaced occupant are dropped, never surfaced.

First-token activation (PR 26): an admission reads nothing. The prompt's
first token is drawn on the device from the last chunk's logits by the
sampler the steps use (``LLMServer._get_first_token``), threaded into the
slot's device state by ``set_slot``, and queued in ``_inflight`` as a
``_FirstToken`` record behind the steps dispatched before the chunk; the
slot joins the decode batch at the next dispatch and the token reaches the
host when its record drains (``_drain_first``), in device order, with the
same ``(slot, gen)`` masking as a step's tokens. The loop holds back the
read of such a record only while the token is not there yet, nothing is
queued behind it and the turn still found something to enqueue
(``_first_token_can_wait``): the slot's next step, or the next request's
first chunk, is queued behind a last chunk before anything waits for its
token, and with that behind it the read may block.

When the admit queue is empty, ``decode_fuse_steps`` K>1 fuses K steps into
one device-side ``lax.scan`` between syncs (one dispatch + one host read
per K tokens).

Speculative decoding (PR 8): with ``spec_mode`` "ngram" or "draft" each
dispatched step is a fused draft+verify program
(``LLMServer._get_spec_step``): up to K tokens are proposed per slot — by
a zero-weight device-side prompt-lookup match over the slot's
prompt+generated history, or by a small draft model with its own KV pool —
and verified in ONE K+1-token target forward that accepts the longest
prefix agreeing with the slot's exact sampling chain. Each step therefore
advances a slot by a VARIABLE 1..K+1 tokens (``n_acc``), known only at
drain time: the dispatch side books the pessimistic maximum into
``disp_new`` (page provisioning and cache-edge caps must cover the
all-accepted case) and the drain corrects it back to
``n_new + pending-in-flight maxima`` once actual advances land. Rejected
drafts' KV rows are position-reset to PAD_POS inside the verify program
itself, so the cache never holds tokens that lost verification.
``decode_fuse_steps`` > 1 is rejected in combination with speculation: a
fused fixed-K scan and variable accept lengths are incompatible until a
follow-up (the scan would need per-slot variable stride).

Disaggregated prefill/decode (PR 9): with ``disaggregation="remote_prefill"``
admission prefill leaves this batcher's device entirely — the device world
splits into a prefill slice and a decode slice (parallel/mesh.py
``disaggregated_mesh``; the decode slice anchors the process default
device, where the slot pool lives), prefill-slice workers
(runtime/disagg.py) run the server's own compiled prefill programs on
their devices and ``jax.device_put`` the written KV straight onto the
decode device, and the admission path here stages remote jobs and
consumes finished handoffs instead of prefilling locally: one donated
jitted scatter imports the staged pages into the slot's pool pages
(``_get_handoff_import``), then the slot
commits exactly as a local admission would. Because the prefill programs
and the sampling chain are shared with the local path, remote-prefill
serving is bit-exact against single-slice serving (tests/test_disagg.py);
what changes is WHO pays for the burst — the decode slice's worst victim
inter-token gap under a long-prefill adversary drops from "a chunk's
forward" to "one jitted page import" (docs/performance.md
"Disaggregated serving"). Unlike the single local chunked-prefill job,
MULTIPLE remote jobs may be staged at once (that concurrency is the
point); sheds cancel a staged job through the TransferQueue's
exactly-once protocol, so a handoff racing a shed can never double-free
its decode-side pages (tests/test_schedules.py).

Request-scoped tracing (PR 10): when the tracer is enabled (TRACING=1)
every request records a flight-recorder timeline (runtime/flight.py) —
queue wait, each prefill chunk, handoff stages, every drained decode step
with token/accept counts, page-grow stalls, sheds, EOS — written
single-writer from this loop's serialized offload context at points that
already touch host state (NO new lock acquisition or device sync on the
decode path), and materialized into one span tree per request at
completion, rooted at the transport ingress that carried the request's
``traceparent``. Disabled tracing leaves ``_flight`` None and every hook
is a None check; the compiled step programs are identical either way.

Loop time budget (PR 23): every second of a loop turn is put down to exactly
one phase (``LoopPhases``: admit, handoff, dispatch, prefill,
first_token_wait, first_token, drain_wait, emit, idle, and hop for what is
left of the turn — event loop, ``to_thread`` hand-offs). Each phase opens a
``jax.profiler.TraceAnnotation`` named ``llm.<phase>`` under the turn's
``llm.turn``, so a device trace's idle gaps can be put down to the host
phase that covers them on the profiler's own clock, and adds its
``perf_counter`` wall to a per-phase accumulator that ``llm_stats`` exports
(``seldon_llm_loop_seconds_total{phase}``). Always on: an inactive
annotation and two clock reads per phase against a turn of milliseconds.
Since PR 33 a second level, parts, names what a phase is made of without
taking anything from it (``llm.<phase>.<part>``,
``seldon_llm_loop_part_seconds_total{part}``: dispatch's page growth, jitted
call and booking, the drain's reads beside the tokens, emit's slot loop and
finishes, a chunk's build, call and activation), and ``hop`` is measured, not
subtracted: every ``to_thread`` of the loop is stamped at submission, the
worker's entry and exit, and resumption (``LoopPhases.handoff``), so the
two waits for a thread, the worker's own Python and the loop coroutine's
own code add up to it.

Paged KV cache (PR 7; the vLLM/PagedAttention design, Kwon et al., SOSP
2023): HBM is billed for pages actually written, so a deliberately undersized
pool (``kv_pool_pages``) oversubscribes: more concurrent slots per HBM byte,
with page-exhaustion shedding (503 + Retry-After, runtime/resilience.py
ShedError) as the relief valve — the decode loop never raises. The chunked
admission (``prefill_chunk``; Sarathi-Serve, Agrawal et al., OSDI 2024) means
a 2k-token prompt never stalls in-flight decodes for a whole prompt's forward
(a chunk is wider while the narrow chunks of what is left of the prompt would
compute a wide chunk's rows anyway, the last one padded, and no other live
slot streams: ``_chunk_width``).
Page bookkeeping is host-side (PageAllocator, lock-guarded); block-table
updates are jitted device ops that serialize behind in-flight steps in device
program order.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from seldon_core_tpu.models import cache as kvcache
from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS, RESERVED_PAGES, TRASH_PAGE
from seldon_core_tpu.runtime.flight import (
    EV_FIRST_TOKEN,
    EV_HANDOFF_IMPORT,
    EV_HANDOFF_STAGED,
    EV_PAGE_GROW,
    EV_PREFILL_CHUNK,
    EV_PREFIX_HIT,
    EV_RESUME,
    EV_SHED,
    EV_STEP,
)
from seldon_core_tpu.servers.llmserver import LLMServer, _bucket
from seldon_core_tpu.tracing.start import get_ledger

logger = logging.getLogger(__name__)

DEFAULT_PAGE_SIZE = 64
DEFAULT_PREFILL_CHUNK = 256
# ... and the rows of a WIDE chunk, which a prompt's next chunk is while more
# than that many LESS A NARROW CHUNK'S of its rows are left (the narrow program
# would compute that many rows for them anyway, its last call padded; a
# seeded request: more than that many) and no other live slot streams
# (``ContinuousBatcher._chunk_width``): a chunk streams every weight it touches
# once whatever its rows, a routed expert sees a few dozen of 256 rows, and the
# decode step that follows each chunk streams them all once more for the few
# slots that decode beside it (docs/performance.md "Chunked prefill"). One
# constant, so two chunk programs a server whose slots are longer than it; a
# multiple of the delta rule's 64-row sub-chunks and of the page
WIDE_PREFILL_CHUNK = 1024


def pow2_bucket(n: int, cap: int) -> int:
    """Power-of-two page-bucket size covering ``n`` pages, capped at
    ``cap``. THE one definition shared by every staged-transfer producer
    (disagg handoffs, prefix exports — runtime/disagg.py) and consumer:
    bucket shapes name compiled import programs on both sides, so a
    divergent rounding rule would silently desynchronize exporter and
    importer shapes (and the hlolint contract dims built on them)."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


def _build_program(fn, shapes: tuple, what: str) -> None:
    """Trace, lower and compile a step program (``what``, for the log) for
    ``shapes``, or load it from the compile cache: a thread's whole job
    (``ContinuousBatcher._build_wide_program``); it touches no batcher."""
    try:
        fn.lower(*shapes).compile()
    except Exception:   # the call that needs the program builds it, and raises what is wrong
        logger.warning("%s was not built ahead of its first call", what, exc_info=True)


def _page_table_ops():
    """Jitted block-table / page ops, shared by every batcher instance
    (jax.jit caches per input shape, so two batchers with equal shapes
    share compiled code — a per-batcher closure would recompile these on
    every instance, and the page-growth path runs them MID-DECODE where a
    compile is a stall). Built on first use; the double-build race is
    benign (both results are equivalent, last write wins)."""
    ops = _page_table_ops.__dict__.get("ops")
    if ops is not None:
        return ops
    import jax
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def set_block_row(bt, slot, row):
        return bt.at[slot].set(row)

    @partial(jax.jit, donate_argnums=(0,))
    def set_block_entry(bt, slot, idx, page):
        return bt.at[slot, idx].set(page)

    # The operations over the whole pool tree are models/cache.py's (what an
    # entry is made of is its business); decided HERE are the jit and the
    # donation. Newly-allocated pages' stale positions reset, in place:
    reset_pages = jax.jit(kvcache.reset_pages, donate_argnums=(0,))

    # Per-slot admission update for the device-resident decode state (both
    # layouts; slot index is traced, so one compile serves every slot). The
    # position and key arrays are donated — the host never reads them;
    # last_tok is NOT donated because its buffer may alias a stacked token
    # output the host still has to read (see LLMServer._get_decode_step).
    @partial(jax.jit, donate_argnums=(1, 2))
    def set_slot(last_tok, next_pos, keys, slot, tok, pos, key):
        return (last_tok.at[slot].set(tok), next_pos.at[slot].set(pos),
                keys.at[slot].set(key))

    # Admission write of a slot's token-history row (speculative decoding:
    # the n-gram proposer and the verify step's accepted-token appends read
    # and extend this device-resident history). Donated like the other
    # per-slot state — the host keeps no mirror.
    @partial(jax.jit, donate_argnums=(0,))
    def set_hist_row(hist, slot, row):
        return hist.at[slot].set(row)

    # Per-slot adapter-id write (batched LoRA, runtime/adapters.py): the
    # admitted tenant's adapter row, gathered by every adapted step.
    # Donated like the other per-slot admission state.
    @partial(jax.jit, donate_argnums=(0,))
    def set_adapter_id(ids, slot, aid):
        return ids.at[slot].set(aid)

    # Copy-on-write page copy (radix prefix cache, runtime/radix.py): ONE
    # page, the pool donated in place; pinned by the batcher.cow_page_copy
    # hlolint contract (zero host transfers, budgeted bytes, no prefix gather).
    cow_page_copy = jax.jit(kvcache.cow_page_copy, donate_argnums=(0,))

    # Page export (disaggregated prefix reuse): the decode pool's cached-prefix
    # pages into a staged bucket, so a prefill worker computes ONLY the
    # uncached suffix. NOT donated — the pool (and the trie's pages in it)
    # stays live. Pinned by the disagg.prefix_export hlolint contract.
    export_pages = jax.jit(kvcache.export_pages)

    ops = (set_block_row, set_block_entry, reset_pages, set_slot,
           set_hist_row, cow_page_copy, export_pages, set_adapter_id)
    _page_table_ops.ops = ops
    return ops


class PageAllocator:
    """Host-side refcounted free-list allocator over the global KV page
    pool.

    Pages 0/1 are reserved (NULL/TRASH — models/cache.py); the rest
    are handed out lowest-id-first, all-or-nothing, at refcount 1. The
    radix prefix cache (runtime/radix.py) shares live pages between the
    trie and slot block tables by growing the refcount (``retain``);
    ``free`` is one uniform decrement-and-free-on-zero for every release
    path, so a page returns to the free list exactly when its LAST owner
    lets go — and a page's refcount is the shared-ownership truth the
    trie's eviction policy reads (refcount 1 = trie-only, evictable;
    >1 = a live slot references it, never evictable). Every state
    transition happens under ``self._lock``: alloc/retain/free run on the
    batcher loop's worker threads while /metrics scrapes read the gauges
    from transport threads, and an unlocked refcount read-modify-write is
    exactly the double-free/double-allocation the deterministic-
    interleaving suite (tests/test_schedules.py) guards against.
    Over-freeing raises — a page freed past zero would be handed to two
    slots and silently cross-corrupt their KV."""

    def __init__(self, total_pages: int, page_size: int):
        if total_pages <= RESERVED_PAGES:
            raise ValueError(
                f"page pool needs > {RESERVED_PAGES} pages (got {total_pages})")
        self.total = int(total_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        # pop() from the tail hands out the lowest free id: deterministic
        # placement makes schedule replays and parity tests reproducible
        self._free = list(range(self.total - 1, RESERVED_PAGES - 1, -1))
        self._refs: Dict[int, int] = {}   # page -> refcount (allocated only)
        self.shed_total = 0
        # pages given back while the request that held them lived (the window
        # class's, behind each slot's window: ``give_back``)
        self.released_total = 0

    @property
    def capacity(self) -> int:
        return self.total - RESERVED_PAGES

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages at refcount 1, all-or-nothing; None when the pool
        can't cover it."""
        with self._lock:
            if n > len(self._free):
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            return pages

    def retain(self, pages: Sequence[int]) -> None:
        """Add one reference to each (already-allocated) page — the trie
        pinning matched pages into a slot's block table. Retaining a free
        page raises: it would resurrect a page another alloc may own."""
        with self._lock:
            for p in pages:
                if p not in self._refs:
                    raise ValueError(f"retain of unallocated page {p}")
            for p in pages:
                self._refs[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page rejoins the free list when
        its count reaches zero. Raises on a page not currently allocated
        (double free / reserved id)."""
        with self._lock:
            for p in pages:
                rc = self._refs.get(p)
                if rc is None or not (RESERVED_PAGES <= p < self.total):
                    raise ValueError(f"double/invalid free of page {p}")
                if rc > 1:
                    self._refs[p] = rc - 1
                else:
                    del self._refs[p]
                    self._free.append(p)

    def give_back(self, pages: Sequence[int]) -> None:
        """``free`` for pages a LIVE request lets go of (a window layer's,
        behind its window), counted under the same lock as the free list."""
        self.free(pages)
        with self._lock:
            self.released_total += len(pages)

    def refs_of(self, page: int) -> int:
        """Current refcount (0 = free) — the trie's evictability probe."""
        with self._lock:
            return self._refs.get(page, 0)

    def refs_map(self, pages: Sequence[int]) -> List[int]:
        """Refcounts for many pages under ONE lock acquisition (the
        trie's stats walk reads every node's count per /metrics scrape —
        per-page locking would be O(nodes) lock round-trips)."""
        with self._lock:
            return [self._refs.get(p, 0) for p in pages]

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    def count_shed(self) -> None:
        """One page-exhaustion shed (counted under the same lock as the
        free list it describes)."""
        with self._lock:
            self.shed_total += 1

    def stats(self):
        """(total, in_use, shed_total) — one consistent snapshot."""
        with self._lock:
            return self.total, self.capacity - len(self._free), self.shed_total


class _PrefillJob:
    """One chunked admission in progress: the slot it targets, the (already
    truncated) prompt, the next write offset, and the device block-table
    row its chunks write through. Only one job runs at a time; decode
    dispatches interleave between its chunks."""

    __slots__ = ("slot", "ids", "L", "start", "next", "chunk", "max_new", "fut",
                 "on_token", "info", "seed", "bt_row", "pages", "t_arrival",
                 "req", "asides", "wrow")

    def __init__(self, slot, ids, start, chunk, max_new, fut, on_token,
                 info, seed, bt_row, pages, t_arrival=None, req=None):
        # (aside, flight event, first position, tokens) of each chunk dispatched so far: read when
        # the first token's record drains, never by a sync of their own
        self.asides: List[tuple] = []
        self.slot = slot
        self.ids = ids
        self.L = len(ids)
        self.start = start           # first position of the uncached suffix
        self.next = start            # first position the next chunk writes
        self.chunk = chunk
        self.max_new = max_new
        self.fut = fut
        self.on_token = on_token
        self.info = info
        self.seed = seed
        self.bt_row = bt_row         # device [1, n_pages] int32
        # the WINDOW class's row (a model with sliding-attention layers): host
        # [n_pages] int32, booked chunk by chunk (``_window_book``): NULL_PAGE
        # behind the window and ahead of the rows written so far
        self.wrow: Optional[np.ndarray] = None
        self.pages = pages           # host mirror of the allocated pages
        self.t_arrival = t_arrival   # submit() wall clock, for TTFT
        # the scheduler's PendingRequest: tenant/SLO identity, adapter id,
        # and the preemption return path (an interactive admission may
        # push a staged batch-class job back into the queue)
        self.req = req


class _RemoteJob:
    """One admission staged on the prefill slice (disaggregated serving):
    the slot reserved for it, the (already truncated) prompt, the
    decode-side pages allocated for the import (``row`` is
    the NULL-padded host block row those pages form, led by
    ``prefix_pages`` shared radix-trie pages the worker never recomputes),
    and the request bookkeeping the consume path needs to commit the
    slot. The handoff itself travels through the TransferQueue; this
    record is the decode side's half of the rendezvous, keyed by
    ``job_id``."""

    __slots__ = ("job_id", "slot", "ids", "L", "plen", "max_new", "fut",
                 "on_token", "info", "seed", "pages", "row", "prefix_pages",
                 "t_arrival", "req")

    def __init__(self, job_id, slot, ids, plen, max_new, fut, on_token,
                 info, seed, pages, row, t_arrival, prefix_pages=0,
                 req=None):
        self.job_id = job_id
        self.slot = slot
        self.ids = ids
        self.L = len(ids)
        self.plen = plen
        self.max_new = max_new
        self.fut = fut
        self.on_token = on_token
        self.info = info
        self.seed = seed
        self.pages = pages           # decode-side SUFFIX pages (host mirror)
        self.row = row               # host [n_pages] int32 block row, or None
        self.prefix_pages = int(prefix_pages)  # shared trie pages leading row
        self.t_arrival = t_arrival
        self.req = req               # scheduler PendingRequest (tenant/SLO)


class _Slot:
    __slots__ = ("future", "tokens", "true_len", "n_new", "max_new", "active",
                 "on_token", "gen", "disp_new", "pages", "shared", "ids",
                 "prefilling", "admit_seq", "t_last", "tenant", "slo_class",
                 "adapter_id", "logits", "routing", "state", "wpages")

    def __init__(self):
        self.active = False
        # a probe request's own ``info["state"]``, filled where it finishes
        # with the matrix state the sequence leaves (``_finish``); else None
        self.state: Optional[dict] = None
        # a probe request's float32 logits, one row per generated token (the
        # distribution it was sampled from): the request's own ``info``
        # list, or None for every request that did not ask
        self.logits: Optional[List[np.ndarray]] = None
        # and, for an MoE model, the experts each of its tokens took
        # ([n_moe_layers, k] a token: the prompt's, then one a decode step)
        self.routing: Optional[List[np.ndarray]] = None
        # multi-tenant identity (runtime/scheduler.py): who this occupant
        # belongs to, which SLO class its latency counts against, and the
        # LoRA adapter row every adapted step gathers for it (0=identity).
        # The adapter is PINNED in the registry while this slot holds it.
        self.tenant = ""
        self.slo_class = "interactive"
        self.adapter_id = 0
        # wall clock of the last token surfaced for this occupant (TTFT /
        # inter-token-gap observability; reset at every commit)
        self.t_last = None
        self.future: Optional[asyncio.Future] = None
        self.tokens: List[int] = []
        self.true_len = 0
        self.n_new = 0          # tokens the HOST has processed (drain side)
        self.max_new = 0
        self.on_token: Optional[Any] = None
        # pipelining state: gen disambiguates a slot reused between a step's
        # dispatch and its drain (trailing speculative tokens for the old
        # occupant must be ignored, never credited to the new one);
        # disp_new is the DISPATCH-side token count advanced when a step is
        # enqueued, used to stop dispatching for exhausted slots and to
        # clamp the fused-K block so it never overruns max_new/max_len
        self.gen = 0
        self.disp_new = 0
        # the slot's OWNED page ids (host mirror of the
        # owned tail of its block-table row — freed, or adopted by the
        # radix trie, at release), the SHARED trie pages its row leads
        # with (radix prefix hit: pinned at admission, unpinned at
        # release, never written by this slot), whether a chunked prefill
        # is mid-flight for it, and its admission sequence number
        # (shed-victim ordering: newest admitted sheds first on page
        # exhaustion). ``ids`` keeps the truncated prompt so completion
        # can insert prompt+generated blocks back into the trie.
        self.pages: List[int] = []
        self.shared: List[int] = []
        # the WINDOW class's pages it holds, by the table entry each backs (a
        # model with sliding-attention layers): only the entries from the
        # window's first page on; those behind were given back
        self.wpages: Dict[int, int] = {}
        self.ids: Optional[List[int]] = None
        self.prefilling = False
        self.admit_seq = 0

    def covered_pages(self) -> int:
        """Block-table entries pointing at real pages (shared + owned)."""
        return len(self.shared) + len(self.pages)

    # cache positions are derived, never mirrored: after the prompt's L
    # tokens the n-th generated token sits at position true_len + n - 1
    def host_pos(self) -> int:
        return self.true_len + self.n_new - 1

    def dispatched_pos(self) -> int:
        return self.true_len + self.disp_new - 1


MOE_PROGRAMS = ("decode", "chunk")   # the step programs the loop counts by
# the counters' names for the kinds of state layer (models/transformer.py
# STATE_LAYER_KINDS): seldon_llm_<name>_rows_total / _layer_calls_total
STATE_COUNTERS = {"conv": "conv", "gdn": "linear_attention", "ssd": "mamba", "s6": "s6"}
KV_WRITE_PATHS = ("page", "token")   # how a chunk's rows reach the paged pool
# how a decode step's read-modify-write of a matrix state runs, for the kinds
# that have a kernel of the repo's own: seldon_llm_<name>_step_path{path}
STEP_PATHS = ("kernel", "expression")
STEP_PATH_KINDS = ("gdn", "ssd")


LOOP_PHASES = ("admit", "handoff", "dispatch", "prefill", "first_token_wait",
               "first_token", "drain_wait", "emit", "idle", "hop")


class _Phase:
    """One open phase, or one open part of a phase: a context manager that
    times itself on the owner's clock (``time.perf_counter``) and shows in a
    profiler trace as ``llm.<name>``. Opened inside another of its level it
    takes its time out of the outer one (``stack`` is that level's open
    spans; ``book(name, own seconds)`` the owner's accumulator for it).
    ``t0``/``t1``/``seconds`` stay readable after exit, so a site that needs
    its own timestamps takes them from here: one clock pair a site."""

    __slots__ = ("owner", "name", "t0", "t1", "seconds", "nested", "_ann",
                 "_stack", "_book")

    def __init__(self, owner: "LoopPhases", name: str, stack: list, book):
        self.owner = owner
        self.name = name
        self.nested = 0.0
        self._stack, self._book = stack, book

    def __enter__(self) -> "_Phase":
        self._ann = self.owner._annotation("llm." + self.name)
        self._ann.__enter__()
        self._stack.append(self)
        self.t0 = self.owner._clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = self.owner._clock()
        self.seconds = self.t1 - self.t0
        self._stack.pop()       # self: spans close innermost first
        self._book(self.name, self.seconds - self.nested)
        if self._stack:
            self._stack[-1].nested += self.seconds
        self._ann.__exit__(*exc)


HOP_PARTS = ("hop.wake_worker", "hop.wake_loop", "hop.worker", "hop.loop")


class _Handoff:
    """``fn(*args)`` on its way through ``asyncio.to_thread``, with the
    hand-off's four stamps on the owner's one clock: submission (here, on
    the loop thread) and resumption (``resumed``), the worker's entry and
    exit around ``fn`` (``__call__``). ``hop.wake_worker`` = entry -
    submission and ``hop.wake_loop`` = resumption - exit are the two waits
    for a thread to be woken, each also a span (``llm.hop.wake_worker`` /
    ``llm.hop.wake_loop``: the profiler pairs an annotation entered on one
    thread and left on another); ``hop.worker`` = exit - entry less the
    phases ``fn`` opened (it may open several, or none): the worker's own
    Python outside every phase."""

    __slots__ = ("owner", "fn", "args", "t_submit", "t_in", "t_out",
                 "in_phases", "_leg")

    def __init__(self, owner: "LoopPhases", fn, args: tuple):
        self.owner, self.fn, self.args = owner, fn, args
        self.t_in: Optional[float] = None
        self.t_submit = owner._clock()
        owner._leave_own(self.t_submit)
        self._leg = owner._annotation("llm.hop.wake_worker")
        self._leg.__enter__()

    def __call__(self):
        owner = self.owner
        self.t_in = owner._clock()
        self._leg.__exit__(None, None, None)
        before = owner._turn_phases
        try:
            return self.fn(*self.args)
        finally:
            self._leg = owner._annotation("llm.hop.wake_loop")
            self.in_phases = owner._turn_phases - before
            self.t_out = owner._clock()
            self._leg.__enter__()

    def resumed(self) -> None:
        owner = self.owner
        t_back = owner._clock()
        if self.t_in is not None:   # else the await was cancelled before fn ran
            self._leg.__exit__(None, None, None)
            owner.handoffs += 1
            owner._add_part("hop.wake_worker", self.t_in - self.t_submit)
            owner._add_part("hop.worker",
                            max(self.t_out - self.t_in - self.in_phases, 0.0))
            owner._add_part("hop.wake_loop", t_back - self.t_out)
        if owner._turn is not None:
            owner._enter_own(t_back)


class LoopPhases:
    """The batcher loop's time budget. Single writer, like the flight
    recorder: phases open and close only in the loop's own serialized
    context (the loop coroutine and the worker threads it awaits one at a
    time), so there is no lock. A phase opened inside another takes its
    time out of the outer one (``drain_wait`` inside ``emit``): every
    second belongs to the innermost phase, and the phases of a turn plus its ``hop`` ARE the turn's wall.
    Readers (``stats()`` at a scrape) may be one phase behind.

    Below the phases, parts (``part``; docs/observability.md "Loop phases"
    has the table): a named stretch inside a phase whose seconds are ALSO
    counted in ``part_seconds``, the phase's own total untouched. ``hop``'s
    parts are measured where it happens: ``handoff`` stamps each
    ``asyncio.to_thread`` of the loop at submission, the worker's entry and
    exit, and resumption (one clock for all threads), so that
    ``hop.wake_worker + hop.wake_loop + hop.worker + hop.loop`` is ``hop``
    again, every piece of a turn named."""

    _clock = staticmethod(time.perf_counter)

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.seconds: Dict[str, float] = dict.fromkeys(LOOP_PHASES, 0.0)
        self.counts: Dict[str, int] = dict.fromkeys(LOOP_PHASES, 0)
        # "<phase>.<part>" -> wall seconds / occurrences; a key appears with
        # the first part opened under it
        self.part_seconds: Dict[str, float] = {}
        self.part_counts: Dict[str, int] = {}
        self.handoffs = 0
        self.turns = 0
        self.slot_seconds = 0.0
        # how each first_token_wait found its token: already computed
        # ("yes") or still behind queued device work, so the read waited
        self.first_token_reads = {"yes": 0, "no": 0}
        # cached rows each program's attention had to read (the live context
        # of its rows, written row included), from host integers at dispatch
        self.attn_calls = dict.fromkeys(MOE_PROGRAMS, 0)
        self.attn_context_tokens = dict.fromkeys(MOE_PROGRAMS, 0)
        # ... and the rows the read VISITED for them (the whole block-table
        # view, or whole visits of the live-page kernel): visited / context
        # is the over-read
        self.attn_rows_read = dict.fromkeys(MOE_PROGRAMS, 0)
        # ... and how much of the three went through latent attention's
        # expanded-once read (form="expanded": a wide chunk's; the rest is
        # form="absorbed"), by the rule the module itself takes
        self.attn_expanded = {key: dict.fromkeys(MOE_PROGRAMS, 0)
                              for key in ("calls", "context_tokens", "rows_read")}
        # the same three for the sliding-attention layers of a model that has
        # them (kind="window"; the three above are then its full layers'):
        # ``context_tokens`` the rows INSIDE the window, and ``unwindowed``
        # what a full layer would have had to read for the same queries
        self.attn_window = {key: dict.fromkeys(MOE_PROGRAMS, 0)
                            for key in ("calls", "context_tokens", "rows_read",
                                        "context_tokens_unwindowed")}
        # ... and the first three for the cross-attention layers of a model that
        # has them (kind="shared": a row a layer; each reads the pool of the
        # layer cfg.kv_source in place), with the rows that ran the layers up
        # to cfg.kv_source and the rows that ran those past it (a prompt's
        # chunk: one row, or none)
        self.attn_shared = {key: dict.fromkeys(MOE_PROGRAMS, 0)
                            for key in ("calls", "context_tokens", "rows_read")}
        self.decoder_rows = {half: dict.fromkeys(MOE_PROGRAMS, 0) for half in ("self", "cross")}
        # how the chunks' rows reached the paged pool (whole pages, or one
        # scatter row a token) and the pool pages a layer's write wrote
        self.kv_chunk_writes = dict.fromkeys(KV_WRITE_PATHS, 0)
        self.kv_pages_written = dict.fromkeys(KV_WRITE_PATHS, 0)
        # prefill chunks by whether the program's conditional ran the head
        # ("1" a prompt's last chunk, for its one last row; "0" the rest) and
        # the width of the chunk program that ran: {ran: {width: chunks}}
        self.chunk_head: Dict[str, Dict[str, int]] = {"1": {}, "0": {}}
        # live rows (prompt tokens) by the width of the chunk program that took
        # them (a prompt's last chunk may be part full at either width)
        self.chunk_rows: Dict[str, int] = {}
        # live rows through the state layers of each kind (a model with
        # layer_types: "conv" the short convolutions, "gdn" the linear-attention
        # layers, "ssd" the mamba layers), and such layers x calls, from host
        # integers at dispatch
        self.state_rows = {kind: dict.fromkeys(MOE_PROGRAMS, 0) for kind in STATE_COUNTERS}
        self.state_layer_calls = {kind: dict.fromkeys(MOE_PROGRAMS, 0) for kind in STATE_COUNTERS}
        # decode step programs built over linear-attention ("gdn") or mamba
        # ("ssd") layers, by how their rule runs (the kernel, or the
        # expression's further pass over the state)
        self.step_path = {kind: dict.fromkeys(STEP_PATHS, 0) for kind in STEP_PATH_KINDS}
        self._open: List[_Phase] = []
        self._open_parts: List[_Phase] = []
        self._turn: Optional[Any] = None   # the open turn's annotation
        self._turn_t0 = 0.0
        self._turn_phases = 0.0            # phase seconds inside this turn
        # the loop coroutine's own stretch of the turn (``hop.loop``): its
        # annotation, when it began and the phase seconds booked by then
        self._own: Optional[Any] = None
        self._own_t0 = 0.0
        self._own_phases = 0.0

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name, self._open, self._add_phase)

    def part(self, name: str) -> _Phase:
        """A part of the innermost open phase (one must be open):
        ``llm.<phase>.<part>`` in a trace, ``<phase>.<part>`` in
        ``part_seconds``. A second level that takes nothing from the first:
        the phase's seconds stay its whole wall. Parts nest among themselves
        as phases do (``emit.finish`` comes out of ``emit.slots``)."""
        return _Phase(self, self._open[-1].name + "." + name,
                      self._open_parts, self._add_part)

    def _add_phase(self, name: str, seconds: float) -> None:
        self.seconds[name] += seconds
        self.counts[name] += 1
        self._turn_phases += seconds

    def _add_part(self, name: str, seconds: float) -> None:
        self.part_seconds[name] = self.part_seconds.get(name, 0.0) + seconds
        self.part_counts[name] = self.part_counts.get(name, 0) + 1

    def turn(self, active_slots: int) -> None:
        """Top of a loop turn: close the previous one (what its phases did
        not cover is ``hop``; its wall times the active slots goes into the
        occupancy integral) and open the next."""
        self.end_turn(active_slots)
        self._turn = self._annotation("llm.turn")
        self._turn.__enter__()
        self._turn_t0 = self._clock()
        self._turn_phases = 0.0
        self._enter_own(self._turn_t0)

    def end_turn(self, active_slots: int) -> None:
        if self._turn is None:
            return
        now = self._clock()
        self._leave_own(now)
        wall = now - self._turn_t0
        self._turn.__exit__(None, None, None)
        self._turn = None
        self.seconds["hop"] += max(wall - self._turn_phases, 0.0)
        self.counts["hop"] += 1
        self.turns += 1
        self.slot_seconds += active_slots * wall

    def _enter_own(self, now: float) -> None:
        """The loop coroutine runs its own code from ``now`` on."""
        self._own = self._annotation("llm.hop.loop")
        self._own.__enter__()
        self._own_t0, self._own_phases = now, self._turn_phases

    def _leave_own(self, now: float) -> None:
        """... until ``now``: a hand-off's submission or the turn's end.
        What it spent in no phase (``idle`` is opened here) is ``hop.loop``."""
        if self._own is None:
            return
        self._own.__exit__(None, None, None)
        self._own = None
        self._add_part("hop.loop", max(
            now - self._own_t0 - (self._turn_phases - self._own_phases), 0.0))

    def handoff(self, fn, *args) -> "_Handoff":
        """One hand-off of the loop coroutine to a worker thread, stamped:
        call this at submission, run the result on the worker, and call its
        ``resumed()`` back on the loop thread (``ContinuousBatcher._to_thread``)."""
        return _Handoff(self, fn, args)

    def stats(self) -> dict:
        state = {}
        for kind in STATE_COUNTERS:     # absent for a model without such layers
            if any(self.state_layer_calls[kind].values()):
                state[f"{kind}_rows"] = dict(self.state_rows[kind])
                state[f"{kind}_layer_calls"] = dict(self.state_layer_calls[kind])
        for kind, paths in self.step_path.items():
            if any(paths.values()):
                state[f"{kind}_step_path"] = dict(paths)
        return {**state,
                "loop_seconds": dict(self.seconds),
                "loop_phase_counts": dict(self.counts),
                "loop_part_seconds": dict(self.part_seconds),
                "loop_part_counts": dict(self.part_counts),
                "loop_handoffs": self.handoffs,
                "loop_turns": self.turns,
                "slot_seconds": self.slot_seconds,
                "first_token_reads": dict(self.first_token_reads),
                "attn_calls": dict(self.attn_calls),
                "attn_context_tokens": dict(self.attn_context_tokens),
                "attn_rows_read": dict(self.attn_rows_read),
                **{f"attn_expanded_{key}": dict(tally) for key, tally in self.attn_expanded.items()},
                **({f"attn_window_{key}": dict(tally) for key, tally in self.attn_window.items()}
                   if any(self.attn_window["calls"].values()) else {}),
                **({**{f"attn_shared_{key}": dict(tally)
                       for key, tally in self.attn_shared.items()},
                    **{f"{half}_decoder_rows": dict(tally)
                       for half, tally in self.decoder_rows.items()}}
                   if any(self.decoder_rows["self"].values()) else {}),
                "kv_chunk_writes": dict(self.kv_chunk_writes),
                "kv_pages_written": dict(self.kv_pages_written),
                "chunk_head": {ran: dict(widths) for ran, widths in self.chunk_head.items()},
                "chunk_rows": dict(self.chunk_rows)}

    def count_attention(self, program: str, context_tokens: int, rows_read: int,
                        form: str = "absorbed") -> None:
        self.attn_calls[program] += 1
        self.attn_context_tokens[program] += context_tokens
        self.attn_rows_read[program] += rows_read
        if form == "expanded":
            tally = self.attn_expanded
            tally["calls"][program] += 1
            tally["context_tokens"][program] += context_tokens
            tally["rows_read"][program] += rows_read

    def count_window_attention(self, program: str, context_tokens: int, rows_read: int,
                               unwindowed: int) -> None:
        tally = self.attn_window
        tally["calls"][program] += 1
        tally["context_tokens"][program] += context_tokens
        tally["rows_read"][program] += rows_read
        tally["context_tokens_unwindowed"][program] += unwindowed

    def count_decoders(self, program: str, rows: int, cross_rows: int, context_tokens: int,
                       rows_read: int) -> None:
        """A call of ``program`` of a model with cross-attention layers:
        ``rows`` live rows through the layers up to cfg.kv_source, ``cross_rows``
        of them through the rest, whose cross-attention read (a layer) had
        ``context_tokens`` of the shared pool to read and visited ``rows_read``."""
        self.decoder_rows["self"][program] += rows
        self.decoder_rows["cross"][program] += cross_rows
        if cross_rows:
            self.attn_shared["calls"][program] += 1
            self.attn_shared["context_tokens"][program] += context_tokens
            self.attn_shared["rows_read"][program] += rows_read

    def count_chunk_write(self, path: str, pages: int) -> None:
        self.kv_chunk_writes[path] += 1
        self.kv_pages_written[path] += pages

    def count_state_layers(self, program: str, live_rows: int, calls: int,
                           layers: Dict[str, int]) -> None:
        """``calls`` calls of ``program`` with ``live_rows`` live rows in all,
        through ``layers`` = {kind: state layers of that kind}."""
        for kind, n in layers.items():
            self.state_rows[kind][program] += live_rows
            self.state_layer_calls[kind][program] += calls * n


def _in_phase(name: str):
    """Run a batcher method as one loop phase."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(self, *args, **kwargs):
            with self._phases.phase(name):
                return fn(self, *args, **kwargs)
        return run
    return wrap


class MoECounters:
    """Routing tallies of an MoE model, single writer like LoopPhases. By
    program kind, what the DEVICE did (a slot whose budget is spent rides
    along in a step until the drain releases it: its row chose experts and
    their weights were read): calls, live rows, routed (token, expert) pairs,
    and, summed over the ``n_layers`` MoE layer-calls of each call, the distinct
    experts touched, the largest expert group and the rows the grouped-matmul
    kernel multiplied (``tile_rows``: visits x row tile, a 128-row tile's visits
    count their sub-block, the run of 32-row blocks that holds their rows; so
    ``routed_pairs`` / ``tile_rows`` is the fill; 0 where ``ragged_dot`` serves).
    ``expert_tokens`` [e] is
    what was DELIVERED: prompt tokens, and decode rows whose token was
    credited to a request, by expert, summed over layers."""

    FIELDS = ("calls", "live_rows", "routed_pairs", "experts_touched",
              "max_group", "tile_rows", "pairs_elsewhere")

    def __init__(self, n_experts: int, n_layers: int, first: int = 0):
        """``n_experts`` experts HELD here, global ids from ``first`` (a model
        that holds a share of its experts counts those: ``routed_pairs`` /
        ``experts_touched`` / ``max_group`` are of held pairs and experts, and
        ``pairs_elsewhere`` the pairs whose expert lies on another chip)."""
        self.n_layers = n_layers
        self.first = first
        self.by_program = {kind: dict.fromkeys(self.FIELDS, 0)
                           for kind in MOE_PROGRAMS}
        self.expert_tokens = np.zeros((n_experts,), np.int64)

    def add(self, kind: str, stats: np.ndarray) -> None:
        """``stats`` [calls, 6]: moe_routing_stats of each call."""
        tally = self.by_program[kind]
        tally["calls"] += len(stats)
        for name, total in zip(self.FIELDS[1:], stats.sum(axis=0)):
            tally[name] += int(total)

    def flight_fields(self, stats: np.ndarray) -> dict:
        """One call's ``stats`` [6] as the fields its flight events carry."""
        return {"moe_live": int(stats[0]),
                "moe_touched": round(float(stats[2]) / self.n_layers, 2)}

    def stats(self) -> dict:
        return {"moe_layers": self.n_layers, "moe_expert_first": self.first,
                "moe_by_program": {k: dict(v) for k, v in self.by_program.items()},
                "moe_expert_tokens": self.expert_tokens.tolist()}


class _InFlight:
    """One dispatched (possibly K-fused) decode step the host has not yet
    drained: the device token array, the per-slot (index, gen) snapshot
    taken at dispatch, and the dispatch timestamp.

    Speculative verify steps additionally carry ``acc`` (the device [S]
    accepted-token counts — how far each slot ACTUALLY advanced, 1..K+1)
    and ``booked`` (slot -> the pessimistic K+1 maximum the dispatch
    side credited to ``disp_new``; the drain reconciles the difference)."""

    __slots__ = ("tokens", "k", "snapshot", "t_dispatch", "acc", "booked",
                 "aside", "built")

    def __init__(self, tokens, k, snapshot, t_dispatch, acc=None,
                 booked=None, aside=None, built=None):
        # {"built": program} where the dispatching call had to build the
        # program, or load it from the compile cache (tracing/start.py): for
        # the step's flight event; empty for every call but a first
        self.built = built or {}
        # what left the step beside its tokens (LLMServer._get_decode_step):
        # device arrays the drain reads only after the tokens have landed
        self.aside = aside or {}
        self.tokens = tokens
        self.k = k
        self.snapshot = snapshot
        self.t_dispatch = t_dispatch
        self.acc = acc
        self.booked = booked


class _FirstToken:
    """One activation the host has not yet read: the prompt's first token
    as a device scalar, queued in ``_inflight`` behind the steps that were
    dispatched before its last chunk, with the ``(slot, gen)`` it was
    sampled for. Its drain surfaces the token (TTFT, ``on_token``, EOS and
    ``max_new <= 1`` finishes) and is the one place that reads what the
    admission left on the device: the chunks' ``asides`` and, for a probe
    that asked for logits (``info``), the prompt's last ``row``. ``k`` = 0:
    it is no decode step, takes no place of ``pipeline_depth`` and adds
    nothing to the host's lag."""

    __slots__ = ("slot", "gen", "token", "row", "asides", "info",
                 "t_arrival")
    k = 0

    def __init__(self, slot, gen, token, row, asides, info, t_arrival):
        self.slot = slot
        self.gen = gen
        self.token = token
        self.row = row
        self.asides = asides
        self.info = info
        self.t_arrival = t_arrival


class BatcherService:
    """Owns a ContinuousBatcher on a dedicated event-loop thread so every
    transport can reach ONE shared batch: async REST handlers await
    ``submit``, the sync gRPC servicer blocks on ``submit_sync`` — either
    way the request joins the in-flight decode batch instead of running its
    own ``generate()``. Created lazily per component by
    ``get_batcher_service`` (keyed on the component, so REST and gRPC in one
    process share slots)."""

    def __init__(self, server: "LLMServer", max_slots: int = 4):
        import threading

        self._loop = asyncio.new_event_loop()
        threading.Thread(target=self._loop.run_forever, name="batcher-loop",
                         daemon=True).start()
        max_len = getattr(server, "continuous_batching_max_len", None)

        async def make():
            return ContinuousBatcher(server, max_slots=max_slots,
                                     max_len=max_len)

        # the pool's and the tables' allocation and their zero-fill programs:
        # the start's one stage after /ready (tracing/start.py)
        with get_ledger().stage("batcher.build"):
            self.batcher = asyncio.run_coroutine_threadsafe(make(), self._loop).result()
        self.submitted = 0
        # Requests handed to the loop whose futures have not resolved yet.
        # This covers the drain blind window the batcher itself cannot see:
        # between run_coroutine_threadsafe and the submit coroutine actually
        # running on the loop thread, a request exists in NO batcher
        # structure (_pending/_slots/_inflight) — is_idle() must still
        # count it, or collect_drained could close a batcher holding a
        # live client request.
        self._inflight_reqs = 0
        # submit() runs on transport loops and submit_sync() on gRPC worker
        # threads at once; the counter bumps are read-modify-writes, and
        # unlocked concurrent increments lose updates
        self._stats_lock = threading.Lock()

    def _track(self, cfut):
        """Count one submission in flight until its future settles (any
        outcome — tokens, shed, error: settled means the batcher no longer
        owes the client anything). Incremented BEFORE the caller can
        observe the future, so is_idle() has no window where a submitted
        request is invisible."""
        with self._stats_lock:
            self.submitted += 1
            self._inflight_reqs += 1

        def _settled(_f):
            with self._stats_lock:
                self._inflight_reqs -= 1

        cfut.add_done_callback(_settled)
        return cfut

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
        return self._track(asyncio.run_coroutine_threadsafe(
            self.batcher.submit(prompt, max_new_tokens, on_token=on_token,
                                info=info, seed=seed,
                                trace=trace, tenant=tenant,
                                slo_class=slo_class, adapter=adapter,
                                deadline_s=deadline_s,
                                resume_tokens=resume_tokens),
            self._loop
        )).result(timeout_s)

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
        cfut = self._track(asyncio.run_coroutine_threadsafe(
            self.batcher.submit(prompt, max_new_tokens, on_token=on_token,
                                info=info, seed=seed, trace=trace,
                                tenant=tenant, slo_class=slo_class,
                                adapter=adapter, deadline_s=deadline_s,
                                resume_tokens=resume_tokens),
            self._loop))
        return await asyncio.wrap_future(cfut)

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
        """Streaming submit from a SYNC thread (the gRPC server-streaming
        servicer): returns the concurrent.futures.Future of the final token
        list while ``on_token`` fires per token from the batcher's worker
        thread — the caller pumps its own response stream from them."""
        return self._track(asyncio.run_coroutine_threadsafe(
            self.batcher.submit(prompt, max_new_tokens, on_token=on_token,
                                info=info, seed=seed, trace=trace,
                                tenant=tenant, slo_class=slo_class,
                                adapter=adapter, deadline_s=deadline_s,
                                resume_tokens=resume_tokens),
            self._loop))

    def drain(self) -> None:
        """Scale-down drain mark (docs/control-plane.md): flips the
        batcher's advisory flag — in-flight and queued work is untouched."""
        self.batcher.drain()

    def resume(self) -> None:
        """Cancel a drain (scale-up arrived before detach): the warm
        batcher rejoins fleet dispatch."""
        self.batcher.resume()

    def is_idle(self) -> bool:
        """Detach gate for the autoscaler's collect sweep: the batcher's
        own idle check AND zero unsettled service-level submissions — the
        latter closes the window where a request scheduled onto the loop
        thread is not yet visible in any batcher structure."""
        with self._stats_lock:
            busy = self._inflight_reqs
        return busy == 0 and self.batcher.is_idle()

    def close(self) -> None:
        asyncio.run_coroutine_threadsafe(self.batcher.close(), self._loop).result(30)
        self._loop.call_soon_threadsafe(self._loop.stop)


# created at import time: a lazily-created lock would itself race, which is
# the exact bug this lock exists to prevent
import threading as _threading

_service_init_lock = _threading.Lock()


def _init_lock():
    return _service_init_lock


def get_batcher_service(component: Any) -> Optional[BatcherService]:
    """The component's shared BatcherService, created on first use when the
    component opted in (``continuous_batching`` slots > 0) and exposes the
    LLM generate surface; None otherwise. Creation is locked: the first REST
    request (event loop) and first gRPC request (worker thread) can race,
    and two batchers would each allocate slot caches and step the device."""
    if getattr(component, "is_fleet", False):
        # a ReplicaSet IS the service: it fans submits across replicas
        # (with health ejection + deterministic resume — runtime/engine.py)
        # and must never be wrapped in a batcher of its own
        return component
    svc = getattr(component, "_batcher_service", None)
    if svc is not None:
        return svc  # reuse even when batching is off (streaming's 1-slot svc)
    slots = int(getattr(component, "continuous_batching", 0) or 0)
    if slots <= 0 or not hasattr(component, "generate"):
        return None
    with _init_lock():
        svc = getattr(component, "_batcher_service", None)
        if svc is None:
            svc = BatcherService(component, max_slots=slots)
            component._batcher_service = svc
    return svc


def ensure_stream_service(component: Any) -> BatcherService:
    """Streaming without continuous batching: one shared 1-slot service per
    component (same double-checked lock; never one per request).
    A fleet (ReplicaSet) short-circuits through get_batcher_service."""
    svc = get_batcher_service(component)
    if svc is not None:
        return svc
    with _init_lock():
        svc = getattr(component, "_batcher_service", None)
        if svc is None:
            svc = BatcherService(component, max_slots=1)
            component._batcher_service = svc
    return svc


class ContinuousBatcher:
    def __init__(
        self,
        server: LLMServer,
        max_slots: int = 4,
        max_len: Optional[int] = None,
        len_buckets: Optional[Sequence[int]] = None,
        pipeline_depth: Optional[int] = None,
        fuse_steps: Optional[int] = None,
        page_size: Optional[int] = None,
        pool_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        spec_mode: Optional[str] = None,
        spec_k: Optional[int] = None,
        disaggregation: Optional[str] = None,
        disagg_mesh: Optional[Any] = None,
        prefill_workers: Optional[int] = None,
        handoff_transport: Optional[str] = None,
        tracing: Optional[bool] = None,
    ):
        server.load()
        self.server = server
        self.S = int(max_slots)
        cfg = server._cfg
        # Slot caches are HBM-resident for the batcher's whole life (S slots
        # x max_len x KV bytes/token — ~0.5 MB/token at 7B), so size them to
        # what serving actually admits: prompts bucket to len_buckets with
        # one round-up step past the top bucket (_bucket), plus decode
        # headroom. Defaulting to the model's full trained context instead
        # (4k at 7B) allocates 17 GB of KV and OOMs the chip before the
        # first request. Prompts longer than 2x the top bucket truncate to
        # the cache (admit keeps the TAIL, same rule as before); a
        # deployment expecting longer prompts passes max_len explicitly
        # (LLMServer.continuous_batching_max_len).
        self.len_buckets = tuple(len_buckets or server.len_buckets)
        if max_len is not None and int(max_len) <= 0:
            # 0/negative means "unset" from every caller's point of view;
            # taking it literally would produce plen=min(...,-1) nonsense
            # tail slicing (ADVICE.md round 5)
            max_len = None
        if max_len is None:
            max_len = min(2 * max(self.len_buckets), cfg.max_seq_len) + max(
                int(server.max_new_tokens), 1
            )
        self.max_len = int(max_len)
        self.eos_id = server.eos_id
        self._slots = [_Slot() for _ in range(self.S)]
        from collections import deque

        # SLO-aware weighted-fair admission queue (runtime/scheduler.py,
        # ISSUE 15): replaces the FIFO deque — requests order by SLO class
        # (interactive vs batch) and tenant under stride-scheduled
        # weighted fairness, with per-tenant quotas shedding early and
        # deadline-carrying requests ordered EDF within their tenant. The
        # peek-try-commit admission idiom is unchanged: a failed admit
        # keeps the request queued.
        from seldon_core_tpu.runtime.scheduler import WeightedFairScheduler

        self._pending: Any = WeightedFairScheduler(
            class_weights=getattr(server, "slo_class_weights", None),
            tenant_weights=getattr(server, "tenant_weights", None),
            tenant_quota=int(getattr(server, "tenant_quota", 0) or 0),
            tenant_quotas=getattr(server, "tenant_quotas", None))
        # Batched LoRA (runtime/adapters.py): when the server carries an
        # AdapterRegistry every compiled step runs the adapted variant —
        # per-slot adapter ids gather each tenant's low-rank delta, with
        # id 0 the zero-delta identity for untenanted traffic.
        self._adapters = getattr(server, "adapter_registry", None)
        self._wakeup = asyncio.Event()
        self._closed = False
        self._task: Optional[asyncio.Task] = None
        # Fleet health view (docs/resilience.md "Fleet fault tolerance"):
        # the loop stamps ``heartbeat`` once per turn from its single
        # serialized context and parks its terminal exception in
        # ``crashed`` — plain single-writer fields ReplicaSet.check_health
        # reads to eject a dead replica from dispatch. ``clock`` is
        # injectable so chaos tests drive staleness from a FaultClock, and
        # ``_chaos`` is the deterministic fault hook the chaos harness
        # installs (called at the top of every loop turn; raising there
        # kills the loop exactly where a real device fault would).
        import time as _time

        self.clock: Any = _time.monotonic
        self.heartbeat: float = self.clock()
        self.crashed: Optional[BaseException] = None
        self._chaos: Optional[Any] = None
        # dispatch-ahead pipeline: how many steps may be in flight before
        # the host drains the oldest (>=2 overlaps host bookkeeping with
        # device compute), and the fused-K knob (0/1 = single steps)
        depth = pipeline_depth if pipeline_depth is not None else getattr(
            server, "decode_pipeline_depth", 2)
        self.pipeline_depth = max(int(depth), 1)
        fuse = fuse_steps if fuse_steps is not None else getattr(
            server, "decode_fuse_steps", 0)
        self.fuse_steps = max(int(fuse), 0)
        # Speculative decoding (module docstring): draft mode + depth K,
        # resolved from the server unless overridden. The per-slot
        # acceptance-rate controller adapts the offered draft length to
        # what each slot's text actually accepts.
        from seldon_core_tpu.runtime.spec import (
            DEFAULT_SPEC_K, SpecController, normalize_spec_mode)

        mode = spec_mode if spec_mode is not None else getattr(
            server, "spec_mode", "off")
        self.spec_mode = normalize_spec_mode(mode)
        k = spec_k if spec_k is not None else getattr(server, "spec_k", 0)
        self.spec_k = int(k or 0) or DEFAULT_SPEC_K
        if self.spec_mode != "off":
            if self.fuse_steps > 1:
                raise ValueError(
                    f"decode_fuse_steps={self.fuse_steps} cannot combine "
                    f"with spec_mode={self.spec_mode!r}: the fused scan "
                    f"runs a FIXED K steps per dispatch while a verify "
                    f"step advances each slot by a data-dependent 1.."
                    f"{self.spec_k + 1} tokens — a fused variable-stride "
                    f"scan is a follow-up; run speculation with "
                    f"decode_fuse_steps=0 (pipelining composes fine)")
            if self.spec_k < 1:
                raise ValueError(
                    f"spec_k={self.spec_k} must be >= 1 when speculation "
                    f"is on")
            if self.spec_mode == "draft" and getattr(
                    server, "_draft_module", None) is None:
                raise ValueError(
                    "spec_mode='draft' needs the server loaded with a "
                    "draft model (draft_model= / draft_model_uri=)")
            self._spec = SpecController(self.S, self.spec_k)
        # KV store: a global page pool + per-slot block tables. max_len keeps
        # its requested value and the block-table view simply spans
        # ceil(max_len/page_size) pages (the past-max_len tail of the last
        # page is never written and its PAD_POS rows are never attended).
        ps = int(page_size if page_size is not None else
                 getattr(server, "kv_page_size", 0) or 0) or DEFAULT_PAGE_SIZE
        if ps <= 0:
            raise ValueError(f"kv_page_size={ps} must be positive")
        self.page_size = ps
        self.n_pages = -(-self.max_len // ps)   # pages per slot
        pool = int(pool_pages if pool_pages is not None else
                   getattr(server, "kv_pool_pages", 0) or 0)
        # 0 = fully provisioned (every slot can reach max_len at once —
        # never sheds on pages); smaller pools oversubscribe
        self.pool_pages = pool or (self.S * self.n_pages + RESERVED_PAGES)
        if self.pool_pages - RESERVED_PAGES < self.n_pages:
            raise ValueError(
                f"kv_pool_pages={self.pool_pages} cannot hold even one "
                f"max_len sequence ({self.n_pages} pages of {ps} tokens "
                f"+ {RESERVED_PAGES} reserved)")
        chunk = int(prefill_chunk if prefill_chunk is not None else
                    getattr(server, "prefill_chunk", 0) or 0)
        self.prefill_chunk = chunk or DEFAULT_PREFILL_CHUNK
        # a width somebody gave is every chunk's; the default widens, whatever
        # the model (PRs 48-53: only where it routes experts, for what a second
        # chunk program cost a dense server's start; PERF.md section 6, PR 54)
        self.prefill_wide = 0 if chunk else WIDE_PREFILL_CHUNK
        # the wide program is the one program no request needs (a narrow chunk
        # does what it does), so no start pays for it: a thread of its own
        # builds it once a request has FINISHED, so behind every program a
        # request waits for (``_build_wide_program``), and a chunk is wide once
        # it is there (a seeded request's waits for it). The abstract arguments
        # of a chunk call, kept from the first chunk, and the thread
        self._chunk_shapes: Optional[tuple] = None
        self._wide_build: Optional[threading.Thread] = None
        self._allocator = PageAllocator(self.pool_pages, ps)
        # The WINDOW class (a model with sliding-attention layers,
        # cfg.window_layers): a second pool, allocator and block table a slot,
        # for the layers whose pages behind the window are given back while the
        # request lives. Always fully provisioned: a slot holds at most a
        # window, the widest chunk and a page of it (``window_slot_pages``),
        # whatever its length, so the class cannot run out and sheds nobody;
        # ``kv_pool_pages`` oversubscribes the full class alone.
        self.window = int(cfg.sliding_window) if cfg.window_layers else 0
        self._window_allocator: Optional[PageAllocator] = None
        if self.window:
            self.window_slot_pages = min(self.n_pages, kvcache.window_slot_pages(
                self.window, max(self.prefill_chunk, self.prefill_wide), ps))
            self.window_pool_pages = self.S * self.window_slot_pages + RESERVED_PAGES
            self._window_allocator = PageAllocator(self.window_pool_pages, ps)
        # Radix prefix cache (runtime/radix.py, docs/performance.md "Radix
        # prefix cache"): prefix caching opted in. The trie
        # shares pool pages between cached prefixes and live slots
        # (refcounted, copy-on-write), so a hit costs block-table entries
        # instead of a page gather/copy; completed slots insert their
        # blocks back in place.
        self._radix = None
        if int(getattr(server, "prefix_cache_size", 0)) > 0:
            from seldon_core_tpu.runtime.radix import RadixPrefixCache

            self._radix = RadixPrefixCache(
                self._allocator, self.page_size,
                bytes_per_block=self.page_size * kvcache.kv_cache_bytes_per_token(
                    cfg, server.kv_cache_dtype))
        self._prefill: Optional[_PrefillJob] = None
        self._admit_seq = 0
        # Drain state (docs/control-plane.md "Drain semantics"): set by the
        # autoscaler's scale-down path through ReplicaSet.drain_replica —
        # fleet routing stops targeting this replica, but anything already
        # queued or in flight here runs to completion, and a request that
        # slipped through the routing race window is still served (a drain
        # may delay detach; it must never fail a client).
        self.draining = False
        # dispatched decode steps (_InFlight) and activations (_FirstToken)
        # the host has not read yet, in device program order
        self._inflight: Any = deque()
        self._steps_in_flight = 0    # the _InFlight records among them
        self._inflight_hwm = 0       # max steps in flight ever reached
        self._last_admit_inflight = 0  # steps in flight at the last admit
        self._last_drain_t: Optional[float] = None
        # the loop's time budget (module docstring): always on
        self._phases = LoopPhases()
        self._read_walks: Dict[int, Any] = {}   # query tokens a call -> how its read walks
        cfg = server._cfg
        self._moe = (MoECounters(cfg.n_experts_held, cfg.n_moe_layers, cfg.experts_first)
                     if cfg.n_experts > 0 else None)
        # Disaggregated prefill/decode (module docstring): remote-prefill
        # admission stages jobs on prefill-slice workers and consumes
        # finished handoffs from the TransferQueue instead of prefilling
        # locally. Resolved from the server unless overridden.
        from seldon_core_tpu.runtime.disagg import normalize_disaggregation

        disagg = disaggregation if disaggregation is not None else getattr(
            server, "disaggregation", "off")
        self.disaggregation = normalize_disaggregation(disagg)
        # How finished prefills reach the decode slice: "device" keeps the
        # jax.device_put fast path; "network" frames the KV bucket and
        # streams it through a HandoffReceiver (cross-host decode —
        # bit-exact vs device, tests/test_network_handoff.py).
        from seldon_core_tpu.runtime.disagg import HANDOFF_TRANSPORTS

        ht = handoff_transport if handoff_transport is not None else getattr(
            server, "handoff_transport", "") or "device"
        if ht not in HANDOFF_TRANSPORTS:
            raise ValueError(
                f"unknown handoff_transport {ht!r}: expected one of "
                f"{HANDOFF_TRANSPORTS}")
        self.handoff_transport = ht
        self._remote = None
        self._transfer = None
        self._receiver = None
        self._remote_jobs: "dict[int, _RemoteJob]" = {}
        self._job_seq = 0
        # Flight recorder (module docstring, runtime/flight.py): built only
        # when the tracer is enabled (``tracing`` overrides for tests and
        # the bench's overhead arm) — disabled tracing leaves every hook a
        # None check and the compiled step path untouched.
        from seldon_core_tpu.tracing import get_tracer, tail_thresholds

        self._tracer = get_tracer()
        self._ledger = get_ledger()
        enabled = self._tracer.enabled if tracing is None else bool(tracing)
        if enabled:
            from seldon_core_tpu.runtime.flight import FlightRecorder

            tail_ttft_s, tail_gap_s = tail_thresholds()
            self._flight: Optional[Any] = FlightRecorder(
                self.S, tail_ttft_s=tail_ttft_s, tail_gap_s=tail_gap_s)
        else:
            self._flight = None
        self._build()
        if self.disaggregation != "off":
            self._build_remote(disagg_mesh, prefill_workers)

    # ------------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp

        from functools import partial

        server, cfg = self.server, self.server._cfg
        # the ONE tree the step programs thread and donate (models/cache.py),
        # in the server's KV storage format: a page pool for every attention
        # layer and, for a state layer (cfg.layer_types), fixed blocks a slot
        self._caches = jax.jit(
            lambda: kvcache.init_paged_kv_caches(
                cfg, self.pool_pages, self.page_size, server.kv_cache_dtype,
                state_slots=self.S,
                **({"window_pages": self.window_pool_pages} if self.window else {}))
        )()
        self._cache_nbytes = sum(
            int(getattr(leaf, "nbytes", 0)) for leaf in jax.tree.leaves(self._caches)
        )
        # state layers by the kind their counters name, and their entries' bytes
        self._state_layers = {
            name: len(cfg.layers_of(kind)) for name, kind in STATE_COUNTERS.items()
            if cfg.layers_of(kind)}
        self.state_nbytes = kvcache.state_nbytes(self._caches)
        # ... of which the float32 matrix state, as the arrays count it and as
        # the chip tiles it (equal where S holds no padded lane or sublane)
        self.state_matrix_nbytes, self.state_matrix_tiled_nbytes = (
            kvcache.matrix_state_nbytes(self._caches))
        # the decode step programs seen so far (a new one is counted by the
        # path its rule takes: seldon_llm_gdn_step_path / seldon_llm_ssd_step_path)
        self._step_programs: set = set()
        # a model whose layers past cfg.kv_source cache nothing and read that
        # layer's pool (seldon_llm_*_decoder_rows_total, kind="shared")
        self._cross_decoder = getattr(cfg, "kv_source", None) is not None
        # which state row a chunk's one sequence continues: its slot, as a
        # device array made once (no transfer a chunk)
        self._state_slot = [jnp.asarray([i], jnp.int32) for i in range(self.S)
                            ] if self._state_layers else None
        self._matrix_state = None    # the program a probe's "state" is read by, once asked
        # the step's read is the XLA gather of the whole view, except on one
        # TPU, where a kernel walks the live pages (ops/page_walk.py) — said
        # here so a server's log names it
        logger.info(
            "paged KV pool: %d pages x %d tokens, %s, %d B a token (%s), "
            "%.2f GB; decode read: %s", self.pool_pages, self.page_size,
            server.kv_cache_dtype,
            kvcache.kv_cache_bytes_per_token(cfg, server.kv_cache_dtype),
            "latent rows" if cfg.kv_lora_rank else "per-head K/V",
            self._cache_nbytes / 1e9,
            "gather" if self._read_walk(1) is None else "live_pages")
        if self.window:
            logger.info(
                "window page class: %d sliding-attention layers (window %d) on a pool of %d "
                "pages, %d a slot (window + widest chunk + one page), beside %d full layers "
                "on %d pages", len(cfg.window_layers), self.window, self.window_pool_pages,
                self.window_slot_pages,
                cfg.n_layers - len(cfg.state_layers) - len(cfg.window_layers), self.pool_pages)
        if self._state_layers:
            logger.info(
                "per-slot state: %s layers x %d slots, %d B a slot, %.1f MB resident",
                self._state_layers, self.S, self.state_nbytes // self.S,
                self.state_nbytes / 1e6)

        # No insert: chunked prefill writes straight into the pool through
        # the slot's block-table row. The device block table (one row per
        # slot) starts all-TRASH so inactive slots' ride-along decode writes
        # land in the trash page; rows switch to real pages at activation
        # and back to trash at release. Every table/pos mutation is a
        # donated jit, so program order on the device stream serializes it
        # behind in-flight steps (see module docstring).
        self._block_tables = jnp.full(
            (self.S, self.n_pages), TRASH_PAGE, jnp.int32)
        self._trash_row = jnp.full((self.n_pages,), TRASH_PAGE, jnp.int32)
        # the window class's table: the same logical entries (entry j backs
        # positions j * page_size ..), NULL_PAGE behind each slot's window
        self._window_tables = jnp.full(
            (self.S, self.n_pages), TRASH_PAGE, jnp.int32) if self.window else None

        # jitted table/slot-state ops are process-shared singletons
        # (_page_table_ops): a fresh batcher reuses the compiled code of
        # any prior batcher with the same shapes instead of recompiling
        # its own closures — page growth runs these mid-decode, where a
        # compile is a serving stall
        (self._set_block_row, self._set_block_entry, self._reset_pages,
         self._set_slot, self._set_hist_row, self._cow_page_copy,
         self._export_pages, self._set_adapter_id) = _page_table_ops()

        if self._adapters is not None:
            # per-slot adapter ids, device-resident like the decode state
            # (every adapted step gathers by them); 0 = identity
            self._adapter_ids = jnp.zeros((self.S,), jnp.int32)

        if self.spec_mode != "off":
            # Per-slot prompt+generated token history, device-resident: the
            # n-gram proposer matches against it and the verify step appends
            # accepted tokens to it inside the compiled program. One entry
            # per cache position, so every token a slot can ever hold fits.
            self.hist_len = self.max_len
            self._hist = jnp.zeros((self.S, self.hist_len), jnp.int32)
            if self.spec_mode == "draft":
                # The draft model's KV is always DENSE [S, max_len]: the
                # draft is small by construction, so paging it would buy
                # nothing and cost a second allocator. Its prompt prefill
                # lands through a donated insert: the big cache is
                # reassigned from the output, so XLA updates it in place
                # (the batcher.insert contract in tools/hlolint).
                dcfg = server._draft_cfg
                self._draft_caches = jax.jit(
                    lambda: kvcache.init_kv_caches(dcfg, self.S, self.max_len))()

                @partial(jax.jit, donate_argnums=(0,))
                def draft_insert(big, small, slot):
                    return jax.tree.map(
                        lambda b, s: b.at[slot].set(s[0]), big, small)

                self._draft_insert = draft_insert

        # device-resident per-slot decode state, threaded output->input
        # through every dispatched step (the decode jit updates them; the
        # host never round-trips them through NumPy)
        self._last_tok = jnp.zeros((self.S,), jnp.int32)
        self._next_pos = jnp.zeros((self.S,), jnp.int32)
        self._keys = jnp.zeros((self.S, 2), jnp.uint32)

        self._rng = jax.random.PRNGKey(server.seed)
        self._temp = jnp.asarray(server.temperature, jnp.float32)

    # ------------------------------------------------------------------
    # Disaggregated prefill: slice setup, handoff import, stats
    # ------------------------------------------------------------------
    def _build_remote(self, disagg_mesh, prefill_workers):
        """Split the device world and start the prefill-worker pool. The
        decode slice must contain the process DEFAULT device: the slot
        pool and every decode-side jit live uncommitted there, so
        anchoring the decode role on it means no serving-path array ever
        needs explicit placement — only the prefill workers commit copies
        to their own devices."""
        from seldon_core_tpu.parallel.topology import get_topology
        from seldon_core_tpu.runtime.disagg import (HandoffReceiver,
                                                    PrefillWorkerPool,
                                                    TransferQueue)

        server = self.server
        topo = getattr(server, "topology", None) or get_topology()
        mesh = disagg_mesh or getattr(server, "disagg_mesh", None)
        if mesh is None:
            mesh = topo.disaggregated(
                getattr(server, "prefill_devices", 0) or 1,
                getattr(server, "decode_devices", 0) or 0)
        default = topo.default_device
        if default not in mesh.decode_devices:
            raise ValueError(
                "the decode slice must contain the process default device "
                f"({default}): the batcher's slot pool lives there — put "
                "the PREFILL slice on the non-default devices")
        self.disagg_mesh = mesh
        n_workers = (prefill_workers
                     if prefill_workers is not None else
                     getattr(server, "prefill_workers", 0)) or len(
                         mesh.prefill_devices)
        devices = [mesh.prefill_devices[i % len(mesh.prefill_devices)]
                   for i in range(int(n_workers))]
        # the queue is built here (not inside the pool) so the network
        # receiver and the worker pool share it from birth — rebalance
        # swaps pools around BOTH
        queue = TransferQueue()
        receiver_addr = None
        if self.handoff_transport == "network":
            self._receiver = HandoffReceiver(queue, default)
            receiver_addr = self._receiver.addr
        self._remote = PrefillWorkerPool(
            server, devices, default,
            max_len=self.max_len, page_size=self.page_size,
            n_pages=self.n_pages, prefill_chunk=self.prefill_chunk,
            queue=queue, transport=self.handoff_transport,
            receiver_addr=receiver_addr)
        self._transfer = self._remote.queue

    def rebalance_disagg(self, prefill_devices: int) -> bool:
        """Move the prefill:decode device split to ``prefill_devices``
        prefill devices — the autoscaler's TPU-native actuator
        (controlplane/autoscaler.py; docs/control-plane.md "Rebalancing
        the disagg split").  Zero requests are dropped and generation is
        bit-exact across the move:

        - the NEW worker pool publishes into the SAME TransferQueue, so
          every registered job keeps its exactly-once delivery path;
        - the OLD pool's close() drains its backlog first — workers
          finish staged jobs and publish them before their threads join;
        - workers run the server's own cached compiled prefill programs,
          so WHERE prefill runs changes, never which KV bits come out
          (tests/test_autoscaler.py parity).

        Returns False when disaggregation is off, the split is already
        there, or the requested split is infeasible (decode must keep the
        process default device — the slot pool lives on it)."""
        if self._remote is None:
            return False
        from seldon_core_tpu.parallel.topology import get_topology
        from seldon_core_tpu.runtime.disagg import PrefillWorkerPool

        topo = getattr(self.server, "topology", None) or get_topology()
        n_pre = int(prefill_devices)
        if n_pre < 1 or n_pre >= topo.device_count:
            return False
        if n_pre == len(self.disagg_mesh.prefill_devices):
            return False
        mesh = topo.disaggregated(n_pre, 0)
        default = topo.default_device
        if default not in mesh.decode_devices:
            return False
        old = self._remote
        new_pool = PrefillWorkerPool(
            self.server, mesh.prefill_devices, default,
            max_len=self.max_len, page_size=self.page_size,
            n_pages=self.n_pages, prefill_chunk=self.prefill_chunk,
            queue=self._transfer, transport=old.transport,
            receiver_addr=old.receiver_addr)
        self.disagg_mesh = mesh
        # swap first (new admissions land on the new pool), then drain the
        # old pool: an admission that grabbed the old reference mid-swap
        # either submits before close (job drains normally) or gets the
        # closed error and retries on the new pool (_admit_remote)
        self._remote = new_pool
        old.close()
        logger.info("rebalanced disagg split to %d prefill / %d decode "
                    "devices", len(mesh.prefill_devices),
                    len(mesh.decode_devices))
        return True

    def _get_handoff_import(self, staged_pages: Optional[int] = None):
        """Jitted staged-pool -> slot-pool page import (the decode-side
        half of the KV handoff). ``staged_pages`` is the page count of the
        transferred buffer beyond the reserved rows (workers ship a
        power-of-two bucket, not the whole staging pool). Compiled and
        cached ON THE SERVER (servers/llmserver.py ``_get_handoff_import``,
        like the prefill programs) so rebuilt batchers and bench arms
        share one compile per bucket. Compiled-form contract:
        ``disagg.import_pages`` in tools/hlolint (zero host transfers,
        donation intact, bytes within budget)."""
        return self.server._get_handoff_import(self.n_pages, staged_pages)

    def drain(self) -> None:
        """Mark this batcher draining (scale-down): purely advisory state —
        admission keeps working so nothing routed here can ever fail, but
        the fleet dispatcher (ReplicaSet) stops targeting the replica and
        the scaling snapshot reports the state."""
        self.draining = True

    def resume(self) -> None:
        self.draining = False

    def is_idle(self) -> bool:
        """True when detaching this batcher cannot drop work: no queued
        request, no occupied or prefilling slot, no in-flight step, no
        staged local or remote prefill job.  The autoscaler's
        ``collect_drained`` gate."""
        return (len(self._pending) == 0 and not self._inflight
                and self._prefill is None and not self._remote_jobs
                and not any(s.active or s.prefilling for s in self._slots))

    def retry_after_hint(self) -> float:
        """Dynamic ``Retry-After`` for shed responses, derived from the
        actual backlog instead of the fixed constant: the drain capacity
        is S slots per wave, so a client retrying after
        ``base x ceil(queued work / S)`` seconds arrives roughly when the
        work ahead of it has drained — backoff scales with the exact
        spike the autoscaler is reacting to, instead of stampeding back
        into it.  Near page-pool exhaustion the hint doubles (pages free
        slower than slots under LIFO shedding).  Clamped to
        [base, 30s]."""
        from seldon_core_tpu.runtime.resilience import DEFAULT_RETRY_AFTER_S

        base = float(getattr(self.server, "shed_retry_after_s",
                             DEFAULT_RETRY_AFTER_S))
        queued = len(self._pending) + sum(
            1 for s in self._slots if s.active or s.prefilling)
        waves = -(-queued // max(self.S, 1))
        hint = base * max(waves, 1)
        total, in_use, _ = self._allocator.stats()
        usable = max(total - RESERVED_PAGES, 1)
        if in_use / usable >= 0.9:
            hint *= 2
        # the cap must never undercut an explicitly configured base: a
        # 60s floor stays 60s, it does not become 30s
        return float(min(max(hint, base), max(30.0, base)))

    def handoff_stats(self) -> dict:
        """Transfer-queue counters for llm_stats/metrics: handoffs
        delivered, bytes moved device-to-device, and the jobs currently
        staged or ready (the prefill-slice backlog signal replica routing
        steers by). All-off zeros when disaggregation is off."""
        if self._remote is None:
            return {"disaggregation": "off", "handoffs_total": 0,
                    "handoff_transfer_bytes_total": 0,
                    "handoff_queue_depth": 0,
                    "handoff_network_bytes_total": 0}
        total, nbytes, depth = self._transfer.stats()
        net = (self._receiver.stats()["handoff_network_bytes_total"]
               if self._receiver is not None else 0)
        return {
            "disaggregation": self.disaggregation,
            "handoffs_total": total,
            "handoff_transfer_bytes_total": nbytes,
            # staged + ready jobs (a registered job stays counted while it
            # waits in a worker backlog, runs, and sits ready — exactly
            # the prefill-side congestion a replica router cares about)
            "handoff_queue_depth": depth,
            # wire payload bytes received by the network transport (0 on
            # the device fast path — the split tells an operator which
            # transport is actually carrying the KV)
            "handoff_network_bytes_total": net,
        }

    # ------------------------------------------------------------------
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
        """prompt: str or token sequence. Resolves to generated token ids.

        ``resume_tokens`` (fleet recovery, docs/resilience.md): non-zero
        marks this submission as the RESUMPTION of a generation that
        already delivered that many tokens on a replica that died —
        ``prompt`` then carries prompt+generated-prefix and the sampling
        chain fast-forwards past the delivered tokens so the continuation
        is bit-exact (see _sample_first).

        Multi-tenant identity (docs/multitenancy.md): ``tenant`` names the
        traffic owner (``Seldon-Tenant`` header), ``slo_class`` its
        scheduling class ("interactive" default / "batch" — the
        ``Seldon-SLO-Class`` header; unknown values raise), ``adapter``
        a loaded LoRA adapter (``"adapter"`` body/jsonData field; unknown
        names raise — never a silent base-model fallback), and
        ``deadline_s`` a latency budget in seconds that orders this
        request EDF within its tenant queue and marks it for the
        interactive preemption path.

        ``trace`` (optional ``tracing.TraceContext``) carries the request's
        trace identity from the transport ingress (W3C ``traceparent``) into
        the flight recorder, which roots this request's span tree at it. A
        None trace with the recorder running still records a timeline under
        a fresh trace id; with the recorder off it is ignored entirely.

        ``on_token(tok)`` (optional) fires for every generated token as it is
        decoded and ``on_token(None)`` once at completion — from a worker
        thread, so the callback must be thread-safe (streaming transports
        bridge it onto their loop with call_soon_threadsafe). Under
        pipelining the callback trails the device by up to
        ``pipeline_depth`` steps (token ORDER is unchanged).

        ``info`` (optional dict) is filled in-place at admission with
        anything the caller should surface to the client — today the
        ``truncated_prompt`` record when the slot cache is smaller than the
        prompt (transports attach it to the response meta).

        ``seed`` (optional) pins this request's sampling rng to the same
        chain ``generate(..., seed=seed)`` uses, so a seeded sampled request
        decodes the identical token sequence through the batcher (each slot
        carries its own per-request key device-side)."""
        if self._closed:
            # retryable, not a hard failure: the only way a live request
            # reaches a closed batcher is the stale-dispatch tail of a
            # scale-down (a pick held across multiple autoscaler ticks —
            # docs/control-plane.md "Drain semantics"); a 503+Retry-After
            # sends the client back through routing onto a live replica
            from seldon_core_tpu.runtime.resilience import ShedError

            raise ShedError("batcher closed (replica detached by "
                            "scale-down); retry routes to a live replica")
        import time

        if isinstance(prompt, str):
            ids = self.server._tokenizer.encode(prompt)
        else:
            ids = [int(t) for t in np.asarray(prompt).ravel()]
        if not ids:
            raise ValueError("empty prompt")
        self._loop = asyncio.get_running_loop()
        if self._transfer is not None and self._transfer.on_ready is None:
            # a finished handoff must wake the loop like a submit does —
            # otherwise activation waits out the 0.5 s idle timeout
            loop = self._loop
            self._transfer.on_ready = lambda: loop.call_soon_threadsafe(
                self._wakeup.set)
        from seldon_core_tpu.contracts.payload import SeldonError
        from seldon_core_tpu.runtime.scheduler import (PendingRequest,
                                                       normalize_slo_class)

        try:
            cls = normalize_slo_class(slo_class)
        except ValueError as e:
            raise SeldonError(str(e), status_code=400)
        aid = 0
        if adapter:
            if self._adapters is None:
                raise SeldonError(
                    f"adapter {adapter!r} requested but the server has no "
                    f"adapter pool (set lora_rank > 0)", status_code=400)
            # resolve + pin atomically, from the moment the request
            # exists anywhere: eviction refuses while this request is
            # queued or in a slot, so the dispatch-time gather can never
            # read a freed (or evict+load-repurposed) row. Unpinned
            # exactly once: on the terminal shed/fail while queued
            # (_unpin_request), or at slot release once admitted
            # (ownership moves to the slot at _commit_slot).
            try:
                aid = self._adapters.resolve_and_pin(adapter)
            except KeyError as e:
                raise SeldonError(str(e.args[0]), status_code=400)
        now = time.perf_counter()
        fut: asyncio.Future = self._loop.create_future()
        req = PendingRequest(
            ids=ids, max_new=int(max_new_tokens or self.server.max_new_tokens),
            fut=fut, on_token=on_token, info=info, seed=seed,
            t_arrival=now, trace=trace, tenant=str(tenant or ""),
            slo_class=cls, adapter_id=aid,
            deadline_t=((now + float(deadline_s))
                        if deadline_s is not None else None),
            resume_tokens=int(resume_tokens or 0))
        if not self._pending.push(req):
            # tenant over its queued-request quota: shed NOW with the
            # backlog-derived Retry-After (the scheduler counted it
            # against the tenant — seldon_tenant_shed_total)
            if aid:
                self._adapters.unpin(aid)
            from seldon_core_tpu.runtime.resilience import ShedError

            raise ShedError(
                f"tenant {req.tenant!r} over its admission quota",
                retry_after_s=self.retry_after_hint())
        self._ensure_running()
        self._wakeup.set()
        return await fut

    def accommodates(self, prompt: Any,
                     max_new_tokens: Optional[int] = None) -> bool:
        """True when this batcher decodes the request IDENTICALLY to a
        private ``generate()`` call: the prompt fits the fixed slot cache
        at the same bucketed length generate() would use (no extra
        truncation) and the token budget fits behind it (no clipping).
        Transports use this to keep the seeded-reproducibility contract —
        a seeded request that does NOT fit falls back to generate(), whose
        cache is sized per request."""
        if isinstance(prompt, str):
            n = len(self.server._tokenizer.encode(prompt))
        else:
            n = int(np.asarray(prompt).size)
        # admission's exact prompt cap (_truncate_prompt): beyond it the
        # batcher keeps the tail (generate() only truncates past the model
        # context, which is covered by the same min) — and the slot cache
        # must leave the whole token budget behind the prompt (the batcher
        # stops at the cache edge; generate() never clips)
        plen = min(_bucket(n, self.len_buckets), self.server._cfg.max_seq_len,
                   self.max_len - 1)
        max_new = int(max_new_tokens or self.server.max_new_tokens)
        return n <= plen and max_new <= self.max_len - n

    def _ensure_running(self):
        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._run())

    def _resolve(self, fut: asyncio.Future, result=None, exc: Optional[BaseException] = None):
        """Thread-safe future completion: _finish runs inside asyncio.to_thread,
        and Future.set_result must happen on the loop thread."""

        def do():
            if fut.done():
                return
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)

        self._loop.call_soon_threadsafe(do)

    async def close(self):
        self._closed = True
        self._wakeup.set()
        if self._task is not None:
            await self._task
        if self._wide_build is not None:
            await asyncio.to_thread(self._wide_build.join)
        if self._remote is not None:
            # bounded worker joins (runtime/disagg.py close uses timeouts);
            # workers first — their last frames must land before the
            # receiver's listener goes away
            await asyncio.to_thread(self._remote.close)
        if self._receiver is not None:
            await asyncio.to_thread(self._receiver.close)

    # ------------------------------------------------------------------
    def _truncate_prompt(self, ids: List[int], max_new: int,
                         info: Optional[dict]):
        """Shared admission clipping: same truncation rule as
        LLMServer.generate — never beyond the model's trained context, and
        leave room for at least one generated token. Returns
        (clipped ids, plen bucket)."""
        plen = min(
            _bucket(len(ids), self.len_buckets),
            self.server._cfg.max_seq_len,
            self.max_len - 1,
        )
        if len(ids) > plen:
            # same tail-keeping rule as before, but observable: batched and
            # unbatched serving can differ here (generate() sizes its cache
            # per request; the batcher's slot cache is fixed at max_len).
            # The info record travels back to the CLIENT as a response meta
            # tag / field — truncation changes outputs, so a server-side log
            # alone is not enough (ADVICE.md round 5)
            if info is not None:
                info["truncated_prompt"] = {
                    "prompt_tokens": len(ids),
                    "kept_tokens": plen,
                    "max_len": self.max_len,
                }
            logger.warning(
                "batcher truncating %d-token prompt to its last %d tokens "
                "(slot cache max_len=%d; raise continuous_batching_max_len "
                "to match generate())", len(ids), plen, self.max_len)
        if max_new > self.max_len - plen:
            logger.warning(
                "batcher will stop at %d new tokens (requested %d): slot "
                "cache max_len=%d minus prompt %d",
                self.max_len - plen, max_new, self.max_len, plen)
        return ids[-plen:], plen

    def _sample_first(self, logits, seed: Optional[int],
                      resume_tokens: int = 0):
        """The prompt's first token, drawn ON THE DEVICE from the one row
        ``logits`` [1, 1, vocab] holds by the sampler every decode step uses
        (``LLMServer._get_first_token``: split -> lax.top_k descending ->
        categorical -> gather, argmax under temperature <= 0), on exactly
        generate()'s rng chain (PRNGKey -> one split per emitted token, the
        first included). Returns device arrays ``(token, key', row)``:
        nothing here waits for the device, and ``key'`` is the key the
        slot's decode steps go on from.

        ``resume_tokens`` > 0 means this admission RESUMES a generation
        interrupted after that many delivered tokens (fleet recovery,
        docs/resilience.md): the prompt already carries the generated
        prefix and the token drawn here is token ``resume_tokens`` of the
        ORIGINAL chain. The chain consumes exactly one first-component
        split per emitted token, so fast-forwarding PRNGKey(seed) by
        ``resume_tokens`` splits and drawing through the one sampler
        reproduces it bit-exactly (greedy takes no notice of the key)."""
        import jax

        # Per-request rng: an explicit seed reproduces generate(seed=...)'s
        # exact chain; otherwise derive an independent key from the batcher
        # rng so concurrent requests don't share a stream.
        if seed is None:
            self._rng, key = jax.random.split(self._rng)
        elif resume_tokens > 0:
            from seldon_core_tpu.servers.llmserver import fast_forward_key

            key = fast_forward_key(seed, resume_tokens)
        else:
            key = jax.random.PRNGKey(int(seed))
        return self.server._get_first_token()(logits, key, self._temp)

    def _commit_slot(self, i: int, logits, seed: Optional[int],
                     L: int, max_new: int, fut: asyncio.Future,
                     on_token: Optional[Any],
                     ids: Optional[List[int]] = None,
                     t_arrival: Optional[float] = None,
                     req: Optional[Any] = None,
                     info: Optional[dict] = None, asides: Sequence = ()):
        """Activation, shared by local admission (``_activate``) and a
        consumed handoff: draw the first token from the prompt's last row,
        ``logits`` [1, 1, vocab] (the last chunk's, or a worker's), on the
        device, thread it and the new occupant's state into the device
        arrays, and queue a ``_FirstToken`` record behind the steps already
        in flight. NO host read: the slot joins the decode batch at the
        next dispatch, and its first token is surfaced when the record
        drains (``_drain_first``), like any step's tokens. Program order on
        the device stream puts the set_slot after every already-dispatched
        step, so in-flight steps still see (and waste compute on) the old
        state while step N+1 picks up the new occupant. ``ids`` (the
        truncated prompt) seeds the speculative token history and the
        draft-model cache when speculation is on."""
        import jax.numpy as jnp

        resume_tokens = req.resume_tokens if req is not None else 0
        first, key, row = self._sample_first(logits, seed, resume_tokens)
        slot = self._slots[i]
        slot.active = True
        slot.prefilling = False
        slot.future = fut
        slot.true_len = L
        slot.max_new = max_new
        # the host has processed nothing yet: the first token is credited
        # where its record drains, before any step that decoded for this
        # occupant (the record is queued ahead of them)
        slot.n_new = 0
        slot.tokens = []
        slot.t_last = None
        slot.on_token = on_token
        # multi-tenant identity rides the slot for the whole occupancy:
        # tenant token/shed accounting, per-class TTFT, and the adapter
        # row every adapted dispatch gathers for this slot
        slot.tenant = req.tenant if req is not None else ""
        slot.slo_class = req.slo_class if req is not None else "interactive"
        slot.adapter_id = req.adapter_id if req is not None else 0
        if self._adapters is not None:
            self._adapter_ids = self._set_adapter_id(
                self._adapter_ids, jnp.asarray(i, jnp.int32),
                jnp.asarray(slot.adapter_id, jnp.int32))
        # the truncated prompt feeds the radix trie's completion-time
        # insertion (prompt + generated blocks re-enter the cache)
        slot.ids = list(ids) if ids is not None else None
        slot.gen += 1          # invalidates in-flight tokens for the old occupant
        slot.disp_new = 1      # the prefill-sampled first token counts
        self._admit_seq += 1
        slot.admit_seq = self._admit_seq
        slot_i = jnp.asarray(i, jnp.int32)
        self._last_tok, self._next_pos, self._keys = self._set_slot(
            self._last_tok, self._next_pos, self._keys, slot_i, first,
            jnp.asarray(L, jnp.int32), key)
        if self.spec_mode != "off" and ids is not None:
            # Seed the slot's device-resident token history: prompt at
            # positions 0..L-1, the prefill-sampled first token at L
            # (L <= max_len - 1 — _truncate_prompt leaves decode room).
            # Overwriting the WHOLE row retires the previous occupant's
            # tokens.
            row_np = np.zeros((self.hist_len,), np.int32)
            row_np[:L] = ids
            self._hist = self._set_hist_row(
                self._hist, slot_i, jnp.asarray(row_np))
            # the one entry the host does not know: written on the device
            # by the table ops' entry write ([slot, index] of any int32 table)
            self._hist = self._set_block_entry(
                self._hist, slot_i, jnp.asarray(L, jnp.int32), first)
            self._spec.reset(i)
            if self.spec_mode == "draft":
                self._draft_prefill_slot(i, ids)
        self._last_admit_inflight = self.steps_in_flight()
        if self._flight is not None and resume_tokens:
            # fleet recovery: this admission continues an interrupted
            # generation — mark the timeline so the span tree shows where
            # the failover re-attached (docs/resilience.md)
            self._flight.record(i, EV_RESUME, tokens=int(resume_tokens))
        probe = info is not None and "logits" in info
        self._inflight.append(_FirstToken(
            i, slot.gen, first, row if probe else None, list(asides), info,
            t_arrival))

    def _drain_first(self, rec: _FirstToken):
        """Consume an activation's record: read the first token
        (``first_token_wait``: the read waits for whatever device work is
        still queued ahead of the prompt's last chunk, and for the chunk),
        then do the host's half of the commit (``first_token``): TTFT and
        token accounting, the flight event, ``on_token``, and ``_finish`` on
        EOS or ``max_new <= 1`` (steps dispatched for the slot meanwhile
        are run-ahead tokens, masked as after any EOS). A record whose
        ``(slot, gen)`` no longer matches (shed, cancel, deadline between
        activation and read) surfaces nothing, like a stale step's tokens."""
        ready = rec.token.is_ready()
        with self._phases.phase("first_token_wait"):
            # graftlint: allow-host-sync-in-hot-path(the drain's read of an activation, once per request: queued behind the steps dispatched before the prompt's last chunk and read in their order, while newer steps and chunks keep the chip busy)
            first = int(np.asarray(rec.token))
        with self._phases.phase("first_token"):
            self._phases.first_token_reads["yes" if ready else "no"] += 1
            self._count_chunks(rec.asides)
            i = rec.slot
            slot = self._slots[i]
            if not slot.active or slot.gen != rec.gen:
                return
            if rec.row is not None:
                # a probe asked for logits (transport/rest.py): the prompt's
                # last position first, then one row per decode step
                # graftlint: allow-host-sync-in-hot-path(a probe request only: one [vocab] row of a program that has finished)
                rec.info["logits"].append(np.asarray(rec.row))
                slot.logits = rec.info["logits"]
                if self._moe is not None and rec.asides:
                    # and the experts the prompt's tokens took, chunk by chunk
                    # (from ``routing_start`` on: a reused prefix ran no chunk)
                    rec.info["routing_start"] = rec.asides[0][2]
                    took: List[np.ndarray] = []
                    for aside, _, _, n in rec.asides:
                        # graftlint: allow-host-sync-in-hot-path(a probe request only: [chunk, n_moe_layers, k] int32 of a chunk that has finished)
                        took.extend(np.asarray(aside["moe_choice"])[0, :n])
                    slot.routing = rec.info["routing"] = took
            if rec.info is not None and "state" in rec.info:
                slot.state = rec.info["state"]
            slot.n_new = 1
            slot.tokens = [first]
            # first token surfaced NOW: time-to-first-token from submit(),
            # and the baseline the next token's gap measures from
            now = time.perf_counter()
            if rec.t_arrival is not None:
                self.server.observe("ttft_s", now - rec.t_arrival)
                self.server._ttft_by_class.append(
                    (slot.slo_class, now - rec.t_arrival))
            self._pending.count_tokens(slot.tenant, slot.slo_class, 1)
            slot.t_last = now
            if self._flight is not None:
                self._flight.record(i, EV_FIRST_TOKEN, tokens=1)
            if slot.on_token is not None and first != self.eos_id:
                slot.on_token(first)
            if first == self.eos_id or slot.max_new <= 1:
                self._finish(i)

    def _draft_prefill_slot(self, i: int, ids: List[int]):
        """spec_mode='draft': prefill the slot's DENSE draft-model cache
        over the (already truncated) prompt and insert it whole — the
        fresh cache covers all max_len positions, so the previous
        occupant's rows are retired.
        The draft's logits are discarded: drafting always restarts from
        the last accepted TARGET token inside the verify step."""
        import jax.numpy as jnp

        L = len(ids)
        plen = min(_bucket(L, self.len_buckets),
                   self.server._cfg.max_seq_len, self.max_len - 1)
        toks = np.zeros((1, plen), np.int32)
        pos = np.full((1, plen), PAD_POS, np.int32)
        toks[0, :L] = ids
        pos[0, :L] = np.arange(L)
        fn = self.server._get_draft_prefill(1, plen, self.max_len)
        _, dcache = fn(self.server._draft_params, jnp.asarray(toks),
                       jnp.asarray(pos))
        self._draft_caches = self._draft_insert(
            self._draft_caches, dcache, jnp.asarray(i, jnp.int32))

    def _begin(self, slot: int, req, prompt_tokens: int) -> None:
        """A slot is reserved for ``req``: its queue wait ends here (counted
        whether or not tracing is on) and, with tracing, its flight-recorder
        timeline starts."""
        if req.t_arrival is not None:
            self.server.observe("queue_wait_s",
                                time.perf_counter() - req.t_arrival)
        if self._flight is not None:
            self._flight.begin(slot, req.trace, req.t_arrival, prompt_tokens,
                               tags=self._flight_tags(req))

    @staticmethod
    def _flight_tags(req) -> Optional[dict]:
        """Tenant identity on the request's flight-recorder timeline/root
        span (None when untenanted — the timeline stays byte-identical to
        the single-tenant layout)."""
        if not req.tenant and req.slo_class == "interactive" \
                and not req.adapter_id:
            return None
        return {"tenant": req.tenant, "slo_class": req.slo_class,
                "adapter_id": req.adapter_id}

    # ------------------------------------------------------------------
    # Disaggregated admission: stage remote jobs, consume handoffs
    # ------------------------------------------------------------------
    @_in_phase("admit")
    def _admit_remote(self, req) -> bool:
        """Remote-prefill admission, decode-side half: reserve a slot,
        consult the radix trie so the prefill slice only computes the
        UNCACHED suffix (matched whole blocks stay decode-side, shared
        into the slot's row; their KV ships forward to the worker as one
        exported page bucket so its suffix chunks can attend over them),
        allocate the suffix pages the import will land in, and stage the
        job. Returns True when the request was CONSUMED (staged or shed)
        — False leaves it pending. No prefill compute happens here: that
        is the point."""
        import jax.numpy as jnp

        free = next((i for i, s in enumerate(self._slots)
                     if not s.active and not s.prefilling), None)
        if free is None:
            return False
        ids, plen = self._truncate_prompt(req.ids, req.max_new, req.info)
        L = len(ids)
        shared: List[int] = []
        prefix_staged = None
        k0 = 0
        n0 = -(-L // self.page_size)
        if self._radix is not None:
            # whole blocks only: the worker's suffix prefill starts at
            # a page boundary and partial-block COW stays a local
            # (decode-side) move — capped at L-1 so the worker always
            # computes the first-token logits
            # leaklint: allow-leak-on-path(full_blocks_only=True guarantees cow is None — no cow pin is ever taken, so the discarded third element holds nothing)
            k0, shared, _ = self._radix.match_and_pin(
                ids, limit=L - 1, full_blocks_only=True)
        got = self._alloc_pages(n0 - len(shared))
        if got is None:
            if shared:
                self._allocator.free(shared)  # drop pins: retry later
            # same liveness posture as _admit_begin: with no tenant in
            # flight anywhere (active, local prefill, or staged remote
            # — remote slots hold prefilling=True), nothing will ever
            # free a page, so shed now instead of queueing forever
            if not any(s.active or s.prefilling for s in self._slots):
                self._shed_queued_request(
                    req,
                    f"admission needs {n0} KV pages "
                    f"(pool capacity {self._allocator.capacity}, "
                    f"{self._allocator.stats()[1]} in use)")
                return True
            return False
        pages = got
        row = np.full((self.n_pages,), NULL_PAGE, np.int32)
        row[:n0] = shared + pages
        if shared:
            # export the matched blocks as a power-of-two page bucket
            # (handoff-shaped: RESERVED leading rows, then pages) the
            # worker imports into its staging pool — D2D forward
            # shipment of already-computed KV, never a recompute
            b = pow2_bucket(len(shared), self.n_pages)
            idx = np.full((RESERVED_PAGES + b,), TRASH_PAGE, np.int32)
            idx[RESERVED_PAGES:RESERVED_PAGES + len(shared)] = shared
            prefix_staged = self._export_pages(self._caches,
                                               jnp.asarray(idx))
        from seldon_core_tpu.runtime.disagg import PrefillRequest

        slot = self._slots[free]
        slot.pages = list(pages)
        slot.shared = list(shared)
        slot.prefilling = True
        slot.future = req.fut
        slot.on_token = req.on_token
        slot.tenant = req.tenant
        slot.slo_class = req.slo_class
        self._job_seq += 1
        job = _RemoteJob(self._job_seq, free, ids, plen, req.max_new,
                         req.fut, req.on_token, req.info, req.seed, pages,
                         row, req.t_arrival, prefix_pages=len(shared),
                         req=req)
        self._remote_jobs[job.job_id] = job
        if k0:
            # once per funded admission, like the local path
            self._radix.record_hit(k0, len(shared), False)
        self._begin(free, req, L)
        if self._flight is not None:
            if k0:
                self._flight.record(free, EV_PREFIX_HIT, tokens=k0,
                                    blocks=len(shared))
            self._flight.record(free, EV_HANDOFF_STAGED, job_id=job.job_id,
                                pages=n0 - len(shared))
        req = PrefillRequest(job.job_id, ids, plen, n0,
                             record_events=self._flight is not None,
                             prefix_len=k0,
                             prefix_pages=len(shared),
                             prefix_staged=prefix_staged)
        pool = self._remote
        try:
            pool.submit(req)
        except RuntimeError:
            # a rebalance swapped the worker pool between our read of
            # self._remote and the submit: the old pool is closing (its
            # backlog drains into the SHARED TransferQueue, so nothing
            # already staged is lost) — retry once on the new pool, which
            # publishes into the same queue
            self._remote.submit(req)
        return True

    @_in_phase("handoff")
    def _consume_handoffs(self):
        """Drain every READY handoff: import the staged KV into the slot
        pool (one donated jitted scatter through the slot's block row),
        then commit the slot exactly as
        a local admission would — same first-token sampling chain, so
        tokens are bit-identical to single-slice serving."""
        import time

        import jax.numpy as jnp

        while True:
            h = self._transfer.pop()
            if h is None:
                return
            job = self._remote_jobs.pop(h.job_id, None)
            if job is None:
                continue  # defensive: cancel removes READY records itself
            if h.error is not None:
                # worker-side failure: fail THIS request, release its slot
                # and pages — the batch keeps serving (release before
                # notifying, like _finish)
                if self._flight is not None:
                    self._flight.complete(job.slot, "error", 0, self._tracer)
                self._release_slot(job.slot)
                if job.on_token is not None:
                    try:
                        job.on_token(None)
                    except Exception:
                        pass
                self._resolve(job.fut, exc=h.error)
                continue
            if self._flight is not None and h.events:
                # worker-stamped stages (compute, D2D transfer) recorded on
                # the prefill thread BEFORE the handoff was published —
                # ownership moved through the TransferQueue's lock
                self._flight.extend(job.slot, h.events)
            try:
                t0 = time.perf_counter()
                import jax

                n0 = -(-job.L // self.page_size)
                # only the SUFFIX pages travelled (the prefix blocks
                # never left this device — they are shared trie pages
                # already in the row's lead); import targets row
                # entries past them
                n_suffix = n0 - job.prefix_pages
                # the worker shipped a power-of-two page bucket; the
                # buffer's own shape names the compile to import it
                staged_pages = (jax.tree.leaves(h.staged)[0].shape[0]
                                - RESERVED_PAGES)
                imp = self._get_handoff_import(staged_pages)
                row_suffix = np.full((self.n_pages,), NULL_PAGE,
                                     np.int32)
                row_suffix[:n_suffix] = job.row[
                    job.prefix_pages:job.prefix_pages + n_suffix]
                self._caches = imp(self._caches, h.staged,
                                   jnp.asarray(row_suffix),
                                   jnp.asarray(n_suffix, jnp.int32))
                self._block_tables = self._set_block_row(
                    self._block_tables,
                    jnp.asarray(job.slot, jnp.int32),
                    jnp.asarray(job.row))
            except Exception as e:
                # poisoned handoff (malformed staged payload, import
                # raising): fail THIS request and free its slot + staging
                # pages — exactly the h.error semantics above. Letting it
                # propagate would kill the whole consume sweep and, one
                # frame up, the batcher loop itself — one bad handoff
                # must never take down the batch (ISSUE 16 satellite).
                logger.exception("poisoned handoff (slot %d): %s",
                                 job.slot, e)
                if self._flight is not None:
                    self._flight.complete(job.slot, "error", 0,
                                          self._tracer)
                self._release_slot(job.slot)
                if job.on_token is not None:
                    try:
                        job.on_token(None)
                    except Exception:
                        pass
                self._resolve(job.fut, exc=e)
                continue
            self.server._handoff_times.append(
                h.prefill_s + (time.perf_counter() - t0))
            if self._flight is not None:
                self._flight.record(job.slot, EV_HANDOFF_IMPORT,
                                    bytes=h.transfer_bytes,
                                    dur_s=time.perf_counter() - t0)
            # the worker read the logits row on its own slice; it goes
            # through the same sampler and the same record as a local one
            self._commit_slot(job.slot, jnp.asarray(h.first_logits[None, None]),
                              job.seed, job.L, job.max_new, job.fut,
                              job.on_token, ids=job.ids,
                              t_arrival=job.t_arrival, req=job.req,
                              info=job.info)

    def _shed_remote_job(self, job_id: int, why: str):
        """Shed a staged remote admission (page pressure / shutdown): the
        TransferQueue's cancel makes the outcome exactly-once — either we
        take the READY handoff out of the queue (its payload drops with
        it) or the worker's later put is refused; in BOTH cases this
        path, and only this path, frees the decode-side pages (via the
        slot release)."""
        job = self._remote_jobs.pop(job_id, None)
        if job is None:
            return
        self._transfer.cancel(job_id)
        self._allocator.count_shed()
        if job.req is not None:
            self._pending.count_shed(job.req.tenant, job.req.slo_class)
            # adapters reject disaggregation at load() today, so this is
            # a no-op — kept so the pin-ownership rule (queue entry owns
            # it until _commit_slot) survives that restriction lifting
            self._unpin_request(job.req)
        logger.warning("shedding staged remote prefill (slot %d): %s",
                       job.slot, why)
        if self._flight is not None:
            self._flight.record(job.slot, EV_SHED, why=why)
            self._flight.complete(job.slot, "shed", 0, self._tracer)
        self._release_slot(job.slot)  # before notifying, like _finish
        if job.on_token is not None:
            try:
                job.on_token(None)
            except Exception:
                pass
        self._resolve(job.fut, exc=self._shed_error(why))

    def _fail_remote_jobs(self, exc: BaseException):
        """Shutdown/crash path: no staged request may leave its future
        hanging."""
        for job_id in list(self._remote_jobs):
            job = self._remote_jobs.pop(job_id)
            self._transfer.cancel(job_id)
            if self._flight is not None:
                self._flight.complete(job.slot, "error", 0, self._tracer)
            self._release_slot(job.slot)  # before notifying, like _finish
            if job.on_token is not None:
                try:
                    job.on_token(None)
                except Exception:
                    pass
            self._resolve(job.fut, exc=exc)

    # ------------------------------------------------------------------
    # Paged admission: page allocation + chunked prefill + activation
    # ------------------------------------------------------------------
    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Pool allocation with radix-eviction relief: when the free list
        can't cover ``n``, ask the trie to evict LRU leaf blocks nothing
        references (refcount 1) before giving up — cached prefixes are a
        cache, live slots are the tenants, and the cache yields first.
        Shedding only starts where eviction ends."""
        got = self._allocator.alloc(n)
        if got is not None or self._radix is None:
            return got
        if not self._radix.evict(n):
            return None
        return self._allocator.alloc(n)

    @_in_phase("admit")
    def _admit_begin(self, req) -> bool:
        """Paged admission, phase 1 (host-side, cheap): match the prompt
        against the radix prefix cache (shared full blocks enter the block
        row as-is — zero copies; a partial-block continuation pays one
        copy-on-write page copy), allocate fresh pages for the uncached
        suffix, reset their stale positions, and stage a chunked-prefill
        job covering ONLY the suffix. The match is capped at L-1 tokens so
        the last prompt token always prefills — its logits seed the first
        sampled token on generate()'s exact rng chain (the trie stores
        pages, never logits). Returns True when the request was CONSUMED
        (job staged or shed with 503) — False leaves it pending for a
        later loop turn."""
        import jax.numpy as jnp

        free = next((i for i, s in enumerate(self._slots)
                     if not s.active and not s.prefilling), None)
        if free is None:
            return False
        ids, plen = self._truncate_prompt(req.ids, req.max_new, req.info)
        L = len(ids)
        n0 = -(-L // self.page_size)
        k0, shared, cow = 0, [], None
        if self._radix is not None and req.adapter_id == 0:
            # radix reuse serves BASE-adapter traffic only: an adapted
            # request's hidden states embed its q/o/FFN deltas from layer
            # 1 on, so its deep-layer KV is not the trie's KV (the k/v
            # PROJECTIONS are base for everyone — runtime/adapters.py —
            # but projection inputs differ). Adapted admissions prefill
            # their whole prompt and never insert (docs/multitenancy.md).
            k0, shared, cow = self._radix.match_and_pin(ids, limit=L - 1)
        n_fresh = n0 - len(shared) - (1 if cow is not None else 0)
        if self.window:
            # the window class is counted too: what the request can hold of it at
            # once (a fully provisioned class always has them: see __init__)
            need = min(-(-(L + req.max_new) // self.page_size), self.window_slot_pages)
            if self._window_allocator.free_count() < need:
                if not any(s.active or s.prefilling for s in self._slots):
                    self._shed_queued_request(
                        req, f"admission needs {need} window-class KV pages "
                        f"({self._window_allocator.free_count()} free)")
                    return True
                return False
        fresh = self._alloc_pages(n_fresh + (1 if cow is not None else 0))
        if fresh is None and cow is not None:
            # the cow pin itself can be what starves the pool: its source
            # page is refcount-2 (unevictable) while pinned, so on a
            # minimum-size pool the eviction pass may be exactly one page
            # short. A partial-block match is an OPTIMIZATION, never a
            # requirement — drop it (treat the tail as a miss, keeping
            # the full-block shares) and retry before parking/shedding,
            # preserving the invariant that an admission always fits an
            # otherwise-idle pool.
            self._allocator.free([cow[0]])
            k0 -= cow[1]
            cow = None
            fresh = self._alloc_pages(n0 - len(shared))
        if fresh is None:
            if shared:
                self._allocator.free(shared)  # drop the pins: retry later
            # Liveness rests entirely on this busy check: _truncate_prompt
            # caps prompts at max_len-1 so n0 <= n_pages, and the
            # constructor rejects pools with capacity < n_pages — an
            # admission can always fit an empty pool (the radix trie
            # yields its unreferenced blocks first, via _alloc_pages). So
            # if nothing is in flight to ever free a page, shed now
            # instead of queueing forever; otherwise wait for in-flight
            # completions.
            if not any(s.active or s.prefilling for s in self._slots):
                self._shed_queued_request(
                    req,
                    f"admission needs {n0} KV pages "
                    f"(pool capacity {self._allocator.capacity}, "
                    f"{self._allocator.stats()[1]} in use)")
                return True
            return False  # wait: in-flight completions will free pages
        cow_dst = fresh[0] if cow is not None else None
        plain = fresh[1:] if cow is not None else fresh
        slot = self._slots[free]
        slot.shared = list(shared)
        slot.pages = ([cow_dst] if cow_dst is not None else []) + plain
        slot.prefilling = True
        slot.future = req.fut
        slot.on_token = req.on_token
        slot.tenant = req.tenant
        slot.slo_class = req.slo_class
        self._begin(free, req, L)
        # neutralize the FRESH pages' previous-owner positions BEFORE any
        # write lands through them (stale real positions would make this
        # slot's mask attend another sequence's leftover KV). Shared trie
        # pages are live cached KV — never reset; the cow destination is
        # fully overwritten (values + masked position row) by the copy.
        if plain:
            ids_np = np.full((self.n_pages,), TRASH_PAGE, np.int32)
            ids_np[:len(plain)] = plain
            self._caches = self._reset_pages(self._caches,
                                             jnp.asarray(ids_np))
        if cow is not None:
            # one donated jitted page copy: the shared page's valid prefix
            # moves into this slot's own page, stale positions masked —
            # the ONLY copy a radix hit can cost (full blocks share). The
            # source was PINNED by match_and_pin (the _alloc_pages above
            # may have evicted its leaf; unpinned it could have been
            # handed back as one of OUR fresh pages) — drop the pin now
            # that the copy is in device program order before any reuse.
            self._caches = self._cow_page_copy(
                self._caches, jnp.asarray(cow[0], jnp.int32),
                jnp.asarray(cow_dst, jnp.int32),
                jnp.asarray(cow[1], jnp.int32))
            self._allocator.free([cow[0]])
        row = np.full((self.n_pages,), NULL_PAGE, np.int32)
        row[:n0] = slot.shared + slot.pages
        bt_row = jnp.asarray(row[None, :])
        if k0:
            # counted HERE, once per funded admission — a match that
            # failed allocation above retries every loop turn and must
            # not inflate the reuse counters per retry
            self._radix.record_hit(k0, len(shared), cow is not None)
            if self._flight is not None:
                self._flight.record(free, EV_PREFIX_HIT, tokens=k0,
                                    blocks=len(shared) +
                                    (1 if cow is not None else 0))
        job = _PrefillJob(free, ids, k0, min(self.prefill_chunk, plen),
                          req.max_new, req.fut, req.on_token, req.info,
                          req.seed, bt_row, slot.pages,
                          t_arrival=req.t_arrival, req=req)
        if self.window:
            job.wrow = np.full((self.n_pages,), NULL_PAGE, np.int32)
        self._prefill = job
        return True

    @_in_phase("prefill")
    def _prefill_step(self):
        """One chunked-prefill dispatch (worker thread): write the next
        ``chunk`` prompt tokens into the pool through the job's block-table
        row. Enqueue-only, the last chunk included: it ends in the slot's
        activation (``_activate``), whose first token is read when its
        record drains, so decode steps interleave between chunks and behind
        the last one, and in-flight requests keep streaming."""
        import jax.numpy as jnp

        job = self._prefill
        if job is None:
            return
        import time

        C = self._chunk_width(job)
        start = job.next
        with self._phases.part("build"):
            ids = job.ids[start:start + C]
            n = len(ids)
            toks = np.zeros((1, C), np.int32)
            pos = np.full((1, C), PAD_POS, np.int32)
            toks[0, :n] = ids
            pos[0, :n] = np.arange(start, start + n)
            # the one row somebody reads: the prompt's last, in its last chunk
            last = start + n >= job.L
            t0 = time.perf_counter()
            toks, pos = jnp.asarray(toks), jnp.asarray(pos)
            head_row = np.int32(n - 1 if last else -1)   # (goes over with the call)
        block_row = job.bt_row
        if self.window:
            # the window class's pages for THIS chunk: those wholly behind its
            # first row's window are given back, those its rows reach are booked
            with self._phases.part("pages"):
                self._window_book(self._slots[job.slot], start, start + n - 1, row=job.wrow)
                # (a COPY goes over: the row is booked in place again before the
                # next chunk, while this one may still be queued)
                block_row = (job.bt_row, jnp.asarray(job.wrow[None, :].copy()))
        builds = self._ledger.thread_builds()
        with self._phases.part("call"):
            if C == self.prefill_wide and self._wide_build is not None:
                self._wide_build.join()     # (a seeded request's: ``_chunk_width``; ended for any other)
            if self._adapters is not None:
                fn = self.server._get_prefill_chunk(C, self.n_pages, lora=True)
                aid = job.req.adapter_id if job.req is not None else 0
                extra = (self._adapters.pool(), jnp.asarray([aid], jnp.int32))
            else:
                fn = self.server._get_prefill_chunk(C, self.n_pages)
                # a model with conv layers: the chunk continues ITS slot's state
                extra = () if self._state_slot is None else (self._state_slot[job.slot],)
            args = (self.server._params, self._caches, block_row, toks, pos, head_row, *extra)
            if self._chunk_shapes is None:
                self._chunk_shapes = self._shapes_of(args)
            logits, self._caches, aside = fn(*args)
        job.next = start + n
        width, heads = str(C), self._phases.chunk_head[str(int(last))]
        heads[width] = heads.get(width, 0) + 1
        self._phases.chunk_rows[width] = self._phases.chunk_rows.get(width, 0) + n
        self._phases.count_attention("chunk", start + n, self._rows_read(C, [start + n], 1),
                                     self._read_form(C))
        if self.window:
            first = max(start - self.window + 1, 0)
            self._phases.count_window_attention(
                "chunk", start + n - first, self._rows_read(C, [start + n], 1, [first]),
                start + n)
        self._phases.count_chunk_write(*self._chunk_write(C, start, n))
        if self._state_layers:
            self._phases.count_state_layers("chunk", n, 1, self._state_layers)
        if self._cross_decoder:
            # the layers past cfg.kv_source ran on the one row read, or on none
            self._phases.count_decoders("chunk", n, int(last), start + n,
                                        self._rows_read(1, [start + n], 1))
        event = None
        if self._flight is not None:
            # dispatch wall (enqueue-only)
            event = self._flight.record(
                job.slot, EV_PREFILL_CHUNK, start=start, tokens=n,
                head=int(last), dur_s=time.perf_counter() - t0,
                **self._built_since(builds))
        if self._moe is not None:
            job.asides.append((aside, event, start, n))
        if last:
            with self._phases.part("activate"):
                self._activate(job, logits)

    def _built_since(self, mark: int) -> dict:
        """``{"built": program}`` for the flight event of a call that had to
        build its program, or load it, since ``mark`` (the start ledger's count
        of this thread's builds, read before the call); nothing for any other."""
        program = self._ledger.built_since(mark)
        return {"built": program} if program else {}

    @staticmethod
    def _shapes_of(args: tuple) -> tuple:
        """The abstract form of a call's arguments (nothing is read)."""
        import jax

        def shape(x):   # (an array nobody placed resolves like a bare shape)
            placed = isinstance(x, jax.Array) and x.committed
            return jax.ShapeDtypeStruct(np.shape(x), np.result_type(x),
                                        sharding=x.sharding if placed else None)

        return jax.tree.map(shape, args)

    def _build_wide_program(self) -> None:
        """Start the build of the WIDE chunk program, once, when a request has
        finished (``_finish``): a thread traces, lowers and compiles it (or
        loads it from the compile cache) for the shapes ``_prefill_step`` calls
        it with, while the loop goes on with narrow chunks; jax's own caches
        then serve its calls (lowering and executable: shapes and placements
        are the calls' own; a program's first call traces it once more, which
        costs a program whose layers share one trace of their block a few
        tenths of a second).

        WHEN, and why not at the first chunk (PRs 48-53 built both programs
        there): a program's load from the compile cache is seconds, 5.5 for a
        Mistral chunk program on a v5e's host, and two loads side by side take
        as long as one behind the other, so whatever this one stands ahead of
        in that queue waits for it: built at the first chunk it put 5-8 s into
        the start of a server whose first prompt was long (rerank: +14 to +24 %
        of 35 s), and it would stand ahead of the decode step's, a start's
        longest load (granite: 71 s of loads). Nobody waits for this program, so
        it goes LAST: a request that has finished has had every program it
        needs built, whether the server decodes or not (PERF.md section 6,
        PR 54). ``close()`` joins the thread. Nothing runs here and no array is
        read."""
        import jax

        if (self._wide_build is not None or self._chunk_shapes is None
                or not self.max_len - 1 > self.prefill_wide > 0):
            return
        rows, shapes = self.prefill_wide, self._chunk_shapes
        rows_of = jax.ShapeDtypeStruct((1, rows), np.int32)
        fn = self.server._get_prefill_chunk(rows, self.n_pages, lora=self._adapters is not None)
        self._wide_build = threading.Thread(
            target=_build_program, name=f"chunk-{rows}-build", daemon=True,
            args=(fn, (*shapes[:3], rows_of, rows_of, *shapes[5:]), f"prefill_chunk of {rows} rows"))
        self._wide_build.start()

    def _chunk_width(self, job: _PrefillJob) -> int:
        """Rows of the job's NEXT chunk, chosen when it is built from what the
        loop sees: the wide program while the narrow one would compute at least
        a wide chunk's rows for what is left of the prompt anyway (more than
        ``wide - chunk`` rows left: the narrow plan's last call is padded up to
        ``wide`` rows or past them, so ONE wide call computes no row more and
        streams every weight it touches once where the narrow calls stream it
        ``wide / chunk`` times; a prompt's last chunk is then the wide program's,
        its rows behind the prompt's end padding and ``head_row`` inside it), no
        OTHER live slot streams (a stream's gap stays a step and one narrow
        chunk; the job's own stream only gets its first token sooner) and the
        wide program is THERE (``_build_wide_program``); the job's own width
        otherwise. While the program is being built a request takes narrow
        chunks, unless it came with a SEED: that one asks for the same tokens
        whenever it comes, a chunk's width is in its roundings, so its wide
        chunk waits for the program (``_prefill_step``), and it takes the wide
        program only while MORE than its rows are left (its wide chunks are
        always full: it would wait for the program to save three calls, and
        its widths stay what its length alone gave them before PR 58)."""
        wide, build = self.prefill_wide, self._wide_build
        seeded = job.seed is not None
        if (wide > 0 and job.L - job.next > (wide if seeded else wide - job.chunk)
                and build is not None and (seeded or not build.is_alive())
                and not any(s.active and s.on_token is not None
                            for i, s in enumerate(self._slots) if i != job.slot)):
            return wide
        return job.chunk

    def _chunk_write(self, s: int, start: int, n: int) -> Tuple[str, int]:
        """(path, pages) of a chunk of ``s`` rows, ``n`` of them live from
        position ``start``: how a paged layer's write reaches the pool, by the
        ONE rule the modules take (models/cache.py
        ``paged_write_by_page``), and the pool pages it writes: the whole
        pages it reads and writes back, or the pages its live rows lie in."""
        if kvcache.paged_write_by_page(kvcache.first_paged(self._caches), 1, s):
            return "page", kvcache.pages_a_run_writes(s, self.page_size)
        return "token", (start + n - 1) // self.page_size - start // self.page_size + 1

    def _read_walk(self, s: int):
        """How the attention read's kernel walks the live pages for calls of
        ``s`` query tokens a sequence (ops/page_walk.py ``Plan``), or None
        where the read gathers the whole block-table view: the ONE rule
        ``Attention`` and ``LatentAttention`` themselves take
        (models/transformer.py ``paged_read_walk``), in a process whose
        programs are compiled for a TPU."""
        if s not in self._read_walks:
            import jax

            from seldon_core_tpu.models.transformer import paged_read_walk

            self._read_walks[s] = None if jax.default_backend() != "tpu" else paged_read_walk(
                self.server._cfg, s, self.n_pages, self.page_size,
                kvcache.first_paged(self._caches)[0].dtype)
        return self._read_walks[s]

    def _read_form(self, s: int) -> str:
        """``expanded`` where those calls' latent read makes the context's K
        and V once a visit (a wide chunk's, on one TPU), else ``absorbed``:
        the counters' ``form`` label, from the same rule."""
        from seldon_core_tpu.models.transformer import read_form

        return read_form(self._read_walk(s))

    def _state_step_path(self, kind: str) -> str:
        """How a decode step's delta rule (``kind`` "gdn") or state-space
        recurrence ("ssd") runs in this process: the repo's kernel (the state
        read once and written once) where the programs are compiled for a TPU
        and ``gdn_step_walk`` / ``ssd_step_blocks`` has a plan, the expression
        elsewhere."""
        import jax

        from seldon_core_tpu.models.state_mixers import gdn_step_walk, ssd_step_blocks

        plan = {"gdn": gdn_step_walk, "ssd": ssd_step_blocks}[kind]
        kernel = jax.default_backend() == "tpu" and plan(self.server._cfg) is not None
        return "kernel" if kernel else "expression"

    def _rows_read(self, s: int, live_rows: Sequence[int], sequences: int,
                   first_rows: Optional[Sequence[int]] = None) -> int:
        """Cached rows the attention read of step-program calls visits, per
        layer, from host integers: ``sequences`` reads of ``s`` query tokens
        each, of which those with a live context reach ``live_rows`` rows.
        The whole block-table view of every sequence, live or not (the
        gather reads it and the products multiply it); where the kernel walks
        the live pages, whole visits over the live rows, nothing for the rest.
        ``first_rows``: a sliding-attention layer's read, whose visits start at
        the one that holds each sequence's first row inside the window."""
        walk = self._read_walk(s)
        if walk is None:
            return sequences * self.n_pages * self.page_size
        from seldon_core_tpu.ops.page_walk import rows_visited

        firsts = first_rows if first_rows is not None else [0] * len(live_rows)
        return sum(rows_visited(rows, self.page_size, walk, first)
                   for rows, first in zip(live_rows, firsts))

    def _count_chunks(self, asides: Sequence) -> None:
        """The routing tallies of an admission's chunks. They ran before
        the program whose first token the caller has just read, so every
        array here is ready: no read below waits for the device."""
        for aside, event, _, _ in asides:
            # graftlint: allow-host-sync-in-hot-path(no wait: these programs finished before the first token the caller just read, once per request)
            stats = np.asarray(aside["moe_stats"])
            self._moe.add("chunk", stats[None])
            # graftlint: allow-host-sync-in-hot-path(same: a finished chunk's [1, n_experts] tally)
            self._moe.expert_tokens += np.asarray(aside["moe_tokens"])[0]
            if event is not None:
                event.update(self._moe.flight_fields(stats))

    def _activate(self, job: _PrefillJob, logits):
        """Paged admission, final phase, all of it enqueued: point the
        slot's DEVICE block-table row at the real pages (decode writes
        route through it from the next dispatch; in-flight steps still see
        the trash row in program order) and commit the slot into the decode
        batch with its first token drawn from the last chunk's one row of
        ``logits`` on the device. The job is done with its last
        chunk's enqueue: the next admission can start on the next turn."""
        import jax.numpy as jnp

        self._block_tables = self._set_block_row(
            self._block_tables, jnp.asarray(job.slot, jnp.int32),
            job.bt_row[0])
        if self.window:
            self._window_tables = self._set_block_row(
                self._window_tables, jnp.asarray(job.slot, jnp.int32), jnp.asarray(job.wrow.copy()))
        self._prefill = None
        self._commit_slot(job.slot, logits, job.seed, job.L,
                          job.max_new, job.fut, job.on_token, ids=job.ids,
                          t_arrival=job.t_arrival, req=job.req,
                          info=job.info, asides=job.asides)

    # ------------------------------------------------------------------
    # Page accounting: growth, exhaustion shedding, release
    # ------------------------------------------------------------------
    def _ensure_slot_pages(self, i: int, last_write_pos: int) -> bool:
        """Grow slot ``i``'s page list to cover decode writes up to
        ``last_write_pos`` BEFORE the step that writes them is dispatched
        (a write through an unallocated table entry is redirected to trash
        device-side — safe, but the token's KV would be lost). On pool
        exhaustion the newest other request sheds (503 + Retry-After) to
        free pages; if this slot is the only tenant left, its generation
        ends early with the tokens it has — the decode loop itself NEVER
        raises. Returns False when the slot was finished/released."""
        import jax.numpy as jnp

        import time

        slot = self._slots[i]
        if not slot.active:
            # released slots own no pages (release freed them) — growing
            # one would allocate pool pages that nothing ever frees
            return False
        need = min(last_write_pos, self.max_len - 1) // self.page_size + 1
        n0_pages = slot.covered_pages()
        t0_grow = time.perf_counter() if n0_pages < need else 0.0
        while slot.covered_pages() < need:
            got = self._alloc_pages(1)
            if got is None:
                victim = self._pick_page_victim()
                if victim is None:
                    # sole tenant outgrew the pool: stop generating with the
                    # tokens it has — the same posture as the cache edge's
                    # max_len stop, never an error
                    logger.warning(
                        "kv page pool exhausted with no shed candidate: "
                        "slot %d ends at %d generated tokens", i, slot.n_new)
                    self._finish(i)
                    return False
                if victim == "job":
                    self._shed_prefill_job("page pool exhausted by decode")
                    continue
                if isinstance(victim, tuple):  # ("remote", job_id)
                    self._shed_remote_job(victim[1],
                                          "page pool exhausted by decode")
                    continue
                if victim == i:
                    # the growing slot is itself the newest tenant: LIFO
                    # says it yields to the older requests
                    self._shed_slot(i, "page pool exhausted")
                    return False
                self._shed_slot(victim, "page pool exhausted")
                continue
            page = got[0]
            ids_np = np.full((self.n_pages,), TRASH_PAGE, np.int32)
            ids_np[0] = page
            self._caches = self._reset_pages(self._caches, jnp.asarray(ids_np))
            self._block_tables = self._set_block_entry(
                self._block_tables, jnp.asarray(i, jnp.int32),
                jnp.asarray(slot.covered_pages(), jnp.int32),
                jnp.asarray(page, jnp.int32))
            slot.pages.append(page)
        if self._flight is not None and slot.covered_pages() > n0_pages:
            # mid-decode page growth is the pool's stall risk: the
            # allocation (and any shed it forced) ran between this slot's
            # dispatches — the timeline shows it where the gap opened
            self._flight.record(i, EV_PAGE_GROW,
                                pages=slot.covered_pages() - n0_pages,
                                dur_s=time.perf_counter() - t0_grow)
        if self.window:
            # the dispatch's first query sits at the slot's next position
            self._window_book(slot, slot.dispatched_pos(), last_write_pos, slot_index=i)
        return True

    def _tables(self):
        """What a step program takes as ``block_tables``: the one table, or for a
        model with sliding-attention layers the pair (full, window), of which
        each layer reads its class's (models/transformer.py)."""
        return (self._block_tables, self._window_tables) if self.window else self._block_tables

    def _window_book(self, slot: _Slot, p0: int, last_write_pos: int,
                     row: Optional[np.ndarray] = None, slot_index: int = -1) -> None:
        """The window class's pages of ``slot`` for the call about to be
        dispatched, whose first query row is at position ``p0`` and whose rows
        write up to ``last_write_pos``. Every page whose LAST position lies below
        ``p0 - window + 1`` (the smallest position any query of the call may
        see) is given back, once, and its table entry reads NULL_PAGE; the pages
        from there to the last written position are booked, their stale
        positions reset. Either into ``row`` (a prefill job's host row, which
        goes over with its chunk) or into the slot's row of the device table
        (a decode step's: enqueued behind the steps in flight, which still read
        the page they were dispatched with; the reset and the next owner's
        writes come behind them too, in device program order). A page given
        back here may be the next one booked, by this slot or another."""
        import jax.numpy as jnp

        ps = self.page_size
        first = max(p0 - self.window + 1, 0) // ps
        end = min(last_write_pos, self.max_len - 1) // ps + 1
        behind = [j for j in slot.wpages if j < first]
        ahead = [j for j in range(first, end) if j not in slot.wpages]
        if not behind and not ahead:
            return
        changes = []
        if behind:
            self._window_allocator.give_back([slot.wpages.pop(j) for j in behind])
            changes += [(j, NULL_PAGE) for j in behind]
        if ahead:
            got = self._window_allocator.alloc(len(ahead))
            if got is None:     # cannot happen in a fully provisioned class (__init__)
                raise RuntimeError(
                    f"window page class exhausted: {len(ahead)} pages asked, "
                    f"{self._window_allocator.free_count()} free")
            ids_np = np.full((self.n_pages,), TRASH_PAGE, np.int32)
            ids_np[:len(got)] = got
            self._caches = self._reset_pages(self._caches, None, jnp.asarray(ids_np))
            slot.wpages.update(zip(ahead, got))
            changes += list(zip(ahead, got))
        if row is not None:
            for j, page in changes:
                row[j] = page
            return
        for j, page in changes:
            self._window_tables = self._set_block_entry(
                self._window_tables, jnp.asarray(slot_index, jnp.int32),
                jnp.asarray(j, jnp.int32), jnp.asarray(page, jnp.int32))

    def _pick_page_victim(self):
        """LIFO shed order on page exhaustion: the globally NEWEST tenant
        yields — the staged prefill job first (it has produced nothing
        yet), then the newest staged REMOTE job (same reasoning: its
        prefill compute is sunk on the other slice, but no client has a
        token yet), then the most recently admitted active slot, which may
        be the growing slot itself. None when there is at most one tenant
        (shed nothing — the sole request just stops growing)."""
        if self._prefill is not None:
            return "job"
        if self._remote_jobs:
            # dict preserves insertion order: the last key is the newest
            return ("remote", next(reversed(self._remote_jobs)))
        active = [j for j, s in enumerate(self._slots) if s.active and s.pages]
        if len(active) < 2:
            return None
        return max(active, key=lambda j: self._slots[j].admit_seq)

    def _shed_error(self, why: str):
        from seldon_core_tpu.runtime.resilience import ShedError

        # Retry-After derived from the live backlog (retry_after_hint),
        # not the fixed constant: during the exact spikes that cause
        # sheds, a constant backoff stampedes every shed client back at
        # once while the queue is still draining.
        return ShedError(f"kv page pool exhausted: {why}",
                         retry_after_s=self.retry_after_hint())

    def _shed_request(self, fut: asyncio.Future, on_token: Optional[Any],
                      why: str):
        """Shed a not-yet-admitted request (503 + Retry-After)."""
        self._allocator.count_shed()
        logger.warning("shedding admission: %s", why)
        if on_token is not None:
            try:
                on_token(None)
            except Exception:
                pass
        self._resolve(fut, exc=self._shed_error(why))

    def _unpin_request(self, req):
        """Drop a queued/staged request's adapter pin. Ownership lives on
        the queue entry from submit() until _commit_slot moves it to the
        slot, so every TERMINAL pre-commit path (queued shed, staged
        local/remote shed, crash drain) funnels here; the id zeroes so a
        path that fires twice cannot double-unpin."""
        if self._adapters is not None and req.adapter_id:
            self._adapters.unpin(req.adapter_id)
            req.adapter_id = 0

    def _shed_queued_request(self, req, why: str):
        """Shed a request still sitting in the scheduler: remove it there
        (which books the shed against its tenant —
        seldon_tenant_shed_total), drop its adapter pin, then the common
        shed path."""
        self._pending.remove(req)
        self._unpin_request(req)
        self._shed_request(req.fut, req.on_token, why)

    def _shed_slot(self, i: int, why: str):
        """Shed an ACTIVE slot mid-decode to relieve page exhaustion: its
        tokens are discarded and the client gets 503 + Retry-After."""
        slot = self._slots[i]
        self._allocator.count_shed()
        self._pending.count_shed(slot.tenant, slot.slo_class)
        logger.warning(
            "shedding slot %d after %d generated tokens: %s", i, slot.n_new, why)
        fut, on_token = slot.future, slot.on_token
        if self._flight is not None:
            self._flight.record(i, EV_SHED, why=why)
            self._flight.complete(i, "shed", slot.n_new, self._tracer)
        # release BEFORE notifying (same ordering as _finish): the shed
        # client's 503 handler must never observe its own pages as held
        self._release_slot(i)
        if on_token is not None:
            try:
                on_token(None)
            except Exception:
                pass
        if fut is not None:
            self._resolve(fut, exc=self._shed_error(why))

    def _shed_prefill_job(self, why: str):
        job = self._prefill
        if job is None:
            return
        self._prefill = None
        self._allocator.count_shed()
        if job.req is not None:
            self._pending.count_shed(job.req.tenant, job.req.slo_class)
            # pre-commit, the QUEUE ENTRY still owns the adapter pin
            # (slot.adapter_id is only set at _commit_slot, so the slot
            # release below cannot drop it) — this shed is the terminal
            # outcome, so the pin dies here
            self._unpin_request(job.req)
        logger.warning("shedding staged prefill (slot %d): %s", job.slot, why)
        if self._flight is not None:
            self._flight.record(job.slot, EV_SHED, why=why)
            self._flight.complete(job.slot, "shed", 0, self._tracer)
        self._release_slot(job.slot)  # before notifying, like _finish
        if job.on_token is not None:
            try:
                job.on_token(None)
            except Exception:
                pass
        self._resolve(job.fut, exc=self._shed_error(why))

    @_in_phase("admit")
    def _preempt_for_interactive(self) -> bool:
        """Deadline-aware slot reclamation (docs/multitenancy.md): an
        interactive admission blocked on occupied slots pushes ONE staged
        batch-class job back into the scheduler — the local chunked
        prefill first (its compute is sunk but no client has a token),
        else the newest staged remote admission. ACTIVE slots are never
        touched: a slot that has surfaced tokens finishes or sheds on its
        own terms. The preempted request keeps its sequence number
        (re-enters its tenant queue where it left) and is immune to a
        second preemption (``preempted`` flag) — that immunity is what
        makes a sustained interactive flood unable to livelock batch
        admissions: a re-staged job always completes. Returns True when
        something was preempted (the caller retries its admission)."""
        job = self._prefill
        if job is not None and job.req is not None \
                and job.req.slo_class == "batch" and not job.req.preempted:
            self._prefill = None
            return self._requeue_preempted(job.slot, job.req, "local prefill")
        for job_id in reversed(list(self._remote_jobs)):
            rjob = self._remote_jobs[job_id]
            if rjob.req is None or rjob.req.slo_class != "batch" \
                    or rjob.req.preempted:
                continue
            del self._remote_jobs[job_id]
            # exactly-once vs the worker: either the READY handoff leaves
            # the queue with its payload, or the worker's later put is
            # refused — same protocol as _shed_remote_job, different fate
            # for the REQUEST (requeued, not failed)
            self._transfer.cancel(job_id)
            return self._requeue_preempted(rjob.slot, rjob.req,
                                           "staged remote prefill")
        return False

    def _requeue_preempted(self, slot_i: int, req, what: str) -> bool:
        logger.info("preempting %s (slot %d, tenant %r) for an "
                    "interactive admission", what, slot_i, req.tenant)
        slot = self._slots[slot_i]
        # the queue entry keeps the adapter pin: ownership returns to it,
        # so the release below must not unpin (it unpins slot.adapter_id,
        # zeroed here first)
        slot.adapter_id = 0
        if self._flight is not None:
            self._flight.record(slot_i, EV_SHED, why="preempted: " + what)
            self._flight.complete(slot_i, "preempted", 0, self._tracer)
        self._release_slot(slot_i)
        self._pending.push(req, requeue=True)
        return True

    def _release_slot(self, i: int):
        """Common slot teardown: drop page references (owned pages free
        to the pool, shared trie pins decrement — the trie keeps its own
        reference) and point the device block-table row back at trash (in
        device program order, so in-flight steps finish their reads first
        — reused pages are reset/rewritten strictly AFTER)."""
        slot = self._slots[i]
        slot.active = False
        slot.prefilling = False
        slot.future = None
        slot.on_token = None
        slot.logits = None
        slot.routing = None
        slot.state = None
        slot.ids = None
        slot.tenant = ""
        slot.slo_class = "interactive"
        if self._adapters is not None and slot.adapter_id:
            # the slot's pin was the live reference holding this adapter
            # in the pool; eviction becomes legal once it drops. The
            # device id resets to identity so the released slot's
            # ride-along compute gathers row 0 (zeros), never a row a
            # later load may repopulate for someone else.
            self._adapters.unpin(slot.adapter_id)
            import jax.numpy as _jnp

            self._adapter_ids = self._set_adapter_id(
                self._adapter_ids, _jnp.asarray(i, _jnp.int32),
                _jnp.asarray(0, _jnp.int32))
        slot.adapter_id = 0
        if slot.pages:
            self._allocator.free(slot.pages)
            slot.pages = []
        if slot.shared:
            self._allocator.free(slot.shared)  # unpin: refs -= 1
            slot.shared = []
        import jax.numpy as jnp

        self._block_tables = self._set_block_row(
            self._block_tables, jnp.asarray(i, jnp.int32), self._trash_row)
        if self.window:
            if slot.wpages:
                self._window_allocator.free(list(slot.wpages.values()))
                slot.wpages = {}
            self._window_tables = self._set_block_row(
                self._window_tables, jnp.asarray(i, jnp.int32), self._trash_row)

    def page_stats(self, radix_stats: Optional[dict] = None) -> dict:
        """Pool gauges for llm_stats/metrics: in-use/total pages plus
        internal fragmentation (1 - tokens written / page tokens held) —
        the slack the page-size knob trades against table overhead.
        Each allocated page's tokens count exactly ONCE: slots count only their OWNED
        pages' tokens, trie-held blocks (shared ones included — sharing
        is the trie's page) count as full blocks via ``radix_stats``
        (pass a precomputed ``RadixPrefixCache.stats()`` snapshot to
        avoid a second O(nodes) walk per scrape)."""
        total, in_use, sheds = self._allocator.stats()
        by_class = {"full": {"total": total, "in_use": in_use}}
        if self.window:
            w_total, w_in_use, _ = self._window_allocator.stats()
            by_class["window"] = {"total": w_total, "in_use": w_in_use}
        ps = self.page_size
        used_tokens = 0
        for s in self._slots:
            if s.active:
                used_tokens += min(
                    max(s.true_len + s.disp_new - len(s.shared) * ps, 0),
                    len(s.pages) * ps)
        job = self._prefill
        if job is not None:
            jslot = self._slots[job.slot]
            used_tokens += min(max(job.next - len(jslot.shared) * ps, 0),
                               len(jslot.pages) * ps)
        if self._radix is not None:
            # trie-held blocks count as used capacity (they are the cache
            # working set, not slack) — once per page, shared or not
            # (slots above counted owned pages only)
            rs = radix_stats if radix_stats is not None \
                else self._radix.stats()
            used_tokens += rs["prefix_cached_blocks"] * ps
        frag = 0.0
        if in_use > 0:
            frag = 1.0 - used_tokens / float(in_use * self.page_size)
        return {
            # every class summed (a model without sliding-attention layers has
            # the full class alone); fragmentation is the full class's
            "kv_pages_total": sum(c["total"] for c in by_class.values()),
            "kv_pages_in_use": sum(c["in_use"] for c in by_class.values()),
            "kv_pages_by_class": by_class,
            "kv_pages_released": {
                "window": self._window_allocator.released_total if self.window else 0},
            "kv_page_size": self.page_size,
            "kv_page_fragmentation": max(0.0, min(1.0, frag)),
            "kv_page_sheds": sheds,
            "state_bytes": self.state_nbytes,
            "state_matrix_bytes": self.state_matrix_nbytes,
            "state_matrix_tiled_bytes": self.state_matrix_tiled_nbytes,
        }

    def spec_stats(self) -> dict:
        """Speculation counters for llm_stats/metrics: aggregate draft
        acceptance rate, accepted tokens per target forward (the
        >1-per-cache-read multiplier), the per-slot acceptance EMAs the
        draft-length controller steers by, and the draft-overhead
        fraction (verify-forward token columns wasted on rejected
        drafts). All-off zeros when speculation is disabled."""
        if self.spec_mode == "off":
            return {"spec_mode": "off", "spec_k": 0,
                    "spec_accept_rate": 0.0, "spec_tokens_per_forward": 0.0,
                    "spec_slot_steps_total": 0,
                    "spec_accept_rate_per_slot": [],
                    "spec_draft_overhead_fraction": 0.0}
        snap = self._spec.snapshot()
        return {
            "spec_mode": self.spec_mode,
            "spec_k": self.spec_k,
            "spec_accept_rate": snap["spec_accept_rate"],
            "spec_tokens_per_forward": snap["spec_tokens_per_forward"],
            "spec_slot_steps_total": snap["spec_slot_steps_total"],
            "spec_accept_rate_per_slot": self._spec.rates(),
            "spec_draft_overhead_fraction":
                snap["spec_draft_overhead_fraction"],
        }

    def _finish(self, i: int):
        """Complete slot ``i``: trie insertion, slot release, THEN client
        notification. Resolving the future first was a latent race: the
        awaiting client resumes on the loop thread while this worker is
        still freeing pages, so a client-side stats read (or an immediate
        follow-up submit) could observe the finished request's pages as
        leaked/held — releasing before ``_resolve`` makes completion
        observable only after the pool is consistent."""
        slot = self._slots[i]
        self._build_wide_program()   # (once: every program a request needs is built by now)
        toks = slot.tokens
        if self.eos_id in toks:
            toks = toks[: toks.index(self.eos_id)]
        fut, on_token = slot.future, slot.on_token
        if self._flight is not None:
            # ``tokens`` = tokens CREDITED to the slot (n_new): the sum the
            # per-step events must reproduce; an EOS trim shortens the
            # client's list but never the credited count
            self._flight.complete(i, "done", slot.n_new, self._tracer)
        if self._radix is not None and slot.ids is not None \
                and slot.adapter_id == 0:
            # base-adapter slots only: an adapted slot's KV embeds its
            # q/o/FFN deltas from layer 1 on, and inserting it would serve
            # tenant-specific KV to base traffic (docs/multitenancy.md)
            # insert the slot's prompt+generated blocks back into the trie
            # IN PLACE — page ownership transfers node-by-node, no dense
            # export. Only provably-written positions qualify: every token
            # but the last credited one has been FED to a later step (its
            # KV write is in device program order before any future
            # reader); the last token's write is run-ahead-dependent.
            hist = list(slot.ids) + slot.tokens[:max(slot.n_new - 1, 0)]
            consumed = self._radix.insert(
                hist, slot.shared + slot.pages, len(slot.shared))
            if consumed:
                # adopted/deduped pages are no longer this slot's to free
                slot.pages = [p for p in slot.pages if p not in consumed]
        if slot.state is not None:
            self._read_state(i)
        self._release_slot(i)
        if on_token is not None:
            on_token(None)  # stream end sentinel
        if fut is not None:
            self._resolve(fut, result=toks)

    def _read_state(self, i: int):
        """A probe asked for the state its sequence leaves (transport/rest.py
        "state"): the first mamba layer's h, as the cache holds it after
        every program dispatched so far, which have fed the prompt and the
        first ``disp_new - 1`` sampled tokens: ``tokens`` of them in all (the
        read is enqueued behind those programs and ahead of any later one)."""
        import jax

        slot, cfg = self._slots[i], self.server._cfg
        if self._matrix_state is None:
            self._matrix_state = jax.jit(
                lambda caches, rows: kvcache.read_matrix_state(cfg, caches, rows)[0])
        # graftlint: allow-host-sync-in-hot-path(a probe request only: one sequence's state of one layer, read where the request finishes)
        state = np.asarray(self._matrix_state(self._caches, self._state_slot[i]))
        slot.state.update(layer=kvcache.matrix_state_layer(cfg), tokens=slot.dispatched_pos(),
                          array=state)

    # ------------------------------------------------------------------
    # Pipelined decode: dispatch (producer) / drain (consumer)
    # ------------------------------------------------------------------
    def active_slots(self) -> int:
        return sum(1 for s in self._slots if s.active)

    def _dispatch_eligible(self) -> List[int]:
        """Slots worth stepping: active AND not yet dispatched through their
        token budget or cache length. A budget-exhausted slot still rides
        along (static shapes — the whole batch steps), but when NO slot
        needs tokens there is nothing to dispatch."""
        return [
            i for i, s in enumerate(self._slots)
            if s.active and s.disp_new < s.max_new
            and s.dispatched_pos() < self.max_len
        ]

    def _pick_k(self) -> int:
        """Fused-step block size for the next dispatch. K>1 only when the
        admit queue is empty (a fused block delays admission by K steps) and
        every eligible slot has >= K steps of budget left (so the block
        never overruns max_new or writes past the cache). Falling back to 1
        instead of an arbitrary clamp keeps the compile count at two
        programs (K=1 and K=fuse_steps)."""
        if self.fuse_steps <= 1 or self._pending or self._prefill is not None:
            return 1
        eligible = self._dispatch_eligible()
        if not eligible:
            return 1
        room = min(
            min(s.max_new - s.disp_new, self.max_len - s.dispatched_pos())
            for s in (self._slots[i] for i in eligible)
        )
        return self.fuse_steps if room >= self.fuse_steps else 1

    def _dispatch(self) -> bool:
        """Enqueue one (possibly K-fused) decode step on the device WITHOUT
        waiting for its tokens (False: nothing left to step after page
        growth): the state arrays are threaded from the
        previous step's outputs, so the device runs ahead of the host.
        The ``dispatch`` phase is page growth + the enqueue, and its clock
        pair is also the step's dispatch timestamp and the
        ``seldon_llm_decode_dispatch_seconds`` observation."""
        with self._phases.phase("dispatch") as ph:
            if self.spec_mode != "off":
                enqueued = self._dispatch_spec(ph.t0)
            else:
                enqueued = self._dispatch_plain(ph.t0)
        if enqueued:
            self.server._decode_dispatch_times.append(ph.seconds)
        return enqueued

    def _dispatch_plain(self, t0: float) -> bool:
        k = self._pick_k()
        # grow every eligible slot's pages to cover this dispatch's k
        # writes FIRST — positions dispatched_pos()..dispatched_pos()+k-1
        # (the device's next_pos equals dispatched_pos()). An exhaustion
        # shed inside the loop can deactivate a LATER slot of this
        # snapshot, so re-check activity before touching each one:
        # growing a released slot would allocate pages nothing owns.
        with self._phases.part("pages"):
            for i in self._dispatch_eligible():
                if self._slots[i].active:
                    self._ensure_slot_pages(
                        i, self._slots[i].dispatched_pos() + k - 1)
        if not self._dispatch_eligible():
            return False
        # adapted steps (llm.lora_decode_step): the pool/id pair rides at
        # the end of the signature, un-donated — same idiom as the
        # spec-step dispatch below
        lora = self._adapters is not None
        extra = () if not lora else (self._adapters.pool(),
                                     self._adapter_ids)
        builds = self._ledger.thread_builds()
        with self._phases.part("call"):
            fn = self.server._get_decode_step_paged(
                self.S, self.n_pages, k, lora=lora)
            if (k, lora) not in self._step_programs:
                self._step_programs.add((k, lora))
                for kind in STEP_PATH_KINDS:
                    if kind in self._state_layers:
                        self._phases.step_path[kind][self._state_step_path(kind)] += 1
            (self._caches, self._last_tok, self._next_pos, self._keys,
             toks, aside) = fn(
                self.server._params, self._caches, self._last_tok,
                self._next_pos, self._keys, self._temp,
                self._tables(), *extra)
        with self._phases.part("book"):
            snapshot = [(i, s.gen) for i, s in enumerate(self._slots) if s.active]
            context, live = 0, []
            for i, _ in snapshot:
                # micro-step j writes row pos + j and reads rows 0 .. pos + j
                pos = self._slots[i].dispatched_pos()
                context += k * (pos + 1) + k * (k - 1) // 2
                live.extend(pos + 1 + j for j in range(k))
                self._slots[i].disp_new += k
            self._phases.count_attention("decode", context, self._rows_read(1, live, k * self.S))
            if self.window:
                firsts = [max(rows - self.window, 0) for rows in live]
                self._phases.count_window_attention(
                    "decode", context - sum(firsts),
                    self._rows_read(1, live, k * self.S, firsts), context)
            if self._state_layers:
                self._phases.count_state_layers(
                    "decode", k * len(snapshot), k, self._state_layers)
            if self._cross_decoder:
                self._phases.count_decoders("decode", k * len(snapshot), k * len(snapshot),
                                            context, self._rows_read(1, live, k * self.S))
            self._inflight.append(_InFlight(toks, k, snapshot, t0, aside=aside,
                                            built=self._built_since(builds)))
            self._count_steps()
        return True

    def _dispatch_spec(self, t0: float) -> bool:
        """Enqueue one fused draft+verify step (``LLMServer._get_spec_step``)
        WITHOUT waiting for its tokens. Each slot advances a data-dependent
        1..cap+1 tokens known only at drain time, so the dispatch side books
        the PESSIMISTIC maximum (cap+1) into ``disp_new`` — page
        provisioning and the cache-edge/budget caps must cover the
        all-accepted case — and the drain reconciles it back to the actual
        advance. The per-slot cap clamps the drafts offered: the
        acceptance-rate controller's depth, the remaining token budget
        (emits <= cap+1), and the cache edge (writes reach next_pos+cap)."""
        import jax.numpy as jnp

        K = self.spec_k
        caps = np.zeros((self.S,), np.int32)
        for i in self._dispatch_eligible():
            s = self._slots[i]
            cap = min(self._spec.cap(i), K,
                      s.max_new - s.disp_new - 1,
                      (self.max_len - 1) - s.dispatched_pos())
            caps[i] = max(int(cap), 0)
        # provision pages to the step's FURTHEST possible write
        # (next_pos + cap); an exhaustion shed inside the loop can
        # deactivate a later slot of this snapshot — re-check activity
        # (same discipline as the plain dispatch)
        with self._phases.part("pages"):
            for i in self._dispatch_eligible():
                if self._slots[i].active:
                    self._ensure_slot_pages(
                        i, self._slots[i].dispatched_pos() + int(caps[i]))
        if not self._dispatch_eligible():
            return False
        # adapted verify (llm.lora_verify_step): the pool/id pair rides at
        # the end of either signature, un-donated
        extra = () if self._adapters is None else (
            self._adapters.pool(), self._adapter_ids)
        builds = self._ledger.thread_builds()
        with self._phases.part("call"):
            fn = self.server._get_spec_step(
                self.S, K, self.hist_len, mode=self.spec_mode,
                n_pages=self.n_pages, lora=self._adapters is not None)
            cap_dev = jnp.asarray(caps)
            if self.spec_mode == "draft":
                (self._caches, self._last_tok, self._next_pos, self._keys,
                 self._hist, toks, acc, self._draft_caches) = fn(
                    self.server._params, self._caches, self._last_tok,
                    self._next_pos, self._keys, self._temp, self._block_tables,
                    self._hist, cap_dev, self.server._draft_params,
                    self._draft_caches, *extra)
            else:
                (self._caches, self._last_tok, self._next_pos, self._keys,
                 self._hist, toks, acc) = fn(
                    self.server._params, self._caches, self._last_tok,
                    self._next_pos, self._keys, self._temp, self._block_tables,
                    self._hist, cap_dev, *extra)
        with self._phases.part("book"):
            snapshot = [(i, s.gen) for i, s in enumerate(self._slots) if s.active]
            booked = {}
            for i, _ in snapshot:
                booked[i] = int(caps[i]) + 1
                self._slots[i].disp_new += booked[i]
            self._inflight.append(_InFlight(toks, 1, snapshot, t0, acc=acc, booked=booked,
                                            built=self._built_since(builds)))
            self._count_steps()
        return True

    def steps_in_flight(self) -> int:
        """Dispatched decode steps the host has not drained (first-token
        records queue with them and are not steps)."""
        return self._steps_in_flight

    def _count_steps(self) -> None:
        """After a step joined or left ``_inflight``: recount (a handful of
        records; scrapes on other threads read the plain integer)."""
        self._steps_in_flight = sum(1 for rec in self._inflight if rec.k)
        if self._steps_in_flight > self._inflight_hwm:
            self._inflight_hwm = self._steps_in_flight

    def _drain_one(self):
        """Consume the OLDEST record in flight: a decode step's tokens
        (``_drain_step``) or an activation's first token
        (``_drain_first``), in the order the device runs them."""
        rec = self._inflight.popleft()
        if rec.k:
            self._count_steps()
            self._drain_step(rec)
        else:
            self._drain_first(rec)

    def _first_token_can_wait(self, enqueued: bool) -> bool:
        """Would reading the oldest record leave the device with nothing to
        do? Yes when it is an activation whose token is not there yet and
        NOTHING is queued behind it (no step, no later activation, no chunk
        of the staged job), while this turn still found something to
        enqueue (an admission, a step, a chunk): then the next turn's
        enqueues go first, so a prompt's last chunk has the slot's next
        step or the next request's first chunk queued behind it before
        anything waits for its token. With a program behind it the read may
        block: the device has that to run meanwhile. A turn that enqueued
        nothing lets the read block too."""
        head = self._inflight[0]
        job = self._prefill
        return (head.k == 0 and enqueued and len(self._inflight) == 1
                and (job is None or job.next == job.start)
                and not head.token.is_ready())

    @_in_phase("emit")
    def _drain_step(self, rec: _InFlight):
        """Consume a dispatched step: block until its tokens land
        (the ``drain_wait`` phase), then run all host bookkeeping (EOS,
        budgets, streaming callbacks, slot release: the rest is ``emit``).
        Later steps stay dispatched while this runs — the host trails the
        device, never the other way around."""
        # host lag in decode STEPS, not dispatch records: a fused record
        # covers k steps, so depth 2 at K=8 is a 16-step lag
        lag = rec.k + sum(r.k for r in self._inflight)
        with self._phases.phase("drain_wait") as wait:
            # graftlint: allow-host-sync-in-hot-path(the consumer's deliberate drain sync: the host reads tokens one pipeline_depth BEHIND the device, so this blocks on the oldest step only while newer steps keep the chip busy — docs/performance.md)
            arr = np.asarray(rec.tokens)  # [S, k] — the only per-step host sync
            # what is read beside the tokens, after them (nothing for a dense
            # model's plain step): transfers of a program that has finished,
            # told apart from the wait for it
            with self._phases.part("asides"):
                if rec.acc is not None:
                    # graftlint: allow-host-sync-in-hot-path(part of the same drain sync: the verify step's per-slot accepted counts land with its tokens — the program already finished for the token read above)
                    accs = np.asarray(rec.acc)  # [S] accepted counts, 1..K+1
                moe_tokens, moe_fields = None, {}
                if self._moe is not None and rec.acc is None:
                    # graftlint: allow-host-sync-in-hot-path(part of the same drain sync: the step's routing tallies land with its tokens — the program already finished for the token read above)
                    moe_stats = np.asarray(rec.aside["moe_stats"])    # [k, 6]
                    # graftlint: allow-host-sync-in-hot-path(same: [k, S, n_experts] int32, 8 KB a step at 32 slots x 64 experts)
                    moe_tokens = np.asarray(rec.aside["moe_tokens"])
                    self._moe.add("decode", moe_stats)
                    moe_fields = self._moe.flight_fields(moe_stats[0])
                    delivered = np.zeros(moe_tokens.shape[:2], bool)   # [k, S]
        now = wait.t1
        self.server._decode_sync_times.append(wait.seconds)
        self.server.observe("decode_host_lag_steps", lag)
        # steady-state step time: interval since the previous drain (the
        # pipeline overlaps dispatch+sync with device compute, so per-step
        # wall is drain-to-drain), floored at this record's dispatch time so
        # an idle gap doesn't inflate the histogram
        base = rec.t_dispatch if self._last_drain_t is None else max(
            self._last_drain_t, rec.t_dispatch)
        per_step = max(now - base, 0.0) / rec.k
        self.server.observe("decode_step_s", per_step, weight=rec.k)
        self._last_drain_t = now
        self.server._last_decode_kv_bytes = self._cache_nbytes
        if rec.acc is not None:
            self._credit_spec(rec, arr, accs)
            return
        with self._phases.part("slots"):
            for i, gen in rec.snapshot:
                slot = self._slots[i]
                if not slot.active or slot.gen != gen:
                    # trailing run-ahead token for a finished (or already
                    # replaced) occupant — masked, never surfaced
                    continue
                if slot.n_new >= slot.max_new:
                    continue  # budget-exhausted slot riding along
                credited = 0
                finish = False
                for j in range(rec.k):
                    tok = int(arr[i, j])
                    slot.tokens.append(tok)
                    slot.n_new += 1
                    credited += 1
                    if slot.logits is not None:
                        # graftlint: allow-host-sync-in-hot-path(a probe request only: one [vocab] row of a step that has finished)
                        slot.logits.append(np.asarray(rec.aside["logits"][j, i]))
                        if slot.routing is not None:
                            # graftlint: allow-host-sync-in-hot-path(same probe: the [n_moe_layers, k] experts this step's token took)
                            slot.routing.append(np.asarray(rec.aside["moe_choice"][j, i, 0]))
                    # inter-token gap at this drain (a fused block surfaces
                    # its k tokens in one burst: trailing tokens record ~0)
                    if slot.t_last is not None:
                        self.server.observe("inter_token_s", now - slot.t_last)
                    slot.t_last = now
                    if slot.on_token is not None and tok != self.eos_id:
                        slot.on_token(tok)
                    if (tok == self.eos_id or slot.n_new >= slot.max_new
                            or slot.host_pos() >= self.max_len):
                        finish = True
                        break
                if credited:
                    self._pending.count_tokens(slot.tenant, slot.slo_class,
                                               credited)
                if moe_tokens is not None:
                    delivered[:credited, i] = True
                if self._flight is not None and credited:
                    # one step event per slot per drain, BEFORE any finish
                    # materializes the segment: tokens credited this drain plus
                    # the step's device dwell (dispatch -> drain)
                    self._flight.record(i, EV_STEP, tokens=credited,
                                        t_dispatch=rec.t_dispatch, **moe_fields,
                                        **rec.built)
                if finish:
                    with self._phases.part("finish"):
                        self._finish(i)
        if moe_tokens is not None:
            self._moe.expert_tokens += moe_tokens[delivered].sum(axis=0)

    def _credit_spec(self, rec: _InFlight, arr: np.ndarray,
                     accs: np.ndarray):
        """Drain-side bookkeeping for one verify step: reconcile the
        pessimistic dispatch booking to the device's ACTUAL advance, feed
        the acceptance-rate controller, and credit each slot its accepted
        tokens with the same (slot, gen) masking and EOS/budget/cache-edge
        stops as the plain drain. An EOS landing INSIDE an accepted draft
        block cuts the credit loop there — the device ran ahead past it,
        exactly like a trailing run-ahead step, and the leftover tokens
        are dropped, never surfaced."""
        now = time.perf_counter()
        with self._phases.part("slots"):
            for i, gen in rec.snapshot:
                slot = self._slots[i]
                if not slot.active or slot.gen != gen:
                    # the occupant this step decoded for is gone; the new
                    # occupant's disp_new/controller state were reset at
                    # admission, so there is nothing to reconcile either
                    continue
                adv = int(accs[i])
                booked = rec.booked.get(i, 1)
                # dispatch booked the all-accepted maximum (cap+1); the device
                # actually advanced next_pos by adv — restore the invariant
                # dispatched_pos() == device next_pos + later in-flight maxima
                slot.disp_new -= booked - adv
                offered = booked - 1
                self._spec.observe(i, max(adv - 1, 0), offered, adv)
                self.server._spec_accepted.append(adv)
                if slot.n_new >= slot.max_new:
                    continue  # budget-exhausted slot riding along
                credited = 0
                finish = False
                for j in range(adv):
                    tok = int(arr[i, j])
                    slot.tokens.append(tok)
                    slot.n_new += 1
                    credited += 1
                    # inter-token gap (an accepted block surfaces as a burst:
                    # its trailing tokens record ~0 gaps — the block's real
                    # cadence is the first token's gap)
                    if slot.t_last is not None:
                        self.server.observe("inter_token_s", now - slot.t_last)
                    slot.t_last = now
                    if slot.on_token is not None and tok != self.eos_id:
                        slot.on_token(tok)
                    if (tok == self.eos_id or slot.n_new >= slot.max_new
                            or slot.host_pos() >= self.max_len):
                        finish = True
                        break
                if credited:
                    self._pending.count_tokens(slot.tenant, slot.slo_class,
                                               credited)
                if self._flight is not None and credited:
                    # per-verify-step event: tokens surfaced, drafts offered,
                    # device-accepted count — the speculative half of the
                    # timeline's token accounting (recorded before any finish)
                    self._flight.record(i, EV_STEP, tokens=credited,
                                        offered=offered, accepted=adv,
                                        t_dispatch=rec.t_dispatch,
                                        **rec.built)
                if finish:
                    with self._phases.part("finish"):
                        self._finish(i)

    async def _to_thread(self, fn, *args):
        """``asyncio.to_thread(fn, *args)`` for the loop coroutine, the
        hand-off stamped for ``hop``'s parts (``LoopPhases.handoff``)."""
        hop = self._phases.handoff(fn, *args)
        try:
            return await asyncio.to_thread(hop)
        finally:
            hop.resumed()

    async def _run(self):
        self.crashed = None  # a restarted loop is a recovered loop
        phases = self._phases
        try:
            while True:
                # the turn's time budget closes here and nowhere else: what
                # the previous turn's phases did not cover is its hop
                phases.turn(self.active_slots())
                # did this turn put anything on the device's queue or take a
                # request off the scheduler's (_first_token_can_wait)
                enqueued = False
                # liveness heartbeat + deterministic chaos injection: both
                # happen in the loop's own serialized context, so a raising
                # chaos hook dies exactly like a device fault mid-turn
                self.heartbeat = self.clock()
                if self._chaos is not None:
                    self._chaos(self)
                # admit as many pending requests as there are free slots
                # (FIFO, peek-then-pop so a failed admit keeps the request);
                # device work runs in a worker thread so the event loop (and
                # co-hosted HTTP handlers) stays responsive during decode:
                # every hand-off goes through ``phases.handoff``, which is
                # ``asyncio.to_thread`` with the four stamps hop's parts need.
                # Admission happens while earlier steps are STILL IN FLIGHT
                # — the insert/set_slot queue behind them in device program
                # order, and the gen counter masks their stale tokens.
                while True:
                    req = self._pending.next_request()
                    if req is None:
                        break
                    if self._prefill is not None:
                        # one local chunked prefill stages at a time. An
                        # interactive head may preempt a staged BATCH-class
                        # one (the preemption contract: staged jobs only,
                        # never active slots, at most once per request) —
                        # otherwise wait for its chunks to finish
                        if (req.slo_class == "interactive"
                                and await self._to_thread(
                                    self._preempt_for_interactive)):
                            continue
                        break
                    if self._remote is not None:
                        # disaggregated: stage the job on the prefill
                        # slice — host-side only, so MULTIPLE admissions
                        # can be in flight while decode keeps dispatching
                        admitted = await self._to_thread(self._admit_remote, req)
                    else:
                        admitted = await self._to_thread(self._admit_begin, req)
                    if not admitted:
                        # deadline-aware preemption: an interactive head
                        # blocked on occupied slots may push ONE staged
                        # batch-class job (local chunked prefill / staged
                        # remote admission) back into the queue — never
                        # an active slot — then retry the same head
                        if (req.slo_class == "interactive"
                                and await self._to_thread(
                                    self._preempt_for_interactive)):
                            continue
                        break  # no free slot/pages — decode frees them
                    # an _admit_* shed path already removed req from the
                    # scheduler (counting the shed); commit is a no-op then
                    self._pending.commit(req)
                    enqueued = True
                # disaggregated: activate every finished handoff (import +
                # commit — one jitted scatter each, no prefill compute on
                # this slice)
                if self._transfer is not None and self._transfer.ready_depth():
                    await self._to_thread(self._consume_handoffs)
                    enqueued = True
                # producer: keep the device pipeline_depth steps ahead of
                # the host — dispatch is enqueue-only, no sync
                while (self.steps_in_flight() < self.pipeline_depth
                       and self._dispatch_eligible()):
                    if await self._to_thread(self._dispatch):
                        enqueued = True
                # chunked prefill interleaves: ONE chunk per loop turn, so a
                # long admission prefill shares the device with the decode
                # dispatches above instead of stalling them for its whole
                # compile bucket (no chunk syncs: the last one ends in the
                # slot's activation, read below like a step's tokens)
                if self._prefill is not None:
                    await self._to_thread(self._prefill_step)
                    enqueued = True
                # consumer: drain the oldest record one (or more) behind,
                # unless it is a first token that the next turn's enqueues
                # should not stand behind
                if self._inflight and not self._first_token_can_wait(enqueued):
                    await self._to_thread(self._drain_one)
                    continue
                if enqueued:
                    # never fall through to the idle wait on a turn that
                    # enqueued: a chunk advanced its job or ACTIVATED the
                    # slot (now dispatch-eligible), or there is more to
                    # enqueue ahead of a first token — loop back
                    continue
                if self._closed:
                    # staged remote jobs would leave futures hanging past
                    # the loop's death — fail them before returning
                    # (to_thread like every other _release_slot caller:
                    # page/block-table writers stay single-context)
                    if self._remote_jobs:
                        await self._to_thread(
                            self._fail_remote_jobs,
                            RuntimeError("batcher closed"))
                    return
                if self._dispatch_eligible():
                    # a slot became runnable without a wakeup signal (e.g.
                    # activation landed on the final loop turn) — sleeping
                    # 0.5s here would stall its whole decode
                    continue
                self._wakeup.clear()
                try:
                    with phases.phase("idle"):
                        await asyncio.wait_for(self._wakeup.wait(),
                                               timeout=0.5)
                except asyncio.TimeoutError:
                    if self._closed:
                        return
        except BaseException as e:
            # device/compile failure: fail every in-flight and queued request
            # instead of leaving their futures hanging. The crash flag goes
            # up FIRST so fleet health checks eject this replica before any
            # failed future routes its client back through dispatch.
            self.crashed = e
            logger.exception("batcher loop died: %s", e)
            self._inflight.clear()
            self._steps_in_flight = 0
            self._prefill = None
            if self._remote_jobs:
                # cancel staged handoffs first: their slots then read as
                # released, so the slot sweep below cannot double-resolve
                # (to_thread keeps every _release_slot caller in the same
                # offload context the page/block-table state is guarded by)
                await self._to_thread(self._fail_remote_jobs, e)
            for slot in self._slots:
                if slot.active or slot.prefilling:
                    if slot.on_token is not None:
                        try:
                            slot.on_token(None)  # unblock streaming consumers
                        except Exception:
                            pass
                        slot.on_token = None
                    if slot.future is not None:
                        self._resolve(slot.future, exc=e)
                    slot.active = False
                    slot.prefilling = False
                    slot.future = None
            for req in self._pending.drain_all():
                try:
                    self._unpin_request(req)
                except ValueError:
                    pass  # teardown must not mask the original error
                if req.on_token is not None:
                    try:
                        req.on_token(None)
                    except Exception:
                        pass
                self._resolve(req.fut, exc=e)
            raise
        finally:
            phases.end_turn(self.active_slots())
