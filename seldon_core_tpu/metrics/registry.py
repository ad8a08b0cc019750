"""Prometheus metrics with the reference's tag scheme.

Mirrors the engine's micrometer setup (`engine/src/main/java/io/seldon/engine/
metrics/SeldonRestTemplateExchangeTagsProvider.java:40-119`: deployment/
predictor/model tags on every series) and its registration of user metrics
carried in-band in ``meta.metrics`` (`PredictiveUnitBean.java:314-340`), plus
the feedback/reward counters (`:309-312`). Exposed at /metrics and /prometheus
(`ENGINE_PROMETHEUS_PATH` in the reference operator).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

from seldon_core_tpu.contracts.payload import Feedback, SeldonMessage
from seldon_core_tpu.metrics.local import (
    HOST_LAG_BUCKETS,
    LATENCY_BUCKETS,
    QUEUE_WAIT_BUCKETS,
)


class MetricsRegistry:
    def __init__(self, deployment: str = "", predictor: str = ""):
        self.deployment = deployment or os.environ.get("DEPLOYMENT_NAME", "")
        self.predictor = predictor or os.environ.get("PREDICTOR_ID", "")
        self.registry = CollectorRegistry()
        base = ["deployment_name", "predictor_name"]
        self._api = Counter(
            "seldon_api_executor_server_requests_total",
            "API requests by method and code",
            base + ["method", "code"],
            registry=self.registry,
        )
        self._latency = Histogram(
            "seldon_api_executor_server_requests_seconds",
            "API latency",
            base + ["method"],
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._feedback = Counter(
            "seldon_api_model_feedback_total",
            "Feedback events",
            base,
            registry=self.registry,
        )
        self._feedback_reward = Counter(
            "seldon_api_model_feedback_reward_total",
            "Cumulative feedback reward",
            base,
            registry=self.registry,
        )
        self._custom_counters: Dict[str, Counter] = {}
        self._custom_gauges: Dict[str, Gauge] = {}
        self._custom_timers: Dict[str, Histogram] = {}
        # resilience layer (runtime/resilience.py): shed + deadline counters,
        # breaker state gauges/transition counters, admission occupancy
        self._shed = Counter(
            "seldon_resilience_shed_total",
            "Requests shed at admission (503 + Retry-After / RESOURCE_EXHAUSTED)",
            base + ["transport"],
            registry=self.registry,
        )
        self._deadline_exceeded = Counter(
            "seldon_resilience_deadline_exceeded_total",
            "Requests that exhausted their deadline budget",
            base + ["transport"],
            registry=self.registry,
        )
        self._breaker_state = Gauge(
            "seldon_resilience_breaker_state",
            "Per-node circuit breaker state (0 closed, 1 half-open, 2 open)",
            base + ["node"],
            registry=self.registry,
        )
        self._breaker_transitions = Counter(
            "seldon_resilience_breaker_transitions_total",
            "Per-node circuit breaker transitions by target state",
            base + ["node", "to"],
            registry=self.registry,
        )
        self._breaker_rejected = Counter(
            "seldon_resilience_breaker_rejected_total",
            "Calls rejected by an open circuit breaker",
            base + ["node"],
            registry=self.registry,
        )
        self._inflight = Gauge(
            "seldon_resilience_inflight",
            "Admitted requests currently in flight",
            base + ["transport"],
            registry=self.registry,
        )
        self._queue_depth = Gauge(
            "seldon_resilience_queue_depth",
            "Requests waiting in the admission queue",
            base + ["transport"],
            registry=self.registry,
        )
        self._remaining_budget = Histogram(
            "seldon_resilience_remaining_budget_seconds",
            "Deadline budget remaining at response time",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        # LLM decode-bandwidth observability (servers/llmserver.py
        # llm_stats): resident KV bytes, slot occupancy, per-step KV read
        # bytes, and a decode step-time histogram — the knobs the
        # kv_cache_dtype optimization moves, exposed so the
        # bandwidth win is visible at /metrics (benchmarks/DECODE_NOTES.md)
        self._kv_cache_bytes = Gauge(
            "seldon_llm_kv_cache_bytes",
            "Resident KV-cache bytes (continuous-batching slot caches + "
            "pinned prefix-cache entries)",
            base,
            registry=self.registry,
        )
        self._kv_occupancy = Gauge(
            "seldon_llm_kv_cache_occupancy",
            "Fraction of continuous-batching cache slots occupied (0-1)",
            base,
            registry=self.registry,
        )
        self._kv_bytes_per_step = Gauge(
            "seldon_llm_kv_bytes_per_step",
            "KV-cache bytes streamed from HBM per decode step (dense "
            "attention reads the whole static cache every step)",
            base,
            registry=self.registry,
        )
        # Paged KV pool (runtime/batcher.py PageAllocator): the in-use/total
        # page pair is the oversubscription headroom gauge — in_use nearing
        # total means admissions queue and the exhaustion shed path is about
        # to bite; fragmentation is the slack between tokens written and
        # page tokens held (the page-size knob's overhead term) —
        # docs/performance.md "Paged KV cache"
        # one series a page CLASS (runtime/batcher.py): "full", and "window" for
        # a model with sliding-attention layers, whose pages behind the window
        # are given back while the request lives; summed over the label they
        # are what they always were, every page of every pool
        self._kv_pages_in_use = Gauge(
            "seldon_llm_kv_pages_in_use",
            "KV pages currently allocated to slots (paged layout), by page class",
            base + ["class"],
            registry=self.registry,
        )
        self._kv_pages_total = Gauge(
            "seldon_llm_kv_pages_total",
            "Total KV pages in the pool of each page class (incl. its 2 reserved pages)",
            base + ["class"],
            registry=self.registry,
        )
        self._kv_pages_released = Counter(
            "seldon_llm_kv_pages_released_total",
            "KV pages given back to their pool while the request that held them lived: "
            "reason=window a sliding-attention layer's page wholly behind the window of the "
            "next call's first query",
            base + ["reason"],
            registry=self.registry,
        )
        self._state_bytes = Gauge(
            "seldon_llm_state_bytes",
            "Bytes of per-slot state resident beside the page pool, over every "
            "array of every state layer's entry (a conv layer's rows, a "
            "linear-attention layer's conv rows and float32 matrix state; "
            "fixed, whatever the sequences' lengths)",
            base,
            registry=self.registry,
        )
        self._state_matrix_bytes = {
            key: Gauge(f"seldon_llm_state_matrix{suffix}_bytes", text, base,
                       registry=self.registry)
            for key, suffix, text in (
                ("state_matrix_bytes", "",
                 "Of seldon_llm_state_bytes, the float32 MATRIX state of the "
                 "linear-attention (S) and mamba (h) layers alone: the arrays' own bytes"),
                ("state_matrix_tiled_bytes", "_tiled",
                 "What the chip holds for those arrays: their last two axes "
                 "rounded up to (8, 128) float32 tiles; over "
                 "seldon_llm_state_matrix_bytes it is the padding every decode "
                 "step reads and writes (1.0 = none)"))}
        self._sampler_topk_columns = Gauge(
            "seldon_llm_sampler_topk_columns",
            "Columns the sampler's last TopK runs over in a step program that "
            "has been built (program=decode_step|spec_step|first_token): "
            "top_k blocks of 128 columns named by their maxima (5,120 at "
            "top_k 40, plus the columns behind the vocabulary's last whole "
            "block) where the two-stage form engaged, the vocabulary where "
            "the direct lax.top_k stands",
            base + ["program"],
            registry=self.registry,
        )
        self._kv_page_fragmentation = Gauge(
            "seldon_llm_kv_page_fragmentation",
            "Internal fragmentation of allocated KV pages "
            "(1 - tokens written / page tokens held, 0-1)",
            base,
            registry=self.registry,
        )
        # Page-exhaustion sheds 503 from INSIDE the serving loop (LIFO
        # victim / unservable admission, runtime/batcher.py PageAllocator),
        # a path that never touches the AdmissionController — without its
        # own counter these sheds are invisible to an operator alerting on
        # seldon_resilience_shed_total while clients see RESOURCE_EXHAUSTED
        self._kv_page_sheds = Counter(
            "seldon_llm_kv_page_sheds_total",
            "Requests shed (503 + Retry-After / RESOURCE_EXHAUSTED) by KV "
            "page-pool exhaustion",
            base,
            registry=self.registry,
        )
        # Radix prefix cache (runtime/radix.py, docs/performance.md "Radix
        # prefix cache"): hit blocks are block-table entries a request did
        # NOT re-prefill (the FLOPs-saved signal), shared pages the live
        # trie<->slot sharing right now, cow copies the one-page price of
        # partial-block continuations, evictions the LRU churn, and
        # bytes-saved the KV bytes neither copied nor recomputed on hits
        self._prefix_hit_blocks = Counter(
            "seldon_llm_prefix_hit_blocks",
            "Cached KV blocks served by radix prefix-cache hits (block-"
            "table entries written instead of prefilled)",
            base,
            registry=self.registry,
        )
        self._prefix_shared_pages = Gauge(
            "seldon_llm_prefix_shared_pages",
            "Cached pages currently referenced by at least one live slot "
            "(refcount > 1; sampled at scrape)",
            base,
            registry=self.registry,
        )
        self._prefix_cached_blocks = Gauge(
            "seldon_llm_prefix_cached_blocks",
            "Token blocks resident in the radix prefix trie (sampled at "
            "scrape)",
            base,
            registry=self.registry,
        )
        self._prefix_cow_copies = Counter(
            "seldon_llm_prefix_cow_copies_total",
            "Copy-on-write page copies (a slot continuing part-way into a "
            "shared block pays one page copy)",
            base,
            registry=self.registry,
        )
        self._prefix_evicted_blocks = Counter(
            "seldon_llm_prefix_evicted_blocks_total",
            "Trie blocks evicted (LRU-by-leaf on pool pressure, plus "
            "in-place upgrades/clears)",
            base,
            registry=self.registry,
        )
        self._prefix_bytes_saved = Counter(
            "seldon_llm_prefix_bytes_saved",
            "KV bytes radix hits served by sharing pages in place "
            "(bytes neither recomputed by prefill nor copied)",
            base,
            registry=self.registry,
        )
        self._decode_step = Histogram(
            "seldon_llm_decode_step_seconds",
            "LLM decode step latency",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Streaming latency (runtime/batcher.py on_token path): TTFT is
        # the admission-side headline (what chunked prefill and
        # disaggregation move for the ARRIVING request), the inter-token
        # gap is the decode-side one (what they move for the VICTIMS —
        # every already-streaming request sharing the slice). Multi-token
        # drains (fused/speculative steps) surface a block in one burst,
        # so a block's trailing tokens observe ~0 gaps by construction.
        self._ttft = Histogram(
            "seldon_llm_ttft_seconds",
            "Time from request submission to its first generated token "
            "(batcher path)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._inter_token = Histogram(
            "seldon_llm_inter_token_seconds",
            "Gap before each surfaced token (batcher on_token path; "
            "fused/speculative blocks surface as bursts)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Disaggregated prefill/decode (runtime/disagg.py): per-handoff
        # wall (prefill-slice compute + device-to-device transfer +
        # decode-side import), handoffs delivered, and the staged+ready
        # backlog — the prefill-side congestion signal replica routing
        # steers by (docs/performance.md "Disaggregated serving")
        self._handoff = Histogram(
            "seldon_llm_handoff_seconds",
            "Per-admission prefill handoff wall: prefill-slice compute + "
            "D2D transfer + decode-side page import",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._handoffs_total = Counter(
            "seldon_llm_handoffs_total",
            "Prefill->decode KV handoffs delivered (disaggregated serving)",
            base,
            registry=self.registry,
        )
        self._handoff_queue_depth = Gauge(
            "seldon_llm_handoff_queue_depth",
            "Admissions staged on the prefill slice or awaiting import "
            "(sampled at scrape)",
            base,
            registry=self.registry,
        )
        self._handoff_network_bytes = Counter(
            "seldon_llm_handoff_network_bytes_total",
            "KV handoff frame bytes received over the network transport "
            "(handoff_transport='network'; 0 on the device_put fast path)",
            base,
            registry=self.registry,
        )
        # Wire framing (codec/framing.py): encode/decode walls and bytes
        # moved per egress path (rest / grpc / handoff) — the serialization
        # share of end-to-end latency the frame format exists to shrink
        # (docs/performance.md "Wire framing")
        self._frame_encode = Histogram(
            "seldon_frame_encode_seconds",
            "Frame encode wall (metadata pack + single bulk device->host "
            "transfer + buffer concat)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._frame_decode = Histogram(
            "seldon_frame_decode_seconds",
            "Frame decode wall (header/table validation + zero-copy "
            "ndarray views)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._frame_bytes = Counter(
            "seldon_frame_bytes_total",
            "Frame bytes encoded+decoded, by egress path",
            base + ["path"],
            registry=self.registry,
        )
        # Pipelined decode (runtime/batcher.py): the per-step wall above
        # splits into dispatch (enqueue the compiled step, no sync) vs sync
        # (host blocked on the oldest in-flight step's tokens); the gauge +
        # lag histogram prove the host actually trails the device (depth
        # >=2) instead of re-serializing — docs/performance.md
        self._decode_dispatch = Histogram(
            "seldon_llm_decode_dispatch_seconds",
            "Decode step dispatch wall (enqueue-only; the host does not "
            "wait for tokens)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._decode_sync = Histogram(
            "seldon_llm_decode_sync_seconds",
            "Host sync wall per drain (blocked reading the oldest "
            "in-flight step's tokens)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._decode_steps_in_flight = Gauge(
            "seldon_llm_decode_steps_in_flight",
            "Decode steps currently dispatched ahead of the host (sampled "
            "at scrape)",
            base,
            registry=self.registry,
        )
        self._decode_host_lag = Histogram(
            "seldon_llm_decode_host_lag_steps",
            "Steps the host trailed the device at each drain (>=2 means "
            "the pipeline is actually ahead)",
            base,
            buckets=HOST_LAG_BUCKETS,
            registry=self.registry,
        )
        # The batcher loop's time budget (runtime/batcher.py LoopPhases,
        # docs/observability.md "Loop phases"): every second of a loop turn
        # is put down to exactly one phase, so the per-phase rates stack to
        # 1.0 per loop. drain_wait / first_token_wait are the host blocked
        # on the device; idle is nothing to do; the rest is host work the
        # device may or may not be hidden behind.
        self._loop_seconds = Counter(
            "seldon_llm_loop_seconds_total",
            "Batcher loop wall seconds by phase (the phases partition the "
            "loop's time: their sum is the wall)",
            base + ["phase"],
            registry=self.registry,
        )
        self._loop_phase = Counter(
            "seldon_llm_loop_phase_total",
            "Occurrences of each batcher loop phase",
            base + ["phase"],
            registry=self.registry,
        )
        self._loop_turns = Counter(
            "seldon_llm_loop_turns_total",
            "Batcher loop turns",
            base,
            registry=self.registry,
        )
        # below the phases, their parts (LoopPhases.part / .handoff): seconds
        # that ALSO stand in their phase's total, so these never sum with
        # seldon_llm_loop_seconds_total. hop's four parts are the exception
        # that proves the budget: they add up to phase="hop"
        self._loop_part_seconds = Counter(
            "seldon_llm_loop_part_seconds_total",
            "Batcher loop wall seconds by part of a phase (part="
            "\"<phase>.<part>\"; inside the phase's own seconds, not beside "
            "them)",
            base + ["part"],
            registry=self.registry,
        )
        self._loop_part = Counter(
            "seldon_llm_loop_part_total",
            "Occurrences of each part of a batcher loop phase",
            base + ["part"],
            registry=self.registry,
        )
        self._loop_handoffs = Counter(
            "seldon_llm_loop_handoffs_total",
            "Hand-offs of the batcher loop to a worker thread "
            "(asyncio.to_thread out and back)",
            base,
            registry=self.registry,
        )
        # The start ledger (tracing/start.py, docs/observability.md
        # "Start-up"): where the wall from the process's creation to the first
        # /ready went, and what every build of a program cost, the start's and
        # every later one's. Build seconds are thread-seconds of JAX's own
        # legs; nested="1" lies INSIDE a nested="0" leg of the same thread.
        self._start_stage = Gauge(
            "seldon_start_stage_seconds",
            "Wall seconds of each stage of the server's start (import, "
            "construct, load.weights, load.rest, listen partition process "
            "creation -> first /ready; batcher.build follows)",
            base + ["stage"],
            registry=self.registry,
        )
        self._program_build_seconds = Counter(
            "seldon_program_build_seconds_total",
            "Seconds JAX spent building programs, by program, leg (trace, "
            "lower, compile, cache_load) and whether the leg lay inside "
            "another on its thread",
            base + ["program", "leg", "nested"],
            registry=self.registry,
        )
        self._program_builds = Counter(
            "seldon_program_builds_total",
            "Executables made or loaded, by program and the persistent "
            "compile cache's verdict (hit, miss, off)",
            base + ["program", "cache"],
            registry=self.registry,
        )
        self._first_token_reads = Counter(
            "seldon_llm_first_token_reads_total",
            "Reads of a prompt's first token off the drain pipeline, by "
            "whether it was already computed when its record was drained "
            "(ready=no: the read waited for the device)",
            base + ["ready"],
            registry=self.registry,
        )
        # kind: "full" every layer that attends its whole sequence; "window" the
        # sliding-attention layers of a model that has them (a row a layer of
        # each kind: the two differ in what a query may see); "shared" the
        # cross-attention layers of a model that has them (a row a layer: each
        # reads the pool of ANOTHER layer, cfg.kv_source, in place). form: "expanded"
        # a call whose latent read makes the context's K and V once a visit (a
        # wide chunk's, ops/latent_attention.py), "absorbed" every other
        self._attn_context = {
            key: Counter(f"seldon_llm_attn_{key}_total", text,
                         base + ["program", "kind", "form"], registry=self.registry)
            for key, text in (
                ("calls", "Step-program calls whose attention read the cache"),
                ("context_tokens",
                 "Cached rows those calls' attention had to read: the live "
                 "context of each row of the call, the row it wrote included "
                 "(not the block-table view's length)"),
                ("rows_read",
                 "Cached rows those calls' attention read visited: the whole "
                 "block-table view, or whole visits of latent attention's "
                 "live-page kernel; over context_tokens it is the over-read"),
                ("context_tokens_unwindowed",
                 "kind=window alone: the cached rows a FULL layer would have had to "
                 "read for the same queries (context_tokens counts the rows inside "
                 "the window)"))}
        # how the prefill chunks' K / V (latent) rows reached the paged pool
        # (models/cache.py paged_write_by_page), counted on the loop
        self._kv_writes = {
            key: Counter(f"seldon_llm_kv_{key}_total", text,
                         base + ["path"], registry=self.registry)
            for key, text in (
                ("chunk_writes",
                 "Prefill chunks by how a paged layer's write reached the "
                 "pool: path=page whole pages, path=token one scatter row a token"),
                ("pages_written",
                 "Pool pages a paged layer's write of those chunks wrote: "
                 "the whole pages read and written back (page), the pages "
                 "the live rows lie in (token)"))}
        self._chunk_head = Counter(
            "seldon_llm_chunk_head_total",
            "Prefill chunks by whether the chunk program ran the head (ran=1 a "
            "prompt's last chunk, for its one last row; ran=0 every chunk "
            "before: no byte of the head read, no logits written) and by the "
            "width of the chunk program that ran: ran=1 at the wide chunk's "
            "width over ran=1 is the share of prompts whose tail went in one "
            "padded wide call",
            base + ["ran", "width"], registry=self.registry)
        self._chunk_rows = Counter(
            "seldon_llm_chunk_rows_total",
            "Prompt rows (tokens) prefilled by chunks, by the width of the chunk "
            "program that took them: the batcher's prefill_chunk, or its wide "
            "chunk's rows while the narrow program would have computed at least "
            "those for what was left of a prompt and no other live slot streamed",
            base + ["width"], registry=self.registry)
        # A model with conv layers (models/state_mixers.py ShortConv): what
        # went through them, counted on the loop from host integers; absent
        # for every other model
        # ... likewise "gdn" for linear-attention layers (GatedDeltaNet),
        # "ssd" for mamba layers (Mamba2Mixer) and "s6" for s6 layers (Mamba1Mixer)
        self._state_layers = {
            f"{kind}_{key}": Counter(f"seldon_llm_{kind}_{key}_total", text.format(what=what),
                                     base + ["program"], registry=self.registry)
            for kind, what in (("conv", "conv"), ("gdn", "linear-attention (Gated DeltaNet)"),
                               ("ssd", "mamba (Mamba-2 state-space)"),
                               ("s6", "s6 (Mamba-1 selective scan)"))
            for key, text in (
                ("rows", "Live rows (tokens) of the step-program calls of a "
                         "model with {what} layers: what EACH such layer mixed"),
                ("layer_calls", "{what} layers x step-program calls"))}
        # A model whose layers past cfg.kv_source cache nothing (SambaY's
        # cross-decoder): the rows that ran the layers up to it and the rows that
        # ran the rest: a prompt's chunk runs the rest on ONE row, or on none
        # (two names: a ratio's two counters carry one set of labels)
        self._decoder_rows = {
            half: Counter(
                f"seldon_llm_{half}_decoder_rows_total",
                f"Live rows (tokens) of the step-program calls that ran the {what}",
                base + ["program"], registry=self.registry)
            for half, what in (
                ("self", "layers up to cfg.kv_source (the self-decoder and the layer whose "
                         "K/V the cross-attention layers read)"),
                ("cross", "layers past cfg.kv_source (the cross-decoder): every row of a "
                          "decode step, the ONE row read of a prompt's last chunk, none of "
                          "any chunk before it"))}
        # how a decode step program's delta rule runs: the repo's kernel (S
        # read once and written once) or the expression's two passes over S: a
        # silent fall-back shows here; absent without linear-attention layers
        self._step_path = {
            "gdn": Counter(
                "seldon_llm_gdn_step_path",
                "Decode step programs built over linear-attention layers, by how "
                "the delta rule's read-modify-write of the matrix state runs: "
                "path=kernel (ops/gated_delta.py) or path=expression",
                base + ["path"], registry=self.registry),
            # ... and over mamba layers: the expression reads h a second time for h C
            "ssd": Counter(
                "seldon_llm_ssd_step_path",
                "Decode step programs built over mamba layers, by how the "
                "state-space recurrence's read-modify-write of h runs: "
                "path=kernel (ops/ssd.py) or path=expression",
                base + ["path"], registry=self.registry)}
        # An MoE model's routing (runtime/batcher.py MoECounters,
        # docs/observability.md "Expert routing"): counted on the loop from
        # arrays that leave the step programs beside their tokens, absent
        # for a dense model. By program kind (decode step, prefill chunk)
        # what the device did; by expert what was delivered.
        moe_help = {
            "calls": "Step-program calls of an MoE model",
            "live_rows": "Rows of those calls that were tokens (not a dead "
                         "slot, not a chunk's padding)",
            "routed_pairs": "(token, expert) pairs routed to an expert HELD "
                            "here (all of them, without a share), over all layers",
            "pairs_elsewhere": "(token, expert) pairs whose expert lies on another "
                               "chip of the expert-parallel deployment (a model that "
                               "holds a share of its experts), over all layers: they "
                               "join no group here and add nothing",
            "experts_touched": "Distinct experts with at least one row, "
                               "summed over layer-calls",
            "max_group": "Rows of the largest expert group, summed over "
                         "layer-calls",
            "tile_rows": "Rows the grouped-matmul kernel multiplied (visits x "
                         "row tile; a 128-row tile's visits count their "
                         "sub-block), over all layers: routed_pairs over this "
                         "is the fill; 0 where ragged_dot serves",
            "layer_calls": "MoE layer executions (calls x layers)",
        }
        self._moe = {
            field: Counter(f"seldon_llm_moe_{field}_total", text,
                           base + ["program"], registry=self.registry)
            for field, text in moe_help.items()}
        self._moe_expert_tokens = Counter(
            "seldon_llm_moe_expert_tokens_total",
            "Delivered tokens routed to each expert, summed over layers",
            base + ["expert"],
            registry=self.registry,
        )
        self._queue_wait = Histogram(
            "seldon_llm_queue_wait_seconds",
            "Time from request submission to its slot reservation "
            "(batcher path)",
            base,
            buckets=QUEUE_WAIT_BUCKETS,
            registry=self.registry,
        )
        self._slots_active = Gauge(
            "seldon_llm_slots_active",
            "Continuous-batching slots holding an active request "
            "(sampled at scrape)",
            base,
            registry=self.registry,
        )
        self._slot_seconds = Counter(
            "seldon_llm_slot_seconds_total",
            "Active slots x seconds, accumulated once per loop turn: its "
            "rate is the mean number of active slots",
            base,
            registry=self.registry,
        )
        # the transport's own cost per streamed token (transport/rest.py
        # SSE writer): stamped when the batcher surfaces the token,
        # observed when the socket write returns
        self._emit_delay = Histogram(
            "seldon_llm_emit_delay_seconds",
            "Time from the batcher surfacing a token to its SSE event "
            "being written to the socket",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._emit_delay_bound: Any = None
        # ... and the transport thread's busy time (transport/rest.py
        # http_busy): it shares the GIL with the batcher's loop and workers,
        # so its synchronous stretches are time they may wait for
        self._http_busy_seconds = Counter(
            "seldon_http_busy_seconds_total",
            "Wall seconds the transport thread spent in its synchronous "
            "stretches, by what (parse, sse_write, reply, scrape)",
            base + ["what"],
            registry=self.registry,
        )
        self._http_busy = Counter(
            "seldon_http_busy_total",
            "Occurrences of those stretches (one per request, SSE event, "
            "reply, scrape)",
            base + ["what"],
            registry=self.registry,
        )
        self._http_busy_bound: Dict[str, tuple] = {}
        # Speculative decoding (runtime/batcher.py + runtime/spec.py): the
        # accept rate and tokens-per-forward pair is the whole story —
        # tokens/forward > 1 is the >1-accepted-token-per-KV-read
        # multiplier speculation exists to buy, and the accept rate is why
        # it moves (benchmarks/DECODE_NOTES.md "PR 8"). The per-slot gauge
        # mirrors the draft-length controller's steering EMA; the overhead
        # fraction is the verify-forward compute share wasted on drafts
        # that lost verification (what speculation COSTS when text is
        # un-draftable).
        self._spec_accept_rate = Gauge(
            "seldon_llm_spec_accept_rate",
            "Aggregate draft-token acceptance rate (accepted drafts / "
            "offered drafts, 0-1)",
            base,
            registry=self.registry,
        )
        self._spec_accept_rate_slot = Gauge(
            "seldon_llm_spec_accept_rate_per_slot",
            "Per-slot draft acceptance-rate EMA (the draft-length "
            "controller's steering signal)",
            base + ["slot"],
            registry=self.registry,
        )
        self._spec_tokens_per_forward = Gauge(
            "seldon_llm_spec_tokens_per_forward",
            "Accepted tokens per verify forward (>1 = more than one token "
            "per KV-cache read)",
            base,
            registry=self.registry,
        )
        self._spec_accepted_per_step = Histogram(
            "seldon_llm_spec_accepted_tokens_per_step",
            "Tokens emitted by each drained verify step (1..K+1)",
            base,
            buckets=(1, 2, 3, 4, 5, 6, 8, 12, 16),
            registry=self.registry,
        )
        self._spec_draft_overhead = Gauge(
            "seldon_llm_spec_draft_overhead_fraction",
            "Fraction of verify-forward token columns wasted on drafts "
            "that lost verification (0-1)",
            base,
            registry=self.registry,
        )
        self._spec_slot_steps = Counter(
            "seldon_llm_spec_slot_verify_steps_total",
            "Per-slot verify steps drained: each verify forward "
            "contributes one per active slot (divide by the active-slot "
            "count for the forward/program count)",
            base,
            registry=self.registry,
        )
        # Multi-tenant serving (runtime/adapters.py + runtime/scheduler.py;
        # docs/multitenancy.md): the adapter pool's occupancy/churn/bytes,
        # per-(tenant, SLO-class) admission/shed/token tallies — the quota
        # and fairness audit trail — and per-class TTFT so the interactive
        # SLO is observable separately from the batch class it shares the
        # slots with.
        self._adapter_loaded = Gauge(
            "seldon_llm_adapter_loaded",
            "LoRA adapters currently resident in the dense pool "
            "(identity row excluded)",
            base,
            registry=self.registry,
        )
        self._adapter_evictions = Counter(
            "seldon_llm_adapter_evictions_total",
            "Adapters evicted from the pool (refcount-zero rows freed "
            "for reuse)",
            base,
            registry=self.registry,
        )
        self._adapter_pool_bytes = Gauge(
            "seldon_llm_adapter_pool_bytes",
            "HBM bytes held by the dense LoRA adapter pool (all rows, "
            "loaded or free)",
            base,
            registry=self.registry,
        )
        self._tenant_admitted = Counter(
            "seldon_tenant_admitted_total",
            "Requests admitted into the continuous batch, by tenant and "
            "SLO class",
            base + ["tenant", "slo_class"],
            registry=self.registry,
        )
        self._tenant_shed = Counter(
            "seldon_tenant_shed_total",
            "Requests shed (quota breach at push, page-exhaustion victim, "
            "staged-job shed), by tenant and SLO class",
            base + ["tenant", "slo_class"],
            registry=self.registry,
        )
        self._tenant_tokens = Counter(
            "seldon_tenant_tokens_total",
            "Tokens generated and credited, by tenant and SLO class",
            base + ["tenant", "slo_class"],
            registry=self.registry,
        )
        self._tenant_ttft = Histogram(
            "seldon_llm_tenant_ttft_seconds",
            "Time to first token by SLO class (the interactive-isolation "
            "signal bench phase L gates on)",
            base + ["slo_class"],
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        # Fleet fault tolerance (runtime/engine.py ReplicaSet,
        # docs/resilience.md): unplanned-death ejections and the
        # deterministic-recovery machinery. Counters ride the llm_stats ->
        # sync_llm catch-up idiom like every other fleet tally; the
        # journal-depth gauge is the live count of fleet generations whose
        # recovery record is still open (in flight, not yet resolved).
        self._fleet_ejections = Counter(
            "seldon_fleet_ejections_total",
            "Replicas ejected from fleet dispatch after an unplanned "
            "death (crashed or wedged batcher loop, consecutive dispatch "
            "failures)",
            base,
            registry=self.registry,
        )
        self._fleet_reinstatements = Counter(
            "seldon_fleet_reinstatements_total",
            "Ejected replicas reinstated into fleet dispatch after a "
            "successful half-open probe",
            base,
            registry=self.registry,
        )
        self._fleet_resumes = Counter(
            "seldon_fleet_resumes_total",
            "In-flight generations resumed bit-exactly on a surviving "
            "replica after their replica died mid-stream",
            base,
            registry=self.registry,
        )
        self._fleet_resumed_tokens = Counter(
            "seldon_fleet_resumed_tokens_total",
            "Tokens already delivered at resume time (skipped, never "
            "re-sent: the at-most-once streaming contract)",
            base,
            registry=self.registry,
        )
        self._fleet_budget_exhausted = Counter(
            "seldon_fleet_retry_budget_exhausted_total",
            "Recoveries refused because the fleet retry budget was "
            "exhausted (degraded to 503 + Retry-After instead of "
            "amplifying load)",
            base,
            registry=self.registry,
        )
        self._fleet_journal_depth = Gauge(
            "seldon_fleet_resume_journal_depth",
            "Fleet resume-journal entries currently open (fleet "
            "generations in flight with recovery records)",
            base,
            registry=self.registry,
        )
        # Tracing/flight-recorder observability (tracing/__init__.py +
        # runtime/flight.py): spans lost to export failures (a batch is
        # re-enqueued once; the second failure drops it — without this
        # counter a dead collector silently eats every trace), per-flush
        # OTLP export latency, and request traces retained by sampling
        # mode ('head' = the inbound traceparent flag said keep, 'tail' =
        # retained past an unsampled flag because TTFT / worst inter-token
        # gap crossed the tail thresholds) — docs/observability.md
        self._trace_spans_dropped = Counter(
            "seldon_trace_spans_dropped_total",
            "Trace spans dropped after a failed OTLP export's single "
            "bounded re-enqueue",
            base,
            registry=self.registry,
        )
        self._trace_export = Histogram(
            "seldon_trace_export_seconds",
            "OTLP trace export latency per flush (success or failure)",
            base,
            buckets=LATENCY_BUCKETS,
            registry=self.registry,
        )
        self._traces_retained = Counter(
            "seldon_llm_traces_retained_total",
            "Request traces materialized and exported, by sampling mode "
            "(head = inbound sampled flag; tail = latency-threshold "
            "retention of an unsampled request)",
            base + ["mode"],
            registry=self.registry,
        )
        # Elastic control plane (controlplane/autoscaler.py +
        # analytics/canary.py; docs/control-plane.md): fleet shape the
        # autoscaler drives (replicas serving vs draining, scale/rebalance
        # events), the canary rollout state machine, and the shadow
        # divergence record — the loop's own observability, synced at
        # scrape time by sync_controlplane (same catch-up idiom as the
        # resilience counters).
        self._autoscaler_replicas = Gauge(
            "seldon_autoscaler_replicas",
            "Replicas currently attached to the autoscaled ReplicaSet "
            "(draining included until detach)",
            base,
            registry=self.registry,
        )
        self._autoscaler_draining = Gauge(
            "seldon_autoscaler_draining_replicas",
            "Replicas draining toward detach (no fleet traffic; in-flight "
            "work completing)",
            base,
            registry=self.registry,
        )
        self._autoscaler_events = Counter(
            "seldon_autoscaler_scale_events_total",
            "Autoscaler actions applied, by kind (scale_up / scale_down / "
            "rebalance / collect)",
            base + ["action"],
            registry=self.registry,
        )
        self._canary_phase = Gauge(
            "seldon_canary_phase",
            "Canary rollout phase per router node (0 canary, 1 promoted, "
            "2 rolled back)",
            base + ["node"],
            registry=self.registry,
        )
        self._canary_rollbacks = Counter(
            "seldon_canary_rollbacks_total",
            "Automatic or manual canary rollbacks",
            base + ["node"],
            registry=self.registry,
        )
        self._canary_error_rate = Gauge(
            "seldon_canary_error_rate",
            "Windowed error rate per canary branch (baseline / candidate)",
            base + ["node", "branch"],
            registry=self.registry,
        )
        self._shadow_mirrors = Counter(
            "seldon_shadow_mirrors_total",
            "Requests mirrored to a shadow candidate (responses discarded)",
            base + ["node"],
            registry=self.registry,
        )
        self._shadow_divergences = Counter(
            "seldon_shadow_divergences_total",
            "Mirrored requests whose shadow output diverged from the "
            "primary's",
            base + ["node"],
            registry=self.registry,
        )
        self._shadow_errors = Counter(
            "seldon_shadow_errors_total",
            "Shadow-side failures (swallowed — the client never sees them)",
            base + ["node"],
            registry=self.registry,
        )
        self._shadow_max_diff = Gauge(
            "seldon_shadow_max_abs_diff",
            "Largest absolute output divergence observed on the shadow "
            "path",
            base + ["node"],
            registry=self.registry,
        )
        # breakers publish transitions through on_transition; remember which
        # are wired so scrape-time syncs are idempotent
        self._bound_breakers: set = set()

    # ------------------------------------------------------------------
    def _base(self) -> Dict[str, str]:
        return {"deployment_name": self.deployment, "predictor_name": self.predictor}

    def observe_api_call(self, method: str, code: str, seconds: float) -> None:
        self._api.labels(**self._base(), method=method, code=code).inc()
        self._latency.labels(**self._base(), method=method).observe(seconds)

    def observe_prediction(self, engine: Any, response: SeldonMessage, seconds: float) -> None:
        self.observe_api_call("predictions", "200", seconds)
        self.register_custom(response)

    def observe_feedback(self, feedback: Feedback) -> None:
        self._feedback.labels(**self._base()).inc()
        if feedback.reward:
            self._feedback_reward.labels(**self._base()).inc(abs(feedback.reward))

    # ------------------------------------------------------------------
    # Resilience observability (runtime/resilience.py)
    # ------------------------------------------------------------------
    def observe_deadline_exceeded(self, transport: str) -> None:
        self._deadline_exceeded.labels(**self._base(), transport=transport).inc()

    def observe_remaining_budget(self, seconds: float) -> None:
        self._remaining_budget.labels(**self._base()).observe(max(seconds, 0.0))

    def sync_resilience(
        self,
        engine: Any = None,
        admission: Any = None,
        transport: str = "rest",
    ) -> None:
        """Refresh breaker/admission gauges at scrape time; wires each
        breaker's transition callback to the transitions counter on first
        sight (idempotent — scraped every /metrics hit)."""
        if engine is not None and hasattr(engine, "breakers"):
            for node, breaker in engine.breakers():
                if id(breaker) not in self._bound_breakers:
                    self._bound_breakers.add(id(breaker))
                    counter = self._breaker_transitions

                    def on_transition(name, to, _c=counter):
                        _c.labels(**self._base(), node=name, to=to).inc()

                    breaker.on_transition = on_transition
                self._breaker_state.labels(**self._base(), node=node).set(breaker.state_code())
                rejected = self._breaker_rejected.labels(**self._base(), node=node)
                # counter catch-up from the breaker's own tally (breaker
                # rejections happen on the engine hot path, counted locally
                # to avoid a labels() lookup per call)
                delta = breaker.rejected_total - rejected._value.get()
                if delta > 0:
                    rejected.inc(delta)
        if admission is not None:
            self._inflight.labels(**self._base(), transport=transport).set(admission.inflight)
            self._queue_depth.labels(**self._base(), transport=transport).set(
                admission.queue_depth()
            )
            shed = self._shed.labels(**self._base(), transport=transport)
            delta = admission.shed_total - shed._value.get()
            if delta > 0:
                shed.inc(delta)

    # ------------------------------------------------------------------
    # Tracing observability (tracing/__init__.py Tracer.export_stats)
    # ------------------------------------------------------------------
    def sync_tracing(self, tracer: Any = None) -> None:
        """Refresh the trace export/retention series from the (global)
        tracer at /metrics scrape time — same drain/catch-up idiom as
        sync_llm: latencies are drained (observed exactly once), counters
        catch up from the tracer's own lifetime tallies."""
        if tracer is None:
            from seldon_core_tpu.tracing import get_tracer

            tracer = get_tracer()
        stats = tracer.export_stats()
        hist = self._trace_export.labels(**self._base())
        for seconds in stats.get("export_times_s", ()):
            hist.observe(seconds)
        dropped = self._trace_spans_dropped.labels(**self._base())
        delta = stats.get("spans_dropped_total", 0) - dropped._value.get()
        if delta > 0:
            dropped.inc(delta)
        for mode, total in (stats.get("retained_total") or {}).items():
            retained = self._traces_retained.labels(**self._base(), mode=mode)
            delta = total - retained._value.get()
            if delta > 0:
                retained.inc(delta)

    def sync_start(self) -> None:
        """Refresh the start ledger's series (tracing/start.py) at scrape
        time: the stage gauges as they stand, the build counters caught up
        from the ledger's lifetime tallies (listeners book on whatever
        thread builds)."""
        from seldon_core_tpu.tracing.start import get_ledger

        stages, seconds, builds = get_ledger().series()
        for stage, s in stages.items():
            self._start_stage.labels(**self._base(), stage=stage).set(s)
        for (program, leg, nested), s in seconds.items():
            self._counter_catch_up(self._program_build_seconds, s,
                                   program=program, leg=leg, nested=nested)
        for (program, cache), n in builds.items():
            self._counter_catch_up(self._program_builds, n, program=program, cache=cache)

    # ------------------------------------------------------------------
    # Elastic control plane observability (controlplane/autoscaler.py +
    # analytics/canary.py)
    # ------------------------------------------------------------------
    def _counter_catch_up(self, counter, value: float, **labels) -> None:
        """Counter catch-up from a component's lifetime tally (the
        sync_resilience idiom: events are counted locally on the hot/loop
        path; the scrape raises the Prometheus counter to match)."""
        bound = counter.labels(**self._base(), **labels)
        delta = value - bound._value.get()
        if delta > 0:
            bound.inc(delta)

    def _histogram_catch_up(self, histogram, snapshot: Optional[dict]) -> None:
        """Histogram catch-up from a loop-side accumulator's lifetime
        tallies (metrics/local.py ``HistogramAccumulator.snapshot``): the
        counter catch-up idiom, bucket by bucket and for the sum, so an
        observation is exported exactly once however long the scrape
        interval. The accumulator's buckets must be the histogram's."""
        if not snapshot:
            return
        bound = histogram.labels(**self._base())
        for i, n in snapshot["buckets"].items():
            delta = n - bound._buckets[i].get()
            if delta > 0:
                bound._buckets[i].inc(delta)
        delta = snapshot["sum"] - bound._sum.get()
        if delta > 0:
            bound._sum.inc(delta)

    def observe_emit_delay(self, seconds: float) -> None:
        """Once per streamed token, on the transport's loop: the labelled
        child is bound once, not looked up per token."""
        if self._emit_delay_bound is None:
            self._emit_delay_bound = self._emit_delay.labels(**self._base())
        self._emit_delay_bound.observe(seconds)

    def observe_http_busy(self, what: str, seconds: float) -> None:
        """Once per synchronous stretch of the transport thread; the labelled
        children are bound once per ``what``, as the emit delay's is."""
        bound = self._http_busy_bound.get(what)
        if bound is None:
            bound = self._http_busy_bound[what] = (
                self._http_busy_seconds.labels(**self._base(), what=what),
                self._http_busy.labels(**self._base(), what=what))
        bound[0].inc(seconds)
        bound[1].inc()

    def sync_controlplane(self, source: Any = None) -> None:
        """Refresh autoscaler / canary / shadow series at scrape time.
        ``source`` is an engine (its graph nodes are walked for canary and
        shadow components, ``engine.autoscaler`` for the loop), a bare
        component, or an Autoscaler; anything without the stats surfaces
        is a no-op — the handler never needs to know what is deployed."""
        if source is None:
            return
        named = []  # (node label, object)
        autoscalers = []
        state = getattr(source, "state", None)
        if state is not None and hasattr(state, "walk"):
            for unit in state.walk():
                if unit.component is not None:
                    named.append((unit.name, unit.component))
        else:
            named.append((getattr(source, "name", "") or "", source))
        for obj in (source, getattr(source, "autoscaler", None)):
            if obj is not None and hasattr(obj, "autoscaler_stats"):
                autoscalers.append(obj)
        for a in autoscalers:
            stats = a.autoscaler_stats()
            self._autoscaler_replicas.labels(**self._base()).set(
                stats.get("autoscaler_replicas", 0))
            self._autoscaler_draining.labels(**self._base()).set(
                stats.get("autoscaler_draining", 0))
            for action, key in (
                ("scale_up", "autoscaler_scale_ups_total"),
                ("scale_down", "autoscaler_scale_downs_total"),
                ("rebalance", "autoscaler_rebalances_total"),
                ("collect", "autoscaler_collected_total"),
            ):
                self._counter_catch_up(self._autoscaler_events,
                                       stats.get(key, 0), action=action)
        for node, comp in named:
            canary_fn = getattr(comp, "canary_stats", None)
            if canary_fn is not None:
                stats = canary_fn()
                self._canary_phase.labels(**self._base(), node=node).set(
                    stats.get("canary_phase_code", 0))
                self._counter_catch_up(
                    self._canary_rollbacks,
                    stats.get("canary_rollbacks_total", 0), node=node)
                for branch, key in (
                    ("baseline", "canary_baseline_error_rate"),
                    ("candidate", "canary_candidate_error_rate"),
                ):
                    self._canary_error_rate.labels(
                        **self._base(), node=node, branch=branch).set(
                        stats.get(key, 0.0))
            shadow_fn = getattr(comp, "shadow_stats", None)
            if shadow_fn is not None:
                stats = shadow_fn()
                self._counter_catch_up(
                    self._shadow_mirrors,
                    stats.get("shadow_mirrors_total", 0), node=node)
                self._counter_catch_up(
                    self._shadow_divergences,
                    stats.get("shadow_divergences_total", 0), node=node)
                self._counter_catch_up(
                    self._shadow_errors,
                    stats.get("shadow_errors_total", 0), node=node)
                self._shadow_max_diff.labels(**self._base(), node=node).set(
                    stats.get("shadow_max_abs_diff", 0.0))

    # ------------------------------------------------------------------
    # LLM decode observability (servers/llmserver.py)
    # ------------------------------------------------------------------
    def sync_llm(self, component: Any) -> None:
        """Refresh the KV-cache gauges from the component's ``llm_stats()``
        snapshot and drain its pending decode step-time observations into
        the histogram. Called at /metrics scrape time (like
        sync_resilience); components without the surface are a no-op."""
        stats_fn = getattr(component, "llm_stats", None)
        if stats_fn is None:
            return
        stats = stats_fn()
        self._kv_cache_bytes.labels(**self._base()).set(stats.get("kv_cache_bytes", 0))
        self._kv_occupancy.labels(**self._base()).set(stats.get("kv_occupancy", 0.0))
        self._kv_bytes_per_step.labels(**self._base()).set(
            stats.get("kv_bytes_per_step", 0)
        )
        by_class = stats.get("kv_pages_by_class") or {"full": {
            "in_use": stats.get("kv_pages_in_use", 0), "total": stats.get("kv_pages_total", 0)}}
        for page_class, pages in by_class.items():
            self._kv_pages_in_use.labels(**self._base(), **{"class": page_class}).set(
                pages["in_use"])
            self._kv_pages_total.labels(**self._base(), **{"class": page_class}).set(
                pages["total"])
        for reason, n in stats.get("kv_pages_released", {}).items():
            self._counter_catch_up(self._kv_pages_released, n, reason=reason)
        self._kv_page_fragmentation.labels(**self._base()).set(
            stats.get("kv_page_fragmentation", 0.0)
        )
        for program, columns in stats.get("sampler_topk_columns", {}).items():
            self._sampler_topk_columns.labels(**self._base(), program=program).set(columns)
        self._state_bytes.labels(**self._base()).set(stats.get("state_bytes", 0))
        for key, gauge in self._state_matrix_bytes.items():
            gauge.labels(**self._base()).set(stats.get(key, 0))
        # counter catch-up from the allocator's own tally (sheds happen on
        # the decode hot path, counted locally — same idiom as
        # seldon_resilience_shed_total)
        page_sheds = self._kv_page_sheds.labels(**self._base())
        delta = stats.get("kv_page_sheds", 0) - page_sheds._value.get()
        if delta > 0:
            page_sheds.inc(delta)
        # radix prefix cache: gauges refresh from the snapshot, counters
        # catch up from the trie's lifetime tallies (hits/copies/evictions
        # happen on the admission path, counted locally — same idiom as
        # the page-shed counter above)
        self._prefix_shared_pages.labels(**self._base()).set(
            stats.get("prefix_shared_pages", 0)
        )
        self._prefix_cached_blocks.labels(**self._base()).set(
            stats.get("prefix_cached_blocks", 0)
        )
        for counter, key in (
            (self._prefix_hit_blocks, "prefix_hit_blocks"),
            (self._prefix_cow_copies, "prefix_cow_copies"),
            (self._prefix_evicted_blocks, "prefix_evicted_blocks"),
            (self._prefix_bytes_saved, "prefix_bytes_saved"),
        ):
            bound = counter.labels(**self._base())
            delta = stats.get(key, 0) - bound._value.get()
            if delta > 0:
                bound.inc(delta)
        # loop-side accumulators (lifetime bucket tallies), caught up by
        # difference: nothing is lost between scrapes. The raw lists
        # llm_stats still returns are a bounded window of recent samples
        # for tests and debugging, not what the histograms are fed from.
        hists = stats.get("histograms", {})
        for histogram, key in (
            (self._decode_step, "decode_step_s"),
            (self._ttft, "ttft_s"),
            (self._inter_token, "inter_token_s"),
            (self._decode_host_lag, "decode_host_lag_steps"),
            (self._queue_wait, "queue_wait_s"),
        ):
            self._histogram_catch_up(histogram, hists.get(key))
        # the loop's time budget: per-phase seconds and occurrences, turns,
        # and the slot-occupancy integral (counted on the loop, caught up
        # here — same idiom as the page-shed counter above)
        for phase, seconds in stats.get("loop_seconds", {}).items():
            self._counter_catch_up(self._loop_seconds, seconds, phase=phase)
        for phase, n in stats.get("loop_phase_counts", {}).items():
            self._counter_catch_up(self._loop_phase, n, phase=phase)
        self._counter_catch_up(self._loop_turns, stats.get("loop_turns", 0))
        for part, seconds in stats.get("loop_part_seconds", {}).items():
            self._counter_catch_up(self._loop_part_seconds, seconds, part=part)
        for part, n in stats.get("loop_part_counts", {}).items():
            self._counter_catch_up(self._loop_part, n, part=part)
        self._counter_catch_up(self._loop_handoffs,
                               stats.get("loop_handoffs", 0))
        for ready, n in stats.get("first_token_reads", {}).items():
            self._counter_catch_up(self._first_token_reads, n, ready=ready)
        for key, counter in self._attn_context.items():
            for kind, prefix in (("full", "attn_"), ("window", "attn_window_"),
                                 ("shared", "attn_shared_")):
                expanded = stats.get("attn_expanded_" + key, {}) if kind == "full" else {}
                for program, n in stats.get(prefix + key, {}).items():
                    there = expanded.get(program, 0)
                    self._counter_catch_up(counter, n - there, program=program, kind=kind,
                                           form="absorbed")
                    if there:   # (no series where no call ever took the form)
                        self._counter_catch_up(counter, there, program=program, kind=kind,
                                               form="expanded")
        for key, counter in self._kv_writes.items():
            for path, n in stats.get(f"kv_{key}", {}).items():
                self._counter_catch_up(counter, n, path=path)
        for ran, widths in stats.get("chunk_head", {}).items():
            for width, n in widths.items():
                self._counter_catch_up(self._chunk_head, n, ran=ran, width=width)
        for width, n in stats.get("chunk_rows", {}).items():
            self._counter_catch_up(self._chunk_rows, n, width=width)
        for key, counter in self._state_layers.items():
            for program, n in stats.get(key, {}).items():
                self._counter_catch_up(counter, n, program=program)
        for half, counter in self._decoder_rows.items():
            for program, n in stats.get(f"{half}_decoder_rows", {}).items():
                self._counter_catch_up(counter, n, program=program)
        for kind, counter in self._step_path.items():
            for path, n in stats.get(f"{kind}_step_path", {}).items():
                self._counter_catch_up(counter, n, path=path)
        for program, tally in stats.get("moe_by_program", {}).items():
            for field, n in tally.items():
                self._counter_catch_up(self._moe[field], n, program=program)
            self._counter_catch_up(
                self._moe["layer_calls"], tally["calls"] * stats["moe_layers"],
                program=program)
        for expert, n in enumerate(stats.get("moe_expert_tokens", ()),
                                   stats.get("moe_expert_first", 0)):
            self._counter_catch_up(self._moe_expert_tokens, n,
                                   expert=str(expert))
        self._counter_catch_up(self._slot_seconds,
                               stats.get("slot_seconds", 0.0))
        self._slots_active.labels(**self._base()).set(
            stats.get("slots_active", 0))
        handoff = self._handoff.labels(**self._base())
        for seconds in stats.get("handoff_times_s", ()):
            handoff.observe(seconds)
        # counter catch-up from the transfer queue's own tally (handoffs
        # land on the batcher loop, counted locally — same idiom as the
        # page-shed counter above)
        handoffs = self._handoffs_total.labels(**self._base())
        delta = stats.get("handoffs_total", 0) - handoffs._value.get()
        if delta > 0:
            handoffs.inc(delta)
        self._handoff_queue_depth.labels(**self._base()).set(
            stats.get("handoff_queue_depth", 0)
        )
        # wire bytes received by the network KV transport (the receiver's
        # lifetime tally — same catch-up idiom as handoffs_total)
        self._counter_catch_up(self._handoff_network_bytes,
                               stats.get("handoff_network_bytes_total", 0))
        disp = self._decode_dispatch.labels(**self._base())
        for seconds in stats.get("decode_dispatch_times_s", ()):
            disp.observe(seconds)
        sync = self._decode_sync.labels(**self._base())
        for seconds in stats.get("decode_sync_times_s", ()):
            sync.observe(seconds)
        self._decode_steps_in_flight.labels(**self._base()).set(
            stats.get("decode_steps_in_flight", 0)
        )
        # speculative decoding: gauges refresh from the controller's
        # lifetime aggregates; the accepted-tokens histogram drains the
        # per-step observations accumulated since the last scrape, and the
        # slot-step counter catches up from the controller tally (same
        # idiom as the page-shed counter above)
        self._spec_accept_rate.labels(**self._base()).set(
            stats.get("spec_accept_rate", 0.0)
        )
        self._spec_tokens_per_forward.labels(**self._base()).set(
            stats.get("spec_tokens_per_forward", 0.0)
        )
        self._spec_draft_overhead.labels(**self._base()).set(
            stats.get("spec_draft_overhead_fraction", 0.0)
        )
        for slot, rate in enumerate(stats.get("spec_accept_rate_per_slot", ())):
            self._spec_accept_rate_slot.labels(
                **self._base(), slot=str(slot)).set(rate)
        acc_hist = self._spec_accepted_per_step.labels(**self._base())
        for tokens in stats.get("spec_accepted_per_step", ()):
            acc_hist.observe(tokens)
        steps = self._spec_slot_steps.labels(**self._base())
        delta = stats.get("spec_slot_steps_total", 0) - steps._value.get()
        if delta > 0:
            steps.inc(delta)
        # multi-tenant serving: adapter-pool gauges refresh from the
        # registry snapshot; per-(tenant, class) counters catch up from
        # the scheduler's lifetime tallies (admissions/sheds/tokens are
        # counted on the batcher loop — same idiom as the page-shed
        # counter), and per-class TTFT observations drain into the
        # labelled histogram
        self._adapter_loaded.labels(**self._base()).set(
            stats.get("adapter_loaded", 0))
        self._adapter_pool_bytes.labels(**self._base()).set(
            stats.get("adapter_pool_bytes", 0))
        self._counter_catch_up(self._adapter_evictions,
                               stats.get("adapter_evictions_total", 0))
        for row in stats.get("tenant_counters", ()):
            labels = {"tenant": row.get("tenant", ""),
                      "slo_class": row.get("slo_class", "")}
            self._counter_catch_up(self._tenant_admitted,
                                   row.get("admitted", 0), **labels)
            self._counter_catch_up(self._tenant_shed,
                                   row.get("shed", 0), **labels)
            self._counter_catch_up(self._tenant_tokens,
                                   row.get("tokens", 0), **labels)
        for cls, seconds in stats.get("ttft_by_class", ()):
            self._tenant_ttft.labels(
                **self._base(), slo_class=cls).observe(seconds)
        # fleet fault tolerance (ReplicaSet.llm_stats — solo components
        # carry none of these keys, so every line is a no-op for them)
        self._counter_catch_up(self._fleet_ejections,
                               stats.get("fleet_ejections_total", 0))
        self._counter_catch_up(self._fleet_reinstatements,
                               stats.get("fleet_reinstatements_total", 0))
        self._counter_catch_up(self._fleet_resumes,
                               stats.get("fleet_resumes_total", 0))
        self._counter_catch_up(self._fleet_resumed_tokens,
                               stats.get("fleet_resumed_tokens_total", 0))
        self._counter_catch_up(self._fleet_budget_exhausted,
                               stats.get("fleet_retry_budget_exhausted_total",
                                         0))
        self._fleet_journal_depth.labels(**self._base()).set(
            stats.get("fleet_resume_journal_depth", 0))

    def sync_framing(self) -> None:
        """Drain the frame codec's module-level tallies (codec/framing.py
        ``frame_stats``) into the frame histograms and per-path byte
        counter. Process-wide, not per-component — every egress path
        (remote-hop REST, gRPC binData, KV handoff) funnels through the
        one codec, so both /metrics handlers call this once per scrape."""
        from seldon_core_tpu.codec.framing import frame_stats

        stats = frame_stats()
        enc = self._frame_encode.labels(**self._base())
        for seconds in stats.get("frame_encode_times_s", ()):
            enc.observe(seconds)
        dec = self._frame_decode.labels(**self._base())
        for seconds in stats.get("frame_decode_times_s", ()):
            dec.observe(seconds)
        for path, nbytes in stats.get("frame_bytes_total", {}).items():
            self._counter_catch_up(self._frame_bytes, nbytes, path=path)

    # ------------------------------------------------------------------
    def register_custom(self, response: SeldonMessage) -> None:
        """Register COUNTER/GAUGE/TIMER metrics carried in response meta."""
        for m in response.meta.metrics:
            tags = dict(sorted(m.tags.items()))
            key = m.key + "|" + ",".join(f"{k}={v}" for k in tags for v in [tags[k]])
            label_names = list(tags)
            if m.type == "COUNTER":
                c = self._custom_counters.get(key)
                if c is None:
                    c = Counter(m.key, "custom counter", label_names, registry=self.registry)
                    self._custom_counters[key] = c
                (c.labels(**tags) if tags else c).inc(m.value)
            elif m.type == "GAUGE":
                g = self._custom_gauges.get(key)
                if g is None:
                    g = Gauge(m.key, "custom gauge", label_names, registry=self.registry)
                    self._custom_gauges[key] = g
                (g.labels(**tags) if tags else g).set(m.value)
            elif m.type == "TIMER":
                h = self._custom_timers.get(key)
                if h is None:
                    h = Histogram(m.key, "custom timer", label_names, registry=self.registry)
                    self._custom_timers[key] = h
                # reference timers arrive in milliseconds (`metrics.py` docs)
                (h.labels(**tags) if tags else h).observe(m.value / 1000.0)

    def expose(self) -> bytes:
        return generate_latest(self.registry)
