"""LLM_SERVER: autoregressive text-generation prepackaged server.

The BASELINE.json stretch config ("Llama-2-7B Flax jaxserver on v5e-8 pod").
No reference counterpart — the reference's prepackaged servers are
request/response classifiers (`servers/sklearnserver/...`); LLM serving is the
TPU build's native extension, designed around XLA static shapes:

- prompts are bucketed to (batch_bucket, len_bucket) so there is ONE compiled
  prefill program per bucket pair and ONE decode program per batch bucket;
- prefill writes the prompt into the position-tracked KV cache in one pass
  (padded slots carry PAD_POS and are never attended — models/cache.py);
- decode is a single ``lax.scan`` over steps: per-sequence cache offsets,
  greedy or temperature/top-k sampling, EOS masking inside the scan — no
  per-token Python dispatch;
- tensor parallelism: pass a mesh and the params shard per the model's
  logical axes (parallel.sharding), with activations following under GSPMD.

Long-context serving shards the KV cache itself: with a mesh carrying a
'seq' axis, prefill pins each layer's (k, v, pos) cache to a
NamedSharding that splits the max_len dim across devices, so a context
longer than one device's cache slice serves correctly — decode's attention
over the sharded cache becomes a GSPMD sequence-parallel computation (XLA
inserts the softmax all-reduces over ICI). 'data' shards the batch dim and
'model' the kv_heads dim when they divide. ``attention_impl='ring'``
(ops.ring_attention) remains the cache-less forward/training path.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from collections import OrderedDict
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from seldon_core_tpu.components.component import SeldonComponent
from seldon_core_tpu.contracts.payload import SeldonError
from seldon_core_tpu.tracing.start import get_ledger, name_programs

logger = logging.getLogger(__name__)

DEFAULT_LEN_BUCKETS = (32, 128, 512, 2048)
DEFAULT_BATCH_BUCKETS = (1, 4, 8)


class ByteTokenizer:
    """UTF-8 byte fallback tokenizer (ids 0..255): always available, exercises
    the full serving path without a vocab artifact. eos_id defaults to 0."""

    vocab_size = 256

    def __init__(self, eos_id: int = 0):
        self.eos_id = eos_id

    def encode(self, text: str) -> List[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids: Sequence[int]) -> str:
        ids = [int(i) for i in ids if 0 <= int(i) < 256 and int(i) != self.eos_id]
        return bytes(ids).decode("utf-8", errors="replace")


class HFTokenizer:
    """transformers tokenizer adapter (gated import; offline-friendly only if
    the vocab files are local)."""

    def __init__(self, name_or_path: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(name_or_path)
        # id 0 is usually a real token; with no EOS defined, use -1 so the
        # decode loop's EOS check never fires (generates to max_new_tokens)
        self.eos_id = self._tok.eos_token_id if self._tok.eos_token_id is not None else -1
        self.vocab_size = self._tok.vocab_size

    def encode(self, text: str) -> List[int]:
        return self._tok.encode(text)

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode([int(i) for i in ids])


def _cast_params(params, param_dtype: str, module_dtype, keep=None) -> Any:
    """Cast float32 param leaves to the serving dtype ("auto" = the module's
    compute dtype). The module casts weights to its compute dtype inside
    every matmul anyway; pre-casting stores them that way in HBM, halving
    weight-streaming bytes for bf16 models (benchmarks/DECODE_NOTES.md).
    ``keep`` (a tree of bools, parallel/sharding.py ``float32_leaves``) marks
    the leaves that stay float32."""
    if not param_dtype:
        return params
    import jax
    import jax.numpy as jnp

    target = jnp.dtype(module_dtype) if param_dtype == "auto" else jnp.dtype(param_dtype)
    if target == jnp.float32:
        return params

    def cast(leaf, kept=False):
        if not kept and hasattr(leaf, "dtype") and leaf.dtype == jnp.float32:
            return leaf.astype(target)
        return leaf

    if keep is None:
        return jax.tree.map(cast, params)
    return jax.tree.map(cast, params, keep)


#: Consecutive columns a block of `_top_k_candidates`' first stage spans: one
#: lane row. The width that wins at [32, 32000] and loses at no served shape
#: on a TPU v5e (benchmarks/sampler_topk_bench.py, docs/performance.md "The
#: sampler's candidates"): a constant, not a knob.
TOPK_BLOCK = 128


def sampler_topk_columns(vocab: int, top_k: int) -> int:
    """The columns `_top_k_candidates`' last ``lax.top_k`` runs over, from
    the call's static shape alone: the vocabulary where the direct form
    stands (``vocab <= 2 x k x TOPK_BLOCK``: the test models' 256 columns, a
    ``top_k`` near ``vocab / TOPK_BLOCK``), else the ``k`` blocks the first
    stage names plus the columns behind the last whole block."""
    k = min(top_k, vocab)
    if vocab <= 2 * k * TOPK_BLOCK:
        return vocab
    return k * TOPK_BLOCK + vocab % TOPK_BLOCK


def _top_k_candidates(lg, top_k: int):
    """What every emitted token is chosen among: the greedy token and the
    top-k logits with their indices in ``lax.top_k``'s order (descending,
    ties by index). ``lg`` [rows, vocab] float32.

    Over a served vocabulary this is ``lax.top_k(lg, k)`` bit for bit
    (values, indices, order) in two exact stages, so the step's TopK runs
    over `sampler_topk_columns` columns and not the vocabulary (XLA's TopK
    is bound by neither bytes nor FLOPs and grows with its columns). The
    row is viewed as blocks of TOPK_BLOCK consecutive columns; the k blocks
    with the largest maxima (ties by lower block) hold every one of the
    row's top k: a block outside holds nothing above the k-th value, and one
    outside whose maximum EQUALS it lies behind every block that holds a
    chosen element of that value. Their columns, blocks ASCENDING (then the
    columns behind the last whole block, which are always candidates), order
    by position as they do by vocabulary index, so ``lax.top_k`` over them
    breaks ties as it would over the row. The maxima are taken on
    ``lax.top_k``'s own order (the total order of the float32 bit patterns,
    +0.0 above -0.0: `_order_key`), and ``greedy`` is the first candidate:
    the first occurrence of the row's maximum, ``jnp.argmax``'s answer (a
    row whose maximum is zero in BOTH signs gets its first +0.0)."""
    import jax
    import jax.numpy as jnp

    rows, vocab = lg.shape
    k = min(top_k, vocab)
    if sampler_topk_columns(vocab, top_k) == vocab:
        topv, topi = jax.lax.top_k(lg, k)
        return topi[:, 0], topv, topi
    blocks, tail = divmod(vocab, TOPK_BLOCK)
    main = lg[:, :blocks * TOPK_BLOCK].reshape(rows, blocks, TOPK_BLOCK)
    maxima = _order_key(jnp.max(_order_key(
        jax.lax.bitcast_convert_type(main, jnp.int32)), axis=-1))
    _, chosen = jax.lax.top_k(
        jax.lax.bitcast_convert_type(maxima, jnp.float32), k)
    chosen = jnp.sort(chosen, axis=-1)
    # (block ids are in bounds by construction: no fill)
    candidates = jnp.take_along_axis(
        main, chosen[:, :, None], axis=1, mode="promise_in_bounds",
    ).reshape(rows, k * TOPK_BLOCK)
    # the first column of each candidate block (and of the tail, slot k)
    starts = chosen * TOPK_BLOCK
    if tail:
        candidates = jnp.concatenate(
            [candidates, lg[:, blocks * TOPK_BLOCK:]], axis=-1)
        starts = jnp.concatenate(
            [starts, jnp.full((rows, 1), blocks * TOPK_BLOCK, starts.dtype)], axis=-1)
    topv, at = jax.lax.top_k(candidates, k)
    # a winner's slot names its block's first column: a compare against every
    # slot and a sum (one small fusion; as a [rows, k] gather of ``starts``
    # the TPU runs it an element at a time, 10-30 us a call)
    slot = at // TOPK_BLOCK
    first = jnp.sum(jnp.where(
        slot[:, :, None] == jnp.arange(starts.shape[-1]), starts[:, None, :], 0), axis=-1)
    topi = first + at - slot * TOPK_BLOCK
    return topi[:, 0], topv, topi


def _order_key(bits):
    """float32 bit patterns (int32) <-> integers that order as ``lax.top_k``
    orders the floats (its comparator on the TPU and the CPU alike: the
    total order, -0.0 below +0.0); its own inverse."""
    import jax.numpy as jnp

    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _choose(greedy, topi, draw, temperature):
    """The token of each row: ``draw`` [rows] indexes the row's top-k;
    greedy under temperature <= 0."""
    import jax.numpy as jnp

    sampled = jnp.take_along_axis(topi, draw[:, None], axis=-1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _slot_sampler(top_k: int):
    """The per-slot sampling chain of every token the batcher emits: the
    compiled steps (`_get_decode_step`, `_get_decode_step_paged`,
    `_get_spec_step`) and the prompt's first token (`_get_first_token`).
    One key split + top-k categorical per emitted token per slot, greedy
    under temperature <= 0. The speculative verify step, a resumed
    generation and the first token are bit-exact vs plain decode ONLY while
    all of them sample through this single definition — any fork of this
    code re-opens the parity hazard the CI suites
    (tests/test_batcher_pipeline.py, tests/test_speculative.py,
    tests/test_chaos.py) exist to catch. generate() draws a whole batch
    from one key (`_batch_sampler`): the same candidates and the same
    choice, a batch-level key chain by design, and the per-slot chain
    exactly at batch 1."""
    import jax
    import jax.numpy as jnp

    def sample(keys, lg, temperature):
        with jax.named_scope("sample.topk"):
            greedy, topv, topi = _top_k_candidates(lg, top_k)

        def one(key, tv):
            key, sub = jax.random.split(key)
            return key, jax.random.categorical(
                sub, tv / jnp.maximum(temperature, 1e-6))

        with jax.named_scope("sample.draw"):
            keys, draw = jax.vmap(one)(keys, topv)
            return keys, _choose(greedy, topi, draw, temperature)

    return sample


def _batch_sampler(top_k: int):
    """generate()'s sampler: one categorical for the whole batch from one
    key (its first token and every step of its decode scan), over
    `_slot_sampler`'s candidates and choice."""
    import jax
    import jax.numpy as jnp

    def sample(logits, key, temperature):
        with jax.named_scope("sample.topk"):
            greedy, topv, topi = _top_k_candidates(logits, top_k)
        with jax.named_scope("sample.draw"):
            draw = jax.random.categorical(
                key, topv / jnp.maximum(temperature, 1e-6))
            return _choose(greedy, topi, draw, temperature)

    return sample


def fast_forward_key(seed: int, n_tokens: int):
    """The per-request rng key after ``n_tokens`` emitted tokens of a
    seeded generation — the deterministic-resume half of fleet fault
    tolerance (docs/resilience.md). The chain consumes EXACTLY one
    first-component split per emitted token (`_slot_sampler`'s, for the
    first token and for every step alike), so
    replaying ``n_tokens`` splits from PRNGKey(seed) lands on the key the
    dead replica's slot held when it died. The caller then draws token
    ``n_tokens`` with `_slot_sampler`'s exact op order (split ->
    lax.top_k -> categorical -> gather); any fork of that order re-opens
    the bit-exactness hazard tests/test_chaos.py pins."""
    import jax

    key = jax.random.PRNGKey(int(seed))
    for _ in range(int(n_tokens)):
        key, _ = jax.random.split(key)
    return key


from seldon_core_tpu.utils import bucket as _bucket  # single bucketing policy


# terminal marker in the dense prefix-cache index trie: an object() can
# never collide with an int token id
_TERM = object()


class _PrefixTrieIndex:
    """Token trie over the dense prefix-cache entry keys, so
    ``_prefix_lookup`` walks the PROMPT once instead of scanning every
    entry (the old OrderedDict scan was O(entries x prefix length) under
    ``_prefix_lock`` — at fleet cache sizes the lock hold time scaled
    with cache population, not prompt length). ``candidates`` returns
    every stored key that is a prefix of the probe, shortest to longest,
    in O(len(probe)) node steps; the caller picks the longest one whose
    entry passes its predicates (dtype/geometry). ``work`` counts node
    visits — the regression signal tests/test_kv_cache.py pins to the
    prompt length, independent of entry count. NOT thread-safe on its
    own: every call happens under the server's ``_prefix_lock``, exactly
    like the OrderedDict it indexes."""

    __slots__ = ("_root", "work")

    def __init__(self):
        self._root: Dict[Any, Any] = {}
        self.work = 0

    def add(self, key: Tuple[int, ...]) -> None:
        node = self._root
        for t in key:
            node = node.setdefault(t, {})
        node[_TERM] = key

    def remove(self, key: Tuple[int, ...]) -> None:
        path = [(None, self._root)]
        node = self._root
        for t in key:
            nxt = node.get(t)
            if nxt is None:
                return
            path.append((t, nxt))
            node = nxt
        node.pop(_TERM, None)
        # prune now-empty suffix nodes so dead entries cost no walk time
        for i in range(len(path) - 1, 0, -1):
            tok, n = path[i]
            if n:
                break
            del path[i - 1][1][tok]

    def candidates(self, tokens: Sequence[int]) -> List[Tuple[int, ...]]:
        node = self._root
        out: List[Tuple[int, ...]] = []
        self.work += 1
        for t in tokens:
            if _TERM in node:
                out.append(node[_TERM])
            node = node.get(t)
            if node is None:
                return out
            self.work += 1
        if _TERM in node:
            out.append(node[_TERM])
        return out

    def clear(self) -> None:
        self._root = {}


# f32 init trees above this stream leaf-by-leaf through the quantizer
# instead of materializing whole (27 GB at 7B vs 16 GB single-chip HBM).
STREAM_INIT_THRESHOLD_BYTES = 2 << 30

#: What the start ledger's build book (tracing/start.py) calls each program, by
#: the names of the jitted functions the ``_get_*`` builders below define (and
#: runtime/batcher.py ``_page_table_ops``, models/, ops/ for the last rows): a
#: build of any other function is ``other``. The last three are jitted
#: functions of their own that a step program traces INSIDE itself, so their
#: legs are booked ``nested="1"`` under their own names.
BUILD_PROGRAMS = (
    ("decode_step", ("decode_step",)),
    ("prefill_chunk", ("prefill_chunk",)),
    ("first_token", ("first_token",)),
    ("spec_step", ("spec_step",)),
    ("handoff_import", ("import_pages",)),
    ("page_ops", ("set_block_row", "set_block_entry", "reset_pages", "set_slot",
                  "set_hist_row", "cow_page_copy", "export_pages", "set_adapter_id")),
    ("weights", ("make_quantized", "init")),
    ("paged_live_read", ("paged_live_read",)),
    ("_walk_pages", ("_walk_pages",)),
    ("paged_write_pages", ("paged_write_pages",)),
)
name_programs(BUILD_PROGRAMS)


class LLMServer(SeldonComponent):
    """Serves a registered transformer-family model for text generation.

    Parameters (graph-spec ``parameters`` or constructor kwargs):
      model_uri: jaxserver-style checkpoint dir (config.json + params) — or
      model + init_random=True for a randomly-initialised model (tests/bench)
      max_new_tokens, temperature, top_k, eos_id, tokenizer ("bytes" or an HF
      name), len_buckets, batch_buckets, mesh (object, programmatic only).
    """

    def __init__(
        self,
        model_uri: str = "",
        model: Optional[str] = None,
        model_kwargs: Optional[Dict[str, Any]] = None,
        init_random: bool = False,
        max_new_tokens: int = 64,
        temperature: float = 0.0,
        top_k: int = 40,
        eos_id: Optional[int] = None,
        tokenizer: str = "bytes",
        len_buckets: Optional[Sequence[int]] = None,
        batch_buckets: Optional[Sequence[int]] = None,
        mesh: Optional[Any] = None,
        topology: Optional[Any] = None,
        tensor_parallel: int = 0,
        sequence_parallel: int = 0,
        quantize: str = "",
        param_dtype: str = "",
        kv_cache_dtype: str = "",
        kv_page_size: int = 0,
        kv_pool_pages: int = 0,
        prefill_chunk: int = 0,
        continuous_batching: int = 0,
        continuous_batching_max_len: int = 0,
        decode_pipeline_depth: int = 2,
        decode_fuse_steps: int = 0,
        spec_mode: str = "",
        spec_k: int = 0,
        spec_ngram: int = 0,
        disaggregation: str = "",
        prefill_devices: int = 0,
        decode_devices: int = 0,
        prefill_workers: int = 0,
        handoff_transport: str = "",
        disagg_mesh: Optional[Any] = None,
        draft_model: Optional[str] = None,
        draft_model_kwargs: Optional[Dict[str, Any]] = None,
        draft_model_uri: str = "",
        prefix_cache_size: int = 0,
        prefix_cache_bytes: int = 0,
        lora_rank: int = 0,
        lora_max_adapters: int = 8,
        lora_adapters: Optional[Dict[str, str]] = None,
        slo_class_weights: Optional[Dict[str, float]] = None,
        tenant_weights: Optional[Dict[str, float]] = None,
        tenant_quota: int = 0,
        tenant_quotas: Optional[Dict[str, int]] = None,
        seed: int = 0,
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        self.model_uri = model_uri
        self.model_name = model
        self.model_kwargs = dict(model_kwargs or {})
        self.init_random = bool(init_random)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.tokenizer_name = tokenizer
        self.len_buckets = tuple(len_buckets or DEFAULT_LEN_BUCKETS)
        self.batch_buckets = tuple(batch_buckets or DEFAULT_BATCH_BUCKETS)
        self.mesh = mesh
        # The injected device-world view (parallel/topology.py). None =
        # adopt the process topology at load(); tests and virtual-mesh
        # harnesses pass their own so the server never re-derives
        # jax.devices() itself.
        self.topology = topology
        # Spec-reachable sharding (typed unit parameters, like JAXServer's
        # tensor_parallel): builds a ('data', 'seq', 'model') mesh at load.
        self.tensor_parallel = int(tensor_parallel)
        self.sequence_parallel = int(sequence_parallel)
        # "int8": weight-only PTQ (ops/quantize.py) — the KV cache and
        # activations stay in the model dtype; only weights go int8 in HBM
        self.quantize = str(quantize or "")
        # Flax init leaves params f32 even for bf16-compute modules. An
        # interleaved A/B on the real chip showed pre-casting to bf16 is
        # SLOWER here (XLA hoists the f32->bf16 convert out of the decode
        # scan, so storage dtype costs nothing per step, and bf16-stored
        # weights landed in worse layouts) — benchmarks/DECODE_NOTES.md.
        # Default is therefore no cast; "auto" casts to the module compute
        # dtype, or pass an explicit dtype, for configs where HBM residency
        # matters more than step time.
        self.param_dtype = param_dtype
        # KV-cache storage: "bf16" (default — model dtype) or "int8"
        # (quantize-on-write, per-head per-position scales; halves the KV
        # read traffic that dominates the b8 decode step —
        # benchmarks/DECODE_NOTES.md). Normalized + validated at load().
        self.kv_cache_dtype = kv_cache_dtype
        # The continuous batcher's KV store is a global pool of fixed-size
        # pages addressed through per-slot block tables: HBM is billed for
        # pages actually written and admission prefill runs in chunks
        # interleaved with decode. generate()'s per-request caches are dense.
        # kv_page_size: tokens per KV page (0 = default 64); the batcher
        # rounds its cache length up to a page multiple.
        self.kv_page_size = int(kv_page_size)
        # Total pages in the global pool (0 = fully provisioned: every slot
        # can reach max_len simultaneously — no oversubscription, never
        # sheds on pages). Smaller pools oversubscribe: more slots per HBM
        # byte, with page-exhaustion shed (503 + Retry-After) as the relief
        # valve — docs/performance.md "Paged KV".
        self.kv_pool_pages = int(kv_pool_pages)
        # Admission prefill chunk size (0 = default 256).
        # A long prompt prefills chunk-by-chunk between decode steps so
        # admission never stalls serving for a whole compile bucket.
        self.prefill_chunk = int(prefill_chunk)
        # >0: serving transports route single-prompt /v1/generate (REST) and
        # jsonData {"prompt": ...} predicts (gRPC) through a shared
        # ContinuousBatcher with this many slots (runtime/batcher.py), so
        # concurrent clients join one in-flight decode batch.
        self.continuous_batching = int(continuous_batching)
        # cache length for the batcher's slot KV (0 = sized from the
        # len_buckets; see ContinuousBatcher.__init__)
        self.continuous_batching_max_len = int(continuous_batching_max_len) or None
        # Decode pipelining (runtime/batcher.py): how many decode steps the
        # batcher keeps dispatched ahead of the host (>=2 hides the
        # dispatch+sync round trip that serialized the served decode at 11%
        # of direct throughput — docs/performance.md "Decode pipelining"),
        # and how many steps to fuse into one device-side lax.scan between
        # host syncs when the admit queue is empty (0/1 = off).
        self.decode_pipeline_depth = int(decode_pipeline_depth)
        self.decode_fuse_steps = int(decode_fuse_steps)
        # Speculative decoding (runtime/batcher.py + _get_spec_step): "off"
        # (default), "ngram" — a zero-weight device-side prompt-lookup
        # proposer over each slot's prompt+generated history — or "draft"
        # — a small draft model (draft_model / draft_model_uri) runs K+1
        # greedy forwards per turn. Either way each batcher turn verifies
        # the K proposed tokens in ONE K+1-token target forward and accepts
        # the longest prefix agreeing with the per-slot sampling chain, so
        # greedy and seeded-sampled outputs stay bit-exact vs generate()
        # while accepted tokens per KV-cache read can exceed 1
        # (docs/performance.md "Speculative decoding"). Normalized +
        # validated at load().
        self.spec_mode = spec_mode
        # draft tokens per verify step (0 = default 4); the verify forward
        # is spec_k + 1 tokens wide
        self.spec_k = int(spec_k)
        # longest n-gram the self-draft proposer matches (0 = default 3)
        self.spec_ngram = int(spec_ngram)
        # Disaggregated prefill/decode (runtime/disagg.py,
        # docs/performance.md "Disaggregated serving"): "remote_prefill"
        # splits the device world into a prefill slice and a decode slice
        # (parallel/mesh.py disaggregated_mesh) — admission prefill runs on
        # prefill-slice workers and the written KV moves device-to-device
        # into the decode slice's pool, so the compute burst never touches
        # the latency-critical decode batch. Bit-exact vs single-slice
        # serving (tests/test_disagg.py). Normalized + validated at load().
        self.disaggregation = disaggregation
        # slice sizing (counts; the prefill slice takes devices from the
        # END of the enumeration, decode from the front; 0 decode = all
        # the rest) — or pass a prebuilt DisaggregatedMesh programmatically
        self.prefill_devices = int(prefill_devices)
        self.decode_devices = int(decode_devices)
        # prefill workers (one thread+device each; 0 = one per
        # prefill-slice device)
        self.prefill_workers = int(prefill_workers)
        # "" / "device" = direct jax.device_put KV handoff (shared
        # topology); "network" = frame the KV bucket and stream it over a
        # socket to the decode host (runtime/disagg.py HandoffReceiver) —
        # bit-exact either way, validated at load()
        self.handoff_transport = handoff_transport
        self.disagg_mesh = disagg_mesh
        # optional draft model: registry name + kwargs (random init on the
        # server's seed) or a jaxserver-style checkpoint dir. Must share
        # the target's vocab — draft proposals index the target's tokens.
        self.draft_model = str(draft_model or "")
        self.draft_model_kwargs = dict(draft_model_kwargs or {})
        self.draft_model_uri = str(draft_model_uri or "")
        # Prefix caching (opt-in): single-prompt requests reuse the KV cache
        # of the longest previously-prefilled token prefix (shared system
        # prompts prefill once); entries are LRU-evicted past this size.
        # Safe to share: jax arrays are immutable, decode never mutates them.
        # Each entry pins full per-layer KV caches of max_len, so the count
        # bound alone can hold multi-GB of HBM — prefix_cache_bytes (default
        # 512 MB whenever the cache is enabled) bounds the total pinned bytes.
        self.prefix_cache_size = int(prefix_cache_size)
        self.prefix_cache_bytes = int(prefix_cache_bytes) or (
            512 * 1024 * 1024 if self.prefix_cache_size else 0)
        self._prefix_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # longest-prefix lookups walk this trie index in O(prompt) instead
        # of scanning the OrderedDict (which stays for LRU order + byte
        # accounting); membership is mirrored add/remove under _prefix_lock
        self._prefix_index = _PrefixTrieIndex()
        self._prefix_bytes = 0
        self._prefix_lock = threading.Lock()
        self._prefix_hits = 0
        # Batched LoRA multi-tenancy (runtime/adapters.py,
        # docs/multitenancy.md): lora_rank > 0 builds an AdapterRegistry
        # at load() — a dense [lora_max_adapters, ...] HBM pool of
        # low-rank q/o/FFN deltas gathered per slot inside the shared
        # decode/prefill/verify programs (adapter id 0 = identity).
        # ``lora_adapters`` maps name -> storage URI, preloaded at load().
        self.lora_rank = int(lora_rank)
        self.lora_max_adapters = int(lora_max_adapters)
        self.lora_adapters = dict(lora_adapters or {})
        self.adapter_registry: Optional[Any] = None
        # SLO-aware weighted-fair scheduling (runtime/scheduler.py): the
        # continuous batcher's admission queue orders requests by SLO
        # class ("interactive" latency-sensitive vs "batch" throughput)
        # and tenant under stride-scheduled weighted fairness, with
        # per-tenant queue quotas shedding 503 + Retry-After on breach.
        self.slo_class_weights = dict(slo_class_weights or {})
        self.tenant_weights = dict(tenant_weights or {})
        self.tenant_quota = int(tenant_quota)
        self.tenant_quotas = dict(tenant_quotas or {})
        self.seed = int(seed)
        self.ready = False
        self._eos_override = eos_id
        self._prefill_cache: Dict[Tuple[int, int], Any] = {}
        self._decode_cache: Dict[tuple, Any] = {}
        self._request_count = 0
        # decode observability (metrics.registry sync_llm drains these at
        # /metrics scrape time): per-step wall times and the KV bytes the
        # last decode streamed per step
        from collections import deque

        self._decode_step_times: Any = deque(maxlen=4096)
        self._last_decode_kv_bytes = 0
        # pipelined-decode observability (batcher): per-call dispatch wall
        # (enqueue only, no sync), per-drain host sync wall, and the number
        # of steps in flight observed at each drain (host lag)
        self._decode_dispatch_times: Any = deque(maxlen=4096)
        self._decode_sync_times: Any = deque(maxlen=4096)
        self._decode_host_lag: Any = deque(maxlen=4096)
        # speculative decode observability: tokens accepted by each drained
        # verify step (drained into the accepted-tokens-per-step histogram
        # at /metrics scrape time, like the step-time deques above)
        self._spec_accepted: Any = deque(maxlen=4096)
        # streaming-latency observability (batcher on_token path): time to
        # first token per request and the gap before each surfaced token —
        # the headline pair disaggregation/chunked-prefill move
        # (seldon_llm_ttft_seconds / seldon_llm_inter_token_seconds)
        self._ttft_times: Any = deque(maxlen=4096)
        self._inter_token_times: Any = deque(maxlen=8192)
        # per-SLO-class TTFT observations (multi-tenant serving): the
        # batcher appends (class, ttft) pairs at first-token commit; the
        # scrape drains them into seldon_llm_tenant_ttft_seconds{slo_class}
        self._ttft_by_class: Any = deque(maxlen=4096)
        # disaggregated serving: per-handoff wall (prefill-slice compute +
        # device-to-device transfer + decode-side import)
        self._handoff_times: Any = deque(maxlen=4096)
        # what the /metrics histograms are fed from: lifetime bucket tallies
        # counted where the work happens (metrics/local.py), caught up by
        # difference at the scrape, so a long scrape interval loses nothing.
        # The deques above keep a bounded window of the same samples for
        # llm_stats' raw lists (tests, debugging).
        from seldon_core_tpu.metrics.local import (
            HOST_LAG_BUCKETS, LATENCY_BUCKETS, QUEUE_WAIT_BUCKETS,
            HistogramAccumulator)

        self._hists: Dict[str, Any] = {
            "decode_step_s": HistogramAccumulator(LATENCY_BUCKETS),
            "ttft_s": HistogramAccumulator(LATENCY_BUCKETS),
            "inter_token_s": HistogramAccumulator(LATENCY_BUCKETS),
            "decode_host_lag_steps": HistogramAccumulator(HOST_LAG_BUCKETS),
            "queue_wait_s": HistogramAccumulator(QUEUE_WAIT_BUCKETS),
        }
        self._recent: Dict[str, Any] = {
            "decode_step_s": self._decode_step_times,
            "ttft_s": self._ttft_times,
            "inter_token_s": self._inter_token_times,
            "decode_host_lag_steps": self._decode_host_lag,
        }
        # per-device committed param copies for prefill-slice workers
        # (runtime/disagg.py); built on first use under its own lock
        self._device_params: Dict[Any, Any] = {}
        self._device_params_lock = threading.Lock()

    def observe(self, key: str, value: float, weight: int = 1) -> None:
        """One loop-side observation of a /metrics histogram (single
        writer: the batcher loop's serialized context, or generate()):
        ``weight`` equal observations go into the lifetime accumulator,
        one sample into the recent window."""
        self._hists[key].observe(value, weight)
        recent = self._recent.get(key)
        if recent is not None:
            recent.append(value)

    # ------------------------------------------------------------------
    def load(self) -> None:
        if self.ready:
            return
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models import get_model
        from seldon_core_tpu.models.cache import normalize_kv_cache_dtype
        from seldon_core_tpu.parallel.topology import get_topology

        # Resolve the device-world view ONCE; everything below (mesh
        # construction, disagg splits, the batcher's placement defaults)
        # consumes it instead of re-deriving jax.devices().
        # racelint: allow-unguarded-shared-state(load()-time config normalization: runs once, before any serving thread or batcher loop exists — nothing can interleave with it)
        self.topology = self.topology or get_topology()
        topo = self.topology

        # Validate dtype knobs HERE, with a clear ValueError, instead of
        # letting an unknown string explode later inside a jitted cast or
        # cache init (where the traceback names nothing actionable).
        # racelint: allow-unguarded-shared-state(load()-time config normalization: runs once, before any serving thread or batcher loop exists — nothing can interleave with it)
        self.kv_cache_dtype = normalize_kv_cache_dtype(self.kv_cache_dtype)
        if self.kv_page_size < 0:
            raise ValueError(
                f"kv_page_size={self.kv_page_size} must be >= 0 "
                f"(0 = default page size)")
        if self.kv_pool_pages < 0:
            raise ValueError(
                f"kv_pool_pages={self.kv_pool_pages} must be >= 0 "
                f"(0 = fully provisioned pool)")
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be >= 0 "
                f"(0 = default chunk size)")
        if self.param_dtype and self.param_dtype != "auto":
            try:
                jnp.dtype(self.param_dtype)
            except (TypeError, ValueError) as e:
                raise ValueError(
                    f"unknown param_dtype {self.param_dtype!r}: expected '', "
                    f"'auto', or a jax dtype name (e.g. 'bfloat16')"
                ) from e
        if self.decode_pipeline_depth < 1:
            raise ValueError(
                f"decode_pipeline_depth={self.decode_pipeline_depth} must be "
                f">= 1 (1 = serial dispatch-then-sync, >=2 pipelines)"
            )
        if self.decode_fuse_steps < 0:
            raise ValueError(
                f"decode_fuse_steps={self.decode_fuse_steps} must be >= 0 "
                f"(0/1 = no fusing)"
            )
        from seldon_core_tpu.runtime.spec import normalize_spec_mode

        # racelint: allow-unguarded-shared-state(load()-time config normalization: runs once, before any serving thread or batcher loop exists — nothing can interleave with it)
        self.spec_mode = normalize_spec_mode(self.spec_mode)
        if self.spec_k < 0:
            raise ValueError(
                f"spec_k={self.spec_k} must be >= 0 (0 = default draft "
                f"depth when speculation is on)")
        if self.spec_ngram < 0:
            raise ValueError(
                f"spec_ngram={self.spec_ngram} must be >= 0 (0 = default "
                f"3-gram prompt lookup)")
        if self.spec_mode == "draft" and not (
                self.draft_model or self.draft_model_uri):
            raise ValueError(
                "spec_mode='draft' needs a draft model: pass draft_model="
                "<registry name> (+ draft_model_kwargs) or draft_model_uri")
        from seldon_core_tpu.runtime.disagg import normalize_disaggregation

        # racelint: allow-unguarded-shared-state(load()-time config normalization: runs once, before any serving thread or batcher loop exists — nothing can interleave with it)
        self.disaggregation = normalize_disaggregation(self.disaggregation)
        if self.prefill_devices < 0 or self.decode_devices < 0 or \
                self.prefill_workers < 0:
            raise ValueError(
                f"prefill_devices={self.prefill_devices} / decode_devices="
                f"{self.decode_devices} / prefill_workers="
                f"{self.prefill_workers} must be >= 0")
        if self.lora_rank < 0:
            raise ValueError(
                f"lora_rank={self.lora_rank} must be >= 0 (0 = adapters "
                f"off)")
        if self.lora_rank > 0:
            if self.disaggregation not in ("", "off"):
                raise ValueError(
                    "lora_rank > 0 does not yet compose with "
                    "disaggregation='remote_prefill': the adapter pool "
                    "lives on the decode slice and prefill-slice workers "
                    "would need committed replicas — a follow-up")
            if int(self.model_kwargs.get("n_experts", 0) or 0) > 0:
                raise ValueError(
                    "lora_rank > 0 does not support MoE FFNs: adapters "
                    "target the dense q/o/FFN projections")
        if int(self.model_kwargs.get("kv_lora_rank", 0) or 0) > 0:
            # latent attention (models/transformer.py LatentAttention): what
            # is not built is refused here, by name (ROADMAP C1)
            from seldon_core_tpu.models.cache import LATENT_INT8_REFUSAL

            if self.kv_cache_dtype == "int8":
                raise ValueError(LATENT_INT8_REFUSAL)
            if self.tensor_parallel > 1 or self.sequence_parallel > 1 \
                    or self.mesh is not None:
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) does not compose "
                    "with tensor/sequence parallelism or a mesh: its cache "
                    "row has no head axis to shard, and a replicated latent "
                    "pool under sharded heads is not built")
            if self.lora_rank > 0:
                raise ValueError(
                    "lora_rank > 0 does not support latent attention: "
                    "adapters target the per-head q/o projections of "
                    "Attention")
        from seldon_core_tpu.runtime.scheduler import normalize_slo_class

        for cls in self.slo_class_weights:
            normalize_slo_class(cls)  # unknown class names fail at load()
        if self.disaggregation != "off":
            if self.tensor_parallel > 1 or self.sequence_parallel > 1 \
                    or self.mesh is not None:
                raise ValueError(
                    "disaggregation='remote_prefill' does not yet compose "
                    "with tensor/sequence parallelism or an explicit mesh: "
                    "the batcher's slot pool is single-device per slice — "
                    "shard WITHIN a slice is a follow-up")
            if self.disagg_mesh is None and topo.device_count < 2:
                raise ValueError(
                    "disaggregation='remote_prefill' needs >= 2 devices "
                    "(one per slice); this process sees "
                    f"{topo.device_count}")
        if self.handoff_transport not in ("", "device", "network"):
            raise ValueError(
                f"unknown handoff_transport {self.handoff_transport!r}: "
                "expected '', 'device' or 'network'")
        if self.handoff_transport == "network" \
                and self.disaggregation == "off":
            raise ValueError(
                "handoff_transport='network' only applies to "
                "disaggregation='remote_prefill' (there is no KV handoff "
                "without a prefill/decode split)")

        # (the start's stages, tracing/start.py: the checks above are the last
        # of `construct`)
        get_ledger().advance("load.weights")
        cfg_kwargs = dict(self.model_kwargs)
        name = self.model_name
        params = None
        if self.model_uri:
            from seldon_core_tpu import storage

            path = storage.download(self.model_uri)
            with open(os.path.join(path, "config.json")) as f:
                file_cfg = json.load(f)
            name = name or file_cfg["model"]
            cfg_kwargs = {**file_cfg.get("kwargs", {}), **cfg_kwargs}
            params = self._load_params(path, name, cfg_kwargs)
        if name is None:
            raise SeldonError("LLMServer needs model_uri or model=<registry name>", status_code=500)

        if self.mesh is None and (self.tensor_parallel > 1 or self.sequence_parallel > 1):
            tp = max(self.tensor_parallel, 1)
            sp = max(self.sequence_parallel, 1)
            n = topo.device_count
            if n % (tp * sp):
                raise SeldonError(
                    f"tensor_parallel={tp} * sequence_parallel={sp} does not "
                    f"divide {n} available devices",
                    status_code=500,
                )
            self.mesh = topo.mesh({"data": -1, "seq": sp, "model": tp})
        module = get_model(name, **cfg_kwargs)
        if self.mesh is not None and module.cfg.mesh is None:
            # the modules' kernels are one device's programs: sharded over a
            # mesh, MoEFFN keeps jax.lax.ragged_dot, the Sinkhorn chain and
            # the paged attention read keep their expressions, which GSPMD
            # partitions, and a K / V pool of heads of 128 keeps its kv_heads
            # axis (models/transformer.py: kv_rows_flat, paged_read_walk)
            import dataclasses

            module = module.clone(cfg=dataclasses.replace(module.cfg, mesh=self.mesh))
        self._module = module
        self._cfg = module.cfg
        self._abstract_init = None  # _init_shapes(), of this module
        refusal = self._state_layers_refusal() or self._window_layers_refusal()
        if refusal:
            raise ValueError(refusal)

        # Big-config random init (e.g. Llama-2-7B dims for capacity/perf
        # work): whole-tree f32 init is 4 bytes/param — 27 GB at 7B, over
        # single-chip HBM — so when the int8 serving path is requested and
        # the f32 tree would exceed 2 GiB, initialize leaf-by-leaf on
        # device, quantizing each leaf as it is made. Peak residency is the
        # final int8 tree plus one f32 leaf.
        streamed = (
            params is None
            and self.init_random
            and self.quantize == "int8"
            and self._init_nbytes_f32() > STREAM_INIT_THRESHOLD_BYTES
        )
        if params is None and not streamed:
            if not self.init_random:
                raise SeldonError(
                    "No checkpoint: pass model_uri or init_random=True", status_code=500
                )
            params = jax.jit(self._module.init)(
                jax.random.PRNGKey(self.seed), jnp.zeros((1, 8), jnp.int32)
            )

        # the stream mixing's leaves and the selection bias stay float32 in
        # every tree (models/leaves.py FLOAT32_AXES)
        from seldon_core_tpu.parallel.sharding import float32_leaves

        if not streamed:
            keep = float32_leaves(params, self._logical_axes())
            params = _cast_params(params, self.param_dtype, self._cfg.dtype, keep)

        # quantize BEFORE sharding: shard_params understands QuantizedTensor
        # leaves (q under the weight's logical spec, scale under the channel
        # axis), so int8 + tensor parallelism compose.
        self._dequant = lambda p: p
        if self.quantize:
            if self.quantize != "int8":
                raise SeldonError(f"unsupported quantize={self.quantize!r} (int8 only)", status_code=500)
            from seldon_core_tpu.ops.quantize import (
                QuantizedTensor, dequantize_params, quantize_params)
            from seldon_core_tpu.parallel.sharding import head_split_outputs, row_lookups

            # the projections that feed the head split are held output-major,
            # the order their consumer reads; the embedding table is marked as
            # the row lookup it is (ops/quantize.py)
            if streamed:
                params = self._streamed_quantized_init()
            else:
                axes = self._logical_axes()
                params = quantize_params(
                    params, out_major=head_split_outputs(params, axes), keep=keep,
                    lookup=row_lookups(params, axes))
            is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
            held = sum(is_q(leaf) and leaf.out_major
                       for leaf in jax.tree.leaves(params, is_leaf=is_q))
            logger.info("int8 weights: %d leaves held output-major "
                        "(the q/k/v projections)", held)
            # expert stacks and the embedding table stay int8 inside the
            # programs: MoEFFN's grouped matmul and the token lookup take them
            # as they are (ops/quantize.py). So does the head, in every program:
            # Transformer dequantizes it where it multiplies by it, which in a
            # prefill chunk is inside a conditional, and the compiler moves no
            # dequant into a branch (tests/test_tpu_program.py)
            # ... and so do the layers a prompt's chunk runs inside that
            # conditional (cfg.cross_decoder_layers: a decoder-hybrid-decoder's)
            held = ["lm_head"] + [f"layer_{i}" for i in getattr(
                self._cfg, "cross_decoder_layers", ())]

            def dequant(params):
                weights = dequantize_params(params, keep_consumed=True)
                kept = {name: params["params"][name] for name in held if name in params["params"]}
                return {**weights, "params": {**weights["params"], **kept}} if kept else weights

            self._dequant = dequant

        if self.mesh is not None:
            from seldon_core_tpu.parallel.sharding import shard_params

            params = shard_params(params, self.mesh, self._logical_axes())
        else:
            # a msgpack restore yields host (numpy) arrays; left there, jit
            # uploads every weight again on every call (servers/jaxserver.py)
            params = jax.device_put(params)
        self._params = params
        get_ledger().advance("load.rest")

        # Draft model for spec_mode="draft": loaded alongside the target,
        # replicated (it is small by construction — sharding it would cost
        # more in collectives than its forwards). Random init reuses the
        # server seed, so a draft configured identically to the target is
        # a bit-identical copy (the perfect-drafter fixture in
        # tests/test_speculative.py).
        self._draft_module = None
        self._draft_params = None
        self._draft_dequant = lambda p: p
        if self.draft_model or self.draft_model_uri:
            dname = self.draft_model or None
            dkw = dict(self.draft_model_kwargs)
            dparams = None
            if self.draft_model_uri:
                from seldon_core_tpu import storage

                dpath = storage.download(self.draft_model_uri)
                with open(os.path.join(dpath, "config.json")) as f:
                    dfile = json.load(f)
                dname = dname or dfile["model"]
                dkw = {**dfile.get("kwargs", {}), **dkw}
                dparams = self._load_params(dpath, dname, dkw)
            self._draft_module = get_model(dname, **dkw)
            self._draft_cfg = self._draft_module.cfg
            if self._draft_cfg.vocab_size != self._cfg.vocab_size:
                raise ValueError(
                    f"draft model vocab {self._draft_cfg.vocab_size} != "
                    f"target vocab {self._cfg.vocab_size}: draft proposals "
                    f"index the target's token space")
            if dparams is None:
                dparams = jax.jit(self._draft_module.init)(
                    jax.random.PRNGKey(self.seed), jnp.zeros((1, 8), jnp.int32))
            self._draft_params = _cast_params(
                dparams, self.param_dtype, self._draft_cfg.dtype)

        # Batched LoRA pool: built after params so pool dtype follows the
        # module compute dtype; preloads any configured adapter URIs
        # through the storage layer. A registry exists exactly when
        # lora_rank > 0 — the batcher keys its adapted-program choice on
        # ``adapter_registry is not None``.
        if self.lora_rank > 0:
            from seldon_core_tpu.runtime.adapters import AdapterRegistry

            # racelint: allow-unguarded-shared-state(load()-time build: runs once, before any serving thread or batcher loop exists)
            self.adapter_registry = AdapterRegistry(
                self._cfg, self.lora_rank, self.lora_max_adapters)
            for aname, uri in self.lora_adapters.items():
                self.adapter_registry.load_uri(aname, uri)

        if self.tokenizer_name == "bytes":
            self._tokenizer = ByteTokenizer()
        else:
            self._tokenizer = HFTokenizer(self.tokenizer_name)
        self.eos_id = self._eos_override if self._eos_override is not None else self._tokenizer.eos_id
        self.ready = True
        logger.info("LLMServer loaded %s (vocab=%d)", name, self._cfg.vocab_size)

    def _state_layers_refusal(self) -> Optional[str]:
        """What is not built over a layer that carries STATE (a conv, a
        linear-attention, a mamba or an s6 layer, cfg.layer_types; a gmu or a
        cross_attention layer beside them keeps none of its own), by name; None where nothing
        asked for is missing. Each of the first three would restart a sequence
        mid-way, and needs the state AT a token boundary, which pages do not
        hold."""
        state_layers = getattr(self._cfg, "state_layers", ())
        if not state_layers:
            return None
        kinds = " and ".join(sorted({self._cfg.layer_kind(i) for i in state_layers}))
        what = None
        if self.prefix_cache_size > 0:
            what = ("prefix_cache_size > 0: a prefix hit (the radix trie's shared pages, "
                    "generate()'s stored caches) restarts a sequence behind tokens it did "
                    "not run, and the state at that boundary is not kept")
        elif self.spec_mode != "off":
            what = (f"spec_mode={self.spec_mode!r}: a rejected draft rolls the cache back "
                    "by positions, and the state cannot be rolled back")
        elif self.disaggregation != "off":
            what = ("disaggregation='remote_prefill': the hand-off exports and imports "
                    "pages, and the state a prefill worker leaves is not among them")
        elif self.tensor_parallel > 1 or self.sequence_parallel > 1 or self.mesh is not None:
            what = ("tensor / sequence parallelism or a mesh: no sharding of the per-slot "
                    "state blocks, nor of the layers' input projections, is built")
        elif self.lora_rank > 0:
            what = "lora_rank > 0: adapters target the attention and dense-FFN projections"
        return what and (f"a model with {kinds} layers (layer_types) keeps per-sequence state "
                         "beside the paged cache, and does not compose with " + what)

    def _params_on(self, device):
        """Committed copy of the serving params on ``device`` (cached —
        one copy per prefill-slice device, built on a worker's first job).
        Disaggregation pays this duplication deliberately: on a real pod
        each slice owns its HBM anyway, and replicating the weights is
        what lets the prefill burst run without touching the decode
        slice. The cache is lock-guarded: two workers' first jobs race
        the build, and losing a copy would device_put the tree twice."""
        import jax

        with self._device_params_lock:
            params = self._device_params.get(device)
            if params is None:
                params = jax.device_put(self._params, device)
                self._device_params[device] = params
            return params

    def _init_shapes(self):
        """The module's variables as shapes (its logical axes among them),
        traced once a load: a 32-layer module takes about a second."""
        import jax
        import jax.numpy as jnp

        if self._abstract_init is None:
            self._abstract_init = jax.eval_shape(
                self._module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        return self._abstract_init

    def _logical_axes(self):
        from seldon_core_tpu.parallel.sharding import logical_axes_of

        return logical_axes_of(self._init_shapes())

    def _init_nbytes_f32(self) -> int:
        import jax

        return sum(leaf.size * 4 for leaf in jax.tree.leaves(self._init_shapes()))

    def _streamed_quantized_init(self):
        """Leaf-by-leaf on-device random init + int8 quantize; the leaves
        that ``quantize_params`` is told to hold output-major for a
        checkpoint are held so here.

        Semantics match the whole-tree path in kind (≥2-D float leaves
        become QuantizedTensor, 1-D leaves stay float) but not in exact
        values: leaves draw from per-leaf keys (seed folded with the leaf
        path) with variance-scaled normals (std = 1/sqrt(fan_in)) for ≥2-D
        leaves, ones for 1-D scale/weight (norm) leaves, zeros otherwise. A
        3-D leaf is a stack of matrices [e, d, f] and its fan_in is one
        matrix's d: counted over the stack, every expert's output would be
        sqrt(e) too small, and a wrong expert layer too faint to notice
        (latent attention's W_UK is held output-first: its own line below).
        One program a distinct (shape, std, layout), compiled side by side:
        the 32 identical layers of a 7B config cost ~a dozen compiles, not
        ~200, and a cold start waits for the longest, not their sum."""
        import zlib
        from concurrent.futures import ThreadPoolExecutor
        from functools import partial as _partial

        import jax
        import jax.numpy as jnp
        from jax.tree_util import keystr, tree_flatten_with_path

        from seldon_core_tpu.models.leaves import draw_small_leaf
        from seldon_core_tpu.ops.quantize import _register_pytree, quantize_array
        from seldon_core_tpu.parallel.sharding import (
            float32_leaves, head_split_outputs, row_lookups)

        _register_pytree()  # jit returns QuantizedTensor leaves
        target = jnp.dtype(self._cfg.dtype) if self.param_dtype == "auto" else (
            jnp.dtype(self.param_dtype) if self.param_dtype else jnp.float32
        )

        @_partial(jax.jit, static_argnums=(1, 2, 3, 4))
        def make_quantized(key, shape, std, out_major, lookup):
            # a stack is drawn as ONE matrix of its rows and reshaped: the same
            # values (the generator counts elements row-major whatever the
            # shape), and the TPU compiler takes 3 s over it where the 3-D draw
            # of an expert stack took 10-30 s, a minute of every cold start of
            # an MoE model (PR 38)
            rows = int(np.prod(shape[:-1]))
            w = jax.random.normal(key, (rows, shape[-1]), jnp.float32).reshape(shape) * std
            return quantize_array(w.astype(target), out_major=out_major, lookup=lookup)

        shapes = self._init_shapes()
        flat, treedef = tree_flatten_with_path(shapes)
        axes = self._logical_axes()
        transposed = jax.tree.leaves(head_split_outputs(shapes, axes))
        kept = jax.tree.leaves(float32_leaves(shapes, axes))
        indexed = jax.tree.leaves(row_lookups(shapes, axes))
        root = jax.random.PRNGKey(self.seed)

        def quantized(name, spec, keep, out_major, lookup):
            """make_quantized's static arguments for a leaf it draws, else None."""
            if keep or spec.ndim < 2 or not jnp.issubdtype(spec.dtype, jnp.floating):
                return None
            fan_in = int(np.prod(spec.shape[1 if spec.ndim == 3 else 0:-1]))
            if name.endswith("['w_uk']"):
                # latent attention's key expansion is held [H, nope,
                # latent], the order q~ = W_UK^T q reads it; as a map it
                # is k = W_UK c, so its fan-in is the latent axis
                fan_in = spec.shape[-1]
            if name.endswith("['dt_proj']"):
                # Mamba-1's step projection is published U(+- rank^-1/2): a
                # normal of THAT spread (a third of the variance below), so that
                # the seeded steps Delta are the sizes the layer is built for
                fan_in *= 3
            return spec.shape, 1.0 / float(fan_in) ** 0.5, out_major, lookup

        plan = [(keystr(path), path[-1].key, spec, keep,
                 quantized(keystr(path), spec, keep, out_major, lookup))
                for (path, spec), out_major, keep, lookup in zip(flat, transposed, kept, indexed)]
        # the distinct draws are compiled side by side: one after another they
        # were 73 s of a cold start on the chip's host (eleven programs of a
        # 12-layer Qwen3-Next, PR 38), and none waits for another
        draws = list(dict.fromkeys(draw for *_, draw in plan if draw is not None))
        with ThreadPoolExecutor(max(len(draws), 1)) as pool:
            compiled = dict(zip(draws, pool.map(
                lambda draw: make_quantized.lower(root, *draw).compile(), draws)))
        leaves = []
        for name, leaf_name, spec, keep, draw in plan:
            key = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if keep:
                # float32 as drawn, by the module's own rule for the leaf
                leaves.append(draw_small_leaf(self._cfg.small_leaf(leaf_name), key, spec.shape))
            elif draw is not None:
                leaves.append(compiled[draw](key))
            elif jnp.issubdtype(spec.dtype, jnp.floating):
                fill = 1.0 if ("norm" in name.lower() or "scale" in name.lower()
                               or name.lower().endswith("weight']")) else 0.0
                # target, not spec.dtype: the whole-tree path casts 1-D f32
                # leaves through _cast_params too, and the two init paths
                # must serve with the same norm-weight dtype
                leaves.append(jnp.full(
                    spec.shape, fill,
                    target if spec.dtype == jnp.float32 else spec.dtype))
            else:
                leaves.append(jnp.zeros(spec.shape, spec.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _load_params(self, path: str, name: str, cfg_kwargs: Dict[str, Any]):
        orbax_dir = os.path.join(path, "params")
        if os.path.isdir(orbax_dir):
            import orbax.checkpoint as ocp

            return ocp.StandardCheckpointer().restore(os.path.abspath(orbax_dir))
        msgpack = os.path.join(path, "params.msgpack")
        if os.path.exists(msgpack):
            import flax.serialization
            import jax
            import jax.numpy as jnp

            from seldon_core_tpu.models import get_model

            module = get_model(name, **cfg_kwargs)
            target = jax.eval_shape(
                lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
            )
            target = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), target)
            with open(msgpack, "rb") as f:
                blob = f.read()
            try:
                return flax.serialization.from_bytes(target, blob)
            except ValueError as orig:
                # checkpoint may hold only the 'params' collection (e.g. a
                # converted HF checkpoint); if the subtree restore also
                # fails, surface the ORIGINAL diagnostic (shape mismatch /
                # corruption), not the fallback's
                if "params" not in target:
                    raise
                try:
                    return flax.serialization.from_bytes({"params": target["params"]}, blob)
                except ValueError:
                    raise orig
        raise SeldonError(f"No params under {path}", status_code=500)

    # ------------------------------------------------------------------
    # Compiled stages
    # ------------------------------------------------------------------
    def _cache_shardings(self, b: int, max_len: int):
        """NamedSharding tree for the per-layer (k, v, pos) caches: max_len
        over 'seq' (the long-context axis), batch over 'data', kv_heads over
        'model' — each only when the mesh has that axis and it divides the
        dim. Returns None when the mesh can't shard anything."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        shape = dict(self.mesh.shape)

        def axis(name: str, dim: int):
            size = shape.get(name, 1)
            return name if size > 1 and dim % size == 0 else None

        dp = axis("data", b)
        sp = axis("seq", max_len)
        tp = axis("model", self._cfg.n_kv_heads)
        if not (dp or sp or tp):
            return None
        kv = NamedSharding(self.mesh, P(dp, sp, tp, None))
        pos = NamedSharding(self.mesh, P(dp, sp))
        if self.kv_cache_dtype == "int8":
            # int8 layout adds f32 [b, max_len, kvh] scale planes, sharded
            # alongside their values
            scale = NamedSharding(self.mesh, P(dp, sp, tp))
            return [(kv, scale, kv, scale, pos) for _ in range(self._cfg.n_layers)]
        return [(kv, kv, pos) for _ in range(self._cfg.n_layers)]

    def _get_extend(self, b: int, slen: int, max_len: int, donate: bool = False):
        """Suffix prefill: write ``slen`` tokens into an EXISTING cache at
        offset ``start`` (prefix-cache continuation). Padded slots carry
        PAD_POS positions, so they are never attended.

        ``donate=True`` donates the input cache buffers to the output (the
        scatter updates in place instead of copying the whole cache) — only
        safe when the caller's caches are NOT shared, so the prefix-cache
        continuation path (whose input caches stay live as a stored prefix
        entry) keeps the copying default."""
        key = ("extend", b, slen, max_len, donate)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        import jax

        module = self._module
        deq = self._dequant

        @partial(jax.jit, donate_argnums=(1,) if donate else ())
        def extend(params, caches, tokens, positions, start):
            logits, caches = module.apply(
                deq(params), tokens, positions=positions, caches=caches,
                cache_index=start,
            )
            return logits, caches

        self._prefill_cache[key] = extend
        return extend

    @staticmethod
    def _entry_nbytes(caches, last_logits) -> int:
        n = int(getattr(last_logits, "nbytes", 0))
        for layer in caches:
            for arr in layer:
                n += int(getattr(arr, "nbytes", 0))
        return n

    def clear_prefix_cache(self) -> None:
        """Drop every cached prefix AND its byte accounting. Clearing the
        OrderedDict directly instead leaves ``_prefix_bytes`` stuck at the
        old total, and once that phantom total nears the budget every later
        store immediately self-evicts — a permanent, silent 0% hit rate
        (found at 7B where one entry is ~300 MB of the 512 MB default).
        The continuous batcher's radix prefix cache (runtime/radix.py)
        clears alongside: both layers must read as cold together."""
        with self._prefix_lock:
            self._prefix_cache.clear()
            self._prefix_index.clear()
            self._prefix_bytes = 0
        svc = getattr(self, "_batcher_service", None)
        radix = getattr(svc.batcher, "_radix", None) if svc is not None else None
        if radix is not None:
            radix.clear()

    def _prefix_lookup(self, tokens: List[int],
                       max_len: Optional[int] = None):
        """Longest cached prefix of ``tokens`` with a compatible
        kv_cache_dtype; returns (prefix_len, entry_max_len, caches,
        last_logits) or None. With ``max_len`` set, only entries of exactly
        that cache length serve — generate()'s dense path reuses the whole
        cache object, so its geometry must match. Exact full-prompt hits
        return the stored logits so prefill is skipped entirely. The dtype
        check matters: a bf16 3-tuple cache fed to an int8-configured
        decode (or vice versa) would be structurally wrong, so a dtype
        flip must read as a miss, never a crash.

        Lookup walks the trie index (one pass over the prompt, O(prompt)
        node steps) instead of scanning entries: the lock hold time no
        longer scales with cache population
        (tests/test_kv_cache.py pins the regression). The continuous
        batcher does NOT call this — its prefix reuse is the page-pool
        radix trie (runtime/radix.py), which shares pages instead of
        reusing dense cache objects."""
        with self._prefix_lock:
            best = None
            for key in self._prefix_index.candidates(tokens):
                entry_max_len, entry_kvd, caches, last_logits, _nb = \
                    self._prefix_cache[key]
                if entry_kvd != self.kv_cache_dtype:
                    continue
                if max_len is not None and entry_max_len != max_len:
                    continue
                # candidates arrive shortest -> longest: the last passer
                # is the longest compatible prefix
                best = (len(key), entry_max_len, caches, last_logits)
            if best is not None:
                self._prefix_cache.move_to_end(tuple(tokens[: best[0]]))
                # hit accounting lives under the same lock as the cache it
                # describes (concurrent generate() calls race the bump)
                self._prefix_hits += 1
            return best

    def _prefix_store(self, tokens: List[int], max_len: int, caches, last_logits):
        key = tuple(tokens)
        nbytes = self._entry_nbytes(caches, last_logits)
        if self.prefix_cache_bytes and nbytes > self.prefix_cache_bytes:
            # A single over-budget entry would evict everything else. Warn
            # (once) instead of silently never populating: a large-model
            # config can exceed the default budget on every entry, which
            # would otherwise look like a mysterious 0% hit rate.
            if not getattr(self, "_prefix_overbudget_warned", False):
                self._prefix_overbudget_warned = True
                logger.warning(
                    "prefix cache entry (%d bytes) exceeds prefix_cache_bytes "
                    "(%d); nothing will be cached — raise prefix_cache_bytes "
                    "for this model size", nbytes, self.prefix_cache_bytes)
            return
        with self._prefix_lock:
            old = self._prefix_cache.pop(key, None)
            if old is not None:
                self._prefix_bytes -= old[-1]
            else:
                self._prefix_index.add(key)
            self._prefix_cache[key] = (
                max_len, self.kv_cache_dtype, caches, last_logits, nbytes)
            self._prefix_bytes += nbytes
            while self._prefix_cache and (
                len(self._prefix_cache) > self.prefix_cache_size
                or (self.prefix_cache_bytes
                    and self._prefix_bytes > self.prefix_cache_bytes)
            ):
                evicted_key, entry = self._prefix_cache.popitem(last=False)
                self._prefix_index.remove(evicted_key)
                self._prefix_bytes -= entry[-1]

    def _get_prefill(self, b: int, plen: int, max_len: int):
        key = (b, plen, max_len)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        import jax

        from seldon_core_tpu.models.cache import init_kv_caches

        module, cfg = self._module, self._cfg
        deq = self._dequant

        kvd = self.kv_cache_dtype

        def prefill(params, tokens, positions):
            caches = init_kv_caches(cfg, tokens.shape[0], max_len, kvd)
            logits, caches = module.apply(
                deq(params), tokens, positions=positions, caches=caches, cache_index=0
            )
            return logits, caches

        cache_shardings = self._cache_shardings(b, max_len)
        if cache_shardings is not None:
            # pin the cache layout at the jit boundary: decode then runs
            # sequence-parallel attention over the sharded slices
            fn = jax.jit(prefill, out_shardings=(None, cache_shardings))
        else:
            fn = jax.jit(prefill)
        self._prefill_cache[key] = fn
        return fn

    def _get_decode(self, b: int, max_len: int, donate: bool = True):
        """Compiled decode scan. ``donate=True`` (default) donates the input
        cache pytree to the output: XLA aliases the buffers, so the per-step
        ``dynamic_update_slice`` writes reuse the prefill's cache in place
        instead of copying the whole multi-GB cache into the scan carry.
        generate() passes donate=False only when the caches are shared with
        the prefix cache (a donated buffer is dead to later readers). The
        token/position arrays canNOT be donated here — the scan returns only
        (tokens, caches), so they have no matching output buffer; the
        pipelined per-step variant (``_get_decode_step``) is the one that
        threads and donates that state."""
        key = (b, max_len, donate)
        fn = self._decode_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        module = self._module
        eos_id = self.eos_id
        top_k = self.top_k
        deq = self._dequant

        def decode(params, caches, last_tok, true_len, n_steps, rng, temperature):
            """last_tok [b], true_len [b]; returns (tokens [b, n_steps],
            final caches — returned so donation can alias input to output)."""

            sample = _batch_sampler(top_k)

            def step(carry, _):
                caches, tok, offset, done, key = carry
                positions = (true_len + offset)[:, None]
                cache_index = true_len + offset
                # dequant inside the scan body: the int8 copy is the one that
                # persists in HBM (hoisting the f32 copy out of the loop
                # would double weight residency for the whole decode)
                logits, caches = module.apply(
                    deq(params), tok[:, None], positions=positions, caches=caches,
                    cache_index=cache_index,
                )
                key, sub = jax.random.split(key)
                nxt = sample(logits[:, -1].astype(jnp.float32), sub, temperature)
                nxt = jnp.where(done, eos_id, nxt)
                done = done | (nxt == eos_id)
                return (caches, nxt, offset + 1, done, key), nxt

            done0 = jnp.zeros_like(last_tok, dtype=bool)
            (caches, _, _, _, _), toks = jax.lax.scan(
                step, (caches, last_tok, jnp.zeros_like(true_len), done0, rng), None,
                length=n_steps,
            )
            # the final caches are in the output ONLY so donate_argnums can
            # alias the cache argument onto them (input_output_alias in the
            # compiled HLO); generate() discards them
            return toks.T, caches  # [b, n_steps], caches

        donate_kw = dict(donate_argnums=(1,)) if donate else {}
        cache_shardings = self._cache_shardings(b, max_len)
        if cache_shardings is not None:
            # keep the scan carry on the prefill's sharded layout instead of
            # letting XLA gather the cache onto every device
            decode = jax.jit(
                decode,
                static_argnames=("n_steps",),
                in_shardings=(None, cache_shardings, None, None, None, None),
                **donate_kw,
            )
        else:
            decode = partial(jax.jit, static_argnames=("n_steps",), **donate_kw)(decode)
        self._decode_cache[key] = decode
        return decode

    def _get_first_token(self):
        """Compiled first-token draw for the ContinuousBatcher's activation:
        ``(logits [1, 1, vocab], key [2], temperature)`` ->
        ``(token, key', row)``, all on the device. ``logits`` is the one row
        the last chunk ran its head for (``_get_prefill_chunk``), ``row`` is
        that row in float32 (what a probe that asked for logits gets), the
        token is `_slot_sampler`'s draw on it with the request's key, and
        ``key'`` is the key the slot decodes on: the prompt's first token
        leaves the device through the batcher's drain like any step's, never
        by a sync of its own."""
        key = ("first_token",)
        fn = self._decode_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        sample = _slot_sampler(self.top_k)

        @jax.jit
        def first_token(logits, key, temperature):
            row = logits[0, 0].astype(jnp.float32)
            keys, tok = sample(key[None], row[None], temperature)
            return tok[0], keys[0], row

        self._decode_cache[key] = first_token
        return first_token

    def _get_first_draw(self):
        """generate()'s first-token draw, compiled: `_batch_sampler` over
        the prefill's last-position logits [b, vocab], the same function
        its decode scan samples every later token with."""
        key = ("first_draw",)
        fn = self._decode_cache.get(key)
        if fn is None:
            import jax

            fn = self._decode_cache[key] = jax.jit(_batch_sampler(self.top_k))
        return fn

    def _window_layers_refusal(self) -> Optional[str]:
        """What is not built over SLIDING-ATTENTION layers (cfg.layer_types), by
        name; None where nothing asked for is missing. Their pages behind the
        window are given back while a request lives (runtime/batcher.py: the
        window page class), so whatever restarts a sequence mid-way, or rolls
        it back, would need pages that are gone; the rest is what no test
        holds over the window's mask."""
        if not getattr(self._cfg, "window_layers", ()):
            return None
        what = None
        if self.prefix_cache_size > 0:
            what = ("prefix_cache_size > 0: a prefix hit (the radix trie's shared pages and "
                    "its copy-on-write page copy, generate()'s stored caches) restarts a "
                    "sequence behind tokens it did not run, and a window layer's pages "
                    "behind the window were given back")
        elif self.spec_mode != "off":
            what = (f"spec_mode={self.spec_mode!r}: a rejected draft rolls the cache back by "
                    "positions, over pages that may have been given back meanwhile")
        elif self.disaggregation != "off":
            what = ("disaggregation='remote_prefill': the hand-off exports and imports a "
                    "sequence's pages (export_pages), and a window layer keeps only those "
                    "inside its window")
        elif self.tensor_parallel > 1 or self.sequence_parallel > 1 or self.mesh is not None:
            what = ("tensor / sequence parallelism or a mesh: the window's walk is one "
                    "device's kernel, and no sharding of the window class's pools is built")
        elif self.kv_cache_dtype == "int8":
            what = ("kv_cache_dtype='int8': the int8 pool reads by the expression over the "
                    "whole block-table view, which no test holds to the window's bound")
        elif self.lora_rank > 0:
            what = "lora_rank > 0: no test holds adapters over the two page classes"
        return what and ("a model with sliding_attention layers (layer_types; sliding_window) "
                         "serves from two page classes, and does not compose with " + what)

    def _forward_with_aside(self, params, tokens, **kwargs):
        """``module.apply`` for the batcher's step programs: (logits, caches,
        aside). ``aside`` is what leaves a program beside its tokens and costs
        no sync of its own (the host reads it after the tokens have landed):
        for an MoE model ``moe_tokens`` [b, experts held] and ``moe_stats`` [6]
        (models/transformer.py moe_routing_stats) and ``moe_choice``
        [b, s, n_moe_layers, k], the experts every row took, which only a
        logits probe reads (its reference follows them); empty for a dense one."""
        if self._cfg.n_experts == 0:
            logits, caches = self._module.apply(self._dequant(params), tokens, **kwargs)
            return logits, caches, {}
        from seldon_core_tpu.models.transformer import moe_choices, moe_routing_stats

        (logits, caches), sown = self._module.apply(
            self._dequant(params), tokens, mutable=["moe"], **kwargs)
        moe_tokens, moe_stats = moe_routing_stats(sown["moe"], self._cfg)
        return logits, caches, {"moe_tokens": moe_tokens, "moe_stats": moe_stats,
                                "moe_choice": moe_choices(sown["moe"], self._cfg)}

    def _get_prefill_chunk(self, chunk: int, n_pages: int,
                           lora: bool = False):
        """Compiled chunked-prefill step for the PAGED continuous batcher:
        write ``chunk`` prompt tokens (one sequence, PAD_POS padding) into
        the global page pool through the slot's block-table row, reading the
        earlier chunks' KV back from the pool — so a long admission prefill
        runs piecewise between decode steps instead of stalling serving for
        its whole compile bucket (Sarathi-Serve-style chunked prefill;
        Agrawal et al., OSDI 2024). The pool pytree is donated: the scatter
        updates in place, and the batcher threads the returned pool into
        the next dispatch.

        ``head_row`` (int32 scalar, traced) is the row whose logits the caller
        will read: the prompt's last, in the chunk that holds it, and negative
        in every chunk before. The head runs for that one row (taken after the
        final norm, which stays over all rows: the row is then the all-rows
        form's bit for bit), under a ``lax.cond`` on
        ``head_row >= 0``: a chunk that is not a prompt's last reads no byte of
        the head and writes no logits (zeros come back; nobody reads them). ONE program a chunk shape either way.
        Returns (logits [1, 1, vocab] float32, pools, aside):
        ``_forward_with_aside``'s entries, for the chunk's live rows."""
        key = ("pchunk", chunk, n_pages, lora)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        import jax

        forward = self._forward_with_aside

        if lora:
            # adapted chunked prefill: the admitted sequence's adapter id
            # rides as a [1] array so its q/o/FFN deltas shape the hidden
            # states its KV is computed FROM (the k/v projections stay
            # base — runtime/adapters.py, the KV-purity invariant)
            @partial(jax.jit, donate_argnums=(1,))
            def prefill_chunk(params, pools, block_row, tokens, positions, head_row,
                              adapter_pool, adapter_ids):
                return forward(
                    params, tokens, positions=positions, caches=pools,
                    block_tables=block_row, head_row=head_row,
                    adapters=adapter_pool, adapter_ids=adapter_ids,
                )
        else:
            # ``state_slots``: a model with state layers (cfg.layer_types: conv,
            # linear attention) holds their per-slot state in the pool tree
            # beside the pages, and its chunk is told WHICH slot's state it
            # continues and leaves behind
            @partial(jax.jit, donate_argnums=(1,))
            def prefill_chunk(params, pools, block_row, tokens, positions, head_row,
                              state_slots=None):
                return forward(
                    params, tokens, positions=positions, caches=pools,
                    block_tables=block_row, head_row=head_row, state_slots=state_slots,
                )

        self._prefill_cache[key] = prefill_chunk
        return prefill_chunk

    def _get_handoff_import(self, n_pages: int,
                            staged_pages: Optional[int] = None):
        """Compiled decode-side KV-handoff import for DISAGGREGATED serving
        (runtime/disagg.py): copy a prefill worker's staged pages (staging
        pool rows RESERVED_PAGES..) into the decode pool pages the
        admission allocated, whole pages at a time. ``staged_pages`` is
        the STATIC page count of the transferred buffer — workers ship
        only a power-of-two bucket covering the prompt's written pages,
        not the whole staging pool, so interconnect bytes track prompt
        length (DECODE_NOTES.md "interconnect math") at a bounded
        O(log n_pages) compile count. ``n_valid`` (traced) masks the copy
        to the prompt's exact pages — rows past it (and NULL block-row
        entries) target TRASH_PAGE, so one compile serves every prompt
        length inside a bucket. The slot pool is donated (the scatter
        updates in place behind in-flight steps in device program order);
        the staged buffer is NOT — it is a transient dropped after the
        call. Cached on the server (like the prefill programs) so every
        batcher built on it shares one compile per bucket. Compiled-form
        contract: ``disagg.import_pages`` in tools/hlolint (zero host
        transfers, donation intact, bytes within the committed budget)."""
        m = n_pages if staged_pages is None else min(staged_pages, n_pages)
        key = ("handoff_import", n_pages, m)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        import jax

        from seldon_core_tpu.models import cache as kvcache

        @partial(jax.jit, donate_argnums=(0,))
        def import_pages(pools, staged, block_row, n_valid):
            return kvcache.import_pages(pools, staged, block_row, n_valid, m)

        self._prefill_cache[key] = import_pages
        return import_pages

    def _get_staging_pool_init(self, pool_pages: int, page_size: int):
        """Compiled zero-init of a prefill worker's staging page pool
        (runtime/disagg.py): cached on the server so M workers (and every
        rebuilt batcher) share one compile — each worker still executes it
        once and commits the result to its own device."""
        key = ("staging_init", pool_pages, page_size)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        import jax

        from seldon_core_tpu.models.cache import init_paged_kv_caches

        fn = jax.jit(lambda: init_paged_kv_caches(
            self._cfg, pool_pages, page_size, self.kv_cache_dtype))
        self._prefill_cache[key] = fn
        return fn

    def _get_decode_step_paged(self, slots: int, n_pages: int, k: int = 1,
                               lora: bool = False):
        """Compiled pipelined decode step for the ContinuousBatcher: runs
        ``k`` decode micro-steps device-side (``lax.scan``) over ``slots``
        slots of the page pool, with the sampling state IN the loop —
        per-slot rng keys, last token and next position all live on device
        and are threaded from output to input across calls, so the host
        never round-trips token/position state through NumPy between steps.
        The KV read/write is routed through per-slot block tables: an extra
        input, NOT donated and NOT modified by the step — the host updates
        them through the batcher's jitted table ops between dispatches, and
        device program order serializes those against in-flight steps.

        Returns ``(pools, last_tok, next_pos, keys, tokens[slots, k],
        aside)``: ``aside["logits"]`` [k, slots, vocab] float32 is what each
        token was sampled from (the host fetches a slot's rows only for a
        request that asked for logits), plus ``_forward_with_aside``'s
        entries, each with a leading [k]. The pools, position array and key
        array are donated (the per-step scatter updates in place; the caller
        reassigns from the outputs). ``last_tok`` is deliberately NOT
        donated: the stacked ``tokens`` output can alias the final-token
        carry buffer (reshape bitcasts), and the host reads ``tokens`` while
        the next step — which would invalidate a donated ``last_tok`` — is
        already in flight.

        Per-slot sampling reproduces generate()'s chain exactly (split then
        top-k categorical per step, one key per sequence), so a slot seeded
        like a generate() request emits identical tokens — the parity bar in
        tests/test_batcher_pipeline.py; the pool is read with the XLA gather
        (tests/test_paged_kv.py). The donation/transfer/dtype shape of the
        COMPILED step is pinned as llm.paged_decode_step_s4 in tools/hlolint
        (docs/static-analysis.md): changing the carry structure here must
        keep every donated leaf aliasable or CI goes red on the dropped
        donation."""
        key = ("pagedstep", slots, n_pages, k, lora)
        fn = self._decode_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        top_k = self.top_k
        forward = self._forward_with_aside

        def core(params, pools, last_tok, next_pos, keys, temperature,
                 block_tables, adapter_pool, adapter_ids):
            sample = _slot_sampler(top_k)

            def step(carry, _):
                pools, tok, pos, keys = carry
                logits, pools, aside = forward(
                    params, tok[:, None], positions=pos[:, None],
                    caches=pools, block_tables=block_tables,
                    adapters=adapter_pool, adapter_ids=adapter_ids,
                )
                last = logits[:, -1].astype(jnp.float32)
                keys, nxt = sample(keys, last, temperature)
                return (pools, nxt, pos + 1, keys), (nxt, {"logits": last, **aside})

            (pools, tok, pos, keys), (toks, aside) = jax.lax.scan(
                step, (pools, last_tok, next_pos, keys), None, length=k)
            return pools, tok, pos, keys, toks.T, aside  # tokens [slots, k]

        if lora:
            # adapted paged step (llm.lora_decode_step hlolint contract):
            # same donation shape as the base step; the adapter pool/ids
            # ride along un-donated like the block tables
            @partial(jax.jit, donate_argnums=(1, 3, 4))
            def decode_step(params, pools, last_tok, next_pos, keys,
                            temperature, block_tables, adapter_pool,
                            adapter_ids):
                return core(params, pools, last_tok, next_pos, keys,
                            temperature, block_tables, adapter_pool,
                            adapter_ids)
        else:
            @partial(jax.jit, donate_argnums=(1, 3, 4))
            def decode_step(params, pools, last_tok, next_pos, keys,
                            temperature, block_tables):
                return core(params, pools, last_tok, next_pos, keys,
                            temperature, block_tables, None, None)

        self._decode_cache[key] = decode_step
        return decode_step

    def _get_draft_prefill(self, b: int, plen: int, max_len: int):
        """DRAFT-model prompt prefill into a fresh dense cache (the
        batcher's draft admission, ``_draft_admit``): same shape contract as ``_get_prefill``
        but over the draft module; the logits are discarded — only the
        written KV matters, drafting always restarts from the last accepted
        target token."""
        key = ("draft_prefill", b, plen, max_len)
        fn = self._prefill_cache.get(key)
        if fn is not None:
            return fn
        import jax

        from seldon_core_tpu.models.cache import init_kv_caches

        module, cfg = self._draft_module, self._draft_cfg
        deq = self._draft_dequant

        def prefill(params, tokens, positions):
            caches = init_kv_caches(cfg, tokens.shape[0], max_len)
            logits, caches = module.apply(
                deq(params), tokens, positions=positions, caches=caches,
                cache_index=0)
            return logits, caches

        fn = jax.jit(prefill)
        self._prefill_cache[key] = fn
        return fn

    def _get_spec_step(self, slots: int, spec_k: int, hist_len: int, *,
                       mode: str = "ngram", n_pages: int,
                       lora: bool = False):
        """Compiled speculative decode step for the ContinuousBatcher: ONE
        dispatch drafts up to K tokens per slot, verifies them in a single
        K+1-token target forward, and accepts the longest prefix that
        agrees with the slot's exact sampling chain.

        Drafting. ``mode="ngram"`` runs a zero-weight prompt-lookup
        proposer (Saxena's prompt-lookup decoding; the self-draft family of
        Leviathan et al. 2023) over the slot's device-resident
        prompt+generated token history ``hist [S, hist_len]``: the longest
        (up to spec_ngram) trailing n-gram is matched against every earlier
        position — most recent longest match wins — and the K tokens that
        followed it are proposed. ``mode="draft"`` runs K+1 sequential
        greedy forwards of the small draft model over its own cache
        (drafting consumes NO slot rng — the chain belongs to the target).
        The draft cache is always DENSE [S, max_len] while the target's
        is the page pool: the draft is small by construction, so paging it
        would buy nothing and cost a second allocator. Either way the
        per-slot ``draft_cap`` input clamps the offer (the batcher's
        acceptance-rate controller + cache-edge headroom).

        Verification. The target forward feeds [last_tok, d_1..d_K] at
        positions next_pos..next_pos+K (columns past the cap carry PAD_POS:
        masked from attention, writes dropped/trash-redirected). Token j+1
        is then SAMPLED from the target logits at column j on generate()'s
        exact per-slot rng chain — split once per ACCEPTED token, never per
        forward — and the draft is accepted only while the sample equals
        it. This is the chain-exact form of the rejection-sampling
        correction: the emitted tokens are precisely the ones sequential
        decode would have emitted (greedy bit-exact, seeded sampling on the
        identical key sequence), speculation only changes how many arrive
        per forward (1..K+1, output ``n_acc``).

        Cache repair. Rows written for drafts that lost verification
        (positions next_pos+a..next_pos+K, and the draft model's own rows
        in draft mode) have their position entries reset to PAD_POS inside
        this same program — the reset_pages idiom — so the cache never
        holds tokens that lost verification: they are unattendable
        immediately and their rows are overwritten when the true tokens
        reach those positions.

        Returns ``(caches, last_tok, next_pos, keys, hist,
        tokens[S, K+1], n_acc[S])`` (+ draft caches in draft mode) with the
        decode-step donation discipline: caches, next_pos, keys, hist (and
        draft caches) donated; last_tok NOT (its buffer may alias the
        stacked token output the host still reads). The compiled form is
        pinned by the llm.verify_step_k4 / llm.draft_verify_step_k4
        contracts in tools/hlolint (zero host transfers, intact aliasing,
        cost bands)."""
        key = ("specstep", slots, spec_k, hist_len, mode, n_pages, lora)
        fn = self._decode_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.cache import PAD_POS, forget_positions

        module = self._module
        top_k_cfg = self.top_k
        deq = self._dequant
        K = int(spec_k)
        S = int(slots)
        H = int(hist_len)
        NGRAM = max(int(self.spec_ngram) or 3, 1)
        draft_mode = mode == "draft"
        if draft_mode:
            dmodule = self._draft_module
            ddeq = self._draft_dequant

        def core(params, caches, last_tok, next_pos, keys, temperature,
                 hist, draft_cap, bt, dparams, dcaches,
                 apool=None, aids=None):
            # verification samples through the SAME chain every compiled
            # decode step uses — the bit-exactness contract lives in
            # _slot_sampler, not in a local copy
            _sample = _slot_sampler(top_k_cfg)

            def sample(keys_, lg):
                return _sample(keys_, lg, temperature)

            cap = jnp.clip(draft_cap, 0, K)

            if draft_mode:
                # K+1 sequential greedy draft forwards: feeds t_0,d_1..d_K
                # so the draft cache covers every position the target may
                # accept (incl. the all-accepted bonus case)
                def dstep(carry, _):
                    dc, tok, pos = carry
                    dlg, dc = dmodule.apply(
                        ddeq(dparams), tok[:, None],
                        positions=pos[:, None], caches=dc,
                        cache_index=pos)
                    nxt = jnp.argmax(
                        dlg[:, -1].astype(jnp.float32), axis=-1
                    ).astype(tok.dtype)
                    return (dc, nxt, pos + 1), nxt

                (dcaches, _, _), dtoks = jax.lax.scan(
                    dstep, (dcaches, last_tok, next_pos), None, length=K + 1)
                drafts = dtoks.T[:, :K]
                dlen = cap
            else:
                # prompt-lookup proposer: matched-length score per earlier
                # position (prefix-AND over the trailing NGRAM tokens),
                # longest match wins, most recent breaks ties
                idx = jnp.arange(H)
                ok = jnp.ones((S, H), bool)
                length = jnp.zeros((S, H), jnp.int32)
                for j in range(NGRAM):
                    hj = hist[:, jnp.clip(idx - j, 0, H - 1)]
                    cj = jnp.take_along_axis(
                        hist, jnp.clip(next_pos - j, 0, H - 1)[:, None],
                        axis=1)
                    ok = ok & (hj == cj) & ((idx - j) >= 0)[None, :] \
                        & ((next_pos - j) >= 0)[:, None]
                    length = length + ok.astype(jnp.int32)
                cand = idx[None, :] < next_pos[:, None]
                score = jnp.where(cand & (length > 0),
                                  length * H + idx[None, :], -1)
                best = jnp.argmax(score, axis=1)
                has = jnp.take_along_axis(score, best[:, None], axis=1)[:, 0] >= 0
                offs = jnp.arange(1, K + 1)
                src = best[:, None] + offs[None, :]
                drafts = jnp.take_along_axis(
                    hist, jnp.clip(src, 0, H - 1), axis=1)
                dlen = jnp.where(
                    has,
                    jnp.sum((src <= next_pos[:, None]).astype(jnp.int32),
                            axis=1),
                    0)
                dlen = jnp.minimum(dlen, cap)

            cols = jnp.arange(K + 1)
            tokens_in = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            positions = jnp.where(cols[None, :] <= dlen[:, None],
                                  next_pos[:, None] + cols[None, :], PAD_POS)
            # the TARGET verify forward carries the per-slot adapters
            # (llm.lora_verify_step contract); the draft forwards above
            # stay base-model — proposals are only proposals, and the
            # chain-exact accept loop below enforces the ADAPTED target's
            # distribution either way
            logits, caches = module.apply(
                deq(params), tokens_in, positions=positions,
                caches=caches, block_tables=bt,
                adapters=apool, adapter_ids=aids)
            lg32 = logits.astype(jnp.float32)

            # chain-exact accept loop: sample column j -> token j+1; rng
            # advances ONLY while accepting, so the key state after this
            # step equals sequential decode's after the same tokens
            a = jnp.zeros((S,), jnp.int32)
            valid = jnp.ones((S,), bool)
            out_cols = []
            cur_keys = keys
            for j in range(K + 1):
                keys2, sj = sample(cur_keys, lg32[:, j])
                cur_keys = jnp.where(valid[:, None], keys2, cur_keys)
                a = a + valid.astype(jnp.int32)
                out_cols.append(jnp.where(valid, sj, 0))
                if j < K:
                    valid = valid & (sj == tokens_in[:, j + 1]) \
                        & (j + 1 <= dlen)
            toks = jnp.stack(out_cols, axis=1)  # [S, K+1]
            new_last = jnp.take_along_axis(toks, (a - 1)[:, None], axis=1)[:, 0]

            # history append: fed t_0 plus the a accepted samples (columns
            # past a land at index H -> dropped)
            wcols = jnp.arange(K + 2)
            wtok = jnp.concatenate([last_tok[:, None], toks], axis=1)
            wpos = jnp.where(wcols[None, :] <= a[:, None],
                             next_pos[:, None] + wcols[None, :], H)
            rows = jnp.arange(S)[:, None]
            hist = hist.at[rows, wpos].set(wtok, mode="drop")

            # reject repair: columns a..K lost verification — reset their
            # position rows to PAD_POS (unattendable now, overwritten when
            # the true tokens reach those positions). Surviving columns map
            # to PAD_POS write targets (the dense draft cache: dropped; the
            # pool: trash).
            rcols = jnp.arange(1, K + 1)
            rej = rcols[None, :] >= a[:, None]
            rpos = jnp.where(rej, next_pos[:, None] + rcols[None, :], PAD_POS)

            caches = forget_positions(caches, rpos, bt)
            if draft_mode:
                dcaches = forget_positions(dcaches, rpos)  # draft cache is dense
                return (caches, new_last, next_pos + a, cur_keys, hist,
                        toks, a, dcaches)
            return (caches, new_last, next_pos + a, cur_keys, hist, toks, a)

        # lora=True appends (adapter_pool, adapter_ids) to each signature
        # (un-donated, like the block tables); the donation shape of the
        # serving state is identical to the base variant
        if draft_mode and lora:
            @partial(jax.jit, donate_argnums=(1, 3, 4, 7, 10))
            def spec_step(params, pools, last_tok, next_pos, keys,
                          temperature, block_tables, hist, draft_cap,
                          draft_params, draft_caches, adapter_pool,
                          adapter_ids):
                return core(params, pools, last_tok, next_pos, keys,
                            temperature, hist, draft_cap, block_tables,
                            draft_params, draft_caches, adapter_pool,
                            adapter_ids)
        elif draft_mode:
            @partial(jax.jit, donate_argnums=(1, 3, 4, 7, 10))
            def spec_step(params, pools, last_tok, next_pos, keys,
                          temperature, block_tables, hist, draft_cap,
                          draft_params, draft_caches):
                return core(params, pools, last_tok, next_pos, keys,
                            temperature, hist, draft_cap, block_tables,
                            draft_params, draft_caches)
        elif lora:
            @partial(jax.jit, donate_argnums=(1, 3, 4, 7))
            def spec_step(params, pools, last_tok, next_pos, keys,
                          temperature, block_tables, hist, draft_cap,
                          adapter_pool, adapter_ids):
                return core(params, pools, last_tok, next_pos, keys,
                            temperature, hist, draft_cap, block_tables,
                            None, None, adapter_pool, adapter_ids)
        else:
            @partial(jax.jit, donate_argnums=(1, 3, 4, 7))
            def spec_step(params, pools, last_tok, next_pos, keys,
                          temperature, block_tables, hist, draft_cap):
                return core(params, pools, last_tok, next_pos, keys,
                            temperature, hist, draft_cap, block_tables,
                            None, None)

        self._decode_cache[key] = spec_step
        return spec_step

    # ------------------------------------------------------------------
    def generate(
        self,
        prompts: Sequence[Any],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        seed: Optional[int] = None,
    ) -> Dict[str, Any]:
        """prompts: list of strings or of int token lists/arrays."""
        if not self.ready:
            self.load()
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.cache import PAD_POS

        max_new = int(max_new_tokens or self.max_new_tokens)
        temp = self.temperature if temperature is None else float(temperature)

        token_lists: List[List[int]] = []
        text_mode = []
        for p in prompts:
            if isinstance(p, str):
                token_lists.append(self._tokenizer.encode(p))
                text_mode.append(True)
            else:
                # graftlint: allow-host-sync-in-hot-path(prompt ingress: p is caller-supplied host tokens, never a device array)
                token_lists.append([int(t) for t in np.asarray(p).ravel()])
                text_mode.append(False)
        if not token_lists:
            raise SeldonError("generate() needs at least one prompt")
        if any(len(t) == 0 for t in token_lists):
            raise SeldonError("empty prompt")

        n = len(token_lists)
        max_batch = self.batch_buckets[-1]
        if n > max_batch:
            # split oversized batches and merge (one compiled program per bucket)
            out_tokens, out_texts = [], []
            for i in range(0, n, max_batch):
                part = self.generate(
                    prompts[i : i + max_batch], max_new_tokens=max_new,
                    temperature=temp, seed=seed,
                )
                out_tokens.extend(part["tokens"])
                out_texts.extend(part["texts"])
            return {"tokens": out_tokens, "texts": out_texts}
        nb = _bucket(n, self.batch_buckets)
        longest = max(len(t) for t in token_lists)
        plen = min(_bucket(longest, self.len_buckets), self._cfg.max_seq_len)
        if longest > plen:
            logger.warning("prompt of %d tokens truncated to max_seq_len %d", longest, plen)
        token_lists = [t[-plen:] for t in token_lists]  # keep the prompt tail
        if self.prefix_cache_size > 0 and n == 1:
            # one shared cache size for all single-prompt requests — a
            # per-request max_len would make every different prompt-length
            # bucket a guaranteed prefix-cache miss. Never smaller than the
            # actual prompt bucket (over-long prompts exceed the top bucket).
            max_len = (
                max(plen, min(self.len_buckets[-1], self._cfg.max_seq_len))
                + max(max_new, self.max_new_tokens)
            )
        else:
            max_len = min(plen + max_new, self._cfg.max_seq_len + max_new)
        if self.mesh is not None:
            # round the cache length up to a multiple of the seq axis so the
            # KV cache can actually shard over it
            sp = dict(self.mesh.shape).get("seq", 1)
            if sp > 1:
                max_len = -(-max_len // sp) * sp

        tokens = np.zeros((nb, plen), np.int32)
        positions = np.full((nb, plen), PAD_POS, np.int32)
        true_len = np.ones((nb,), np.int32)  # dummy rows decode from slot 1
        last_tok = np.zeros((nb,), np.int32)
        for i, toks in enumerate(token_lists):
            L = len(toks)
            tokens[i, :L] = toks
            positions[i, :L] = np.arange(L)
            true_len[i] = L
            last_tok[i] = toks[-1]

        # Prefix cache: single-prompt requests skip recomputing the KV of a
        # previously-seen token prefix (e.g. a shared system prompt); only
        # the suffix prefills, at its own bucketed length.
        use_prefix = self.prefix_cache_size > 0 and n == 1 and nb == 1
        # Donate the cache buffers into the decode scan (in-place
        # dynamic_update_slice, no full-cache copy per call) — except when
        # the same cache object lives on as a prefix-cache entry, which a
        # donation would invalidate.
        decode = self._get_decode(nb, max_len, donate=not use_prefix)
        hit = self._prefix_lookup(token_lists[0], max_len) if use_prefix else None
        if hit is not None and hit[0] == len(token_lists[0]):
            _, _, caches, first_logits = hit
        elif hit is not None:
            p0, _, caches, _ = hit
            suffix = token_lists[0][p0:]
            L = len(suffix)
            slen = min(_bucket(L, self.len_buckets), max_len - p0)
            stoks = np.zeros((1, slen), np.int32)
            spos = np.full((1, slen), PAD_POS, np.int32)
            stoks[0, :L] = suffix
            spos[0, :L] = np.arange(p0, p0 + L)
            extend = self._get_extend(1, slen, max_len)
            logits, caches = extend(
                self._params, caches, jnp.asarray(stoks), jnp.asarray(spos),
                jnp.asarray(p0, jnp.int32),
            )
            # graftlint: allow-host-sync-in-hot-path(generate() is the synchronous API: the first sampled token is drawn on the host once per request, before decode dispatch)
            first_logits = np.asarray(logits[:, L - 1]).astype(np.float32)
            self._prefix_store(token_lists[0], max_len, caches, first_logits)
        else:
            prefill = self._get_prefill(nb, plen, max_len)
            logits, caches = prefill(self._params, jnp.asarray(tokens), jnp.asarray(positions))
            # next-token logits live at each sequence's last real slot
            # graftlint: allow-host-sync-in-hot-path(generate() is the synchronous API: first-token sampling happens on the host once per request)
            first_logits = np.asarray(
                logits[jnp.arange(nb), jnp.asarray(true_len) - 1]
            ).astype(np.float32)
            if use_prefix:
                self._prefix_store(token_lists[0], max_len, caches, first_logits)
        # explicit seed => reproducible; otherwise vary per request. The
        # fetch-and-increment is atomic under the lock: two concurrent
        # unseeded generate() calls must not share an rng chain (and the
        # count must not lose updates)
        with self._prefix_lock:
            request_index = self._request_count
            self._request_count += 1
        rng = jax.random.PRNGKey(
            int(seed) if seed is not None else self.seed + request_index
        )

        # one split per emitted token, the first included, and the device
        # sampler's order of the top-k (greedy takes no notice of the key)
        rng, sub = jax.random.split(rng)
        # graftlint: allow-host-sync-in-hot-path(generate() is the synchronous API: its first token is read once per request, before the decode scan is dispatched)
        first_tok = np.asarray(self._get_first_draw()(
            jnp.asarray(first_logits), sub, jnp.asarray(temp, jnp.float32)))

        out_tokens = [first_tok[:, None]]
        if max_new > 1:
            import time as _time

            self._last_decode_kv_bytes = self._entry_nbytes(caches, None)
            t0 = _time.perf_counter()
            toks, _ = decode(
                self._params, caches, jnp.asarray(first_tok), jnp.asarray(true_len),
                max_new - 1, rng, jnp.asarray(temp, jnp.float32),
            )
            # graftlint: allow-host-sync-in-hot-path(generate()'s one deliberate result sync: the whole fused decode ran device-side; callers that must not block use the pipelined batcher instead)
            toks = np.asarray(toks)  # blocks: the wall below covers device time
            self.observe("decode_step_s",
                         (_time.perf_counter() - t0) / (max_new - 1))
            out_tokens.append(toks)
        all_toks = np.concatenate(out_tokens, axis=1)[:n]  # drop batch padding

        results_tokens: List[List[int]] = []
        results_text: List[Optional[str]] = []
        for i in range(n):
            seq = all_toks[i].tolist()
            if self.eos_id in seq:
                seq = seq[: seq.index(self.eos_id)]
            results_tokens.append(seq)
            results_text.append(self._tokenizer.decode(seq) if text_mode[i] else None)
        return {"tokens": results_tokens, "texts": results_text}

    # ------------------------------------------------------------------
    # SeldonComponent surface
    # ------------------------------------------------------------------
    def predict(self, X, names: Sequence[str], meta: Optional[Dict] = None):
        if isinstance(X, (bytes, bytearray)):
            X = X.decode("utf-8")
        if isinstance(X, str):
            out = self.generate([X])
            return out["texts"][0]
        if isinstance(X, dict):
            prompts = X.get("prompts") or X.get("prompt")
            if prompts is None:
                raise SeldonError("jsonData needs 'prompts'")
            if isinstance(prompts, str):
                prompts = [prompts]
            out = self.generate(
                prompts,
                max_new_tokens=X.get("max_new_tokens"),
                temperature=X.get("temperature"),
                seed=X.get("seed"),
            )
            return {"texts": out["texts"], "tokens": out["tokens"]}
        # graftlint: allow-host-sync-in-hot-path(request ingress: X is the transport's host payload, never a device array)
        arr = np.atleast_2d(np.asarray(X, dtype=np.int64))
        prompts = [row[row >= 0] for row in arr]  # -1 right-padding
        out = self.generate(prompts)
        width = max(len(t) for t in out["tokens"])
        padded = np.full((len(prompts), width), -1, np.int64)
        for i, t in enumerate(out["tokens"]):
            padded[i, : len(t)] = t
        return padded

    def tags(self) -> Dict[str, Any]:
        # request/prefix-cache accounting mutates under _prefix_lock on the
        # serving path; the stats scrape reads it under the same lock
        with self._prefix_lock:
            out = {"llm_requests": self._request_count}
            if self.prefix_cache_size:
                out["prefix_cache_hits"] = self._prefix_hits
                out["prefix_cache_entries"] = len(self._prefix_cache)
        return out

    def prefix_match_len(self, prompt: Any) -> int:
        """Cached-prefix length (tokens) this server already holds for
        ``prompt`` — the cheap probe ReplicaSet's prefix-aware routing
        calls before dispatch (runtime/engine.py). Reads the batcher's
        page-pool radix trie when continuous batching is on, else the
        dense entry index; both are O(prompt) walks under their own
        locks, no device work, no pinning."""
        if not self.ready:
            return 0
        if isinstance(prompt, str):
            ids = self._tokenizer.encode(prompt)
        else:
            # graftlint: allow-host-sync-in-hot-path(routing probe ingress: prompt is caller-supplied host tokens, never a device array)
            ids = [int(t) for t in np.asarray(prompt).ravel()]
        svc = getattr(self, "_batcher_service", None)
        radix = getattr(svc.batcher, "_radix", None) if svc is not None \
            else None
        if radix is not None:
            return radix.match_len(ids)
        with self._prefix_lock:
            cands = self._prefix_index.candidates(ids)
            return len(cands[-1]) if cands else 0

    def flight_recorder(self):
        """The active batcher's flight recorder (runtime/flight.py), or
        None when tracing is off / no batcher service exists — the
        /debug/timeline + gRPC DebugTimeline data source
        (observability/timeline.py)."""
        svc = getattr(self, "_batcher_service", None)
        if svc is None:
            return None
        return getattr(svc.batcher, "_flight", None)

    def llm_stats(self) -> Dict[str, Any]:
        """Decode-bandwidth observability snapshot, consumed by
        MetricsRegistry.sync_llm at /metrics scrape time: resident KV bytes
        (continuous-batching slot caches + pinned prefix entries), slot
        occupancy, the KV bytes the last decode streamed per step, and the
        decode step-time observations accumulated since the last scrape
        (drained here — each is observed into the histogram exactly once)."""
        def drain(dq) -> List[float]:
            out: List[float] = []
            while True:
                try:
                    out.append(dq.popleft())
                except IndexError:
                    return out

        occupancy = 0.0
        slot_bytes = 0
        in_flight = 0
        inflight_hwm = 0
        depth = self.decode_pipeline_depth
        fuse = self.decode_fuse_steps
        page_stats = {"kv_pages_total": 0, "kv_pages_in_use": 0,
                      "kv_page_size": 0, "kv_page_fragmentation": 0.0,
                      "kv_page_sheds": 0, "state_bytes": 0,
                      "state_matrix_bytes": 0, "state_matrix_tiled_bytes": 0}
        spec_stats = {"spec_mode": self.spec_mode, "spec_k": self.spec_k,
                      "spec_accept_rate": 0.0,
                      "spec_tokens_per_forward": 0.0,
                      "spec_slot_steps_total": 0,
                      "spec_accept_rate_per_slot": [],
                      "spec_draft_overhead_fraction": 0.0}
        handoff_stats = {"disaggregation": self.disaggregation or "off",
                         "handoffs_total": 0,
                         "handoff_transfer_bytes_total": 0,
                         "handoff_queue_depth": 0,
                         "handoff_network_bytes_total": 0}
        # radix prefix cache (runtime/radix.py): cached/shared block
        # gauges + the hit/cow/eviction/bytes-saved lifetime counters
        # (metrics/registry.py seldon_llm_prefix_*)
        prefix_stats = {"prefix_cached_blocks": 0, "prefix_shared_pages": 0,
                        "prefix_hit_blocks": 0, "prefix_hit_tokens": 0,
                        "prefix_cow_copies": 0, "prefix_evicted_blocks": 0,
                        "prefix_bytes_saved": 0}
        # multi-tenant serving (docs/multitenancy.md): adapter-pool
        # occupancy/churn/bytes plus the scheduler's per-(tenant, class)
        # tallies — seldon_llm_adapter_* / seldon_tenant_*_total
        adapter_stats = {"adapter_loaded": 0, "adapter_evictions_total": 0,
                        "adapter_pool_bytes": 0}
        reg = getattr(self, "adapter_registry", None)
        if reg is not None:
            snap = reg.stats()
            adapter_stats = {k: snap[k] for k in adapter_stats}
        tenant_counters: List[dict] = []
        queue_by_class: Dict[str, int] = {}
        slots_active = 0
        loop_stats: Dict[str, Any] = {}
        svc = getattr(self, "_batcher_service", None)
        if svc is not None:
            batcher = svc.batcher
            slots_active = batcher.active_slots()
            occupancy = slots_active / max(batcher.S, 1)
            loop_stats = batcher._phases.stats()
            if batcher._moe is not None:
                # an MoE model's routing tallies (runtime/batcher.py
                # MoECounters; metrics/registry.py seldon_llm_moe_*)
                loop_stats.update(batcher._moe.stats())
            slot_bytes = self._entry_nbytes(batcher._caches, None)
            in_flight = batcher.steps_in_flight()
            inflight_hwm = batcher._inflight_hwm
            depth = batcher.pipeline_depth
            fuse = batcher.fuse_steps
            radix_stats = None
            if getattr(batcher, "_radix", None) is not None:
                # ONE trie walk per scrape: page_stats reuses the snapshot
                radix_stats = batcher._radix.stats()
                prefix_stats.update(radix_stats)
            page_stats = batcher.page_stats(radix_stats=radix_stats)
            if getattr(batcher, "spec_mode", "off") != "off":
                spec_stats.update(batcher.spec_stats())
            if getattr(batcher, "_remote", None) is not None:
                handoff_stats.update(batcher.handoff_stats())
            sched = getattr(batcher, "_pending", None)
            if hasattr(sched, "counters"):
                tenant_counters = sched.counters()
                queue_by_class = sched.depths()
        with self._prefix_lock:
            prefix_bytes = self._prefix_bytes
        # the columns the sampler's last TopK runs over in each step program
        # built so far (static: `_top_k_candidates`' rule on vocab and top_k)
        sampling = {"pagedstep": "decode_step", "specstep": "spec_step",
                    "first_token": "first_token"}
        sampler_columns = {
            sampling[key[0]]: sampler_topk_columns(self._cfg.vocab_size, self.top_k)
            for key in list(self._decode_cache) if key[0] in sampling}
        return {
            "kv_cache_dtype": self.kv_cache_dtype,
            "sampler_topk_columns": sampler_columns,
            # page-pool accounting (zeros until a batcher is attached):
            # in-use/total page gauge pair plus internal fragmentation —
            # the slack between tokens written and pages held
            **page_stats,
            "kv_cache_bytes": slot_bytes + prefix_bytes,
            "kv_occupancy": occupancy,
            "slots_active": slots_active,
            # the batcher loop's time budget (runtime/batcher.py LoopPhases):
            # loop_seconds / loop_phase_counts by phase, loop_part_seconds /
            # loop_part_counts by "<phase>.<part>", loop_handoffs, loop_turns,
            # slot_seconds (active slots x seconds, per turn)
            **loop_stats,
            # lifetime bucket tallies of the histograms counted on the loop
            # (sync_llm catches the Prometheus histograms up to them)
            "histograms": {k: h.snapshot() for k, h in self._hists.items()},
            "kv_bytes_per_step": self._last_decode_kv_bytes,
            "decode_step_times_s": drain(self._decode_step_times),
            # pipelined decode: dispatch (enqueue-only) vs sync (host block)
            # split, current/high-water steps-in-flight, and the host lag
            # observed at each drain (steps the host trails the device)
            "decode_dispatch_times_s": drain(self._decode_dispatch_times),
            "decode_sync_times_s": drain(self._decode_sync_times),
            "decode_host_lag_steps": drain(self._decode_host_lag),
            "decode_steps_in_flight": in_flight,
            "decode_inflight_hwm": inflight_hwm,
            "decode_pipeline_depth": depth,
            "decode_fuse_steps": fuse,
            # speculative decoding: aggregate + per-slot acceptance, the
            # accepted-tokens-per-verify-step observations accumulated
            # since the last scrape, and the draft compute-overhead
            # fraction (metrics/registry.py seldon_llm_spec_*)
            **spec_stats,
            "spec_accepted_per_step": drain(self._spec_accepted),
            # streaming latency (batcher on_token path): TTFT per request
            # and the gap observed before each surfaced token — the
            # headline pair disaggregation moves (seldon_llm_ttft_seconds /
            # seldon_llm_inter_token_seconds). Multi-token drains (fused /
            # speculative steps) surface their block in one burst, so a
            # block's trailing tokens record ~0 gaps by construction.
            "ttft_s": drain(self._ttft_times),
            "inter_token_s": drain(self._inter_token_times),
            # disaggregated serving: per-handoff wall (prefill + D2D
            # transfer + import) and the transfer-queue counters
            **handoff_stats,
            "handoff_times_s": drain(self._handoff_times),
            # radix prefix cache: block-level reuse counters + the
            # shared-page gauge (docs/performance.md "Radix prefix cache")
            **prefix_stats,
            # multi-tenant serving: adapter pool + per-tenant fairness
            # tallies + per-class TTFT drains (docs/multitenancy.md)
            **adapter_stats,
            "tenant_counters": tenant_counters,
            "queue_by_class": queue_by_class,
            "ttft_by_class": drain(self._ttft_by_class),
        }
