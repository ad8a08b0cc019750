"""The hlolint contract registry: the serving-critical jitted functions and
their declared compiled-form contracts.

Contracts compile a PRODUCTION-SHAPED configuration at test dims: the
bf16-compute transformer with the int8 KV cache (the PR 2 serving layout)
at llama-tiny sizes, on the CPU backend with the virtual 8-device mesh —
the same lowering environment as CI's unit tests. Budgets in budgets.json
are snapshots of THIS environment; the contracts are about structure
(aliases, transfers, dtypes, collective sets) and relative cost, which is
what survives the CPU-for-TPU substitution.

Shared fixtures are lazy singletons: one base server feeds the prefill /
extend / decode / decode-step / batcher contracts so the registry costs a
handful of tiny compiles, not a model load per contract.
"""

from __future__ import annotations

import os
import tempfile
import threading
from typing import Dict, List

from tools.hlolint.core import Contract

# tiny-but-production-shaped dims, shared by every LLM contract
PLEN = 16          # prompt bucket
MAX_LEN = 24       # cache length (prompt bucket + decode headroom)
SLOTS = 4          # continuous-batcher slots
N_STEPS = 7        # decode scan length (max_new_tokens - 1)
KV_HEADS = 2       # llama-tiny n_kv_heads
HEAD_DIM = 16      # llama-tiny head_dim
# the page pool (PR 7): 8-token pages, 3 pages/slot view, and an
# OVERSUBSCRIBED pool (10 pages = 8 usable + 2 reserved, vs the 12 a fully
# provisioned 4-slot pool would need) — the contract compiles the pool
# shape serving actually runs, so the cost budget records the step's
# bytes against a pool smaller than S x max_len
PAGE_SIZE = 8
PAGES_PER_SLOT = 3  # ceil(MAX_LEN / PAGE_SIZE)
POOL_PAGES = 10
# speculative decoding (PR 8): draft depth of the verify-step contracts —
# the serving default, so the cost budget records the K+1=5-token-wide
# verify forward serving actually dispatches
SPEC_K = 4
# reserved rows leading every staged page bucket (models/transformer.py
# RESERVED_PAGES — named locally so the contract dims read in one place)
RESERVED_PAGES_N = 2
# batched LoRA (ISSUE 15): the adapted-step contracts compile a small
# dense adapter pool — rank 2 x 4 rows (identity + 3 tenants). At these
# toy dims the adapter machinery is a far larger FRACTION of the step
# than at 7B (the 64-wide projections are nearly free while the
# gather+einsum overhead is fixed), so the rank is chosen to keep the
# adapted step INSIDE the plain step's tolerance band — the
# near-base-model-throughput claim tests/test_adapters.py pins against
# budgets.json; at serving dims the margin only widens.
LORA_RANK = 2
LORA_ADAPTERS = 4
# sparse MoE (ISSUE 25): a small OLMoE shape (QK-norm, top-4 of 16 experts,
# weights not renormalised), int8 weights with per-expert scales, bf16
# paged KV. The three sizes differ so that a shape names one thing: an
# expert stack is [16, 64, 32] or [16, 32, 64], the dense form's
# intermediate is [rows.., 16, 32]
MOE_EXPERTS = 16
MOE_TOP_K = 4
MOE_DIM = 64
MOE_WIDTH = 32


def ensure_platform() -> None:
    """Pin the lowering environment BEFORE jax initializes: CPU backend
    with 8 virtual devices (the CI mesh). Mirrors tests/conftest.py; the
    config update covers a caller whose environment names another
    platform."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")


_STATE: Dict[str, object] = {}
# --jobs builds contract artifacts in a thread pool; the lazy fixtures
# below are check-then-act on _STATE, so unlocked concurrent builders
# would each load (and compile) their own server. RLock because fixtures
# nest (_batcher builds on _base_server).
_STATE_LOCK = threading.RLock()


def _base_server():
    """bf16 compute + int8 KV llama-tiny LLMServer — the serving layout the
    PR 2/3 perf work targets, at test dims."""
    with _STATE_LOCK:
        if "server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="llama-tiny", model_kwargs={"dtype": "bfloat16"},
                init_random=True, max_new_tokens=N_STEPS + 1,
                len_buckets=(PLEN,), batch_buckets=(1, SLOTS), seed=7,
                kv_cache_dtype="int8",
            )
            s.load()
            _STATE["server"] = s
        return _STATE["server"]


def _tp_server():
    """tensor_parallel=2 over the virtual 8-mesh: the TP decode contract."""
    with _STATE_LOCK:
        if "tp_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="llama-tiny", model_kwargs={"dtype": "bfloat16"},
                init_random=True, max_new_tokens=N_STEPS + 1,
                len_buckets=(PLEN,), batch_buckets=(1,), seed=7,
                kv_cache_dtype="int8", tensor_parallel=2,
            )
            s.load()
            _STATE["tp_server"] = s
        return _STATE["tp_server"]


def _draft_server():
    """base-server layout plus a draft model (spec_mode='draft'): the
    draft shares the target's config — what matters to the contract is
    the compiled SHAPE of the fused draft+verify program, not drafting
    quality."""
    with _STATE_LOCK:
        if "draft_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="llama-tiny", model_kwargs={"dtype": "bfloat16"},
                init_random=True, max_new_tokens=N_STEPS + 1,
                len_buckets=(PLEN,), batch_buckets=(1, SLOTS), seed=7,
                kv_cache_dtype="int8", spec_mode="draft",
                draft_model="llama-tiny",
                draft_model_kwargs={"dtype": "bfloat16"},
            )
            s.load()
            _STATE["draft_server"] = s
        return _STATE["draft_server"]


def _lora_server():
    """base-server layout plus the batched-LoRA adapter pool
    (rank LORA_RANK=2, LORA_ADAPTERS=4 rows — see the constants' comment
    for why rank 2): the adapted decode/verify-step contracts. The
    pool rides into the compiled step as an un-donated pytree argument
    plus per-slot adapter ids — the registry swaps pools functionally on
    load/evict, so the program must never alias them."""
    with _STATE_LOCK:
        if "lora_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="llama-tiny", model_kwargs={"dtype": "bfloat16"},
                init_random=True, max_new_tokens=N_STEPS + 1,
                len_buckets=(PLEN,), batch_buckets=(1, SLOTS), seed=7,
                kv_cache_dtype="int8", lora_rank=LORA_RANK,
                lora_max_adapters=LORA_ADAPTERS,
            )
            s.load()
            _STATE["lora_server"] = s
        return _STATE["lora_server"]


def _moe_server():
    """int8 weights, bf16 compute OLMoE-shaped LLMServer at test dims."""
    with _STATE_LOCK:
        if "moe_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=MOE_DIM, n_layers=2, n_heads=4,
                    n_kv_heads=4, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE,
                    n_experts=MOE_EXPERTS, n_experts_per_token=MOE_TOP_K,
                    router_renormalize=False, qk_norm=True,
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["moe_server"] = s
        return _STATE["moe_server"]


# latent attention (ISSUE 29): DeepSeek-V2's block at test dims (a leading
# dense layer, then routed + shared experts; one LATENT_ROW-wide cached row
# a token for all heads)
MLA_HEADS = 4
MLA_NOPE = 24
MLA_LATENT = 32
LATENT_ROW = MLA_LATENT + 8


def _mla_server():
    """int8 weights, bf16 compute DeepSeek-V2-shaped LLMServer at test dims."""
    with _STATE_LOCK:
        if "mla_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=MOE_DIM, n_layers=2, n_heads=MLA_HEADS,
                    n_kv_heads=MLA_HEADS, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE,
                    n_experts=MOE_EXPERTS, n_experts_per_token=MOE_TOP_K,
                    router_renormalize=False, first_dense_layers=1,
                    dense_ffn_dim=96, n_shared_experts=2,
                    kv_lora_rank=MLA_LATENT, qk_nope_head_dim=MLA_NOPE,
                    qk_rope_head_dim=LATENT_ROW - MLA_LATENT, v_head_dim=MLA_NOPE,
                    rope_scaling={"type": "yarn", "factor": 40,
                                  "original_max_position_embeddings": 16,
                                  "mscale": 0.707, "mscale_all_dim": 0.707},
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["mla_server"] = s
        return _STATE["mla_server"]


# hyper-connections (ISSUE 31): Xing4.0's block at test dims: the same latent
# MoE with compressed queries, the sigmoid / selection-bias router and several
# residual streams mixed around every sub-layer (THREE here, not the model's
# four: MOE_TOP_K is 4, and the routed rows [t, top_k, dim] are float32 by
# design, which a signature of four streams could not be told from)
HC_STREAMS = 3


def _xing4_server():
    with _STATE_LOCK:
        if "xing4_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=MOE_DIM, n_layers=2, n_heads=MLA_HEADS,
                    n_kv_heads=MLA_HEADS, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE,
                    n_experts=MOE_EXPERTS, n_experts_per_token=MOE_TOP_K,
                    router_renormalize=True, routed_scaling_factor=2.0,
                    router_score="sigmoid", router_bias=True,
                    first_dense_layers=1, dense_ffn_dim=96, n_shared_experts=1,
                    kv_lora_rank=MLA_LATENT, qk_nope_head_dim=MLA_NOPE,
                    qk_rope_head_dim=LATENT_ROW - MLA_LATENT, v_head_dim=MLA_NOPE,
                    q_lora_rank=24, hc_mult=HC_STREAMS,
                    rope_scaling={"type": "yarn", "factor": 64,
                                  "original_max_position_embeddings": 16,
                                  "mscale": 1, "mscale_all_dim": 1},
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["xing4_server"] = s
        return _STATE["xing4_server"]


# a second kind of state (ISSUE 35): LFM2's block at test dims: gated short
# convolutions with a fixed [taps - 1, dim] block a slot beside paged GQA
# layers whose heads are narrower than a lane tile (two heads of 64 = one flat
# row of 128 a token), a norm per head, the sigmoid router behind a dense layer
HYBRID_DIM = 256
HYBRID_KV_ROW = 128


def _hybrid_server():
    with _STATE_LOCK:
        if "hybrid_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=HYBRID_DIM, n_layers=3, n_heads=4,
                    n_kv_heads=2, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE,
                    n_experts=MOE_EXPERTS, n_experts_per_token=MOE_TOP_K,
                    router_renormalize=True, router_renormalize_eps=1e-6,
                    router_score="sigmoid", router_bias=True,
                    first_dense_layers=1, dense_ffn_dim=96, qk_norm="head",
                    layer_types=("conv", "full_attention", "conv"),
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["hybrid_server"] = s
        return _STATE["hybrid_server"]


# a state entry of two arrays (ISSUE 38): Qwen3-Next's block at test dims: Gated
# DeltaNet layers whose per-slot state is three conv rows AND a float32 matrix a
# value head, beside a gated GQA layer of explicit head_dim and partial rotary;
# every layer MoE with a SHARE of the experts held (GDN_HELD of MOE_EXPERTS)
# behind a router over all of them, one gated shared expert
GDN_DIM = 256
GDN_HEADS = (2, 4, 32, 32)      # key heads, value heads, key dim, value dim
GDN_HELD = 8


def _gdn_server():
    with _STATE_LOCK:
        if "gdn_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            hk, hv, dk, dv = GDN_HEADS
            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=GDN_DIM, n_layers=3, n_heads=4,
                    n_kv_heads=2, head_dim=128, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE,
                    n_experts=MOE_EXPERTS, n_experts_per_token=MOE_TOP_K,
                    experts_first=4, experts_held=GDN_HELD,
                    router_renormalize=True, n_shared_experts=1,
                    shared_expert_gate=True, qk_norm="head", attn_gate=True,
                    partial_rotary_factor=0.25,
                    linear_num_key_heads=hk, linear_num_value_heads=hv,
                    linear_key_head_dim=dk, linear_value_head_dim=dv,
                    layer_types=("linear_attention", "full_attention",
                                 "linear_attention"),
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["gdn_server"] = s
        return _STATE["gdn_server"]


# Olmo-Hybrid's block at test dims: Gated DeltaNet layers whose state a head is
# NOT square and no whole lane tile ([32, 64]: two heads side by side along the
# lanes, models/cache.py pack_state), beta in (0, 2), beside a multi-head
# attention layer without a rotary embedding; the norms on the branches; a dense FFN
GDN_RECT_HEADS = (6, 32, 64)    # heads (key = value), key dim, value dim
GDN_RECT_SIDE = 2               # heads a lane row: 2 x 64 = one lane tile


def _gdn_rect_server():
    with _STATE_LOCK:
        if "gdn_rect_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            heads, dk, dv = GDN_RECT_HEADS
            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=GDN_DIM, n_layers=3, n_heads=2,
                    n_kv_heads=2, head_dim=128, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE, qk_norm=True,
                    rope_theta=None, norm_placement="branch",
                    linear_allow_neg_eigval=True, linear_dt_bias="range",
                    linear_num_key_heads=heads, linear_num_value_heads=heads,
                    linear_key_head_dim=dk, linear_value_head_dim=dv,
                    layer_types=("linear_attention", "full_attention",
                                 "linear_attention"),
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["gdn_rect_server"] = s
        return _STATE["gdn_rect_server"]


# granite-4.0-h's block at test dims: Mamba-2 layers whose state a head is
# [64, 128] as the published model's (8 heads in one group; held transposed,
# two heads side by side along the lanes: four units of [128, 128], whole
# (8, 128) float32 tiles, models/cache.py), beside a GQA layer without position at a softmax scale of the
# config's own; the four scalar multipliers, the tied table, a dense FFN
SSD_HEADS = (8, 64, 128)        # heads, head dim, state dim
SSD_SIDE = 2                    # heads a lane row: 2 x 64 = one lane tile


def _ssd_server():
    with _STATE_LOCK:
        if "ssd_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            heads, p, n = SSD_HEADS
            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=GDN_DIM, n_layers=3, n_heads=4,
                    n_kv_heads=2, ffn_dim=MOE_WIDTH,
                    max_seq_len=PAGES_PER_SLOT * PAGE_SIZE, rope_theta=None,
                    tie_embeddings=True, embedding_multiplier=12.0,
                    attention_multiplier=0.015625, residual_multiplier=0.22,
                    logits_scaling=8.0, mamba_n_heads=heads, mamba_d_head=p,
                    mamba_d_state=n,
                    layer_types=("mamba", "full_attention", "mamba"),
                    dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["ssd_server"] = s
        return _STATE["ssd_server"]


def _paged_batcher():
    with _STATE_LOCK:  # nests into _base_server's hold: RLock
        if "paged_batcher" not in _STATE:
            from seldon_core_tpu.runtime.batcher import ContinuousBatcher

            _STATE["paged_batcher"] = ContinuousBatcher(
                _base_server(), max_slots=SLOTS, max_len=MAX_LEN,
                page_size=PAGE_SIZE, pool_pages=POOL_PAGES,
                prefill_chunk=PAGE_SIZE)
        return _STATE["paged_batcher"]


def _cache_specs(batch: int):
    """ShapeDtypeStruct pytree of the int8 KV caches — the checks only
    need shapes/dtypes, so nothing is materialized."""
    import jax

    from seldon_core_tpu.models.cache import init_kv_caches

    s = _base_server()
    return jax.eval_shape(
        lambda: init_kv_caches(s._cfg, batch, MAX_LEN, s.kv_cache_dtype))


def _paged_cache_specs():
    """ShapeDtypeStruct pytree of the int8 paged pool (10 pages x 8
    tokens) — shapes/dtypes only, nothing materialized."""
    import jax

    from seldon_core_tpu.models.cache import init_paged_kv_caches

    s = _base_server()
    return jax.eval_shape(
        lambda: init_paged_kv_caches(
            s._cfg, POOL_PAGES, PAGE_SIZE, s.kv_cache_dtype))


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


# full-KV-cache dtype signatures in the LOWERED module: an f32 tensor of
# the whole cache shape means the int8 path materialized a dequantized
# (or upcast) copy of the cache — the exact regression the int8 KV work
# exists to prevent. bf16 full-cache tensors are the expected dequant
# target and are allowed.
def _f32_cache_sig(batch: int) -> str:
    return rf"tensor<{batch}x{MAX_LEN}x{KV_HEADS}x{HEAD_DIM}xf32>"


F32_CACHE_WHY = (
    "a full-cache f32 tensor in the int8 KV path means the quantized "
    "cache was dequantized/upcast wholesale (2-4x the HBM traffic the "
    "int8 layout bought back)"
)


# same regression class for the paged pool: a whole-pool f32 tensor means
# the int8 pages were dequantized/upcast wholesale
def _f32_pool_sig() -> str:
    return rf"tensor<{POOL_PAGES}x{PAGE_SIZE}x{KV_HEADS}x{HEAD_DIM}xf32>"


# the sparse MoE's two promises, as signatures in the LOWERED module. The
# dense formulation ("bsd,edf->bsef") computes every expert for every row
# and holds a floating [rows.., experts, width] intermediate; a dequantized
# expert stack is a floating [experts, dim, width] / [experts, width, dim]
MOE_DENSE_FORM = (
    rf"tensor<(\d+x)+{MOE_EXPERTS}x{MOE_WIDTH}x(bf16|f16|f32)>",
    "a floating [rows, n_experts, expert_width] result: every expert is "
    "computing every row (n_experts / top_k times the FLOPs, and the "
    "temporary), which the sparse expert FFN exists to avoid")
MOE_FLOAT_STACK = (
    rf"tensor<{MOE_EXPERTS}x({MOE_DIM}x{MOE_WIDTH}|{MOE_WIDTH}x{MOE_DIM})"
    r"x(bf16|f16|f32)>",
    "a floating copy of an int8 expert stack: the grouped matmul takes the "
    "int8 array and scales the product; dequantizing the stack writes and "
    "re-reads 2-4x its bytes every layer of every step")


# latent attention's promise: the cached view is never expanded into
# per-head keys or values. An expanded K or V of the gathered view is a
# floating [.., view rows, heads, nope (+ rope)] array
MLA_EXPANDED_KV = (
    rf"tensor<(\d+x)*{PAGES_PER_SLOT * PAGE_SIZE}x{MLA_HEADS}x"
    rf"({MLA_NOPE}|{MLA_NOPE + LATENT_ROW - MLA_LATENT})x(bf16|f16|f32)>",
    "a floating [view rows, heads, head width] array: the cached latents are "
    "being expanded into per-head keys or values, heads x (nope + v) / "
    "latent_row times the bytes of the view, every layer of every call; the "
    "absorbed read multiplies the latent rows as they are cached")


# ... and, for a TPU, the paged pool's read holds no gathered copy of the
# logical view either (ISSUE 32): the kernel of ops/latent_attention.py takes
# the pool as it is held. At dims the kernel takes (a latent part and a row
# of whole 128-lane tiles, 64-row pages; the contracts above are under them
# and keep the expression): a floating array of the view's rows a slot
LIVE_READ_LATENT, LIVE_READ_ROW, LIVE_READ_PAGE, LIVE_READ_PAGES = 128, 256, 64, 4
MLA_GATHERED_VIEW = (
    rf"tensor<({SLOTS}x{LIVE_READ_PAGES * LIVE_READ_PAGE}|{SLOTS * LIVE_READ_PAGES}x{LIVE_READ_PAGE}"
    rf"|{SLOTS}x{LIVE_READ_PAGES}x{LIVE_READ_PAGE})x{LIVE_READ_ROW}x(bf16|f16|f32)>",
    "a floating [slots, view rows, row] array: the paged latent pool is being "
    "gathered into a copy of the whole logical view (168 MB a layer at the "
    "served shapes, read twice more by the products) where the live-page "
    "kernel reads the pool's pages in place")


def _build_mla_live_page_read():
    """One decode step of a latent-attention block over the paged pool, at
    the smallest dims the live-page kernel takes."""
    ensure_platform()
    import jax

    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.models.cache import init_paged_kv_caches

    model = get_model(
        "transformer", vocab_size=96, dim=MOE_DIM, n_layers=1, n_heads=16, n_kv_heads=16,
        ffn_dim=MOE_WIDTH, max_seq_len=LIVE_READ_PAGES * LIVE_READ_PAGE,
        kv_lora_rank=LIVE_READ_LATENT, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        dtype="bfloat16")
    assert model.cfg.latent_row_dim == LIVE_READ_ROW
    tokens = _sds((SLOTS, 1), "int32")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    pools = jax.eval_shape(lambda: init_paged_kv_caches(
        model.cfg, 2 + SLOTS * LIVE_READ_PAGES, LIVE_READ_PAGE, "bf16"))

    def step(params, pools, tokens, positions, block_tables):
        return model.apply(params, tokens, positions=positions, caches=pools,
                           block_tables=block_tables)

    return jax.jit(step, donate_argnums=(1,)), (
        params, pools, tokens, tokens, _sds((SLOTS, LIVE_READ_PAGES), "int32"))


# the streams are bf16 in HBM: float32 is for the mixing's arithmetic, one
# stream at a time inside a fusion, and for the [.., n x dim] vector the maps
# are read from; a float32 [.., n, dim] array is the whole stream widened
HC_FLOAT32_STREAMS = (
    rf"tensor<(\d+x)+{HC_STREAMS}x{MOE_DIM}xf32>",
    "a float32 [.., streams, dim] array: the residual streams are held in the "
    "model's dtype and widened one stream at a time inside the mixing's "
    "fusions; the whole stream in float32 is twice its bytes written and "
    "re-read around every sub-layer")


HYBRID_FLOAT32_STATE = (
    rf"tensor<{SLOTS}x2x{HYBRID_DIM}xf32>",
    "a float32 [slots, taps - 1, dim] array: the conv layers' per-slot state is "
    "held, read and written in the model's dtype; the whole block in float32 "
    "is a widened copy of every slot's state a layer a call")
HYBRID_FLOAT_STACK = (
    rf"tensor<{MOE_EXPERTS}x({HYBRID_DIM}x{MOE_WIDTH}|{MOE_WIDTH}x{HYBRID_DIM})"
    r"x(bf16|f16|f32)>", MOE_FLOAT_STACK[1])
HYBRID_SPLIT_POOL = (
    rf"tensor<{POOL_PAGES}x{PAGE_SIZE}x2x{HYBRID_KV_ROW // 2}x(bf16|f32)>",
    "the page pool with its narrow heads split out, [pages, page, kv heads, "
    "64]: the pool is held as flat rows [pages, page, kv heads * 64] because a "
    "minor dimension of half a lane tile makes the chip's compiler re-lay and "
    "copy the whole pool (PR 35); the heads are split in the gathered view")


GDN_NARROW_STATE = (
    rf"tensor<{SLOTS}x{GDN_HEADS[1]}x{GDN_HEADS[2]}x{GDN_HEADS[3]}x(bf16|f16)>",
    "the linear-attention layers' matrix state [slots, value heads, key dim, "
    "value dim] in a 16-bit type: S is held, decayed and corrected in float32 "
    "(as the published implementation holds it); a narrowed copy is a rounding "
    "of every slot's state a layer a call")
GDN_FLOAT_STACK = (
    rf"tensor<({GDN_HELD}|{MOE_EXPERTS})x({GDN_DIM}x{MOE_WIDTH}|{MOE_WIDTH}x{GDN_DIM})"
    r"x(bf16|f16|f32)>", MOE_FLOAT_STACK[1])


_RECT_PACKED = (f"{GDN_RECT_HEADS[0] // GDN_RECT_SIDE}x{GDN_RECT_HEADS[1]}"
                f"x{GDN_RECT_SIDE * GDN_RECT_HEADS[2]}")
_RECT_A_HEAD = f"{GDN_RECT_HEADS[0]}x{GDN_RECT_HEADS[1]}x{GDN_RECT_HEADS[2]}"
GDN_RECT_NARROW_STATE = (
    rf"tensor<({SLOTS}|1)x({_RECT_PACKED}|{_RECT_A_HEAD})x(bf16|f16)>",
    GDN_NARROW_STATE[1] + " (a state [key dim, value dim] that is not square, two "
    "heads side by side along the lanes or a head a row)")
GDN_RECT_UNPACKED_STATE = (
    rf"tensor<{SLOTS}x{_RECT_A_HEAD}xf32>",
    "every slot's matrix state a head a row, [slots, heads, key dim, value dim] "
    "with a value dim of half a lane tile: the cache holds it two heads side by "
    "side along the lanes ([slots, heads / 2, key dim, 2 x value dim]: no padded "
    "lane in HBM) and the step's kernel reads and writes it as it lies; this "
    "array is a re-laid copy of every slot's state a layer a step (the "
    "expression's two passes over S, or an unpack around the kernel)")


_SSD_STATE = f"{SSD_HEADS[0] // SSD_SIDE}x{SSD_HEADS[2]}x{SSD_SIDE * SSD_HEADS[1]}"
_SSD_A_HEAD = (f"({SSD_HEADS[0]}x{SSD_HEADS[2]}x{SSD_HEADS[1]}|"
               f"{SSD_HEADS[0]}x{SSD_HEADS[1]}x{SSD_HEADS[2]})")
SSD_NARROW_STATE = (
    rf"tensor<({SLOTS}|1)x({_SSD_STATE}|{_SSD_A_HEAD})x(bf16|f16)>",
    "the mamba layers' matrix state (in the cache's layout or a head a row) in a 16-bit "
    "type: h is held, decayed and added to in float32 (as the published "
    "implementation holds it); a narrowed copy is a rounding of every slot's state a "
    "layer a call")
SSD_STATE_PASS = (
    rf"stablehlo\.(multiply|add|select|reduce|gather|dynamic_slice|dynamic_update_slice)\b"
    rf"[^\n]*tensor<{SLOTS}x({_SSD_STATE}|{_SSD_A_HEAD})xf32>",
    "an XLA op over every slot's h (as the cache lays it, [slots, heads / 2, state "
    "dim, 2 x head dim], or a head a row): in a step lowered for a TPU the recurrence "
    "is the repo's kernel (ops/ssd.py), which reads each slot's h once and writes it "
    "once in its own buffer AS IT LIES; a multiply, an add, a reduction or a gather "
    "over the whole block is the expression's further pass (h C read again), an "
    "unpack around the kernel or a gathered copy of the state, 2 MB a slot a layer "
    "a step")


# SmallThinker's block at dims the live-page kernel takes (16 query / 2 KV heads
# of 128: a K row of 256; 64-row pages, four a slot): a full-attention layer
# without position beside a sliding-attention layer (window 128 = two pages,
# rotary) served from the WINDOW page class, the router fed the block's input,
# ReGLU experts
SWA_PAGE, SWA_PAGES, SWA_WINDOW, SWA_CHUNK = 64, 4, 128, 64
SWA_HEADS = (16, 2, 128)        # query heads, KV heads, head dim
SWA_POOL_PAGES = 2 + SLOTS * SWA_PAGES
SWA_GATHERED_VIEW = (
    rf"tensor<({SLOTS}|1)x{SWA_PAGES * SWA_PAGE}x({SWA_HEADS[1]}x{SWA_HEADS[2]}|"
    rf"{SWA_HEADS[1] * SWA_HEADS[2]})x(bf16|f16|f32)>",
    "a floating [sequences, view rows, K / V row] array: a layer's paged pool is "
    "being gathered into a copy of the whole block-table view (a window layer's "
    "view is mostly NULL_PAGE: its pages behind the window were given back) where "
    "the live-page kernel walks the pages from the first live one to the last")


def _swa_server():
    with _STATE_LOCK:
        if "swa_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.servers.llmserver import LLMServer

            heads, kv_heads, hd = SWA_HEADS
            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=MOE_DIM, n_layers=2, n_heads=heads,
                    n_kv_heads=kv_heads, head_dim=hd, ffn_dim=MOE_WIDTH,
                    max_seq_len=SWA_PAGES * SWA_PAGE, rope_theta=1.5e6,
                    layer_types=("full_attention", "sliding_attention"),
                    rope_layout=(0, 1), sliding_window=SWA_WINDOW,
                    n_experts=MOE_EXPERTS, n_experts_per_token=MOE_TOP_K,
                    router_renormalize=True, ffn_act="relu",
                    router_input="layer_input", dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,),
                seed=7)
            s.load()
            _STATE["swa_server"] = s
        return _STATE["swa_server"]


def _swa_pool_specs():
    import jax

    from seldon_core_tpu.models.cache import init_paged_kv_caches, window_slot_pages

    window_pages = 2 + SLOTS * window_slot_pages(SWA_WINDOW, SWA_CHUNK, SWA_PAGE)
    return jax.eval_shape(lambda: init_paged_kv_caches(
        _swa_server()._cfg, SWA_POOL_PAGES, SWA_PAGE, "bf16", window_pages=window_pages))


def _build_swa_paged_decode_step():
    s = _swa_server()
    fn = s._get_decode_step_paged(SLOTS, SWA_PAGES, 1)
    tables = (_sds((SLOTS, SWA_PAGES), "int32"),) * 2     # (full, window)
    return fn, (s._params, _swa_pool_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"), tables)


def _build_swa_prefill_chunk():
    s = _swa_server()
    fn = s._get_prefill_chunk(SWA_CHUNK, SWA_PAGES)
    rows = (_sds((1, SWA_PAGES), "int32"),) * 2
    return fn, (s._params, _swa_pool_specs(), rows,
                _sds((1, SWA_CHUNK), "int32"), _sds((1, SWA_CHUNK), "int32"),
                _sds((), "int32"))


# Phi-4-mini-flash's stack at dims the live-page kernel takes (16 query / 4 KV
# heads of 64: 8 pairs over 2 groups, a K row of 256 = two KV heads of 128 as
# the reads see them; 64-row pages, four a slot; window 128): the plan of 8
# layers (s6, window, s6, window | s6 handing m up, full = the shared pool |
# gmu, cross), every kind of layer once or twice. ONE full page class entry
# (layer 5), written by it alone and read in place by it and by the cross
# layer; two window-class entries; three state blocks; two empty entries
SAMBAY_DIM, SAMBAY_INNER = 256, 512
SAMBAY_HEADS = (16, 4, 64)
SAMBAY_GATHERED_VIEW = (
    rf"tensor<({SLOTS}|1)x{SWA_PAGES * SWA_PAGE}x(2x128|4x64|256)x(bf16|f16|f32)>",
    "a floating [sequences, view rows, K / V row] array: the SHARED pool (the one full "
    "layer's, read again by every cross-attention layer) or a window layer's is being "
    "gathered into a copy of the whole block-table view where the live-page kernel "
    "reads the pool in place")
SAMBAY_NARROW_STATE = (
    rf"tensor<({SLOTS}|1)x8x{SAMBAY_INNER}x(bf16|f16)>",
    "an s6 layer's h [slots, d_state, d_inner] narrowed to 16 bits: a rounding of h "
    "after every token adds up over as many tokens as a channel remembers")


def _sambay_server():
    with _STATE_LOCK:
        if "sambay_server" not in _STATE:
            ensure_platform()
            from seldon_core_tpu.models.convert import sambay_layer_types
            from seldon_core_tpu.servers.llmserver import LLMServer

            heads, kv_heads, hd = SAMBAY_HEADS
            s = LLMServer(
                model="transformer",
                model_kwargs=dict(
                    vocab_size=96, dim=SAMBAY_DIM, n_layers=8, n_heads=heads,
                    n_kv_heads=kv_heads, head_dim=hd, ffn_dim=MOE_WIDTH,
                    max_seq_len=SWA_PAGES * SWA_PAGE, rope_theta=None, tie_embeddings=True,
                    layer_types=sambay_layer_types(8), sliding_window=SWA_WINDOW,
                    mamba_d_inner=SAMBAY_INNER, mamba_d_state=8, mamba_dt_rank=16,
                    memory_source=4, kv_source=5, differential=True, attention_bias=True,
                    norm="layer", dtype="bfloat16"),
                quantize="int8", init_random=True, len_buckets=(PLEN,), seed=7)
            s.load()
            _STATE["sambay_server"] = s
        return _STATE["sambay_server"]


def _sambay_pool_specs():
    import jax

    from seldon_core_tpu.models.cache import init_paged_kv_caches, window_slot_pages

    window_pages = 2 + SLOTS * window_slot_pages(SWA_WINDOW, SWA_CHUNK, SWA_PAGE)
    return jax.eval_shape(lambda: init_paged_kv_caches(
        _sambay_server()._cfg, SWA_POOL_PAGES, SWA_PAGE, "bf16", state_slots=SLOTS,
        window_pages=window_pages))


def _build_sambay_paged_decode_step():
    s = _sambay_server()
    fn = s._get_decode_step_paged(SLOTS, SWA_PAGES, 1)
    tables = (_sds((SLOTS, SWA_PAGES), "int32"),) * 2     # (full, window)
    return fn, (s._params, _sambay_pool_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"), tables)


def _build_sambay_prefill_chunk():
    s = _sambay_server()
    fn = s._get_prefill_chunk(SWA_CHUNK, SWA_PAGES)
    rows = (_sds((1, SWA_PAGES), "int32"),) * 2
    return fn, (s._params, _sambay_pool_specs(), rows,
                _sds((1, SWA_CHUNK), "int32"), _sds((1, SWA_CHUNK), "int32"),
                _sds((), "int32"), _sds((1,), "int32"))


def _pool_specs_of(server):
    import jax

    from seldon_core_tpu.models.cache import init_paged_kv_caches

    return jax.eval_shape(
        lambda: init_paged_kv_caches(server._cfg, POOL_PAGES, PAGE_SIZE, "bf16",
                                     state_slots=SLOTS))


def _moe_pool_specs():
    return _pool_specs_of(_moe_server())


# ----------------------------------------------------------------------
# builders
# ----------------------------------------------------------------------

def _build_moe_paged_decode_step():
    s = _moe_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _moe_pool_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_moe_prefill_chunk(chunk: int = PAGE_SIZE):
    s = _moe_server()
    fn = s._get_prefill_chunk(chunk, PAGES_PER_SLOT)
    return fn, (s._params, _moe_pool_specs(),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, chunk), "int32"), _sds((1, chunk), "int32"),
                _sds((), "int32"))


def _build_moe_prefill_chunk_pages():
    """A chunk of whole pages (two of 8 rows): ``paged_write_pages``."""
    return _build_moe_prefill_chunk(2 * PAGE_SIZE)


def _build_mla_paged_decode_step():
    s = _mla_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _pool_specs_of(s), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_mla_prefill_chunk(chunk: int = PAGE_SIZE):
    s = _mla_server()
    fn = s._get_prefill_chunk(chunk, PAGES_PER_SLOT)
    return fn, (s._params, _pool_specs_of(s),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, chunk), "int32"), _sds((1, chunk), "int32"),
                _sds((), "int32"))


def _build_mla_prefill_chunk_wide():
    """The same server's second chunk program, four pages wide (the batcher's
    wide chunk, runtime/batcher.py ``_chunk_width``, at test dims)."""
    return _build_mla_prefill_chunk(4 * PAGE_SIZE)


def _build_xing4_paged_decode_step():
    s = _xing4_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _pool_specs_of(s), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_xing4_prefill_chunk():
    s = _xing4_server()
    fn = s._get_prefill_chunk(PAGE_SIZE, PAGES_PER_SLOT)
    return fn, (s._params, _pool_specs_of(s),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, PAGE_SIZE), "int32"), _sds((1, PAGE_SIZE), "int32"),
                _sds((), "int32"))


def _build_hybrid_paged_decode_step():
    s = _hybrid_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _pool_specs_of(s), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_hybrid_prefill_chunk():
    """The chunk is told WHICH slot's state it continues (its last operand)."""
    s = _hybrid_server()
    fn = s._get_prefill_chunk(PAGE_SIZE, PAGES_PER_SLOT)
    return fn, (s._params, _pool_specs_of(s),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, PAGE_SIZE), "int32"), _sds((1, PAGE_SIZE), "int32"),
                _sds((), "int32"), _sds((1,), "int32"))


def _build_gdn_paged_decode_step():
    s = _gdn_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _pool_specs_of(s), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_gdn_prefill_chunk():
    """The chunk is told WHICH slot's state it continues (its last operand)."""
    s = _gdn_server()
    fn = s._get_prefill_chunk(PAGE_SIZE, PAGES_PER_SLOT)
    return fn, (s._params, _pool_specs_of(s),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, PAGE_SIZE), "int32"), _sds((1, PAGE_SIZE), "int32"),
                _sds((), "int32"), _sds((1,), "int32"))


def _build_gdn_rect_paged_decode_step():
    s = _gdn_rect_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _pool_specs_of(s), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_gdn_rect_prefill_chunk():
    s = _gdn_rect_server()
    fn = s._get_prefill_chunk(PAGE_SIZE, PAGES_PER_SLOT)
    return fn, (s._params, _pool_specs_of(s),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, PAGE_SIZE), "int32"), _sds((1, PAGE_SIZE), "int32"),
                _sds((), "int32"), _sds((1,), "int32"))


def _build_ssd_paged_decode_step():
    s = _ssd_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _pool_specs_of(s), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_ssd_prefill_chunk():
    s = _ssd_server()
    fn = s._get_prefill_chunk(PAGE_SIZE, PAGES_PER_SLOT)
    return fn, (s._params, _pool_specs_of(s),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, PAGE_SIZE), "int32"), _sds((1, PAGE_SIZE), "int32"),
                _sds((), "int32"), _sds((1,), "int32"))


def _build_prefill():
    s = _base_server()
    fn = s._get_prefill(1, PLEN, MAX_LEN)
    return fn, (s._params, _sds((1, PLEN), "int32"), _sds((1, PLEN), "int32"))


def _build_extend():
    s = _base_server()
    fn = s._get_extend(1, PLEN, MAX_LEN, donate=True)
    return fn, (s._params, _cache_specs(1), _sds((1, PLEN), "int32"),
                _sds((1, PLEN), "int32"), _sds((), "int32"))


def _build_decode_scan():
    s = _base_server()
    fn = s._get_decode(1, MAX_LEN, donate=True)
    return fn, (s._params, _cache_specs(1), _sds((1,), "int32"),
                _sds((1,), "int32"), N_STEPS, _sds((2,), "uint32"),
                _sds((), "float32"))


def _build_decode_scan_tp2():
    import jax

    s = _tp_server()
    fn = s._get_decode(1, MAX_LEN, donate=True)
    from seldon_core_tpu.models.cache import init_kv_caches

    caches = jax.eval_shape(
        lambda: init_kv_caches(s._cfg, 1, MAX_LEN, s.kv_cache_dtype))
    return fn, (s._params, caches, _sds((1,), "int32"), _sds((1,), "int32"),
                N_STEPS, _sds((2,), "uint32"), _sds((), "float32"))


def _build_batcher_insert():
    """The one whole-cache insert left: a draft model's prompt prefill
    landing in its dense [S, max_len] cache (spec_mode='draft')."""
    import jax

    from seldon_core_tpu.models.cache import init_kv_caches
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    s = _draft_server()
    b = ContinuousBatcher(
        s, max_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE_SIZE,
        pool_pages=POOL_PAGES, prefill_chunk=PAGE_SIZE)
    small = jax.eval_shape(
        lambda: init_kv_caches(s._draft_cfg, 1, MAX_LEN))
    return b._draft_insert, (b._draft_caches, small, _sds((), "int32"))


def _build_batcher_set_slot():
    b = _paged_batcher()
    return b._set_slot, (b._last_tok, b._next_pos, b._keys,
                         _sds((), "int32"), _sds((), "int32"),
                         _sds((), "int32"), _sds((2,), "uint32"))


def _build_first_token():
    """The prompt's first token, sampled on the device from the last
    chunk's logits (PR 26): the activation reads nothing, so the program
    that replaced the host-side draw must not reach the host either."""
    s = _base_server()
    return s._get_first_token(), (
        _sds((1, 1, s._cfg.vocab_size), "float32"),
        _sds((2,), "uint32"), _sds((), "float32"))


def _build_paged_decode_step():
    s = _base_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1)
    return fn, (s._params, _paged_cache_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"))


def _build_prefill_chunk():
    s = _base_server()
    fn = s._get_prefill_chunk(PAGE_SIZE, PAGES_PER_SLOT)
    return fn, (s._params, _paged_cache_specs(),
                _sds((1, PAGES_PER_SLOT), "int32"),
                _sds((1, PAGE_SIZE), "int32"), _sds((1, PAGE_SIZE), "int32"),
                _sds((), "int32"))


def _build_set_block_row():
    b = _paged_batcher()
    return b._set_block_row, (b._block_tables, _sds((), "int32"),
                              _sds((PAGES_PER_SLOT,), "int32"))


def _build_reset_pages():
    b = _paged_batcher()
    return b._reset_pages, (_paged_cache_specs(),
                            _sds((PAGES_PER_SLOT,), "int32"))


def _build_handoff_import():
    """Disaggregated KV handoff, decode-side import (PR 9): the staged
    pool a prefill worker moved device-to-device
    (runtime/disagg.py ``PrefillWorker``) scattered whole-pages into the
    slot pool through the admission's block row
    (runtime/batcher.py ``_get_handoff_import``). The staged pool has the
    worker's single-sequence shape: RESERVED_PAGES + pages-per-slot."""
    import jax

    from seldon_core_tpu.models.cache import RESERVED_PAGES, init_paged_kv_caches

    b = _paged_batcher()
    fn = b._get_handoff_import()
    s = _base_server()
    staged = jax.eval_shape(
        lambda: init_paged_kv_caches(
            s._cfg, RESERVED_PAGES + PAGES_PER_SLOT, PAGE_SIZE,
            s.kv_cache_dtype))
    return fn, (_paged_cache_specs(), staged,
                _sds((PAGES_PER_SLOT,), "int32"), _sds((), "int32"))


def _build_verify_step_k4():
    """ngram spec step over the page pool: the serving-default
    speculative hot function (self-draft, zero extra weights)."""
    s = _base_server()
    fn = s._get_spec_step(SLOTS, SPEC_K, MAX_LEN, mode="ngram",
                          n_pages=PAGES_PER_SLOT)
    return fn, (s._params, _paged_cache_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"),
                _sds((SLOTS, MAX_LEN), "int32"), _sds((SLOTS,), "int32"))


def _build_draft_verify_step_k4():
    """draft-model spec step: K+1 sequential draft forwards fused with the
    single K+1-token target verify over the page pool, the draft's own
    dense cache donated through the program alongside the pool."""
    import jax

    from seldon_core_tpu.models.cache import init_kv_caches

    s = _draft_server()
    fn = s._get_spec_step(SLOTS, SPEC_K, MAX_LEN, mode="draft",
                          n_pages=PAGES_PER_SLOT)
    dcaches = jax.eval_shape(
        lambda: init_kv_caches(s._draft_cfg, SLOTS, MAX_LEN))
    return fn, (s._params, _paged_cache_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"),
                _sds((SLOTS, MAX_LEN), "int32"), _sds((SLOTS,), "int32"),
                s._draft_params, dcaches)


def _build_lora_decode_step():
    """Batched-LoRA paged decode step (ISSUE 15): the plain pipelined
    step plus one gather+einsum pair per adapted q/o/FFN projection,
    factors gathered from the dense pool by the per-slot adapter ids.
    Serving state donates exactly like the plain step; the pool and ids
    are long-lived shared state and must NOT alias."""
    s = _lora_server()
    fn = s._get_decode_step_paged(SLOTS, PAGES_PER_SLOT, 1, lora=True)
    return fn, (s._params, _paged_cache_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"),
                s.adapter_registry.pool(), _sds((SLOTS,), "int32"))


def _build_lora_verify_step():
    """Batched-LoRA speculative verify step (ISSUE 15): the ngram
    draft+verify program with the per-slot adapter deltas applied in the
    TARGET forward (drafting stays base-model — the chain-exact accept
    loop enforces the adapted distribution either way)."""
    s = _lora_server()
    fn = s._get_spec_step(SLOTS, SPEC_K, MAX_LEN, mode="ngram",
                          n_pages=PAGES_PER_SLOT, lora=True)
    return fn, (s._params, _paged_cache_specs(), _sds((SLOTS,), "int32"),
                _sds((SLOTS,), "int32"), _sds((SLOTS, 2), "uint32"),
                _sds((), "float32"),
                _sds((SLOTS, PAGES_PER_SLOT), "int32"),
                _sds((SLOTS, MAX_LEN), "int32"), _sds((SLOTS,), "int32"),
                s.adapter_registry.pool(), _sds((SLOTS,), "int32"))


def _build_set_hist_row():
    b = _paged_batcher()
    return b._set_hist_row, (_sds((SLOTS, MAX_LEN), "int32"),
                             _sds((), "int32"), _sds((MAX_LEN,), "int32"))


def _build_cow_page_copy():
    """Radix prefix cache, copy-on-write page copy (PR 12): ONE page's
    values move src -> dst across layers, the position row masked to the
    valid token count — the only copy a prefix hit can cost (full shared
    blocks are block-table entries)."""
    b = _paged_batcher()
    return b._cow_page_copy, (_paged_cache_specs(), _sds((), "int32"),
                              _sds((), "int32"), _sds((), "int32"))


def _build_prefix_export():
    """Radix prefix cache, disaggregated prefix export (PR 12): gather the
    decode pool's cached-prefix pages into a handoff-shaped bucket (2
    reserved rows + a power-of-two page bucket) for the D2D ship to a
    prefill worker — the pool is NOT donated (the trie's pages stay
    live), and the bytes are the bucket's, never the pool's."""
    b = _paged_batcher()
    return b._export_pages, (_paged_cache_specs(),
                             _sds((RESERVED_PAGES_N + 2,), "int32"))


def _build_jaxserver_predict():
    ensure_platform()
    import jax.numpy as jnp

    with _STATE_LOCK:
        if "jaxserver" not in _STATE:
            import jax

            from seldon_core_tpu.models import get_model
            from seldon_core_tpu.servers.jaxserver import JAXServer, export_checkpoint

            m = get_model("mlp", features=(16,), num_classes=4)
            params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
            # held in _STATE so the checkpoint dir is removed at interpreter
            # exit instead of leaking one temp dir per hlolint run
            tmp = tempfile.TemporaryDirectory(prefix="hlolint-jaxserver-")
            _STATE["jaxserver_tmp"] = tmp
            export_checkpoint(tmp.name, "mlp", params,
                              kwargs={"features": (16,), "num_classes": 4},
                              input_shape=[8], use_orbax=False)
            js = JAXServer(model_uri=tmp.name, batch_buckets=(4,))
            js.load()
            _STATE["jaxserver"] = js
        js = _STATE["jaxserver"]
    return js._apply, (js._params, _sds((4, 8), "float32"))


def _build_ring_attention():
    ensure_platform()
    import jax

    from seldon_core_tpu.ops.ring_attention import ring_attention
    from seldon_core_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"seq": 8})
    fn = jax.jit(lambda q, k, v, p: ring_attention(q, k, v, p, p, mesh=mesh))
    qkv = _sds((1, 64, 4, HEAD_DIM), "bfloat16")
    return fn, (qkv, qkv, qkv, _sds((1, 64), "int32"))


# ----------------------------------------------------------------------
# the registry
# ----------------------------------------------------------------------

def all_contracts() -> List[Contract]:
    return [
        Contract(
            name="llm.prefill_b1",
            description="LLMServer prefill (b=1, plen=16) into the int8 cache",
            build=_build_prefill,
            check_transfers=True,
            forbid_dtypes=((_f32_cache_sig(1), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.extend_b1",
            description="LLMServer suffix prefill (donating variant): the "
                        "scatter must update the cache in place",
            build=_build_extend,
            donated=(1,),
            forbid_dtypes=((_f32_cache_sig(1), F32_CACHE_WHY),),
            collectives={},
        ),
        Contract(
            name="llm.decode_scan_b1",
            description="LLMServer fused decode scan (b=1): generate()'s "
                        "device-side token loop",
            build=_build_decode_scan,
            donated=(1,),
            forbid_dtypes=((_f32_cache_sig(1), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.decode_scan_tp2",
            description="decode scan under tensor_parallel=2 on the virtual "
                        "8-mesh: the TP collective budget",
            build=_build_decode_scan_tp2,
            donated=(1,),
            # GSPMD entry params are per-device shapes; dtype matching is
            # the shard-stable way to verify the cache donation survived
            alias_by_dtype=True,
            # 2 layers x (attention wo + ffn down) psums + the logits psum.
            # Anything beyond this set is a reshard the sharding annotations
            # never asked for.
            collectives={"all-reduce": 5},
            waivers={
                "collective:all-gather":
                    "sampling epilogue, not a cache reshard: top-k over the "
                    "vocab-sharded logits gathers [1,256] candidate scores "
                    "plus two [1,2] partial-result rows per step — bytes, "
                    "not the KV cache (first enforcing run, 2026-08)",
            },
        ),
        Contract(
            name="llm.paged_decode_step_s4",
            description="ContinuousBatcher pipelined decode step "
                        "(S=4, k=1, 8-token pages, oversubscribed 10-page "
                        "pool): THE hot function of served decode",
            build=_build_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.moe_paged_decode_step_s4",
            description="PAGED decode step of a sparse MoE (16 experts "
                        "top-4, int8 stacks): each row computes its own "
                        "experts, from int8",
            build=_build_moe_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.moe_prefill_chunk_c8",
            description="chunked admission prefill of the same sparse MoE: "
                        "the grouped matmul over the chunk's routed rows",
            build=_build_moe_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.moe_prefill_chunk_c16",
            description="the same model's chunk of WHOLE PAGES (two pages "
                        "of 8): K, V and the positions reach the bf16 pool "
                        "as three page-sized windows a leaf "
                        "(paged_write_pages); the scatter must update the "
                        "donated pool in place",
            build=_build_moe_prefill_chunk_pages,
            donated=(1,),
            forbid_dtypes=(MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.mla_paged_decode_step_s4",
            description="PAGED decode step of a latent-attention MoE "
                        "(DeepSeek-V2's block: one cached row a token for "
                        "all heads, read absorbed; routed + shared experts "
                        "behind a dense first layer)",
            build=_build_mla_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(MLA_EXPANDED_KV, MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.mla_prefill_chunk_c8",
            description="chunked admission prefill of the same model: the "
                        "chunk's rows read the latent view absorbed, and "
                        "the scatter (one page-sized window a page the "
                        "chunk can reach: a chunk of 8 is a page here) "
                        "updates the latent pool in place",
            build=_build_mla_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(MLA_EXPANDED_KV, MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.mla_prefill_chunk_c32",
            description="the same model's WIDE chunk (four pages of 8: what "
                        "a long prompt's chunks are while no other slot "
                        "streams), held to what the narrow one is: the "
                        "latent view read absorbed, the pool updated in "
                        "place, the experts from int8",
            build=_build_mla_prefill_chunk_wide,
            donated=(1,),
            forbid_dtypes=(MLA_EXPANDED_KV, MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.mla_live_page_read_s4",
            description="PAGED decode step of a latent-attention block at "
                        "dims the live-page kernel takes: lowered for a TPU "
                        "the read walks the pool's pages in place, no "
                        "gathered copy of the logical view",
            build=_build_mla_live_page_read,
            donated=(1,),
            forbid_dtypes=(MLA_GATHERED_VIEW,),
            lowering_platform="tpu",
            collectives={},
        ),
        Contract(
            name="llm.xing4_paged_decode_step_s4",
            description="PAGED decode step of a several-stream latent MoE "
                        "(Xing4.0's block: hyper-connections around both "
                        "sub-layers, compressed queries, sigmoid scores with "
                        "a selection bias): the streams stay in the model's "
                        "dtype, the latents unexpanded, the stacks int8",
            build=_build_xing4_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(HC_FLOAT32_STREAMS, MLA_EXPANDED_KV, MOE_DENSE_FORM,
                           MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.xing4_prefill_chunk_c8",
            description="chunked admission prefill of the same model",
            build=_build_xing4_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(HC_FLOAT32_STREAMS, MLA_EXPANDED_KV, MOE_DENSE_FORM,
                           MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.hybrid_paged_decode_step_s4",
            description="PAGED decode step of a model with conv layers "
                        "(LFM2's block: a fixed state block a slot beside "
                        "the pages of GQA layers with narrow heads): the "
                        "state blocks are donated with the pools and stay "
                        "in the model's dtype, the pool stays flat rows",
            build=_build_hybrid_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(HYBRID_FLOAT32_STATE, HYBRID_SPLIT_POOL,
                           MOE_DENSE_FORM, HYBRID_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.hybrid_prefill_chunk_c8",
            description="chunked admission prefill of the same model: the "
                        "chunk continues ONE slot's state and writes it "
                        "back into the donated block",
            build=_build_hybrid_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(HYBRID_FLOAT32_STATE, HYBRID_SPLIT_POOL,
                           MOE_DENSE_FORM, HYBRID_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.gdn_paged_decode_step_s4",
            description="PAGED decode step of a model with linear-attention "
                        "layers (Qwen3-Next's block: conv rows and a float32 "
                        "matrix state a slot beside the pages of a gated GQA "
                        "layer; a share of the experts held): BOTH state "
                        "arrays are donated with the pools, S stays float32, "
                        "no floating copy of an expert stack",
            build=_build_gdn_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(GDN_NARROW_STATE, MOE_DENSE_FORM, GDN_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.gdn_prefill_chunk_c8",
            description="chunked admission prefill of the same model: the "
                        "chunk runs the delta rule's chunked form, continues "
                        "ONE slot's two state arrays and writes them back "
                        "into the donated blocks",
            build=_build_gdn_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(GDN_NARROW_STATE, MOE_DENSE_FORM, GDN_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.gdn_rect_paged_decode_step_s4",
            description="PAGED decode step of a model whose linear-attention "
                        "state a head is not square and no whole lane tile "
                        "(Olmo-Hybrid's block: beta in (0, 2), branch norms, "
                        "attention without a rotary embedding, a dense FFN): "
                        "S is donated, stays float32 in the cache's layout "
                        "(two heads side by side along the lanes) and goes "
                        "through the rule's kernel as it lies",
            build=_build_gdn_rect_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(GDN_RECT_NARROW_STATE, GDN_RECT_UNPACKED_STATE),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.gdn_rect_prefill_chunk_c8",
            description="chunked admission prefill of the same model: the "
                        "chunked form unpacks ONE slot's state, continues it "
                        "in float32 and packs it back into the donated block",
            build=_build_gdn_rect_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(GDN_RECT_NARROW_STATE, GDN_RECT_UNPACKED_STATE),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.ssd_paged_decode_step_s4",
            description="PAGED decode step of a model with mamba layers "
                        "(granite-4.0-h's block: conv rows and a float32 "
                        "[64, 128] state a head a slot beside the pages of a "
                        "position-free GQA layer; four scalar multipliers, a "
                        "tied int8 table): both state arrays are donated with "
                        "the pools; lowered for a TPU the recurrence is the "
                        "kernel, h read once and written once a layer, no XLA "
                        "op and no gathered copy over the whole block",
            build=_build_ssd_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(SSD_NARROW_STATE, SSD_STATE_PASS),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.ssd_prefill_chunk_c8",
            description="chunked admission prefill of the same model: the "
                        "chunk runs the recurrence's chunked form, continues "
                        "ONE slot's two state arrays in float32 and writes "
                        "them back into the donated blocks",
            build=_build_ssd_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(SSD_NARROW_STATE,),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.swa_paged_decode_step_s4",
            description="PAGED decode step of a model with a sliding-attention "
                        "layer beside a full one (SmallThinker's block: RoPE a "
                        "layer, the router fed the block's input, ReGLU "
                        "experts), served from TWO page classes: the pools of "
                        "both classes are donated, each layer reads the table "
                        "of its class, and neither read holds an array of a "
                        "whole block-table view's shape",
            build=_build_swa_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(SWA_GATHERED_VIEW, MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.swa_prefill_chunk_c64",
            description="chunked admission prefill of the same model (one "
                        "sequence's 64 rows through both tables): whole-page "
                        "writes into the donated pools of both classes, the "
                        "window layer's walk from its first live page",
            build=_build_swa_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(SWA_GATHERED_VIEW, MOE_DENSE_FORM, MOE_FLOAT_STACK),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.sambay_paged_decode_step_s4",
            description="PAGED decode step of a decoder-hybrid-decoder "
                        "(Phi-4-mini-flash's plan at 8 layers: Mamba-1 state "
                        "blocks, differential window layers of the window page "
                        "class, ONE full layer whose pool a cross-attention "
                        "layer reads again, a gated memory unit): the shared "
                        "pool is donated, written by the full layer alone and "
                        "read IN PLACE by every layer that reads it (no copy, "
                        "no gathered [slots, view] array); h stays float32",
            build=_build_sambay_paged_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=(SAMBAY_GATHERED_VIEW, SAMBAY_NARROW_STATE),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.sambay_prefill_chunk_c64",
            description="chunked admission prefill of the same model (one "
                        "sequence's 64 rows): the layers up to the shared "
                        "pool's on the rows, the cross-decoder on ONE row "
                        "inside the conditional that skips the head; the "
                        "chunk's scan is the repo's kernel; whole-page "
                        "writes into the donated pools of both classes",
            build=_build_sambay_prefill_chunk,
            donated=(1,),
            forbid_dtypes=(SAMBAY_GATHERED_VIEW, SAMBAY_NARROW_STATE),
            lowering_platform="tpu",
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.prefill_chunk_c8",
            description="chunked admission prefill (chunk=8 tokens into "
                        "the paged int8 pool through a block-table row: "
                        "the five-leaf pool keeps one scatter row a "
                        "token): the scatter must update the pool in place",
            build=_build_prefill_chunk,
            donated=(1,),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.verify_step_k4",
            description="speculative ngram draft+verify step over the "
                        "paged pool (S=4, K=4): ONE K+1-token target "
                        "forward per dispatched turn — the PR 8 hot "
                        "function. Zero host transfers; caches / next_pos "
                        "/ keys / hist donated (last_tok is not: its "
                        "buffer may alias the stacked token output the "
                        "host reads)",
            build=_build_verify_step_k4,
            donated=(1, 3, 4, 7),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.draft_verify_step_k4",
            description="draft-model spec step (S=4, K=4): K+1 "
                        "sequential greedy draft forwards fused with the "
                        "single K+1-token target verify; BOTH caches (the "
                        "target's pool + the draft's dense cache) must "
                        "donate through the program",
            build=_build_draft_verify_step_k4,
            donated=(1, 3, 4, 7, 10),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.lora_decode_step",
            description="batched-LoRA paged decode step (S=4, k=1, rank-2 "
                        "pool x 4 rows): the plain pipelined step plus one "
                        "gather+einsum pair per adapted q/o/FFN projection "
                        "— adapter id 0 is the zero-delta identity, so this "
                        "program serves base and adapted slots alike. Same "
                        "donation shape as the plain step; the pool/ids are "
                        "shared state and must not alias. Its cost budget "
                        "must sit within the plain step's tolerance band "
                        "(tests/test_adapters.py pins it): near-base-model "
                        "throughput is the design claim",
            build=_build_lora_decode_step,
            donated=(1, 3, 4),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="llm.lora_verify_step",
            description="batched-LoRA speculative verify step (S=4, K=4, "
                        "paged): per-slot adapter deltas in the K+1-token "
                        "TARGET forward (ngram drafting stays base-model); "
                        "caches / next_pos / keys / hist donated like the "
                        "plain verify step, adapter pool/ids un-donated",
            build=_build_lora_verify_step,
            donated=(1, 3, 4, 7),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="batcher.set_hist_row",
            description="speculative token-history row write at admission: "
                        "donated like the other per-slot state (the host "
                        "keeps no mirror of the history)",
            build=_build_set_hist_row,
            donated=(0,),
            collectives={},
        ),
        Contract(
            name="batcher.set_block_row",
            description="ContinuousBatcher block-table row update "
                        "(admission activate / slot release): donated so "
                        "the table never copies behind in-flight steps",
            build=_build_set_block_row,
            donated=(0,),
            collectives={},
        ),
        Contract(
            name="batcher.reset_pages",
            description="newly-allocated page position reset (PAD_POS "
                        "scatter across layers): the pool must be donated "
                        "through it, never copied per allocation",
            build=_build_reset_pages,
            donated=(0,),
            collectives={},
        ),
        Contract(
            name="disagg.import_pages",
            description="disaggregated prefill handoff, decode-side "
                        "import (PR 9): the worker's staged pages scatter "
                        "whole-pages into the slot pool through the "
                        "admission's block row — ZERO host transfers (the "
                        "KV moved device-to-device and must stay on "
                        "device), slot pool donated (the import updates in "
                        "place behind in-flight steps; the staged pool is "
                        "a dropped transient, NOT donated), bytes within "
                        "the committed budget",
            build=_build_handoff_import,
            donated=(0,),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="batcher.cow_page_copy",
            description="radix prefix cache copy-on-write page copy "
                        "(PR 12): a slot continuing part-way into a "
                        "shared cached block copies that ONE page into "
                        "its own (values whole-page, position row masked "
                        "past the valid tokens) — pool donated so the "
                        "copy scatters in place, zero host transfers, "
                        "bytes budgeted at one page not a prefix gather",
            build=_build_cow_page_copy,
            donated=(0,),
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="disagg.prefix_export",
            description="radix prefix cache disaggregated export "
                        "(PR 12): cached-prefix pages gather into a "
                        "handoff-shaped bucket for the D2D ship to a "
                        "prefill worker (which then computes ONLY the "
                        "uncached suffix) — the pool is NOT donated (the "
                        "trie's pages stay live) and the cost budget "
                        "pins the bucket's bytes, never the pool's",
            build=_build_prefix_export,
            forbid_dtypes=((_f32_pool_sig(), F32_CACHE_WHY),),
            collectives={},
            cost=True,
        ),
        Contract(
            name="batcher.insert",
            description="ContinuousBatcher draft-cache insert "
                        "(spec_mode='draft'): the big [S, max_len] draft "
                        "cache must be donated through the scatter",
            build=_build_batcher_insert,
            donated=(0,),
            collectives={},
        ),
        Contract(
            name="llm.first_token",
            description="first-token draw of an activation: the step "
                        "sampler on the last chunk's logits row, token, "
                        "key and float32 row left on the device for "
                        "set_slot and the drain (no host transfer: the "
                        "admission paths read nothing)",
            build=_build_first_token,
            out_dtypes=((0, "s32"), (2, "f32")),
            collectives={},
        ),
        Contract(
            name="batcher.set_slot",
            description="ContinuousBatcher per-slot admission update of the "
                        "device-resident decode state",
            build=_build_batcher_set_slot,
            donated=(1, 2),
            collectives={},
        ),
        Contract(
            name="jaxserver.predict_b4",
            description="JAXServer jitted apply (tiny MLP checkpoint, "
                        "bucket=4): the generic predict hot path",
            build=_build_jaxserver_predict,
            collectives={},
            cost=True,
        ),
        Contract(
            name="ops.ring_attention_seq8",
            description="ring attention over the 8-way 'seq' mesh: one "
                        "rotating ppermute per buffer (k, v, positions)",
            build=_build_ring_attention,
            collectives={"collective-permute": 3},
            cost=True,
        ),
    ]
