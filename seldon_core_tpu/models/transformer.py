"""Decoder-only transformer (Llama-family) in Flax, sharding-aware.

Serves the BASELINE.json stretch config (Llama-2-7B on a v5e-8 pod). Written
TPU-first:

- all weights carry flax *logical* partitioning names; the parallel module
  maps them onto a device mesh (tp over 'model', dp over 'data', sequence
  parallel over 'seq') — XLA/GSPMD inserts the collectives over ICI.
- GQA attention, rotary embeddings, RMSNorm, SwiGLU — bfloat16 on the MXU.
- the cache tree (dense for ``generate()``, a page pool for the batcher) is
  models/cache.py's: the token mixers here make a call's rows, hand them to
  ``write_rows`` / ``put_state`` and choose how to READ (expression or kernel).
- the state layers' token mixers (``TransformerBlock`` picks one by the layer's
  kind) are models/state_mixers.py's; the norms, ``param_with_axes`` and the
  seeded small leaves are models/leaves.py's. Neither imports this file.
- optional mixture-of-experts FFN: sparse (each token computes its top-k
  experts only, a grouped matmul over the routed rows sorted by expert).

No reference counterpart: the reference (a serving platform) has no model code
at all; this is the native model family the TPU build adds (SURVEY.md §5
"Long-context / sequence parallelism: absent — design from scratch").
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from seldon_core_tpu.models.cache import (
    PAD_POS,
    TRASH_PAGE,
    dense_view,
    entry_is_int8,
    gather_paged_view,
    normalize_kv_cache_dtype,
    put_state,
    quantize_kv,
    write_rows,
)
from seldon_core_tpu.models.leaves import (
    RMSNorm,
    block_norm,
    normed_by,
    param_with_axes,
    small_leaf_init,
)
from seldon_core_tpu.models.registry import register_model
from seldon_core_tpu.models.state_mixers import (
    GatedDeltaNet,
    GatedMemoryUnit,
    Mamba1Mixer,
    Mamba2Mixer,
    ShortConv,
)

with_sharding_constraint = nn_partitioning.with_sharding_constraint

LAYER_KINDS = ("full_attention", "sliding_attention", "conv", "linear_attention", "mamba",
               "s6", "gmu", "cross_attention")
# the kinds that are ATTENTION (pages of K and V rows): a "sliding_attention"
# layer's mask has a lower bound too (``sliding_window``) and its pages are of
# the window class (models/cache.py WindowEntry), given back behind the window
ATTENTION_LAYER_KINDS = ("full_attention", "sliding_attention")
# the kinds whose layer holds NO pages of its own: a fixed block of STATE a
# sequence, which may be empty (a "gmu" reads the call's own rows of another
# layer's scan output, a "cross_attention" layer another layer's pages:
# ``memory_source`` / ``kv_source``)
STATE_LAYER_KINDS = ("conv", "linear_attention", "mamba", "s6", "gmu", "cross_attention")
# SambaY's three (Phi-4-mini-flash-reasoning): Mamba-1's selective scan, the
# gated memory unit and cross-attention over ONE layer's K/V
SAMBAY_LAYER_KINDS = ("s6", "gmu", "cross_attention")
SAMBAY_LAYERS_COMPOSE_REFUSAL = (
    "an 's6', 'gmu' or 'cross_attention' layer (layer_types; Mamba1Mixer, GatedMemoryUnit, "
    "differential cross-attention), differential attention, norm='layer' and attention_bias are "
    "built for one device beside per-head K/V attention with the bf16 cache and a dense FFN: not "
    "with a mesh, experts (n_experts > 0), latent attention (kv_lora_rank > 0), "
    "hyper-connections (hc_mult > 1), an MTP module, LoRA adapters, "
    "norm_placement='branch', qk_norm, attn_gate, a rotary embedding or kv_cache_dtype='int8': "
    "no model pairs them and no test holds them")
STATE_LAYERS_COMPOSE_REFUSAL = (
    "a 'conv', 'linear_attention' or 'mamba' layer (layer_types) does not compose with "
    "latent attention (kv_lora_rank > 0), hyper-connections (hc_mult > 1) or an "
    "MTP module: no model pairs them and no test holds them")
MAMBA_LAYERS_COMPOSE_REFUSAL = (
    "a 'mamba' layer (layer_types; Mamba2Mixer) is built for one device beside attention and "
    "a dense FFN: not with a mesh, experts (n_experts > 0: the family's members with experts "
    "are not built) or 'linear_attention' layers: no model pairs them and no test holds them")
WINDOW_LAYERS_COMPOSE_REFUSAL = (
    "a 'sliding_attention' layer (layer_types; sliding_window) is built for per-head K/V "
    "attention on one device with the bf16 cache: not with latent attention "
    "(kv_lora_rank > 0), hyper-connections (hc_mult > 1), an MTP module, a mesh, ring "
    "attention or kv_cache_dtype='int8': no model pairs them and no test holds them")


class LayerReads(NamedTuple):
    """``TransformerConfig.layer_reads``: what a block reads of its number."""

    kind: str      # the token mixer's: one of LAYER_KINDS
    window: int    # the keys a query sees; 0 = all before it
    rotary: bool   # q and k are turned by their positions
    routed: bool   # the FFN routes experts
    hands_up: bool = False   # an "s6" layer that also returns its scan output (``memory_source``)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    # None = NO rotary embedding: the attention layers see no position (a model
    # whose recurrent layers carry the order; per-head K/V attention only)
    rope_theta: Optional[float] = 10000.0
    # RoPE frequency rescaling: tuple of (key, value) pairs (hashable
    # frozen-dataclass field); None = plain RoPE. Llama-3.x: factor /
    # low_freq_factor / high_freq_factor / original_max_position_embeddings.
    # YaRN ("type": "yarn"): factor / original_max_position_embeddings /
    # beta_fast / beta_slow / mscale / mscale_all_dim.
    rope_scaling: Any = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Llama-2 uses an untied lm_head; tie only for small/test configs.
    tie_embeddings: bool = False
    # MoE: 0 = dense FFN; otherwise the number of experts, of which each
    # token takes its n_experts_per_token best (softmax over ALL experts,
    # then top-k). router_renormalize divides the k weights by their sum
    # (Mixtral; the same as a softmax over the top-k logits); OLMoE leaves
    # them as they are (norm_topk_prob false: they sum to < 1).
    n_experts: int = 0
    n_experts_per_token: int = 2
    router_renormalize: bool = True
    # RMSNorm on the q and k projections, before RoPE: True = over the WHOLE
    # projection before the split into heads (OLMoE); "head" = over EACH
    # head's values, one weight [head_dim] for q and one for k (LFM2).
    qk_norm: Any = False
    # The first ``first_dense_layers`` layers of an MoE model keep a dense
    # SwiGLU of width ``dense_ffn_dim`` (DeepSeek's first_k_dense_replace);
    # ``n_shared_experts`` adds to every MoE layer one dense SwiGLU of width
    # n_shared_experts * ffn_dim that every token takes, beside its routed
    # experts; ``routed_scaling_factor`` multiplies the routed weights.
    first_dense_layers: int = 0
    dense_ffn_dim: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    # Multi-head latent attention (DeepSeek-V2; LatentAttention below):
    # kv_lora_rank > 0 caches ONE row [c_t ; RoPE(k^R_t)] of kv_lora_rank +
    # qk_rope_head_dim values a token a layer, shared by all heads, in place
    # of per-head K and V. A query head is qk_nope_head_dim (no position) +
    # qk_rope_head_dim (rotated) wide and a value head v_head_dim.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Compressed queries (DeepSeek-V2's full form): q_lora_rank > 0 puts
    # wq_a [dim, rank], an RMSNorm over the rank and wq_b [rank, heads] in
    # place of wq.
    q_lora_rank: int = 0
    # The router's score ("softmax" over all experts, or "sigmoid" of each
    # logit: DeepSeek-V3) and a per-expert selection bias that is added to the
    # scores for the top-k CHOICE only and never enters a weight (noaux_tc).
    router_score: str = "softmax"
    router_bias: bool = False
    # What the renormalisation adds to the k weights' sum: None = 1e-20 under
    # sigmoid scores (DeepSeek-V3) and nothing under softmax; LFM2 says 1e-6.
    router_renormalize_eps: Optional[float] = None
    # The token mixer of each layer (``layer_kind``): None = attention in every
    # layer; else n_layers of "full_attention" | "conv". A "conv" layer is
    # LFM2's gated short convolution (ShortConv below): it caches no keys and
    # values, and keeps conv_L_cache - 1 rows of [dim] a sequence whatever its
    # length, in the cache tree as a 1-tuple ``(state,)`` beside the attention
    # layers' (values..., positions) tuples.
    layer_types: Any = None
    conv_L_cache: int = 3
    # A "sliding_attention" layer attends the last ``sliding_window`` positions,
    # the query's own included: k_pos <= q_pos and k_pos > q_pos - sliding_window
    # (transformers' sliding-window overlay, llama.cpp's standard SWA). Its K/V
    # pages behind the window are given back while the request lives
    # (runtime/batcher.py: the window page class).
    sliding_window: int = 0
    # Rotary embedding a LAYER: None = ``rope_theta`` decides for all; else
    # n_layers of 0 | 1, a 0 layer sees no position at all (the rope_theta=None
    # path, for that layer alone).
    rope_layout: Any = None
    # The gate's activation in every gated FFN (the dense one, the experts, the
    # shared expert): act(x W1) * (x W3). "silu" = SwiGLU; "relu" = ReGLU.
    ffn_act: str = "silu"
    # What the router multiplies: "ffn_input" = the FFN's own input (the normed
    # residual behind attention; every family served before); "layer_input" =
    # the block's INPUT, ahead of the first norm and of attention, so that the
    # routing depends on nothing attention produces (SmallThinker).
    router_input: str = "ffn_input"
    # A "linear_attention" layer is Qwen3-Next's Gated DeltaNet (GatedDeltaNet
    # below): linear_num_key_heads heads of q and k (linear_key_head_dim wide),
    # each serving linear_num_value_heads / linear_num_key_heads value heads
    # (linear_value_head_dim wide); a depthwise causal convolution of
    # linear_conv_kernel_dim taps over [q; k; v]. It keeps, a sequence, the
    # last taps - 1 rows of [q; k; v] and one float32 matrix [key dim, value
    # dim] a value head, whatever the length: the cache entry is the 2-tuple
    # ``(conv_state, S)``.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # beta = 2 sigmoid(b) in place of sigmoid(b): the eigenvalues of
    # I - beta k k^T then lie in (-1, 1] (flash-linear-attention's
    # allow_neg_eigval; Olmo-Hybrid). ``linear_dt_bias``: how a seeded dt_bias
    # is drawn, "ones" (Qwen3-Next's published init) or "range" (the layer's own:
    # softplus^-1 of exp(U(log 0.001, log 0.1)), a decay a token of e^-1.6 .. ~1).
    linear_allow_neg_eigval: bool = False
    linear_dt_bias: str = "ones"
    # A "mamba" layer is Mamba-2's selective state-space mixer (Mamba2Mixer
    # below; granite-4.0-h's GraniteMoeHybridMambaLayer): mamba_n_heads heads of
    # mamba_d_head (d_inner = their product), mamba_n_groups groups of heads
    # sharing a B and a C of mamba_d_state values, a depthwise causal convolution
    # of mamba_d_conv taps (with a bias where mamba_conv_bias) over
    # [x ; B ; C]. It keeps, a sequence, the last taps - 1 rows of [x ; B ; C]
    # and one float32 matrix [mamba_d_head, mamba_d_state] a head: the cache
    # entry is the 2-tuple ``(conv_state, h)``.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    # Four scalars (Granite's): the embedding rows x embedding_multiplier; the
    # softmax scale attention_multiplier in place of head_dim^-1/2 (None);
    # every branch x residual_multiplier before it joins the residual; the
    # logits / logits_scaling. The defaults are what every other model
    # computes, and multiply nothing.
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # SambaY's decoder-hybrid-decoder (Phi-4-mini-flash-reasoning, arXiv
    # 2507.06607). An "s6" layer is Mamba-1's selective-scan mixer (Mamba1Mixer
    # below): mamba_d_inner channels, a state of mamba_d_state a channel, a
    # depthwise causal convolution of mamba_d_conv taps (mamba_conv_bias),
    # Delta through a bottleneck of mamba_dt_rank; it keeps, a sequence, the
    # last taps - 1 rows of x and one float32 h [mamba_d_state, mamba_d_inner]:
    # the cache entry is the 2-tuple ``(conv_state, h)``. ``memory_source``: the
    # "s6" layer that also hands its scan output m (before the gate) up the
    # stack, which every "gmu" layer (GatedMemoryUnit) reads: the SAME token's
    # row in the SAME call, so a "gmu" layer caches nothing. ``kv_source``: the
    # "full_attention" layer whose K and V every "cross_attention" layer reads
    # in place (queries of its own; it projects, writes and keeps none). Every
    # layer past ``kv_source`` is one of those two, so a prompt's chunk runs
    # the layers up to it on its rows and the rest on the ONE row whose logits
    # are read (``Transformer.__call__`` ``head_row``).
    mamba_d_inner: int = 0
    mamba_dt_rank: int = 0
    memory_source: Optional[int] = None
    kv_source: Optional[int] = None
    # Differential attention (arXiv 2410.05258) in EVERY attention layer: the
    # heads pair in stripes (q[2j], q[2j+1]; k[2g], k[2g+1]; v_g = [v[2g] ;
    # v[2g+1]]), two softmaxes over the same mask are subtracted under a
    # learned scalar lambda a layer, and a norm over 2 head_dim lanes follows
    # (``Attention``). ``attention_bias``: a bias on the q, k, v and output
    # projections. ``norm``: "rms" (RMSNorm, a weight) or "layer" (LayerNorm:
    # the mean taken out too, a weight AND a bias) for the two norms of every
    # block and the final one.
    differential: bool = False
    attention_bias: bool = False
    norm: str = "rms"
    # Where a block's two RMSNorms stand: "pre" = x + f(norm(x)) (Llama and
    # every other family served); "branch" = x + norm(f(x)), nothing normed
    # before the mixer or the FFN (Olmo 2 / 3). The same two weights a layer.
    norm_placement: str = "pre"
    # Attention's head width (0 = dim // n_heads, resolved in __post_init__);
    # ``attn_gate``: the query projection makes a gate a head beside the query
    # and the heads' output is multiplied by sigmoid(gate) before wo
    # (Qwen3-Next); ``partial_rotary_factor``: RoPE turns the FIRST
    # factor * head_dim values of a head (rotate-half among them) and passes
    # the rest.
    head_dim: int = 0
    attn_gate: bool = False
    partial_rotary_factor: float = 1.0
    # The expert share (one rank of an expert-parallel deployment): this chip
    # holds experts [experts_first, experts_first + experts_held) of n_experts
    # (0 held = all). The router stays n_experts wide, with its top-k and its
    # renormalisation over all k; a pair whose expert lies elsewhere joins no
    # group and adds nothing; the stacks are [experts_held, ...]; the partial
    # sum goes on to the next layer (no exchange is built).
    experts_first: int = 0
    experts_held: int = 0
    # sigmoid(w_g . x), a scalar gate a token on the shared expert's output
    shared_expert_gate: bool = False
    # Hyper-connections (HyperConnection below): hc_mult > 1 residual streams,
    # mixed per token around every sub-layer; the residual matrix is made
    # doubly stochastic by hc_sinkhorn_iters Sinkhorn iterations over
    # exp(clamp(., -hc_res_clamp, +hc_res_clamp)). 0 / 1 = the plain residual.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # Multi-token-prediction modules behind the last layer (MTPModule): 0 or 1.
    mtp_layers: int = 0
    # "full" = dense attention (GSPMD gathers KV when seq-sharded);
    # "ring" = sequence-parallel ring attention over mesh axis 'seq'
    # (ops.ring_attention) for long-context cache-less forward/training.
    # Any call that passes a KV cache (prefill/decode serving) uses the dense
    # path regardless — ring needs seq-sharded KV, caches are slot-indexed.
    attention_impl: str = "full"
    # KV-cache storage: "bf16" (model dtype) or "int8" (quantized, per-head
    # per-position scales). Attention dispatches on the cache STRUCTURE, so
    # this field only picks the init_kv_caches default — one compiled module
    # serves either layout.
    kv_cache_dtype: str = "bf16"
    mesh: Any = None

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        # what is not built is refused where the config is made: at load()
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm {self.norm!r}: expected 'rms' or 'layer'")
        sambay = (self.differential or self.attention_bias or self.norm == "layer"
                  or set(self.layer_types or ()) & set(SAMBAY_LAYER_KINDS))
        if sambay and (self.mesh is not None or self.n_experts or self.kv_lora_rank
                       or self.hc_mult > 1 or self.mtp_layers
                       or self.norm_placement != "pre" or self.qk_norm or self.attn_gate
                       or self.rope_theta is not None or self.attention_impl == "ring"
                       or normalize_kv_cache_dtype(self.kv_cache_dtype) == "int8"):
            raise ValueError(SAMBAY_LAYERS_COMPOSE_REFUSAL)
        if self.differential and (self.n_heads % 2 or self.n_kv_heads % 2
                                  or (self.n_heads // 2) % (self.n_kv_heads // 2)):
            raise ValueError(
                "differential attention pairs the heads: n_heads and n_kv_heads must be even, "
                "and the pairs of queries a multiple of the pairs of keys")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown router_score {self.router_score!r}: expected 'softmax' or 'sigmoid'")
        if self.norm_placement not in ("pre", "branch"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r}: expected 'pre' or 'branch'")
        if self.norm_placement == "branch" and (self.hc_mult > 1 or self.mtp_layers):
            raise ValueError(
                "norm_placement='branch' (x + norm(f(x))) is built for the plain residual "
                "alone: not with hc_mult > 1 or an MTP module")
        if self.rope_theta is None and (self.kv_lora_rank or self.rope_scaling
                                        or self.partial_rotary_factor != 1.0):
            raise ValueError(
                "rope_theta=None (no rotary embedding) is built for per-head K/V attention "
                "alone: not with latent attention, rope_scaling or partial_rotary_factor")
        if self.attention_multiplier is not None and self.kv_lora_rank:
            raise ValueError(
                "attention_multiplier (a softmax scale of the config's own) is built for per-head "
                "K/V attention: latent attention's scale is latent_attention_scale")
        if self.linear_dt_bias not in ("ones", "range"):
            raise ValueError(
                f"unknown linear_dt_bias {self.linear_dt_bias!r}: expected 'ones' or 'range'")
        if self.mtp_layers > 1:
            raise ValueError(
                f"mtp_layers={self.mtp_layers} is not built: one multi-token-prediction "
                "module (DeepSeek-V3's depth 1) is")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(
                f"unknown qk_norm {self.qk_norm!r}: expected False, True (over the whole "
                "projection) or 'head' (over each head)")
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            if len(kinds) != self.n_layers or set(kinds) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types must name n_layers={self.n_layers} layers, each one of "
                    f"{LAYER_KINDS}; got {len(kinds)}: {sorted(set(kinds))}")
            if set(kinds) & set(STATE_LAYER_KINDS) and (
                    self.kv_lora_rank or self.hc_mult > 1 or self.mtp_layers):
                raise ValueError(STATE_LAYERS_COMPOSE_REFUSAL)
            if "sliding_attention" in kinds:
                if self.sliding_window <= 0:
                    raise ValueError(
                        "a 'sliding_attention' layer needs sliding_window > 0 (the keys a "
                        f"query sees, itself included); got {self.sliding_window}")
                if (self.kv_lora_rank or self.hc_mult > 1 or self.mtp_layers
                        or self.mesh is not None or self.attention_impl == "ring"
                        or normalize_kv_cache_dtype(self.kv_cache_dtype) == "int8"):
                    raise ValueError(WINDOW_LAYERS_COMPOSE_REFUSAL)
            if "conv" in kinds and self.conv_L_cache < 2:
                raise ValueError(f"conv_L_cache={self.conv_L_cache} must be >= 2 (taps)")
            if "mamba" in kinds:
                if (min(self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state,
                        self.mamba_n_groups) <= 0 or self.mamba_n_heads % self.mamba_n_groups
                        or self.mamba_d_conv < 2):
                    raise ValueError(
                        "a 'mamba' layer needs mamba_n_heads (a multiple of mamba_n_groups), "
                        "mamba_d_head, mamba_d_state and mamba_d_conv >= 2")
                if self.mesh is not None or self.n_experts or "linear_attention" in kinds:
                    raise ValueError(MAMBA_LAYERS_COMPOSE_REFUSAL)
            if "s6" in kinds and (min(self.mamba_d_inner, self.mamba_d_state, self.mamba_dt_rank)
                                  <= 0 or self.mamba_d_conv < 2):
                raise ValueError(
                    "an 's6' layer needs mamba_d_inner, mamba_d_state, mamba_dt_rank and "
                    "mamba_d_conv >= 2")
            for source, kind, reader in (("memory_source", "s6", "gmu"),
                                         ("kv_source", "full_attention", "cross_attention")):
                at, readers = getattr(self, source), [i for i, k in enumerate(kinds) if k == reader]
                if at is None and not readers:
                    continue
                if at is None or not 0 <= at < len(kinds) or kinds[at] != kind or (
                        readers and min(readers) < at):
                    raise ValueError(
                        f"{source}={at} must name a {kind!r} layer before every {reader!r} layer")
            if self.kv_source is not None and set(kinds[self.kv_source + 1:]) - {
                    "gmu", "cross_attention"}:
                raise ValueError(
                    "every layer past kv_source is a 'gmu' or a 'cross_attention' layer (the "
                    "cross-decoder: a prompt's chunk runs it on one row)")
            if "linear_attention" in kinds:
                hk, hv = self.linear_num_key_heads, self.linear_num_value_heads
                if (min(hk, hv, self.linear_key_head_dim, self.linear_value_head_dim) <= 0
                        or hv % hk or self.linear_conv_kernel_dim < 2):
                    raise ValueError(
                        "a 'linear_attention' layer needs linear_num_key_heads, "
                        "linear_num_value_heads (a multiple of the key heads), "
                        "linear_key_head_dim, linear_value_head_dim and "
                        "linear_conv_kernel_dim >= 2")
        if self.rope_layout is not None:
            layout = tuple(self.rope_layout)
            if len(layout) != self.n_layers or set(layout) - {0, 1}:
                raise ValueError(
                    f"rope_layout must give n_layers={self.n_layers} layers a 0 (no position) "
                    f"or a 1 (rotary); got {len(layout)}: {sorted(set(layout))}")
            if self.rope_theta is None or self.kv_lora_rank:
                raise ValueError(
                    "rope_layout chooses the layers rope_theta turns: it needs a rope_theta, "
                    "and per-head K/V attention (not latent attention)")
        if self.ffn_act not in ("silu", "relu"):
            raise ValueError(f"unknown ffn_act {self.ffn_act!r}: expected 'silu' or 'relu'")
        if self.router_input not in ("ffn_input", "layer_input"):
            raise ValueError(
                f"unknown router_input {self.router_input!r}: expected 'ffn_input' or "
                "'layer_input'")
        if self.router_input == "layer_input" and (
                not self.n_experts or self.hc_mult > 1 or self.mtp_layers):
            raise ValueError(
                "router_input='layer_input' (the router reads the block's input) is built for "
                "an MoE model with the plain residual: not with hc_mult > 1 (a block's input "
                "is several streams) or an MTP module")
        if self.partial_rotary_factor != 1.0:
            rotary = self.partial_rotary_factor * self.head_dim
            if self.kv_lora_rank or not 0 < rotary <= self.head_dim or rotary % 2:
                raise ValueError(
                    f"partial_rotary_factor={self.partial_rotary_factor} must turn an even "
                    f"number of a head's {self.head_dim} values (per-head K/V attention)")
        if self.experts_held or self.experts_first:
            if not (0 < self.experts_held
                    and 0 <= self.experts_first <= self.n_experts - self.experts_held):
                raise ValueError(
                    f"the expert share [{self.experts_first}, {self.experts_first} + "
                    f"{self.experts_held}) does not lie within n_experts={self.n_experts}")
            if self.mesh is not None:
                raise ValueError(
                    "an expert share (experts_held) is ONE rank's part of an "
                    "expert-parallel deployment: it is not sharded over a mesh")

    def layer_kind(self, layer: int) -> str:
        """One of LAYER_KINDS; a layer past the list (the MTP block) is
        attention."""
        kinds = self.layer_types
        return kinds[layer] if kinds is not None and layer < len(kinds) else "full_attention"

    def layer_rotary(self, layer: int) -> bool:
        """Does ``layer``'s attention turn q and k by their positions?"""
        if self.rope_theta is None:
            return False
        layout = self.rope_layout
        return layout is None or layer >= len(layout) or bool(layout[layer])

    def layer_window(self, layer: int) -> int:
        """The keys a query of ``layer`` sees, itself included; 0 = all before it."""
        return self.sliding_window if self.layer_kind(layer) == "sliding_attention" else 0

    def layer_reads(self, layer: int) -> "LayerReads":
        """ALL that ``TransformerBlock`` reads of its number: the token mixer's
        kind, the window and the rotary embedding of its attention, and whether
        its FFN routes experts (dense under ``first_dense_layers``)."""
        return LayerReads(self.layer_kind(layer), self.layer_window(layer),
                          self.layer_rotary(layer),
                          self.n_experts > 0 and layer >= self.first_dense_layers,
                          layer == self.memory_source)

    def layer_class(self, layer: int) -> int:
        """The FIRST layer that ``TransformerBlock`` builds as it builds
        ``layer`` (the same ``layer_reads``). A program traces a block once a
        class (``SharedBlock``): Mistral has one, a hybrid two or three."""
        mine = self.layer_reads(layer)
        return next(i for i in range(layer + 1) if self.layer_reads(i) == mine)

    @property
    def cross_decoder_layers(self) -> Tuple[int, ...]:
        """The layers past cfg.kv_source (none without one): a prompt's chunk
        runs them inside the conditional that skips the head, so a server that
        holds int8 weights hands THEIR matrices on as they are held, as it does
        the head (``Transformer`` dequantizes them where they are multiplied)."""
        return tuple(range(self.kv_source + 1, self.n_layers)) if self.kv_source is not None else ()

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """The attention layers whose pages are of the window class."""
        return self.layers_of("sliding_attention")

    def small_leaf(self, name: str) -> str:
        """The SMALL_LEAF_INIT rule a seeded float32 leaf ``name`` is drawn by."""
        if name in ("bq", "bk", "bv", "bo"):       # a projection's bias: the one rule of every bias
            return "bias"
        return "dt_bias_range" if name == "dt_bias" and self.linear_dt_bias == "range" else name

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i in range(self.n_layers) if self.layer_kind(i) == kind)

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return self.layers_of("conv")

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that keep a fixed state block a sequence (no pages)."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) in STATE_LAYER_KINDS)

    def lambda_init(self, layer: int) -> float:
        """Differential attention's lambda_init of ``layer`` (arXiv 2410.05258)."""
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    @property
    def read_heads(self) -> Tuple[int, int, int]:
        """(query heads, KV heads, head width) as the attention READS see them.
        Differential attention's pairs read as plain GQA over KV heads of
        [k1_g ; k2_g] and [v1_g ; v2_g], 2 head_dim lanes each (a token's flat
        row as it lies), by queries zero-padded to that width ([q1 ; 0], [0 ;
        q2]): half the KV heads at twice the width."""
        if self.differential:
            return self.n_heads, self.n_kv_heads // 2, 2 * self.head_dim
        return self.n_heads, self.n_kv_heads, self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.partial_rotary_factor * self.head_dim)

    @property
    def n_experts_held(self) -> int:
        """Experts whose weights this tree holds (all, without a share)."""
        return self.experts_held or self.n_experts

    @property
    def kv_rows_flat(self) -> bool:
        """Does the paged bf16 pool hold a token's K (and V) as ONE row of
        n_kv_heads * head_dim values, [pages, page_size, kvh * hd]? On one
        device, always: the decode step's live-page read (ops/gqa_attention.py)
        fetches pages of such rows as they lie, and the expression splits the
        heads out of its gathered view. Under a mesh the pool keeps its
        [.., kvh, hd] axes for the partitioner, except where a head is narrower
        than a 128-lane tile: held [.., 8, 64], LFM2's pool came out
        pages-minor and was copied whole five times a layer a call (compiled
        for a described v5e, PR 35), so such a pool is flat wherever it is."""
        return self.mesh is None or self.head_dim % 128 != 0

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_dense_layers if self.n_experts > 0 else 0

    @property
    def latent_row_dim(self) -> int:
        """Width of a latent-attention cache row, 0 for per-head K/V:
        kv_lora_rank + qk_rope_head_dim rounded up to whole 128-lane tiles
        (576 -> 640, zeros behind). The chip tiles a 576-wide row to 640 lanes
        in HBM either way, and with the odd width the compiler wants the pool
        in another layout for the gather than for the scatter: two copies of
        the whole pool a layer a call (PERF.md section 6, PR 29)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128 if self.kv_lora_rank else 0


def _llama3_scaled_freqs(freqs: jnp.ndarray, scaling: dict) -> jnp.ndarray:
    """Llama-3.1 frequency rescaling (parity with transformers'
    _compute_llama3_parameters): low-frequency bands divide by ``factor``,
    high-frequency bands pass through, the middle band interpolates."""
    import math

    factor = float(scaling["factor"])
    lo = float(scaling["low_freq_factor"])
    hi = float(scaling["high_freq_factor"])
    old_len = float(scaling["original_max_position_embeddings"])

    wavelen = 2.0 * math.pi / freqs
    scaled = jnp.where(wavelen > old_len / lo, freqs / factor, freqs)
    smooth = (old_len / wavelen - lo) / (hi - lo)
    smoothed = (1.0 - smooth) * scaled / factor + smooth * scaled
    is_medium = (wavelen >= old_len / hi) & (wavelen <= old_len / lo)
    return jnp.where(is_medium, smoothed, scaled)


def _is_yarn(scaling: dict) -> bool:
    return scaling.get("rope_type", scaling.get("type")) == "yarn"


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_scaled_freqs(freqs: jnp.ndarray, head_dim: int, theta: float,
                       scaling: dict) -> jnp.ndarray:
    """YaRN (parity with transformers' _compute_yarn_parameters): the
    dimensions that turn more than beta_fast times in the original context
    keep their frequency, those that turn less than beta_slow times divide
    it by ``factor``, and a linear ramp over the dimension index joins them."""
    import math

    factor = float(scaling["factor"])
    old_len = float(scaling["original_max_position_embeddings"])

    def dim_of(turns: float) -> float:
        return head_dim * math.log(old_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(float(scaling.get("beta_fast") or 32))), 0)
    high = min(math.ceil(dim_of(float(scaling.get("beta_slow") or 1))), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rotary_embedding(
    positions: jnp.ndarray, head_dim: int, theta: float, rope_scaling=None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for the given absolute positions: [..., seq, head_dim/2]."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    attention_factor = 1.0
    if rope_scaling:
        scaling = dict(rope_scaling)
        if _is_yarn(scaling):
            freqs = _yarn_scaled_freqs(freqs, head_dim, theta, scaling)
            if scaling.get("mscale") and scaling.get("mscale_all_dim"):
                attention_factor = (yarn_mscale(scaling["factor"], scaling["mscale"])
                                    / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
            else:
                attention_factor = yarn_mscale(scaling["factor"])
        else:
            freqs = _llama3_scaled_freqs(freqs, scaling)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., seq, hd/2]
    if attention_factor != 1.0:
        return jnp.cos(angles) * attention_factor, jnp.sin(angles) * attention_factor
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [batch, seq, heads, head_dim]; cos/sin: [batch, seq, head_dim/2]."""
    cos = cos[:, :, None, :]  # broadcast over heads
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def apply_partial_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """``apply_rotary`` over the FIRST 2 * cos.shape[-1] values of each head
    (rotate-half among those), the rest passed as they are; the whole head
    where the tables are as wide as it."""
    rotary = 2 * cos.shape[-1]
    if rotary == x.shape[-1]:
        return apply_rotary(x, cos, sin)
    return jnp.concatenate([apply_rotary(x[..., :rotary], cos, sin), x[..., rotary:]], axis=-1)


def attention_mask(key_pos, query_pos, window: int = 0):
    """[b, s, L] bool: the ONE predicate of every attention read. ``key_pos``
    [b, L] are the CACHED positions (PAD_POS = empty), ``query_pos`` [b, s]:
    ``k <= q`` is causality, the unwritten rest and padding; with ``window``
    also ``k > q - window`` (a query sees ``window`` keys, itself included), on
    the positions, so a row still lying in a page behind the window is masked
    like one whose page was given back."""
    mask = key_pos[:, None, :] <= query_pos[:, :, None]
    if window:
        mask &= key_pos[:, None, :] > query_pos[:, :, None] - window
    return mask


def paged_attention_ref(q, cache, block_tables, positions, n_kv_heads: int, window: int = 0):
    """The paged read as an expression: gather the logical view through the
    block table and run the one masked-softmax chain on it, K/V kept
    n_kv_heads wide. What ``Attention`` computes on every lowering that keeps
    the expression, and the oracle tests/test_gqa_page_attention.py holds the
    live-page kernel to. q: [b, s, h, hd]; cache: the paged 3-tuple (bf16) or
    5-tuple (int8) pool; positions: [b, s]; ``window``: a sliding-attention
    layer's. Returns [b, s, h, hd] in q.dtype."""
    k_all, v_all, pos_view = gather_paged_view(cache, block_tables, q.dtype, n_kv_heads)
    # one predicate for causality, empty rows (PAD_POS) and padding
    return grouped_query_attention(q, k_all, v_all, attention_mask(pos_view, positions, window))


def grouped_query_attention(q: jnp.ndarray, k_all: jnp.ndarray,
                            v_all: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Masked softmax attention of ``q`` [b, s, n_heads, hd] over K/V
    [b, L, n_kv_heads, hd] as they are stored — the KV heads are never
    expanded to n_heads. ``mask``: [b, s, L] bool. Returns [b, s, n_heads, hd].

    The query heads fold into [n_kv_heads, rep] (head ``h = g * rep + r``
    reads KV head ``g = h // rep``) and both contractions batch over
    (b, n_kv_heads), so each K/V row is read once for its whole group.
    ``rep`` comes from the shapes; ``rep == 1`` (MHA) is the same expression
    with a size-1 axis. bf16 operands, float32 logits and softmax; masked
    positions get ``finfo.min`` and contribute exact zeros.

    The ONE copy of the chain: ``Attention`` (every cache layout but the
    ring) and ``paged_attention_ref`` both call it, so the live-page kernel's
    oracle cannot drift from what serves."""
    b, s, n_heads, hd = q.shape
    kvh = k_all.shape[2]
    dt = q.dtype
    qg = q.reshape(b, s, kvh, n_heads // kvh, hd)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_all.astype(dt)) * hd**-0.5
    logits = logits.astype(jnp.float32)
    logits = jnp.where(mask[:, None, None, :, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_all.astype(dt))
    return out.reshape(b, s, n_heads, hd)


@partial(jax.jit, static_argnames=("n_kv_heads", "walk", "window"))
def paged_live_read(q, cache, block_tables, positions, *, n_kv_heads: int, walk,
                    window: int = 0):
    """The paged bf16 pool's read of a call shape the live-page kernel takes
    (``walk`` = ``paged_read_walk(...)``, not None): the kernel
    (ops/gqa_attention.py) in a program lowered for a TPU, the expression
    (``paged_attention_ref``) in every other. A jitted function of its own, so
    a program's layers share ONE trace of both branches (a trace a layer is
    start-up time no compile cache serves: PERF.md section 6, PR 41)."""
    def read_expression():
        return paged_attention_ref(q, cache, block_tables, positions, n_kv_heads, window)

    def read_live_pages():
        from seldon_core_tpu.ops.gqa_attention import gqa_page_attention

        return gqa_page_attention(q, *cache, block_tables, positions, n_kv_heads, walk,
                                  interpret=False, window=window)

    return jax.lax.platform_dependent(tpu=read_live_pages, default=read_expression)


def lora_delta(x: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
               adapter_ids: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Batched low-rank delta for one adapted projection (S-LoRA /
    Punica-style): gather each sequence's factors from the dense adapter
    pool by its slot's ``adapter_ids`` entry, then one einsum pair —
    ``(x @ A[id]) @ B[id] * scale[id]``.

    x: [b, s, d_in]; A: [n_adapters, d_in, r]; B: [n_adapters, r, d_out];
    adapter_ids: [b] int32; scale: [n_adapters] f32 (alpha / rank).
    Row 0 is the reserved identity (zero factors, zero scale), so a batch
    of untenanted slots computes an exact-zero delta through the SAME
    program — ``base + 0`` is bitwise ``base``, which is what lets one
    compiled step serve adapted and base traffic with identical outputs
    for the base slots (runtime/adapters.py)."""
    dt = x.dtype
    a = A[adapter_ids]                      # [b, d_in, r]   (the gather)
    b = B[adapter_ids]                      # [b, r, d_out]
    s = scale[adapter_ids].astype(dt)       # [b]
    h = jnp.einsum("bsd,bdr->bsr", x, a.astype(dt))
    return jnp.einsum("bsr,bro->bso", h, b.astype(dt)) * s[:, None, None]


class Attention(nn.Module):
    cfg: TransformerConfig
    # the layer's own (TransformerBlock reads them off cfg.layer_types /
    # cfg.rope_layout): the keys a query sees (0 = every one before it),
    # whether q and k are turned by their positions, and whether the layer is a
    # "cross_attention" one: queries of its own over ANOTHER layer's K and V
    # (``cache`` is then that layer's entry as its write left it, read in place;
    # the layer projects no k and v, writes nothing and keeps nothing)
    window: int = 0
    rotary: bool = True
    cross: bool = False

    @nn.compact
    def __call__(self, x, positions, cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                 cache_index: Optional[jnp.ndarray] = None,
                 block_tables: Optional[jnp.ndarray] = None,
                 adapters: Optional[dict] = None,
                 adapter_ids: Optional[jnp.ndarray] = None,
                 lambda_init: Optional[jnp.ndarray] = None):
        """x: [b, s, d]; returns (out, new_cache). ``cache`` is this layer's
        entry of the cache tree (models/cache.py: bf16 ``(k, v, pos)`` or int8
        ``(kq, ks, vq, vs, pos)``, dense [b, max_len, ...] or, with
        ``block_tables`` [b, n_pages] int32, a pool of pages [pages,
        page_size, ...] shared by all sequences). The call's K/V rows go in
        through ``write_rows`` (``cache_index`` addresses a dense cache: a
        scalar offset, a [b] vector of offsets, or with s > 1 each row's own
        position; ``positions`` alone address a pool), and the read masks by
        the cached positions (PAD_POS = empty), so it is exact under
        right-padding. The paged read gathers the sequence's logical view
        through the table and feeds the IDENTICAL chain as the dense one
        (grouped_query_attention), so paged and dense decode are bit-exact
        (tests/test_paged_kv.py), or walks the live pages with the repo's
        kernel where ``paged_read_walk`` says so.
        Without a cache: full causal attention, returns (out, (k, v)).

        DIFFERENTIAL attention (cfg.differential; ``lambda_init`` the layer's,
        a traced float32 scalar, so that the layers of a class share a trace):
        with the heads paired in stripes,

            a1_j = softmax(q[2j] k[2g]^T d^-1/2) v_g   a2_j = softmax(q[2j+1] k[2g+1]^T d^-1/2) v_g
            lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
            o_j = (1 - lambda_init) w * RMSNorm_2d(a1_j - lambda a2_j)

        v_g = [v[2g] ; v[2g+1]], g = j // (pairs of queries a pair of keys). Both
        softmaxes are ONE read of plain GQA over KV heads 2d wide
        (cfg.read_heads): a token's cached row as it lies is [k[2g] ; k[2g+1]]
        a group, and the queries go in zero-padded, [q[2j] ; 0] and [0 ;
        q[2j+1]] (the zeros add nothing to a score); the subtraction, the norm
        and the scale are an epilogue under ``attn.diff``."""
        cfg = self.cfg
        b, s, _ = x.shape
        hd = cfg.head_dim

        wq = param_with_axes(
            "wq", nn.initializers.lecun_normal(), (cfg.dim, cfg.n_heads * hd), jnp.float32,
            axes=("embed", "heads"),
        )
        if not self.cross:
            wk = param_with_axes(
                "wk", nn.initializers.lecun_normal(), (cfg.dim, cfg.n_kv_heads * hd), jnp.float32,
                axes=("embed", "kv_heads"),
            )
            wv = param_with_axes(
                "wv", nn.initializers.lecun_normal(), (cfg.dim, cfg.n_kv_heads * hd), jnp.float32,
                axes=("embed", "kv_heads"),
            )
        wo = param_with_axes(
            "wo", nn.initializers.lecun_normal(), (cfg.n_heads * hd, cfg.dim), jnp.float32,
            axes=("heads", "embed"),
        )

        dt = cfg.dtype
        biased = cfg.attention_bias

        def project(w, name, width):
            """x W (+ the projection's bias: the product left float32, the bias
            added there, rounded once)."""
            if not biased:
                return x @ w.astype(dt)
            bias = param_with_axes(name, small_leaf_init("bias"), (width,), jnp.float32,
                                   axes=("attn_bias",))
            return jnp.matmul(x, w.astype(dt), preferred_element_type=jnp.float32) + bias

        q_flat = project(wq, "bq", cfg.n_heads * hd)
        if cfg.attn_gate:
            # a gate a head beside its query (the published q_proj makes both;
            # models/convert.py splits it): sigmoid(gate) weighs the heads'
            # output before wo
            wq_gate = param_with_axes(
                "wq_gate", nn.initializers.lecun_normal(), (cfg.dim, cfg.n_heads * hd),
                jnp.float32, axes=("embed", "heads"))
            gate = x @ wq_gate.astype(dt)
        if adapters is not None:
            # batched LoRA (runtime/adapters.py): per-slot low-rank delta
            # on q and (below) o — NEVER on k/v, so the KV written from a
            # given hidden state is base-model-pure for every tenant and
            # the paged pool/prefix machinery stays tenant-agnostic
            q_flat = q_flat + lora_delta(x, *adapters["wq"], adapter_ids,
                                         adapters["scale"])
        if not self.cross:
            k_flat = project(wk, "bk", cfg.n_kv_heads * hd).astype(dt)
            if cfg.qk_norm is True:
                q_flat = RMSNorm(cfg.n_heads * hd, cfg.norm_eps, "heads", name="q_norm")(q_flat)
                k_flat = RMSNorm(cfg.n_kv_heads * hd, cfg.norm_eps, "kv_heads", name="k_norm")(k_flat)
            k = k_flat.reshape(b, s, cfg.n_kv_heads, hd)
            v = project(wv, "bv", cfg.n_kv_heads * hd).astype(dt).reshape(b, s, cfg.n_kv_heads, hd)
        q = q_flat.reshape(b, s, cfg.n_heads, hd)
        if cfg.qk_norm == "head":
            # one weight [head_dim] for every query head and one for every KV
            # head, float32 in every tree (FLOAT32_AXES)
            q = RMSNorm(hd, cfg.norm_eps, "head_norm", name="q_norm")(q)
            k = RMSNorm(hd, cfg.norm_eps, "head_norm", name="k_norm")(k)
        # the heads as the reads below see them (differential: pairs, 2 hd wide)
        n_heads, kvh, width = cfg.read_heads
        if cfg.attention_multiplier is not None or width != hd:
            # every read below scales the scores by its heads' width^-1/2: the
            # config's own scale (Granite's 1/64 at heads of 64 is q / 8, exact
            # in any float), or a pair's (hd^-1/2 over reads 2 hd wide), reaches
            # them on the queries
            scale = hd ** -0.5 if cfg.attention_multiplier is None else cfg.attention_multiplier
            q = q * (scale * width ** 0.5)
        q = q.astype(dt)

        if cfg.rope_theta is not None and self.rotary:
            cos, sin = rotary_embedding(positions, cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling)
            q = apply_partial_rotary(q, cos, sin)
            k = apply_partial_rotary(k, cos, sin)
        if cfg.differential:
            # [q[2j] ; 0] and [0 ; q[2j+1]]: 2 hd wide, the pair's two heads
            pair = q.reshape(b, s, n_heads // 2, 2, hd)
            nothing = jnp.zeros_like(pair[:, :, :, 0])
            q = jnp.stack([jnp.concatenate([pair[:, :, :, 0], nothing], axis=-1),
                           jnp.concatenate([nothing, pair[:, :, :, 1]], axis=-1)],
                          axis=3).reshape(b, s, n_heads, width)

        window = self.window
        # a window layer's read and write are scopes of their own (attn.window.*),
        # a cross layer's read likewise (attn.cross.read)
        scope = "attn.cross" if self.cross else "attn.window" if window else "attn.gqa"
        out = None

        def as_read(rows):      # [b, L, ...] K or V rows as the reads see the heads
            return rows.reshape(rows.shape[:2] + (kvh, width))

        if cache is None or (self.cross and len(cache) == 2):
            # no cache: the call's own rows (a cross layer: its source's, ``(k, v)``)
            k_all, v_all = (k, v) if cache is None else cache
            new_cache = (k_all, v_all)
            k_all, v_all, pos_view = as_read(k_all), as_read(v_all), positions
        else:
            new_cache = cache       # (a cross layer reads its source's entry as it came)
            if not self.cross:
                # the entry's rows: int8 quantizes on write (values + a scale a
                # head), and the read dequantizes fused into its einsums
                with jax.named_scope(scope + ".write"):
                    rows = (*quantize_kv(k), *quantize_kv(v)) if entry_is_int8(cache) else (k, v)
                    new_cache = write_rows(cache, rows, positions, block_tables=block_tables,
                                           cache_index=cache_index)
            if block_tables is None:
                k_all, v_all, pos_view = dense_view(new_cache, dt)
                k_all, v_all = as_read(k_all), as_read(v_all)
            else:
                # The read as an expression gathers the logical view and runs
                # the SAME chain the dense layout uses: paged == dense
                # bit-for-bit. For the bf16 pool of flat rows on one TPU the
                # call walks each sequence's live pages with the repo's kernel
                # instead (``paged_live_read``); every other lowering, a mesh,
                # the int8 pool and a call shape the kernel does not take keep
                # the expression.
                bt = jnp.asarray(block_tables, jnp.int32)
                pool = new_cache[0]
                walk = paged_read_walk(cfg, s, bt.shape[1], pool.shape[1], pool.dtype)
                with jax.named_scope(scope + ".read"):
                    if walk is not None:
                        out = paged_live_read(q, new_cache, bt, positions,
                                              n_kv_heads=kvh, walk=walk, window=window)
                    else:
                        out = paged_attention_ref(q, new_cache, bt, positions, kvh, window)
        if cache is None and cfg.attention_impl == "ring":
            from seldon_core_tpu.ops.ring_attention import ring_attention

            # ring is GQA-aware: unrepeated KV rides the ring
            out = ring_attention(
                q, k_all.astype(dt), v_all.astype(dt), positions, positions, mesh=cfg.mesh
            )
        elif out is None:
            # every cache layout but the paged pool's ends here: K/V stay
            # n_kv_heads wide. Empty rows hold PAD_POS, so one predicate covers
            # causality, the unfilled suffix and right-padding garbage
            mask = attention_mask(pos_view, positions, window)  # [b, s, kv]
            with jax.named_scope(scope + ".read"):
                out = grouped_query_attention(q, k_all, v_all, mask)
        if cfg.differential:
            with jax.named_scope("attn.diff"):
                lq1, lk1, lq2, lk2 = param_with_axes(
                    "lambdas", small_leaf_init("lambdas"), (4, hd), jnp.float32,
                    axes=("attn_lambda", "head_norm"))
                lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lambda_init
                a = out.astype(jnp.float32).reshape(b, s, n_heads // 2, 2, width)
                normed = RMSNorm(width, cfg.norm_eps, "head_norm", name="subln")(
                    a[:, :, :, 0] - lam * a[:, :, :, 1])
                out = (normed * (1.0 - lambda_init)).astype(dt)
        out = out.reshape(b, s, cfg.n_heads * hd)
        if cfg.attn_gate:
            out = (out * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
        if biased:
            bo = param_with_axes("bo", small_leaf_init("bias"), (cfg.dim,), jnp.float32,
                                 axes=("attn_bias",))
            proj = (jnp.matmul(out, wo.astype(dt), preferred_element_type=jnp.float32)
                    + bo).astype(dt)
        else:
            proj = out @ wo.astype(dt)
        if adapters is not None:
            proj = proj + lora_delta(out, *adapters["wo"], adapter_ids,
                                     adapters["scale"])
        # (a cross layer keeps nothing of its own: the block hands on an empty entry)
        return proj, None if self.cross else new_cache


def latent_attention_scale(cfg: TransformerConfig) -> float:
    """Softmax scale of latent attention: (nope + rope)^-0.5, times YaRN's
    mscale_all_dim temperature SQUARED when the config carries one (the
    published DeepSeek-V2 modeling file: ``softmax_scale *= mscale * mscale``;
    transformers' port of it leaves the factor out)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    scaling = dict(cfg.rope_scaling or ())
    if _is_yarn(scaling) and scaling.get("mscale_all_dim"):
        scale *= yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def absorbed_query_rows(q_nope: jnp.ndarray, q_rope: jnp.ndarray, w_uk: jnp.ndarray,
                        width: int) -> jnp.ndarray:
    """The query rows of the absorbed read, as wide as a cached row:
    ``[q~_h ; q^R_h ; zeros]`` with ``q~_h = W_UK,h^T q^N_h``. [b, s, H, width]."""
    dt = q_nope.dtype
    q_lat = jnp.einsum("bshn,hnc->bshc", q_nope, w_uk.astype(dt))
    pad = width - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope] + ([jnp.zeros(q_rope.shape[:-1] + (pad,), dt)] if pad else []), axis=-1)


def absorbed_latent_attention(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                              w_uk: jnp.ndarray, w_uv: jnp.ndarray,
                              rows: jnp.ndarray, mask: jnp.ndarray,
                              scale: float) -> jnp.ndarray:
    """Masked softmax attention of every head over cached LATENT rows, in
    the absorbed form: the per-head key and value expansions W_UK / W_UV
    move onto the query and the output, so no per-head K or V of the context
    is ever made.

    ``q_nope`` [b, s, H, dn] / ``q_rope`` [b, s, H, dr] (rotated);
    ``w_uk`` [H, dn, dc], ``w_uv`` [H, dc, dv]; ``rows`` [b, L, >= dc + dr] =
    [c_t ; RoPE(k^R_t) ; zeros to the cached width] as cached, one row a
    token for all heads; ``mask`` [b, s, L] bool. Returns [b, s, H, dv].

        q~_h  = W_UK,h^T q^N_h                      (dc wide)
        score = ([q~_h ; q^R_h] . row_t) * scale    (= q_h . [W_UK,h c_t ; k^R_t])
        o_h   = W_UV,h (sum_t p_t c_t)

    One expression for any [b, s]: the decode step (s = 1), a prefill chunk
    over the gathered view (b = 1), the speculative verify and the dense
    cache of generate(). bf16 operands, float32 logits and softmax; masked
    positions get ``finfo.min`` and contribute exact zeros. (The paged pool's
    read on a TPU walks the live pages instead: ops/latent_attention.py, the
    same query rows, predicate and W_UV around it, or for a wide chunk the
    expanded-once form, which makes each head's K and V of the visited rows.)"""
    dt = q_nope.dtype
    dc = w_uk.shape[-1]
    rows = rows.astype(dt)
    q_cat = absorbed_query_rows(q_nope, q_rope, w_uk, rows.shape[-1])
    logits = jnp.einsum("bshc,blc->bhsl", q_cat, rows).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None, :, :], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(dt)
    ctx = jnp.einsum("bhsl,blc->bshc", probs, rows[..., :dc])
    return jnp.einsum("bshc,hcv->bshv", ctx, w_uv.astype(dt))


def paged_read_walk(cfg: "TransformerConfig", s: int, n_pages: int, page_size: int,
                    pool_dtype) -> Optional[Any]:
    """How the paged pool's attention read of a call with ``s`` query tokens a
    sequence is walked by the repo's kernels IN A PROGRAM LOWERED FOR A TPU
    (ops/page_walk.py ``Plan``; ops/latent_attention.py ``ExpandedWalk``), or
    None where it is the expression over the whole view there too: a mesh (the
    kernels are one device's program), a pool in another dtype than the model's
    (the int8 KV pool), a call shape no kernel takes. Latent attention's one
    pool of latent rows has two forms of the one read, by the call's tokens a
    sequence (``expanded_walk``, from the widths alone): a chunk wide enough
    that making the context's K and V once a visit costs under the absorbed
    products (1,024 tokens at DeepSeek-V2's widths) reads EXPANDED ONCE, every
    other call that ``plan`` takes (the decode step, the speculative verify,
    the narrow chunk) ABSORBED. Per-head K and V rows where the pool holds them
    flat (``cfg.kv_rows_flat``: ops/gqa_attention.py) have two forms of the one
    walk too, by the call's query rows a sequence: under one tile (the decode
    step, the speculative verify) each query head against the whole row,
    n_kv_heads times the products it needs, which is free while the rows' bytes
    bound the read; from one tile on (a chunk's 256 x H rows, MXU-shaped) a
    lane block a KV head, the per-head chain's own products (``gqa_plan``).
    From static facts alone, so ``Attention``, ``LatentAttention`` and the
    loop's ``seldon_llm_attn_*_total`` (``read_form`` their ``form`` label)
    agree by construction."""
    from seldon_core_tpu.ops.gqa_attention import gqa_plan
    from seldon_core_tpu.ops.latent_attention import expanded_walk
    from seldon_core_tpu.ops.page_walk import plan

    if cfg.mesh is not None or jnp.dtype(pool_dtype) != jnp.dtype(cfg.dtype):
        return None
    if cfg.kv_lora_rank:
        return (expanded_walk(s, cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim, cfg.kv_lora_rank, n_pages, page_size,
                              cfg.latent_row_dim)
                or plan(s, cfg.n_heads, n_pages, page_size, cfg.latent_row_dim, cfg.kv_lora_rank))
    if not cfg.kv_rows_flat:
        return None
    return gqa_plan(s, *cfg.read_heads, n_pages, page_size)


def read_form(walk) -> str:
    """``expanded`` where ``paged_read_walk`` gave latent attention's
    expanded-once walk, ``absorbed`` for every other read (a model without
    latent attention has one form, counted here too): the ``form`` label of
    ``seldon_llm_attn_calls_total``."""
    from seldon_core_tpu.ops.latent_attention import ExpandedWalk

    return "expanded" if isinstance(walk, ExpandedWalk) else "absorbed"


def _dense_stack(w, dtype):
    """A [H, ., .] stack a module multiplies densely (not a grouped matmul):
    an int8 stack (ops/quantize.py keeps stacks quantized into the module)
    is dequantized where it is used; it is a megabyte."""
    from seldon_core_tpu.ops.quantize import QuantizedTensor, dequantize_array

    if isinstance(w, QuantizedTensor):
        return dequantize_array(w, dtype)
    return w.astype(dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv 2405.04434 sec. 2.1).

        q_h     = [q^N_h ; RoPE(q^R_h)]              from wq, H heads; with cfg.q_lora_rank
                                                     from wq_b RMSNorm_q(wq_a x_t)
        c_t     = RMSNorm(W_DKV x_t)   k^R_t = RoPE(W_KR x_t)     (wkv_a = [W_DKV ; W_KR])
        row_t   = [c_t ; k^R_t]        the ONE thing cached a token, no head axis
        out     = wo concat_h absorbed_latent_attention(...)_h

    The cache entry is ``(rows, pos)`` (models/cache.py), dense or a paged pool
    addressed through ``block_tables``, a row W = cfg.latent_row_dim wide (dc +
    dr in whole 128-lane tiles, zeros behind), written by ``write_rows`` under
    the same rules as ``Attention``'s. The read is one
    expression over the whole view (``absorbed_latent_attention``); for the
    paged pool in a program LOWERED for a TPU it is one of the repo's two
    kernels that walk each sequence's live pages (ops/latent_attention.py),
    chosen by ``jax.lax.platform_dependent`` as ``MoEFFN`` chooses its grouped
    matmul, in the form ``paged_read_walk`` gives the call's shape: ABSORBED
    for a decode step, a speculative verify and a narrow chunk (W_UK on the
    query rows, W_UV on the output, no K or V of the context ever made: what
    few query rows want), EXPANDED ONCE for a wide chunk (the cached rows meet
    W_UK / W_UV inside a visit, once a chunk, and every query then pays the
    per-head products alone: 640 FLOPs a (query, key, head) pair and not 2,176
    at DeepSeek-V2's widths). The two differ by where bf16 rounds (q~ and the
    latent context there, K^N and V here); the float32 reference bounds both.
    Without a cache: full causal attention, returns (out, (rows,))."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 block_tables=None, adapters=None, adapter_ids=None):
        cfg = self.cfg
        if adapters is not None:
            raise ValueError("LoRA adapters do not apply to latent attention")
        b, s, _ = x.shape
        H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
        dt = cfg.dtype
        stack = dict(batch_axis=(0,))

        if cfg.q_lora_rank:
            # compressed queries: c^Q = RMSNorm_q(W_qa x), q = W_qb c^Q. wq_b
            # feeds the head split, so its int8 leaf is held output-major
            wq_a = param_with_axes(
                "wq_a", nn.initializers.lecun_normal(), (cfg.dim, cfg.q_lora_rank), jnp.float32,
                axes=("embed", "q_latent"))
            wq_b = param_with_axes(
                "wq_b", nn.initializers.lecun_normal(), (cfg.q_lora_rank, H * (dn + dr)),
                jnp.float32, axes=("q_latent", "heads"))
        else:
            wq = param_with_axes(
                "wq", nn.initializers.lecun_normal(), (cfg.dim, H * (dn + dr)), jnp.float32,
                axes=("embed", "heads"))
        wkv_a = param_with_axes(
            "wkv_a", nn.initializers.lecun_normal(), (cfg.dim, dc + dr), jnp.float32,
            axes=("embed", "kv_latent"))
        # held in the order the absorbed products read them: [H, K, N] with
        # the contracted axis in the middle. W_UK's fan-in is the LATENT
        # axis (k^N = W_UK c), its last
        w_uk = param_with_axes(
            "w_uk", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2, **stack),
            (H, dn, dc), jnp.float32, axes=("heads", "head_nope", "kv_latent"))
        w_uv = param_with_axes(
            "w_uv", nn.initializers.lecun_normal(**stack), (H, dc, dv), jnp.float32,
            axes=("heads", "kv_latent", "head_v"))
        wo = param_with_axes(
            "wo", nn.initializers.lecun_normal(), (H * dv, cfg.dim), jnp.float32,
            axes=("heads", "embed"))

        cos, sin = rotary_embedding(positions, dr, cfg.rope_theta, cfg.rope_scaling)
        if cfg.q_lora_rank:
            with jax.named_scope("attn.latent.q"):
                c_q = RMSNorm(cfg.q_lora_rank, cfg.norm_eps, "q_latent", name="q_norm")(
                    x @ wq_a.astype(dt))
                q = (c_q @ wq_b.astype(dt)).reshape(b, s, H, dn + dr)
        else:
            q = (x @ wq.astype(dt)).reshape(b, s, H, dn + dr)
        q_nope, q_rope = q[..., :dn], apply_rotary(q[..., dn:], cos, sin)
        with jax.named_scope("attn.latent.write"):
            kv_a = x @ wkv_a.astype(dt)
            c = RMSNorm(dc, cfg.norm_eps, "kv_latent", name="kv_norm")(kv_a[..., :dc])
            k_rope = apply_rotary(kv_a[..., None, dc:], cos, sin)[:, :, 0]
            row = jnp.concatenate([c, k_rope], axis=-1)  # [b, s, dc + dr]
            if cache is not None and cfg.latent_row_dim > dc + dr:
                row = jnp.pad(row, ((0, 0), (0, 0), (0, cfg.latent_row_dim - dc - dr)))
            if cache is None:
                new_cache = (row,)
            else:
                new_cache = write_rows(cache, (row,), positions, block_tables=block_tables,
                                       cache_index=cache_index)
                pool, pos_pool = new_cache
                if block_tables is not None:
                    bt = jnp.asarray(block_tables, jnp.int32)
        with jax.named_scope("attn.latent.read"):
            scale = latent_attention_scale(cfg)
            w_uk, w_uv = _dense_stack(w_uk, dt), _dense_stack(w_uv, dt)

            def read_expression():
                if cache is not None and block_tables is not None:
                    L = bt.shape[1] * pool.shape[1]
                    rows, pos_view = pool[bt].reshape(b, L, -1), pos_pool[bt].reshape(b, L)
                elif cache is not None:
                    rows, pos_view = pool, pos_pool
                else:
                    rows, pos_view = row, positions
                # one predicate for causality, empty rows (PAD_POS) and padding
                mask = pos_view[:, None, :] <= positions[:, :, None]
                return absorbed_latent_attention(
                    q_nope, q_rope, w_uk, w_uv, rows, mask, scale)

            def read_live_pages():
                from seldon_core_tpu.ops import latent_attention

                if read_form(walk) == "expanded":
                    # the projection's rows and W_UK / W_UV as they are: the
                    # context's K and V are made once a visit inside the kernel
                    return latent_attention.latent_expanded_attention(
                        q_nope, q_rope, w_uk, w_uv, pool, pos_pool, bt, positions, scale, walk,
                        interpret=False).reshape(b, s, H, dv)
                ctx = latent_attention.latent_page_attention(
                    absorbed_query_rows(q_nope, q_rope, w_uk, pool.shape[-1]),
                    pool, pos_pool, bt, positions, scale, dc, walk, interpret=False)
                return jnp.einsum("bshc,hcv->bshv", ctx, w_uv)

            # the paged pool on one TPU: the repo's kernels walk each
            # sequence's live pages (ops/latent_attention.py: absorbed, or
            # expanded once for a wide chunk); every other lowering, a mesh,
            # the dense cache and a call shape no kernel takes keep the
            # expression over the whole view
            walk = None
            if cache is not None and block_tables is not None:
                walk = paged_read_walk(cfg, s, bt.shape[1], pool.shape[1], pool.dtype)
            if walk is not None:
                out = jax.lax.platform_dependent(tpu=read_live_pages, default=read_expression)
            else:
                out = read_expression()
        return out.reshape(b, s, H * dv) @ wo.astype(dt), new_cache


def ffn_activation(cfg: TransformerConfig):
    """The gate's activation of every gated FFN (``cfg.ffn_act``)."""
    return jax.nn.relu if cfg.ffn_act == "relu" else jax.nn.silu


class DenseFFN(nn.Module):
    cfg: TransformerConfig
    width: int = 0   # 0 = cfg.ffn_dim

    @nn.compact
    def __call__(self, x, adapters: Optional[dict] = None,
                 adapter_ids: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        width = self.width or cfg.ffn_dim
        w1 = param_with_axes("w1", nn.initializers.lecun_normal(), (cfg.dim, width), jnp.float32,
                             axes=("embed", "mlp"))
        w2 = param_with_axes("w2", nn.initializers.lecun_normal(), (width, cfg.dim), jnp.float32,
                             axes=("mlp", "embed"))
        w3 = param_with_axes("w3", nn.initializers.lecun_normal(), (cfg.dim, width), jnp.float32,
                             axes=("embed", "mlp"))
        dt = cfg.dtype
        up = x @ w1.astype(dt)
        gate = x @ w3.astype(dt)
        if adapters is not None:
            up = up + lora_delta(x, *adapters["w1"], adapter_ids,
                                 adapters["scale"])
            gate = gate + lora_delta(x, *adapters["w3"], adapter_ids,
                                     adapters["scale"])
        h = ffn_activation(cfg)(up) * gate
        down = h @ w2.astype(dt)
        if adapters is not None:
            down = down + lora_delta(h, *adapters["w2"], adapter_ids,
                                     adapters["scale"])
        return down


class MoEFFN(nn.Module):
    """Top-k token-choice MoE, sparse: each token computes its k experts and
    no other. The (token, expert) pairs are sorted by expert and the three
    projections are grouped matmuls over the sorted rows, so an expert's
    weights are read once per call, and only if a row chose it.

    The grouped matmul is chosen by the platform the program is LOWERED for
    (``jax.lax.platform_dependent``). For a TPU it is the repo's own Pallas
    kernel (ops/grouped_matmul.py): an expert's whole matrix is one block,
    and the sorted rows are walked in a row tile that follows the call's
    static shapes (rows / experts = the mean group; ``row_tile``), one visit
    per (expert, row tile) pair, all three projections over one visit list;
    a visit of the 128-row tile (a 1,024-row chunk whose mean group is over 64
    rows) multiplies the run of 32-row blocks that holds its rows.
    Everywhere else (tier-1 on the CPU, the float32 checks), and where the
    stacks are sharded over a mesh (``cfg.mesh``, which LLMServer sets when
    it shards: the kernel is one device's program), it is
    ``jax.lax.ragged_dot``, which on a TPU is XLA's own kernel with a 256-row
    tile (two thirds of a DeepSeek chunk before PR 30, PERF.md section 6).

    ``valid`` ([b, s] bool) marks the rows that are live tokens. The step
    programs have static shapes: a decode step computes every slot and a
    chunk is padded to its program's length, and a row that is no token must
    not choose experts (it would make their weights be read, and count as
    load). Such rows join no group and come out as zeros: the kernel writes
    them; behind ``ragged_dot`` they are masked.

    An int8 stack (ops/quantize.py, per-expert scales [e, f]) goes into the
    grouped matmul as int8, and its scale multiplies the product, where it
    commutes: no floating copy of a stack exists in HBM (the kernel converts
    an expert's block in VMEM).

    When the "moe" collection is mutable the layer sows ``tokens`` [b, e]
    int32, how many tokens of each sequence went to each expert, and
    ``tile_rows``, the rows the kernel multiplied (its visits x the row tile,
    a 128-row tile's visits their sub-block: ``Visits.multiplied``; 0 behind
    ``ragged_dot``): ``moe_routing_stats`` reduces them over the layers."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, valid: Optional[jnp.ndarray] = None,
                 router_in: Optional[jnp.ndarray] = None):
        """``router_in`` [b, s, dim]: the array the router multiplies where it is
        not the FFN's own input ``x`` (cfg.router_input "layer_input": the
        block's input, handed in by TransformerBlock)."""
        from seldon_core_tpu.ops.quantize import QuantizedTensor

        cfg = self.cfg
        e = cfg.n_experts             # the router's width: every expert of the model
        held, first = cfg.n_experts_held, cfg.experts_first   # those whose weights are here
        dt = cfg.dtype
        router = param_with_axes("router", nn.initializers.lecun_normal(), (cfg.dim, e), jnp.float32,
                                 axes=("embed", "expert"))
        # a stack of matrices: the fan-in is one matrix's, not the stack's
        stack_init = nn.initializers.lecun_normal(batch_axis=(0,))
        w1 = param_with_axes("w1", stack_init, (held, cfg.dim, cfg.ffn_dim), jnp.float32,
                             axes=("expert", "embed", "mlp"))
        w2 = param_with_axes("w2", stack_init, (held, cfg.ffn_dim, cfg.dim), jnp.float32,
                             axes=("expert", "mlp", "embed"))
        w3 = param_with_axes("w3", stack_init, (held, cfg.dim, cfg.ffn_dim), jnp.float32,
                             axes=("expert", "embed", "mlp"))
        b, s, d = x.shape
        t = b * s
        k = min(cfg.n_experts_per_token, e)
        xf = x.reshape(t, d)

        if cfg.router_bias:
            select_bias = param_with_axes("router_bias", small_leaf_init("router_bias"), (e,),
                                          jnp.float32, axes=("expert_select",))
        with jax.named_scope("moe.route"):
            routed = xf if router_in is None else router_in.reshape(t, d)
            logits = routed.astype(jnp.float32) @ router
            if cfg.router_score == "sigmoid":
                probs = jax.nn.sigmoid(logits)
            else:
                probs = jax.nn.softmax(logits, axis=-1)
            if cfg.router_bias:
                # the bias chooses and does not weigh
                _, chosen = jax.lax.top_k(probs + select_bias.astype(jnp.float32), k)
                gates = jnp.take_along_axis(probs, chosen, axis=-1)
            else:
                gates, chosen = jax.lax.top_k(probs, k)  # [t, k]
            if cfg.router_renormalize:
                total = jnp.sum(gates, axis=-1, keepdims=True)
                eps = cfg.router_renormalize_eps
                if eps is None:
                    eps = 1e-20 if cfg.router_score == "sigmoid" else 0.0
                gates = gates / (total + eps if eps else total)
            if cfg.routed_scaling_factor != 1.0:
                gates = gates * cfg.routed_scaling_factor
            sowing = self.is_mutable_collection("moe") and not self.is_initializing()
            if sowing:
                # which experts each row took: what a logits probe's reference follows
                self.sow("moe", "choice", chosen.reshape(b, s, k).astype(jnp.int32))
            # the group of each pair among the HELD experts; group ``held`` does
            # not exist: the pairs of a row that is no token, and those whose
            # expert lies on another chip, sort behind every expert's
            here = (chosen >= first) & (chosen < first + held)
            live = here if valid is None else here & valid.reshape(t, 1)
            group = jnp.where(live, chosen - first, held)
            pair_expert = group.reshape(t * k)
            order = jnp.argsort(pair_expert)
            row_expert = pair_expert[order]
            by_token = jnp.sum(
                group[:, :, None] == jnp.arange(held, dtype=group.dtype), axis=1,
                dtype=jnp.int32)  # [t, held]
            group_sizes = jnp.sum(by_token, axis=0)
            if sowing:
                self.sow("moe", "tokens", jnp.sum(by_token.reshape(b, s, held), axis=1))
                routed = jnp.int32(t * k) if valid is None else k * jnp.sum(valid, dtype=jnp.int32)
                self.sow("moe", "pairs", jnp.stack([jnp.sum(group_sizes), routed]))

        with jax.named_scope("moe.experts"):
            rows = xf[order // k].astype(dt)  # [t*k, d], sorted by expert

            def split(w):  # an int8 stack goes in as int8, with its scales
                return (w.q, w.scale) if isinstance(w, QuantizedTensor) else (w, None)

            act = ffn_activation(cfg)

            def swiglu(grouped):
                h = act(grouped(rows, *split(w1))) * grouped(rows, *split(w3))
                return grouped(h.astype(dt), *split(w2))

            def experts_kernel():
                from seldon_core_tpu.ops.grouped_matmul import (
                    grouped_matmul, make_visits, row_tile)

                # the tile follows the mean group, rows over ALL the router's
                # experts; the rows behind the last held group (with a share,
                # three quarters of them) get the kernel's zeros, a visit a
                # tile that multiplies nothing: leaving those visits out and
                # masking the rows instead measured 2 us a layer of a step and
                # 13 us of a chunk SLOWER at Qwen3-Next's shapes (PR 38)
                visits = make_visits(group_sizes, t * k, row_tile(t * k, e))
                y = swiglu(lambda lhs, w, scale: grouped_matmul(
                    lhs, w, visits, scale, interpret=False))
                return y, visits.multiplied

            def experts_ragged_dot():
                row_scale = jnp.minimum(row_expert, held - 1)

                def grouped(lhs, w, scale):
                    if scale is None:   # a floating stack, in the rows' dtype
                        return jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), group_sizes,
                                                  preferred_element_type=jnp.float32)
                    return jax.lax.ragged_dot(
                        lhs, w, group_sizes,
                        preferred_element_type=jnp.float32) * scale[row_scale]

                # what lies behind the last group is not ragged_dot's to define
                y = jnp.where((row_expert < held)[:, None], swiglu(grouped), 0.0)
                return y, jnp.zeros((), jnp.int32)

            # the kernel is one device's program, compiled by Mosaic: stacks
            # sharded over a mesh, and every lowering that is not for a TPU
            # (tier-1, the float32 checks), keep ragged_dot
            if cfg.mesh is None:
                y, tile_rows = jax.lax.platform_dependent(
                    tpu=experts_kernel, default=experts_ragged_dot)
            else:
                y, tile_rows = experts_ragged_dot()
            if sowing:
                self.sow("moe", "tile_rows", tile_rows)
            y = y[jnp.argsort(order)].reshape(t, k, d)
            out = jnp.einsum("tkd,tk->td", y, gates)
        out = out.reshape(b, s, d).astype(x.dtype)
        if cfg.n_shared_experts > 0:
            # the shared experts are one dense SwiGLU every token takes (and
            # every chip of an expert-parallel deployment computes alike)
            with jax.named_scope("moe.shared"):
                shared = DenseFFN(cfg, cfg.n_shared_experts * cfg.ffn_dim, name="shared")(x)
                if cfg.shared_expert_gate:
                    w_gate = param_with_axes(
                        "shared_gate", small_leaf_init("shared_gate"), (cfg.dim, 1),
                        jnp.float32, axes=("embed", "expert_gate"))
                    scalar = jax.nn.sigmoid(x.astype(jnp.float32) @ w_gate)
                    shared = (scalar * shared).astype(x.dtype)
                out = out + shared
        return out


def moe_choices(sown: dict, cfg: TransformerConfig) -> jnp.ndarray:
    """[b, s, n_moe_layers, k] int32 out of the same collection: the experts
    each row of the call took in each MoE layer, in the router's order (a row
    that is padding holds whatever its padding scored)."""
    return jnp.stack([sown[f"layer_{i}"]["moe"]["choice"][0]
                      for i in range(cfg.first_dense_layers, cfg.n_layers)], axis=2)


def moe_routing_stats(sown: dict, cfg: TransformerConfig):
    """Reduce what the MoE layers of one forward sowed (the "moe" collection
    of ``Transformer.apply(..., mutable=["moe"])``) to ``(tokens, stats)``:
    ``tokens`` [b, held] int32, tokens of each sequence routed to each expert
    HELD here, summed over layers; ``stats`` [6] int32 = live rows of the
    call, routed (token, expert) pairs whose expert is held, distinct held
    experts touched, the largest expert group, the rows the grouped-matmul
    kernel multiplied (visits x row tile; 0 where ``ragged_dot`` served) and
    the pairs routed to experts that lie ELSEWHERE (0 without a share), the
    last five summed over the ``n_moe_layers`` layer-calls."""
    layers = [sown[f"layer_{i}"]["moe"] for i in range(cfg.first_dense_layers, cfg.n_layers)]
    per_layer = jnp.stack([layer["tokens"][0] for layer in layers])  # [L, b, held]
    groups = jnp.sum(per_layer, axis=1)  # [L, held]
    pairs = jnp.stack([layer["pairs"][0] for layer in layers])   # [L, 2]: held, routed
    k = min(cfg.n_experts_per_token, cfg.n_experts)
    stats = jnp.stack([
        pairs[0, 1] // k, jnp.sum(groups),
        jnp.sum(groups > 0, dtype=jnp.int32), jnp.sum(jnp.max(groups, axis=1)),
        sum(layer["tile_rows"][0] for layer in layers),
        jnp.sum(pairs[:, 1] - pairs[:, 0])])
    return jnp.sum(per_layer, axis=0), stats.astype(jnp.int32)


@partial(jax.jit, static_argnums=(1, 2))
def sinkhorn_entrywise(m: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """``iters`` Sinkhorn iterations on n x n matrices ``m`` [n, n, ...] (the
    tokens behind the matrix axes): rows divided by their sums, then columns
    by theirs. Written entry by entry, n^2 arrays of tokens, so that every
    iteration is elementwise over the tokens (ops/sinkhorn.py has the one
    body). A jit of its own: the 60 sub-layer calls of a 30-layer program trace
    it once and lower to calls of one function."""
    from seldon_core_tpu.ops.sinkhorn import entrywise_iteration

    n = m.shape[0]
    rows = jax.lax.fori_loop(0, iters, lambda _, rows: entrywise_iteration(rows, eps),
                             [[m[i, j] for j in range(n)] for i in range(n)])
    return jnp.stack([jnp.stack(row) for row in rows])


def sinkhorn(m: jnp.ndarray, iters: int, eps: float, kernel: bool = True) -> jnp.ndarray:
    """One iteration body, run by the platform the program is LOWERED for (as
    MoEFFN's grouped matmul is chosen): for a TPU the repo's Pallas kernel
    (ops/sinkhorn.py: the sixteen entries in registers through all the
    iterations, one op a sub-layer), because no form of the chain as XLA ops is
    both few ops on the device and few instructions for the compiler; a loop
    of the same body elsewhere, and where ``kernel`` is false (on a mesh: the
    kernel is one device's program). The arithmetic and its order are the same."""
    from seldon_core_tpu.ops.sinkhorn import sinkhorn as sinkhorn_kernel

    if not kernel:
        return sinkhorn_entrywise(m, iters, eps)
    return jax.lax.platform_dependent(
        m, tpu=lambda m: sinkhorn_kernel(m, iters, eps, interpret=False),
        default=lambda m: sinkhorn_entrywise(m, iters, eps))


class HyperConnection(nn.Module):
    """One sub-layer's manifold-constrained hyper-connection (mHC, arXiv
    2512.24880, over Hyper-Connections, arXiv 2409.19606): how the sub-layer
    reads the n = cfg.hc_mult residual streams and writes back to them.
    ``X`` [b, s, n, C] in cfg.dtype; everything named H or m is float32.

        v      = vec(X) in R^{nC};  r = (mean(v^2) + norm_eps)^-1/2
        m      = r (v Phi)                     -> [m_pre (n) ; m_post (n) ; m_res (n^2)]
        H_pre  = sigmoid(a_pre m_pre + b_pre)
        H_post = 2 sigmoid(a_post m_post + b_post)
        M      = exp(clamp(a_res mat(m_res) + B_res, -hc_res_clamp, +hc_res_clamp))
        H_res  = hc_sinkhorn_iters x (rows / (rowsum + hc_eps), then columns / (colsum + hc_eps))
        u      = sum_i H_pre[i] X[i]           the sub-layer's input, before its RMSNorm
        X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y       (``write_back``)

    ``__call__`` returns (u, H_post [n, b, s], H_res [n, n, b, s]) under the
    scope ``resid.hc.pre``: the maps are held maps-major, the tokens the minor
    axes of every small array. Departures from the paper are in
    models/reference.py's docstring, which computes the same equations with
    none of this code."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, X):
        cfg = self.cfg
        n, C = cfg.hc_mult, cfg.dim
        maps = 2 * n + n * n
        phi = param_with_axes("phi", small_leaf_init("phi"), (n * C, maps), jnp.float32,
                              axes=("hc_embed", "hc_maps"))
        alpha = param_with_axes("alpha", small_leaf_init("alpha"), (3,), jnp.float32,
                                axes=("hc_maps",))
        b_pre = param_with_axes("b_pre", small_leaf_init("b_pre"), (n,), jnp.float32,
                                axes=("hc_maps",))
        b_post = param_with_axes("b_post", small_leaf_init("b_post"), (n,), jnp.float32,
                                 axes=("hc_maps",))
        b_res = param_with_axes("b_res", small_leaf_init("b_res"), (n, n), jnp.float32,
                                axes=("hc_maps", "hc_maps"))
        b, s = X.shape[:2]
        with jax.named_scope("resid.hc.pre"):
            v = X.reshape(b, s, n * C).astype(jnp.float32)
            r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1) + cfg.norm_eps)          # [b, s]
            # maps-major [maps, b, s]: the tokens are the minor axis of every
            # small array below
            m = jnp.einsum("bsk,km->mbs", v, phi.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST) * r
            # each map's scalar and bias as vectors over the maps (the TPU
            # compiler still splits alpha into three scalars in tiny fusions of
            # their own, 2 us each in a step and 13 us in a chunk: PERF.md
            # section 7)
            which = jnp.asarray([0] * n + [1] * n + [2] * n * n)
            bias = jnp.concatenate([b_pre, b_post, b_res.reshape(-1)]).astype(jnp.float32)
            m = m * alpha.astype(jnp.float32)[which][:, None, None] + bias[:, None, None]
            h_pre = jax.nn.sigmoid(m[:n])
            h_post = 2.0 * jax.nn.sigmoid(m[n:2 * n])
            raw = jnp.exp(jnp.clip(m[2 * n:].reshape(n, n, b, s),
                                   -cfg.hc_res_clamp, cfg.hc_res_clamp))
            h_res = sinkhorn(raw, cfg.hc_sinkhorn_iters, cfg.hc_eps, kernel=cfg.mesh is None)
            u = sum(h_pre[i][..., None] * X[:, :, i].astype(jnp.float32) for i in range(n))
        return u.astype(X.dtype), h_post, h_res


def hc_write_back(X, y, h_post, h_res):
    """X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, float32 inside one fusion,
    the streams stored in X's dtype."""
    n = X.shape[2]
    with jax.named_scope("resid.hc.post"):
        streams = [X[:, :, j].astype(jnp.float32) for j in range(n)]
        y32 = y.astype(jnp.float32)
        return jnp.stack(
            [(sum(h_res[i, j][..., None] * streams[j] for j in range(n))
              + h_post[i][..., None] * y32).astype(X.dtype) for i in range(n)], axis=2)


class TransformerBlock(nn.Module):
    """``x`` is the residual [b, s, dim] or, with cfg.hc_mult > 1, the residual
    streams [b, s, hc_mult, dim]: each sub-layer then reads a mix of the
    streams and writes back through its HyperConnection."""

    cfg: TransformerConfig
    # read through cfg.layer_reads alone: the token mixer's kind, its window
    # and rotary embedding, the FFN's kind
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 block_tables=None, adapters=None, adapter_ids=None,
                 valid=None, state_slots=None, memory=None, lambda_init=None):
        """-> (x, new_cache), or (x, new_cache, m) from the layer cfg.memory_source.
        ``memory``: the m a "gmu" layer reads; ``lambda_init``: differential
        attention's, the layer's own as a traced scalar; a "cross_attention"
        layer's ``cache`` is the entry of cfg.kv_source as its write left it."""
        cfg = self.cfg
        attention = LatentAttention if cfg.kv_lora_rank else Attention
        streams = cfg.hc_mult > 1
        if streams:
            X, (x, h_post, h_res) = x, HyperConnection(cfg, name="attention_hc")(x)
        kind, window, rotary, routed, hands_up = cfg.layer_reads(self.layer)
        handed = None
        # what the router multiplies where it reads the block's INPUT: nothing
        # is computed here, MoEFFN is handed the array
        router_in = x if cfg.router_input == "layer_input" else None
        # the SAME two weights a layer stand before a sub-layer (x + f(norm(x)))
        # or on its branch (x + norm(f(x)): cfg.norm_placement "branch")
        branch = cfg.norm_placement == "branch"
        mixer_norm = block_norm(cfg, "attention_norm" if kind in ATTENTION_LAYER_KINDS
                                or kind == "cross_attention" else "operator_norm")

        def mixer_in():
            return x if branch else mixer_norm(x)

        if kind == "conv":
            # the scopes are the operator's own (mix.conv.*): "attn" stays the
            # attention layers'
            h, new_cache = ShortConv(cfg, name="conv")(
                mixer_in(), positions, valid, cache, state_slots)
        elif kind == "linear_attention":
            # likewise mix.gdn.*
            h, new_cache = GatedDeltaNet(cfg, name="linear_attn")(
                mixer_in(), positions, valid, cache, state_slots)
        elif kind == "mamba":
            # likewise mix.ssd.*
            h, new_cache = Mamba2Mixer(cfg, name="mamba")(
                mixer_in(), positions, valid, cache, state_slots)
        elif kind == "s6":
            # likewise mix.s6.*
            h, new_cache, *handed = Mamba1Mixer(cfg, hands_up, name="s6")(
                mixer_in(), positions, valid, cache, state_slots)
        elif kind == "gmu":
            # mix.gmu; nothing is kept: an empty entry
            h, new_cache = GatedMemoryUnit(cfg, name="gmu")(mixer_in(), memory), put_state(
                None, None, ())
        else:
            # a pair of tables (full, window): each layer reads its class's
            if isinstance(block_tables, tuple):
                block_tables = block_tables[kind == "sliding_attention"]
            own = {} if cfg.kv_lora_rank else {"window": window, "rotary": rotary}
            if kind == "cross_attention":
                own["cross"] = True
            more = {"lambda_init": lambda_init} if cfg.differential else {}
            with jax.named_scope("attn"):
                h, new_cache = attention(cfg, name="attention", **own)(
                    mixer_in(), positions, cache,
                    cache_index, block_tables, adapters, adapter_ids, **more,
                )
            if kind == "cross_attention":
                new_cache = put_state(None, None, ())
        if branch:
            h = mixer_norm(h)
        if cfg.residual_multiplier != 1.0:   # every branch, before it joins the residual
            h = h * cfg.residual_multiplier
        ffn_norm = block_norm(cfg, "ffn_norm")
        if streams:
            X = hc_write_back(X, h, h_post, h_res)
            x, h_post, h_res = HyperConnection(cfg, name="ffn_hc")(X)
            ffn_in = ffn_norm(x)
        else:
            x = x + h
            ffn_in = x if branch else ffn_norm(x)
        if routed:
            f = MoEFFN(cfg, name="moe")(ffn_in, valid, router_in)
        else:
            width = cfg.dense_ffn_dim if cfg.n_experts > 0 else 0
            f = DenseFFN(cfg, width, name="ffn")(ffn_in, adapters, adapter_ids)
        if branch:
            f = ffn_norm(f)
        if cfg.residual_multiplier != 1.0:
            f = f * cfg.residual_multiplier
        if streams:
            return hc_write_back(X, f, h_post, h_res), new_cache
        return (x + f, new_cache, *handed) if handed else (x + f, new_cache)


# the collections a block's modules sow into (MoEFFN's routing counters)
BLOCK_SOWS = ("moe",)


@partial(jax.jit, static_argnames=("cfg", "layer", "sowing", "rules"))
def transformer_block(params, *args, cfg: TransformerConfig, layer: int, sowing: tuple,
                      rules: tuple):
    """``TransformerBlock(cfg, layer)`` over ONE layer's parameter subtree:
    ``((x, new_cache), sown)``. A jitted function of its own, as
    ``paged_live_read`` and ``ssd`` are one level down, so the layers of a
    class (``cfg.layer_class``) share ONE trace of it in whatever program calls
    them, and that program lowers to calls of one function which the compiler
    inlines: a Mistral program's 32 blocks cost the Python of one. ``rules`` are
    flax's logical axis rules as the caller's thread has them (they are no part
    of jax's own key for a trace)."""
    with nn_partitioning.axis_rules(rules):
        # (a module of its own tree: made inside a module's method, it would be a child)
        return TransformerBlock(cfg, layer, parent=None).apply(
            {"params": params}, *args, mutable=list(sowing))


class SharedBlock(nn.Module):
    """Layer ``layer_{i}`` of a model that is not being initialised: its
    parameters, where ``TransformerBlock`` of that name put them, go through
    ``transformer_block`` with ``layer`` = the layer's CLASS, and what the block
    sowed is put where the block would have sown it (``layer_{i}/moe/...``)."""

    cfg: TransformerConfig
    layer: int = 0

    def __call__(self, *args):
        sowing = tuple(c for c in BLOCK_SOWS if self.is_mutable_collection(c))
        out, sown = transformer_block(
            self.variables["params"], *args, cfg=self.cfg, layer=self.layer, sowing=sowing,
            rules=tuple(nn_partitioning.get_axis_rules()))
        for collection, modules in sown.items():
            for name, leaves in modules.items():
                self.put_variable(collection, name, leaves)
        return out


def enter_streams(x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """[b, s, dim] -> the residual streams [b, s, hc_mult, dim], each a copy
    (Hyper-Connections, arXiv 2409.19606 section 3); the identity without."""
    if cfg.hc_mult > 1:
        return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (cfg.hc_mult, cfg.dim))
    return x


def leave_streams(x: jnp.ndarray, cfg: TransformerConfig) -> jnp.ndarray:
    """The streams' sum (float32 inside), [b, s, dim]; the identity without."""
    if cfg.hc_mult > 1:   # widened one stream at a time, as everywhere
        return sum(x[:, :, i].astype(jnp.float32) for i in range(cfg.hc_mult)).astype(x.dtype)
    return x


class MTPModule(nn.Module):
    """DeepSeek-V3's multi-token-prediction module (arXiv 2412.19437 section
    2.2), depth 1: from the main model's hidden state ``h`` [b, s, dim] of
    token t (after the streams' exit, BEFORE the final norm) and the embedding
    of token t + 1, the hidden state that the shared head turns into the logits
    of token t + 2.

        h' = W_eh [RMSNorm_h(h_t) ; RMSNorm_e(emb(x_t+1))]          W_eh [2 dim, dim]
        out = RMSNorm(leave(Block(enter(h'))))      one MoE block, its own final norm

    The block is a TransformerBlock of the model's MoE kind with its own
    residual streams, entered and left as the main model's. It runs without a
    cache (the full causal forward): serving from it is not wired."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, next_emb, positions, valid=None):
        cfg = self.cfg
        w_eh = param_with_axes("eh_proj", nn.initializers.lecun_normal(), (2 * cfg.dim, cfg.dim),
                               jnp.float32, axes=("embed_pair", "embed"))
        both = jnp.concatenate([RMSNorm(cfg.dim, cfg.norm_eps, name="hnorm")(h),
                                RMSNorm(cfg.dim, cfg.norm_eps, name="enorm")(next_emb)], axis=-1)
        x = enter_streams(both @ w_eh.astype(cfg.dtype), cfg)
        x, _ = TransformerBlock(cfg, cfg.n_layers, name="block")(x, positions, valid=valid)
        return RMSNorm(cfg.dim, cfg.norm_eps, name="norm")(leave_streams(x, cfg))


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, caches=None, cache_index=None,
                 block_tables=None, adapters=None, adapter_ids=None, next_tokens=None,
                 state_slots=None, head_row=None):
        """tokens: [b, s] int32. Returns (logits [b, s, vocab], new_caches).
        With ``next_tokens`` ([b, s] int32: each position's NEXT token) and
        cfg.mtp_layers, a cache-less forward also returns the MTP module's
        logits [b, s, vocab] (position t: token t + 2), as a third value.
        ``block_tables`` ([b, n_pages] int32, shared by every layer; or a pair
        of such tables ``(full, window)`` where cfg.window_layers: each layer
        reads the table of its page class) switches the caches to the
        paged-pool layout — see Attention.

        ``adapters`` (the dense LoRA pool pytree from
        runtime/adapters.py: {proj: (A [N, L, d_in, r], B [N, L, r,
        d_out]), "scale": [N]}) plus ``adapter_ids`` ([b] int32) turn on
        per-sequence batched low-rank deltas on the q/o/FFN projections —
        each layer slices its own factors out of the pool and applies one
        gather+einsum pair per adapted projection (``lora_delta``).
        adapter id 0 is the reserved zero-delta identity.

        ``state_slots`` ([b] int32) names the row of the state layers' blocks
        each sequence continues (ShortConv, GatedDeltaNet); None = row i is
        sequence i's.

        ``head_row`` (int32 scalar, traced) is the ONE row of ``s`` whose logits
        the caller reads: the head runs for that row alone and the logits are
        [b, 1, vocab]; negative = the caller reads none, and the head does not
        run (zeros of that shape come back). A prefill chunk's
        (servers/llmserver.py ``_get_prefill_chunk``)."""
        from seldon_core_tpu.ops.quantize import (
            QuantizedTensor, dequantize_array, dequantize_params, lookup_rows)

        cfg = self.cfg
        b, s = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if adapters is not None and adapter_ids is None:
            raise ValueError("adapters need adapter_ids (one id per "
                             "sequence; 0 = identity)")
        emb = param_with_axes(
            "tok_embeddings", nn.initializers.normal(stddev=0.02), (cfg.vocab_size, cfg.dim),
            jnp.float32, axes=("vocab", "embed"),
        )
        # an int8 table arrives as it is held: its rows are gathered, then
        # dequantized (ops/quantize.py, "a row lookup")
        if cfg.embedding_multiplier != 1.0:   # multiplied in float32, rounded once
            x = (lookup_rows(emb, tokens, jnp.float32) * cfg.embedding_multiplier).astype(cfg.dtype)
        else:
            x = lookup_rows(emb, tokens, cfg.dtype)
        x = enter_streams(with_sharding_constraint(x, ("batch", "seq", "embed")), cfg)
        valid = None
        if cfg.n_experts > 0 or cfg.state_layers:
            # rows that are tokens: not the padding of a chunk (PAD_POS), and
            # not a slot nobody holds, whose block-table row is all TRASH_PAGE
            # (a slot that is prefilling is such a row of the decode step: its
            # state is the chunks' to write)
            valid = positions < PAD_POS
            if block_tables is not None:
                full = block_tables[0] if isinstance(block_tables, tuple) else block_tables
                valid &= (jnp.asarray(full)[:, :1] != TRASH_PAGE)
        new_caches = []
        # what goes up the stack beside the residual (SambaY): the scan output m
        # of cfg.memory_source, and the entry of cfg.kv_source as its write left it
        carried = cfg.differential or cfg.kv_source is not None or cfg.memory_source is not None
        memory = shared = None
        rules = tuple(nn_partitioning.get_axis_rules())

        served = not self.is_initializing()

        def run_layer(i, x, positions, valid, memory=None):
            """Layer i over ``x``: (x, its new entry, m where it hands one up)."""
            kind = cfg.layer_kind(i)
            layer_adapters = None
            if adapters is not None:
                # slice this layer's factors: [N, L, ...] -> [N, ...]
                layer_adapters = {
                    proj: (ab[0][:, i], ab[1][:, i])
                    for proj, ab in adapters.items() if proj != "scale"
                }
                layer_adapters["scale"] = adapters["scale"]
            layer_cache = shared if kind == "cross_attention" else (
                caches[i] if caches is not None else None)
            args = (x, positions, layer_cache, cache_index, block_tables,
                    layer_adapters, adapter_ids, valid, state_slots)
            if carried:
                args += (memory if kind == "gmu" else None,
                         jnp.float32(cfg.lambda_init(i)) if cfg.differential else None)
            if served and i in cfg.cross_decoder_layers:
                # a layer a chunk runs inside a conditional's branch: no module is
                # made there (it sows nothing), and its int8 matrices arrive as
                # they are held and are dequantized HERE, where they are multiplied
                # (ahead of the conditional they would be written out whole every
                # call); the same call in every program
                return transformer_block(
                    dequantize_params(self.variables["params"][f"layer_{i}"]), *args, cfg=cfg,
                    layer=cfg.layer_class(i), sowing=(), rules=rules)[0]
            # (an initialisation makes each layer's parameters where they lie)
            block = (SharedBlock(cfg, cfg.layer_class(i), name=f"layer_{i}") if served
                     else TransformerBlock(cfg, i, name=f"layer_{i}"))
            return block(*args)

        # a prompt's chunk (``head_row``) of a model whose layers past
        # cfg.kv_source cache nothing runs THOSE on the one row that is read
        narrow_from = (cfg.kv_source + 1 if head_row is not None and served
                       and cfg.cross_decoder_layers else cfg.n_layers)
        for i in range(narrow_from):
            x, nc, *handed = run_layer(i, x, positions, valid, memory)
            new_caches.append(nc)
            if handed:
                memory, = handed
            if i == cfg.kv_source:
                shared = nc
        final_norm = block_norm(cfg, "norm")
        head = emb if cfg.tie_embeddings else param_with_axes(
            "lm_head", nn.initializers.normal(stddev=0.02), (cfg.dim, cfg.vocab_size),
            jnp.float32, axes=("embed", "vocab"),
        )

        def logits_of(rows):
            # a matrix that arrives int8 (a served head, a tied table) is
            # dequantized HERE, where it is multiplied
            w = dequantize_array(head) if isinstance(head, QuantizedTensor) else head
            logits = rows @ (w.T if cfg.tie_embeddings else w)
            return logits / cfg.logits_scaling if cfg.logits_scaling != 1.0 else logits

        if narrow_from < cfg.n_layers:
            # the cross-decoder (every layer past cfg.kv_source: they write no
            # cache), the final norm and the head on ``head_row``'s row alone,
            # all under the ONE conditional that skips them where no row is read
            weights = final_norm()

            def take(rows):
                return jax.lax.dynamic_slice_in_dim(rows, jnp.maximum(head_row, 0), 1, axis=1)

            def cross_decoder(x, memory, positions, valid):
                for i in cfg.cross_decoder_layers:
                    x, _ = run_layer(i, x, positions, valid, memory)
                return logits_of(normed_by(cfg, weights, x).astype(jnp.float32))

            new_caches += [put_state(None, None, ())] * (cfg.n_layers - narrow_from)
            return jax.lax.cond(
                head_row >= 0, cross_decoder,
                lambda *_: jnp.zeros((b, 1, cfg.vocab_size), jnp.float32),
                take(x), take(memory), take(positions), take(valid)), new_caches
        hidden = leave_streams(x, cfg)
        x = final_norm(hidden).astype(jnp.float32)

        if head_row is not None:
            # the row is taken BEFORE the head (the product is a row's own), and
            # a caller that reads none pays for no head. The norm stays ahead of
            # the conditional, over all rows as everywhere (no dearer than inside
            # it: PERF.md section 7): its row is then the all-rows form's to the
            # bit, which the sampler's parity with generate() rests on. An int8 head arrives as it is held
            # (servers/llmserver.py load()): dequantized ahead of the conditional,
            # the whole matrix would be written out every call
            row = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(head_row, 0), 1, axis=1)
            return jax.lax.cond(
                head_row >= 0, logits_of,
                lambda rows: jnp.zeros((b, 1, cfg.vocab_size), jnp.float32), row), new_caches
        logits = logits_of(x)
        if cfg.mtp_layers and (next_tokens is not None or self.is_initializing()):
            # the embedding and the head are the main model's, the norms its own
            if caches is not None:
                raise ValueError("the MTP module runs cache-less: serving from it is not wired")
            after = tokens if next_tokens is None else next_tokens
            mtp = MTPModule(cfg, name="mtp")(
                hidden, lookup_rows(emb, after, cfg.dtype), positions, valid)
            if next_tokens is not None:
                return logits, new_caches, logits_of(mtp.astype(jnp.float32))
        return logits, new_caches


@register_model("transformer")
def make_transformer(**kwargs):
    dtype = kwargs.pop("dtype", "bfloat16")
    scaling = kwargs.pop("rope_scaling", None)
    if isinstance(scaling, dict):  # normalize to a hashable config field
        scaling = tuple(sorted(scaling.items()))
    for listed in ("layer_types", "rope_layout"):
        if isinstance(kwargs.get(listed), list):
            kwargs[listed] = tuple(kwargs[listed])
    kvd = normalize_kv_cache_dtype(kwargs.pop("kv_cache_dtype", "bf16"))
    cfg = TransformerConfig(dtype=jnp.dtype(dtype), rope_scaling=scaling,
                            kv_cache_dtype=kvd, **kwargs)
    return Transformer(cfg)


@register_model("llama2-7b")
def make_llama2_7b(dtype: str = "bfloat16"):
    cfg = TransformerConfig(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=32,
        ffn_dim=11008, max_seq_len=4096, dtype=jnp.dtype(dtype),
    )
    return Transformer(cfg)


@register_model("llama-tiny")
def make_llama_tiny(dtype: str = "float32", **kwargs):
    """Small config for tests and the multi-chip dry run."""
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=128, max_seq_len=128, dtype=jnp.dtype(dtype),
        tie_embeddings=True, **kwargs,
    )
    return Transformer(cfg)
