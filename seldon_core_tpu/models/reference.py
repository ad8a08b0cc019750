"""Plain reference forward pass of the decoder family models/transformer.py
serves: what the served path is compared with (tests/test_reference.py at a
small size on the CPU; perf/reference/ at published widths beside the chip).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: one sequence, no cache, no
batching, no kernels, the experts as a loop with a mask. It shares the
parameter tree's NAMES with models/transformer.py and no code: a fault there
cannot repeat itself here. The equations are OLMoE's
(``transformers/models/olmoe/modeling_olmoe.py``; tests/test_reference.py holds
this file to ``OlmoeForCausalLM`` on converted weights), of which Llama and
Mistral are the case without experts and without QK-norm:

    n1  = RMSNorm(x)
    q   = RoPE(heads(RMSNorm_q(Wq n1)))     RMSNorm_q / RMSNorm_k (cfg.qk_norm)
    k   = RoPE(heads(RMSNorm_k(Wk n1)))     span the whole projection, not a head
    h   = x + Wo Attn(q, k, heads(Wv n1))   causal; a KV head serves its group
    n2  = RMSNorm(h)
    p   = softmax(Wr n2) over ALL experts, in float32
    out = h + sum_{e in top-k(p)} p_e W2_e(silu(W1_e n2) * W3_e n2)
          (the k weights divided by their sum only if cfg.router_renormalize)

DeepSeek-V2 (``cfg.kv_lora_rank`` > 0; arXiv 2405.04434, and the modeling file
published beside the weights) replaces the attention and mixes FFN kinds:

    q_h   = [q^N_h ; RoPE(q^R_h)]                 from Wq n1 (no query compression)
    c_t   = RMSNorm(W_DKV n1_t)   k^R_t = RoPE(W_KR n1_t)      (wkv_a = [W_DKV ; W_KR])
    k_h,t = [W_UK,h c_t ; k^R_t]  v_h,t = W_UV,h c_t           EXPANDED here, per head
    score = q_h . k_h,t * (nope + rope)^-0.5 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    RoPE  : YaRN frequencies (``yarn_inv_freq``), cos / sin times
            yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    FFN   : the first cfg.first_dense_layers layers a SwiGLU of width
            cfg.dense_ffn_dim; the others the routed experts (weights times
            cfg.routed_scaling_factor) plus ONE dense SwiGLU ("shared") every
            token takes

The ``m^2`` factor is the PUBLISHED model's (``modeling_deepseek.py``:
``softmax_scale *= mscale * mscale`` when rope_scaling has mscale_all_dim);
transformers' port (models/deepseek_v2) uses (nope + rope)^-0.5 alone. This file
follows the published model, so the comparison with ``DeepseekV2ForCausalLM``
(tests/test_reference_mla.py) runs with rope_scaling None, where both agree, and
the YaRN frequencies are held to transformers' ``_compute_yarn_parameters`` on
their own. ``scale_mscale=False`` computes the port's reading: a WRONG model here.

One departure, stated because it is part of what is compared: a leaf that is
int8 in the tree (ops/quantize.py, the configuration's stated weight precision)
is used at its int8-rounded value, dequantized in float32. The reference then
answers "what do THESE weights give in exact arithmetic", and the served
path's distance from it is its activation arithmetic alone.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp


def _f32(leaf, index: Optional[int] = None):
    """A leaf (or matrix ``index`` of a stack) in float32; int8 leaves
    dequantized with their own scales."""
    if hasattr(leaf, "q"):
        q, scale = leaf.q, leaf.scale
        if leaf.out_major:  # held [out, in]: the logical matrix is its transpose
            return (jnp.asarray(q, jnp.float32) * jnp.asarray(scale, jnp.float32)[:, None]).T
        if index is not None:
            q, scale = q[index], (scale[index] if scale.ndim == 2 else scale)
        elif scale.ndim == 2:
            scale = scale[:, None, :]
        return jnp.asarray(q, jnp.float32) * jnp.asarray(scale, jnp.float32)
    return jnp.asarray(leaf if index is None else leaf[index], jnp.float32)


def _rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(weight)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(hd: int, theta: float, scaling: dict):
    """YaRN's inverse frequencies [hd / 2]: dimension i turns
    old_len * theta^(-2i/hd) / 2 pi times in the original context; those
    that turn more than beta_fast times keep theta^(-2i/hd), those that turn
    less than beta_slow times are divided by ``factor``, and between the two
    dimension indices (floor / ceil) a linear ramp blends them."""
    factor, old_len = float(scaling["factor"]), float(scaling["original_max_position_embeddings"])
    plain = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)

    def index_turning(n: float) -> float:
        return hd * math.log(old_len / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_turning(float(scaling.get("beta_fast") or 32))), 0)
    high = min(math.ceil(index_turning(float(scaling.get("beta_slow") or 1))), hd - 1)
    high = high + 0.001 if low == high else high
    keep = 1.0 - jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return plain / factor * (1.0 - keep) + plain * keep


def _rope(x, theta: float, scaling: Optional[dict] = None):
    """x [s, heads, hd] at positions 0..s-1; the halves rotate as pairs.
    ``scaling``: a YaRN rope_scaling (frequencies and the cos / sin factor)."""
    s, _, hd = x.shape
    factor = 1.0
    if scaling:
        inv_freq = yarn_inv_freq(hd, theta, scaling)
        if scaling.get("mscale") and scaling.get("mscale_all_dim"):
            factor = (_mscale(scaling["factor"], scaling["mscale"])
                      / _mscale(scaling["factor"], scaling["mscale_all_dim"]))
        else:
            factor = _mscale(scaling["factor"], 1.0)
    else:
        inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :] * factor, jnp.sin(angle)[:, None, :] * factor
    lo, hi = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def _attention(p: dict, x, cfg):
    s = x.shape[0]
    hd = cfg.dim // cfg.n_heads
    q, k, v = x @ _f32(p["wq"]), x @ _f32(p["wk"]), x @ _f32(p["wv"])
    if cfg.qk_norm:
        q = _rms_norm(q, p["q_norm"]["weight"], cfg.norm_eps)
        k = _rms_norm(k, p["k_norm"]["weight"], cfg.norm_eps)
    q = _rope(q.reshape(s, cfg.n_heads, hd), cfg.rope_theta)
    k = _rope(k.reshape(s, cfg.n_kv_heads, hd), cfg.rope_theta)
    v = v.reshape(s, cfg.n_kv_heads, hd)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, cfg.n_heads * hd) @ _f32(p["wo"])


def _latent_attention(p: dict, x, cfg, scale_mscale: bool = True, block: int = 512):
    """DeepSeek-V2's attention with every head's keys and values expanded
    from the latents (the form the paper defines; the served path never
    expands). Queries go in blocks of ``block`` rows so that a 16 k context
    needs [heads, block, s] of scores at a time; the arithmetic is the same."""
    s = x.shape[0]
    heads, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
    scaling = dict(cfg.rope_scaling or ())
    if scaling and scaling.get("rope_type", scaling.get("type")) != "yarn":
        raise NotImplementedError("the reference's latent attention knows YaRN only")
    q = (x @ _f32(p["wq"])).reshape(s, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg.rope_theta, scaling)], axis=-1)
    kv_a = x @ _f32(p["wkv_a"])
    c = _rms_norm(kv_a[:, :dc], p["kv_norm"]["weight"], cfg.norm_eps)          # [s, dc]
    k_rope = _rope(kv_a[:, None, dc:], cfg.rope_theta, scaling)                 # [s, 1, dr]
    k = jnp.concatenate([jnp.einsum("hnc,sc->shn", _f32(p["w_uk"]), c),
                         jnp.broadcast_to(k_rope, (s, heads, dr))], axis=-1)    # [s, heads, dn + dr]
    v = jnp.einsum("hcv,sc->shv", _f32(p["w_uv"]), c)                           # [s, heads, dv]
    scale = (dn + dr) ** -0.5
    if scale_mscale and scaling.get("mscale_all_dim"):
        scale *= _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    out = []
    for start in range(0, s, block):
        rows = jnp.arange(start, min(start + block, s))
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) * scale
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", probs, v).reshape(len(rows), heads * dv))
    return jnp.concatenate(out) @ _f32(p["wo"])


def _swiglu(x, w1, w2, w3):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _experts(p: dict, x, cfg, leave_out_rank: Optional[int], shared: bool = True):
    """The expert FFN of one layer, and what the router chose: ``experts``
    [s, k] best first, their ``weights`` [s, k], and ``margin`` [s], by how
    much the last chosen probability beats the best one not chosen (a tie
    the served path's bf16 activations may break the other way)."""
    n, k = cfg.n_experts, min(cfg.n_experts_per_token, cfg.n_experts)
    probs = jax.nn.softmax(x @ _f32(p["router"]), axis=-1)
    ranked_p, ranked = jax.lax.top_k(probs, min(k + 1, n))
    experts, weights = ranked[:, :k], ranked_p[:, :k]
    margin = weights[:, -1] - ranked_p[:, k] if k < n else jnp.full(x.shape[:1], jnp.inf)
    if cfg.router_renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * getattr(cfg, "routed_scaling_factor", 1.0)
    used = weights if leave_out_rank is None else weights.at[:, leave_out_rank].set(0.0)
    out = jnp.zeros_like(x)
    for e in range(n):
        share = jnp.sum(jnp.where(experts == e, used, 0.0), axis=-1)  # [s]; 0 = not chosen
        if bool(jnp.any(share > 0)):
            out = out + share[:, None] * _swiglu(
                x, _f32(p["w1"], e), _f32(p["w2"], e), _f32(p["w3"], e))
    if "shared" in p and shared:
        f = p["shared"]
        out = out + _swiglu(x, _f32(f["w1"]), _f32(f["w2"]), _f32(f["w3"]))
    return out, {"experts": experts, "weights": weights, "margin": margin}


def forward(params: Any, cfg: Any, tokens, leave_out_rank: Optional[int] = None,
            scale_mscale: bool = True, shared: bool = True, rows=slice(None)):
    """``tokens`` [s] -> (logits [s, vocab] float32, routing): ``routing`` has
    one entry per MoE layer (``_experts``), and is empty for a dense model.

    ``params`` is the tree models/transformer.py's Transformer takes (with or
    without the outer "params" key); ``cfg`` is anything with its fields (a
    TransformerConfig will do). ``leave_out_rank`` computes a WRONG model, for
    showing that a tolerance is tight: every token loses its rank-th expert.
    So do ``scale_mscale=False`` (latent attention without YaRN's m^2 on the
    softmax scale) and ``shared=False`` (no shared experts). ``rows`` selects
    the positions whose logits are returned (a 16 k context times a 100 k
    vocabulary is more float32 than it is worth keeping)."""
    latent = getattr(cfg, "kv_lora_rank", 0) > 0
    if getattr(cfg, "rope_scaling", None) and not latent:
        raise NotImplementedError("the reference has scaled RoPE for latent attention only")
    first_dense = getattr(cfg, "first_dense_layers", 0)
    p = params.get("params", params)
    routing = []
    with jax.default_matmul_precision("highest"):
        x = _f32(p["tok_embeddings"])[jnp.asarray(tokens, jnp.int32)]
        for i in range(cfg.n_layers):
            layer = p[f"layer_{i}"]
            n1 = _rms_norm(x, layer["attention_norm"]["weight"], cfg.norm_eps)
            if latent:
                x = x + _latent_attention(layer["attention"], n1, cfg, scale_mscale)
            else:
                x = x + _attention(layer["attention"], n1, cfg)
            n2 = _rms_norm(x, layer["ffn_norm"]["weight"], cfg.norm_eps)
            if cfg.n_experts > 0 and i >= first_dense:
                out, chose = _experts(layer["moe"], n2, cfg, leave_out_rank, shared)
                routing.append(chose)
            else:
                f = layer["ffn"]
                out = _swiglu(n2, _f32(f["w1"]), _f32(f["w2"]), _f32(f["w3"]))
            x = x + out
        x = _rms_norm(x, p["norm"]["weight"], cfg.norm_eps)
        head = _f32(p["tok_embeddings"]).T if cfg.tie_embeddings else _f32(p["lm_head"])
        return x[rows] @ head, routing


def expert_token_counts(routing: list, n_experts: int, rows=slice(None)):
    """[n_experts] tokens routed to each expert over all layers, for the
    positions ``rows`` selects: what the served path's counters must equal."""
    counts = jnp.zeros((n_experts,), jnp.int32)
    for layer in routing:
        counts = counts + jnp.bincount(
            layer["experts"][rows].reshape(-1), length=n_experts).astype(jnp.int32)
    return counts
