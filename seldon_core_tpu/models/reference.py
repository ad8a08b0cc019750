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

DeepSeek-V3's forms (arXiv 2412.19437; ``transformers/models/deepseek_v3``,
which tests/test_reference_xing4.py holds this file to with the streams off):

    q_h   = [q^N_h ; RoPE(q^R_h)] from W_qb RMSNorm_q(W_qa n1)       (cfg.q_lora_rank > 0)
    s     = sigmoid(Wr n2) over ALL experts                            (cfg.router_score)
    chosen: top-k of s + b, b a per-expert SELECTION bias (cfg.router_bias) that
            never enters a weight; weights s_e / (sum of the k s + 1e-20) x
            routed_scaling_factor; no group limit (n_group = topk_group = 1)
    MTP   : h' = W_eh [RMSNorm_h(h_t) ; RMSNorm_e(emb(x_t+1))], one MoE block, its
            own final norm, the main model's embedding and head (``forward_mtp``);
            h_t is the main model's hidden state BEFORE its final norm. The paper
            writes [h ; emb]; the published checkpoints' eh_proj takes [emb ; h]
            (models/convert.py swaps the halves)

Manifold-constrained hyper-connections (cfg.hc_mult = n > 1 residual streams;
mHC, arXiv 2512.24880, over Hyper-Connections, arXiv 2409.19606). Per token,
X in R^{n x C}, every sub-layer F (attention or FFN) with its own Phi
[nC, 2n + n^2], alpha = (a_pre, a_post, a_res), b_pre, b_post, B_res:

    v      = vec(X);  r = (mean(v^2) + norm_eps)^-1/2;   m = r (v Phi)
    H_pre  = sigmoid(a_pre m[:n] + b_pre)        H_post = 2 sigmoid(a_post m[n:2n] + b_post)
    M      = exp(clamp(a_res mat(m[2n:]) + B_res, -hc_res_clamp, +hc_res_clamp))
    hc_sinkhorn_iters times:  M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    u      = sum_i H_pre[i] X[i];   y = F(RMSNorm(u));   X'[i] = sum_j M[i, j] X[j] + H_post[i] y

What this file fixes where neither the config nor arXiv 2512.24880 does, or
where it reads the paper one way (the served path computes the same):
(1) the streams are ENTERED by copying the embedding n times and LEFT by their
sum, before the final norm (Hyper-Connections section 3; the config has no key
for either); (2) an iteration normalises ROWS, then COLUMNS, so after the last
one the columns sum to 1 and the rows to 1 within the iteration's error;
(3) hc_eps is added to every row and column sum, and the clamp (the config's
mhc_h_res_clamp_min / _max) is applied before the exponential, neither of which
the paper's equations carry; (4) the normalisation of vec(X) has no weight of
its own (a weight there is a row scaling of Phi) and uses the model's
rms_norm_eps; (5) one matrix Phi holds the three maps' columns [pre ; post ; res],
mat() fills rows first; (6) the MTP block has streams of its own, entered and
left like the main model's. ``streams=False`` computes the plain residual on
the same weights, ``sinkhorn_iters=1`` stops after one iteration,
``select_bias=False`` / ``router_score="softmax"`` / ``q_norm=False`` leave
one piece of the V3 forms out: WRONG models, for showing a tolerance is tight.

LFM2 (``cfg.layer_types``; ``transformers/models/lfm2/modeling_lfm2.py``, the
dense sibling whose ``Lfm2ShortConv``, ``Lfm2Attention`` and block order are
LFM2-8B-A1B's too: tests/test_reference_lfm2.py holds this file to
``Lfm2ForCausalLM`` on converted weights) gives each layer one of two token
mixers, and the attention a norm per head:

    conv  : [B ; C ; X] = W_in n1 (split in THAT order);  z_t = B_t * X_t
            v_t = sum_j w_j z_{t-(L-1)+j}, z_{<0} = 0    (the LAST tap weighs row t:
            torch Conv1d's weight order), an explicit shifted sum over the sequence
            h = x + W_out (C_t * v_t)
    attn  : q, k <- RMSNorm over EACH head's values (cfg.qk_norm == "head"; one
            weight [head_dim] for q, one for k), THEN RoPE; the rest as above
    router: s = sigmoid(Wr n2); chosen = top-k(s + b); weights s_e / (sum + eps),
            eps = cfg.router_renormalize_eps (1e-6; DeepSeek-V3's 1e-20 where None)

What this file fixes where LFM2-8B-A1B's config does not: the final norm is the
tree's ``norm`` (published as ``embedding_norm``, applied after the last
layer); the head is a leaf of its own (the served tree holds an int8 head beside
the int8 table; a tied checkpoint converts to two copies). WRONG models of the
conv layer: ``taps_reversed``, ``gate_b=False`` / ``gate_c=False`` (a gate left
out), ``conv_reset_every=N`` (the taps do not look back over a multiple of N: the
state zeroed at every chunk's start), ``conv_state_pad=(n, m)`` (rows from n on
see, in place of z_{n-1}, z_{n-2}, what the LAST rows of the prompt padded with
token 0 to m rows hold: the state taken from a padded chunk's last row, not its
last valid one); of the attention: ``qk_norm="whole"`` / ``qk_norm=False``.

Qwen3-Next (``transformers/models/qwen3_next/modeling_qwen3_next.py``, which
tests/test_reference_qwen3_next.py holds this file to on converted weights) has
a third token mixer, gates its attention, and holds a SHARE of its experts:

    gdn   : [q ; k ; v ; z] = W_qkvz n1, [b ; a] = W_ba n1 (the tree's order; the
            checkpoint interleaves by key head); [q ; k ; v] <- SiLU(causal depthwise
            taps, the LAST weighing the row itself); q, k L2-normalised a head (eps
            1e-6), q * dk^-1/2, a key head serving Hv / Hk value heads;
            beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias) a value head;
            a ``lax.scan`` over the tokens from S = 0, S [dk, dv] a value head:
              S <- e^{g_t} S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
            h = x + W_out (RMSNorm_dv(o_t) w * SiLU(z_t))   (no chunking, no cache)
    attn  : wq makes the query, wq_gate a gate a head; q, k normed a head; RoPE over
            the FIRST cfg.partial_rotary_factor of a head's values (rotate-half among
            them); heads of cfg.head_dim; the heads' output * sigmoid(gate) before wo
    share : the router is cfg.n_experts wide, top-k and renormalisation over all k; the
            stacks hold experts [cfg.experts_first, + cfg.experts_held) and a chosen
            expert that lies elsewhere adds NOTHING (what its chip would add is left
            out here as in the served path); the shared expert, behind
            sigmoid(w_g . n2), is computed whole
    norms : the tree's weights multiply as they are: the converter writes 1 + w for
            Qwen3NextRMSNorm (the gated norm's weight is plain w there too)

WRONG models of these: ``gdn_decay=False`` (g = 0), ``gdn_beta=False`` (beta = 1),
``gdn_l2norm=False``, ``gdn_reset_every=N`` (S zeroed at every multiple of N: a
chunk that does not carry S), ``conv_state_pad`` / ``taps_reversed`` as LFM2's (over
the rows of [q ; k ; v] before the taps), ``gdn_silu=False``, ``gdn_z_gate=False``,
``gdn_state_bf16=True`` (S rounded to bf16 after every token), ``attn_gate=False``,
``rotary_all=True`` (RoPE over the whole head), ``shared_gate=False``,
``leave_out_held=True`` (every token loses the HELD expert it weighs most).

Olmo-Hybrid (``model_type`` ``olmo_hybrid``; no modeling file is installed:
``transformers`` 4.57.6 has ``olmo3`` and ``qwen3_next``, which
tests/test_reference_olmo_hybrid.py holds the attention block and the rule to;
every reading its config.json does not settle is marked *assumed*). With u the
residual [s, 3840], every matrix without bias:

    block : h = u + RMSNorm_w1(Mixer(u));  out = h + RMSNorm_w2(FFN(h))     (both kinds of
            layer; cfg.norm_placement "branch": the Olmo 2 / 3 placement, NOTHING normed
            before the mixer or the FFN; *assumed*, config.json has no key for it)
            FFN(h) = W_down (SiLU(W_gate h) * (W_up h)); final RMSNorm, untied head
    attn  : q = RMSNorm(W_q u), k = RMSNorm(W_k u) over the WHOLE projection (cfg.qk_norm
            True, as OLMoE's and Olmo 3's), v = W_v u; 30 heads of 128, NO rotation
            (cfg.rope_theta None: *assumed* from ``rope_theta`` null; the 24 recurrent
            layers carry the order), causal softmax at 128^-1/2, W_o
    gdn   : the flash-linear-attention GatedDeltaNet layer, whose option names the
            config's linear_* keys carry: q, k = W_q u, W_k u (30 x 96), v = W_v u
            (30 x 192), each through its own causal depthwise taps (4) then SiLU; q, k
            L2-normalised a head, q * 96^-1/2; beta = 2 sigmoid(W_b u)
            (cfg.linear_allow_neg_eigval: the eigenvalues of I - beta k k^T in (-1, 1]);
            g = -exp(A_log) softplus(W_a u + dt_bias) a head; the rule above with S
            [96, 192] float32 a head; o = RMSNorm_192(o) w * SiLU(W_g u) a head; W_o
            [5760, 3840] (*assumed*: the gate and the per-head gated norm as Qwen3-Next's
            z; the separate projections are held as the tree's two stacked leaves
            W_qkvz = [W_q ; W_k ; W_v ; W_g] and W_ba = [W_b ; W_a], and a depthwise
            convolution over stacked channels IS the separate convolutions: layout,
            not mathematics)

WRONG models of these: ``gdn_beta_doubled=False`` (beta = sigmoid(b)), ``gdn_q_scale=
False`` (q not scaled by dk^-1/2), ``norm_placement="pre"`` (x + f(norm(x)) on the same
weights), ``qk_norm="head_tiled"`` (a norm over EACH head, the whole projection's weight
cut into the heads' parts), ``rope_theta_wrong=500000.0`` (RoPE where the model has
none), and Qwen3-Next's ``gdn_*`` / ``taps_reversed`` / ``conv_state_pad`` above.

SmallThinker (``model_type`` ``smallthinker``; no modeling file is installed, so every
reading its config.json does not settle is *assumed*, and the window's bound is held
to the installed ``Olmo3ForCausalLM`` through models/convert.py:
tests/test_reference_smallthinker.py). With x the residual [s, 2560], layer l, every
matrix without bias:

    route : r = x W_r, the router's logits from the layer's INPUT, ahead of the first
            norm and of attention (cfg.router_input "layer_input"; *assumed* from the
            catalog's "router placed before attention")
    attn  : a = RMSNorm(x); q, k, v = a W_q, a W_k, a W_v (28 / 4 / 4 heads of 128, no
            QK norm); where cfg.rope_layout[l] == 1 rotate-half RoPE over the whole
            head, where 0 NO position at all; scores q k^T / sqrt(128), k_pos <= q_pos
            and, in a "sliding_attention" layer, k_pos > q_pos - cfg.sliding_window (a
            query sees that many keys, itself included); h = x + softmax(.) v W_o
    ffn   : m = RMSNorm(h); top-k of r, weights softmax over ALL experts then divided by
            the k's sum (cfg.router_renormalize); expert e: (relu(m W1_e) * (m W3_e)) W2_e
            (cfg.ffn_act "relu": ReGLU); out = h + sum_e w_e y_e

WRONG models of these: ``window_off=True`` (every layer attends everything),
``window_wrong=N`` (a window of N), ``rope_on_global=True`` (RoPE in the layers the
layout gives none), ``rope_on_window=False`` (none where it gives one),
``router_input="ffn_input"`` (the router fed the normed FFN input), ``ffn_act="silu"``,
``renormalize=False`` (the k weights as the softmax over all left them), and
``leave_out_rank`` above.

granite-4.0-h (``model_type`` ``granitemoehybrid``, a DENSE member; the published
modeling file IS installed: ``transformers/models/granitemoehybrid``, whose
``GraniteMoeHybridMambaLayer.torch_forward``, ``GraniteMoeHybridRMSNormGated`` and
``GraniteMoeHybridDecoderLayer`` tests/test_reference_granite_hybrid.py holds this file
to at 1e-5 through models/convert.py). With u the block's input [s, 2048], every matrix
without bias, H = 64 heads of P = 64 (d_inner 4096), one group, a state of N = 128:

    block : h = u + r Mixer(RMSNorm(u));  out = h + r MLP(RMSNorm(h))    r = cfg.residual_multiplier
            MLP(h) = W_o (SiLU(a) * b), [a ; b] = W_i h                 (the tree's w1, w3, w2)
    mamba : [z ; xBC ; dt] = W_in RMSNorm(u)       (4096 + 4352 + 64 columns)
            xBC <- SiLU(causal depthwise taps (4) over the 4352 channels + conv bias)
            [x ; B ; C] = xBC;  Delta_t = softplus(dt_t + dt_bias), A = -exp(A_log)  a head
            (the tree's leaf ``heads`` [3, H] is [A_log ; dt_bias ; D])
            a ``lax.scan`` over the tokens from h = 0, h [P, N] a head:
              h_t = e^{Delta_t A} h_{t-1} + (Delta_t x_t) B_t^T;   y_t = h_t C_t + D x_t
            Mixer = W_out (w * RMSNorm_4096(y * SiLU(z)))   the gate BEFORE the norm, ONE
            norm over all d_inner channels (no chunked form, no cache)
    attn  : 32 query / 8 KV heads of 64, NO position (cfg.rope_theta None:
            ``position_embedding_type`` "nope"), softmax(q k^T * cfg.attention_multiplier)
            (0.015625, NOT 64^-1/2)
    model : the table's rows * cfg.embedding_multiplier; logits = RMSNorm(h_last) E^T /
            cfg.logits_scaling; the table and the head are ONE leaf (tied)

WRONG models of these: ``ssd_state_bf16=True`` (h rounded to bf16 after every token),
``ssd_gate_after_norm=True`` (w * RMSNorm(y) * SiLU(z)), ``attention_multiplier_off=True``
(scores * head_dim^-1/2), ``residual_multiplier_off=True`` (r = 1), ``ssd_skip=False`` (D
left out), ``ssd_conv_bias=False``, ``ssd_conv_bc=False`` (the taps over x alone: B and C
as projected), ``ssd_reset_every=N`` (h zeroed at every multiple of N: a chunk that does
not carry h), and ``conv_state_pad`` as LFM2's (over the rows of xBC before the taps).
``ssd_state`` is the h one mamba layer holds after a sequence, for a probe that reads
the served cache's back (transport/rest.py "state").

Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; SambaY, arXiv 2507.06607; the
modeling file is NOT installed, so the model as a whole is held to this reading, and two of
its layers to installed implementations: tests/test_reference_phi4flash.py holds ``_mamba1``
to ``transformers`` ``MambaMixer.slow_forward`` and ``_diff_attention`` to ``DiffLlamaAttention``
under a permutation of heads, at 1e-5). n layers, every one h = u + Mixer(LN(u)); out = h +
MLP(LN(h)) with LN = LayerNorm (weight AND bias) and MLP = W_2 (SiLU(W_1 h) * W_3 h); the
mixer by ``cfg.layer_types``:

    s6    : [x ; z] = W_in u;  x <- SiLU(taps (4) over the E channels + conv bias)
            [dt ; B ; C] = W_x x;  Delta = softplus(W_dt dt + b_dt);  A = -exp(A_log)
            a ``lax.scan`` over the tokens from h = 0, h [E, N]:
              h_t = e^{Delta_t A} h_{t-1} + (Delta_t x_t) B_t^T;   y_t = h_t C_t + D x_t
            Mixer = W_out (y * SiLU(z));  the layer cfg.memory_source also hands up m = y
    gmu   : Mixer = W_2 (SiLU(W_1 u) * m)              m the SAME token's row
    attn  : [q ; k ; v] = W u + b, H / G / G heads of d, paired in STRIPES: q1_j = q[2j],
            q2_j = q[2j+1], k1_g = k[2g], k2_g = k[2g+1], v_g = [v[2g] ; v[2g+1]], pair j
            reads group j // (H / G);  a_i = softmax(q_i k_i^T d^-1/2) v  (the same mask);
            lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
            lambda_init = 0.8 - 0.6 exp(-0.3 layer);
            o_j = (1 - lambda_init) w * RMSNorm_2d(a1_j - lambda a2_j);  Mixer = W_o [o] + b_o
            "sliding_attention": a query sees itself and the cfg.sliding_window - 1 rows
            before it; "full_attention": everything before it; "cross_attention": queries
            of its own over the k and v that the layer cfg.kv_source made of ITS input
    model : logits = LN_f(h_last) E^T, the table and the head tied; NO position anywhere

every layer on every row, no cache. WRONG models of these: ``s6_state_bf16=True``,
``s6_reset_every=N`` (h zeroed at every multiple of N), ``gmu_memory_gated=True`` (m taken
AFTER the gate), ``gmu_memory_layer=i`` (m from s6 layer i), ``gmu_memory_skip=False`` (D x
left out of m), ``cross_kv_own=True`` (a cross layer reads k and v made of its OWN input by
the source layer's weights), ``diff_pairs="halves"`` (q1_j = q[j], q2_j = q[j + H/2], and
k, v likewise), ``lambda_init_layer0=True``, ``diff_subln=False``, ``diff_scale=False``
((1 - lambda_init) left out), ``window_wrong=N``, ``layer_norm_mean=False`` (LayerNorm
without the mean), ``bias_off="attention" | "norm"`` (those biases left out).
``s6_state`` is the h one s6 layer holds after a sequence (transport/rest.py "state").

``follow=`` makes the forward take the experts the SERVED path took (a logits
probe's ``routing``): where this router's last chosen and first unchosen expert
score within the served arithmetic's noise of each other, which one is taken is
not a property of the program, and with four experts weighed ~0.5 each one such
choice moves everything after it; followed, the comparison is of the arithmetic,
and ``behind`` in the routing says whether the served CHOICES are this router's.

One departure, stated because it is part of what is compared: a leaf that is
int8 in the tree (ops/quantize.py, the configuration's stated weight precision)
is used at its int8-rounded value, dequantized in float32. The reference then
answers "what do THESE weights give in exact arithmetic", and the served
path's distance from it is its activation arithmetic alone.
"""

from __future__ import annotations

import math
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


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


# threads of ``_in_threads``: the host's cores, up to sixteen
THREADS = min(16, os.cpu_count() or 1)


def _in_threads(f, items: list) -> list:
    """``f`` over ``items`` on a few threads, in order. The CPU backend runs one
    op at a time and finds little parallelism inside an op that memory bounds
    (a block's softmax, an expert's scatter), so INDEPENDENT pieces overlap: a
    6 k-token forward of sixteen layers took 343 s on the chip's host before
    and a quarter of it after (PERF.md section 6, PR 49). The matmul precision
    is a thread's own setting: each worker sets the module's."""
    workers = min(THREADS, len(items))
    if workers <= 1:
        return [f(item) for item in items]

    def run(item):
        with jax.default_matmul_precision("highest"):
            return f(item)

    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(run, items))


_SEEN_SHAPES: set = set()
_FIRST_CALL = threading.Lock()


def _one_compile_at_a_time(key, call):
    """``call()``, a jitted piece, from one of ``_in_threads``' workers: the
    FIRST call of each ``key`` (the piece and its shapes) runs alone, later ones
    freely. A first call compiles, and reads or writes the persistent compile
    cache, whose directory is locked a file at a time: thirteen workers
    compiling at once waited on that lock for most of a 213 s forward that is
    72-88 s otherwise (the chip's host, PR 49)."""
    if key in _SEEN_SHAPES:
        return call()
    with _FIRST_CALL:
        out = jax.block_until_ready(call())
        _SEEN_SHAPES.add(key)
    return out


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


@partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, start, lo, window: int = 0):
    """Causal softmax attention of the query rows ``q`` [b, H, hd] at positions
    ``start`` .. over the keys and values ``k`` / ``v`` [w, G, hd] at positions
    ``lo`` .. (a KV head serves its H / G query heads, never repeated); with
    ``window`` a row sees that many keys, itself included. -> [b, H * hd].
    ONE compiled piece a (b, w): a long context's dozen blocks are a dozen
    shapes, where the eager ops were a pass over [H, b, s] scores each."""
    b, heads, hd = q.shape
    w, groups = k.shape[:2]
    rep = heads // groups
    # (the scale on the queries and the sum divided out of the products: the
    # same softmax with two passes fewer over [H, b, w])
    qg = (q / jnp.sqrt(jnp.float32(hd))).reshape(b, groups, rep, hd).transpose(1, 2, 0, 3)
    scores = jnp.matmul(qg.reshape(groups, rep * b, hd), k.transpose(1, 2, 0))
    rows, keys = start + jnp.arange(b), lo + jnp.arange(w)
    seen = keys[None, :] <= rows[:, None]
    if window:
        seen &= keys[None, :] > rows[:, None] - window
    scores = jnp.where(seen[None, None], scores.reshape(groups, rep, b, w), -jnp.inf)
    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    out = jnp.matmul(weights.reshape(groups, rep * b, w), v.transpose(1, 0, 2))
    out = out / jnp.sum(weights, axis=-1).reshape(groups, rep * b, 1)
    wide = v.shape[2]      # (a value head may be wider than a key head: a differential pair's)
    return out.reshape(groups, rep, b, wide).transpose(2, 0, 1, 3).reshape(b, heads * wide)


def _attention(p: dict, x, cfg, qk_norm=None, gate: bool = True, rotary_all: bool = False,
               block: int = 256, rope_theta_wrong: Optional[float] = None, window: int = 0,
               rotary: bool = True, multiplier: Optional[float] = None):
    """Queries go in blocks of ``block`` rows, each against the keys its rows
    may see and no others (a 6 k context needs [heads, block, <= s] of scores
    at a time; the arithmetic is the same). ``window`` > 0: a query sees that
    many keys, itself included; ``rotary`` False: this layer sees no position;
    ``multiplier``: the softmax scale in place of head_dim^-1/2."""
    s = x.shape[0]
    hd = getattr(cfg, "head_dim", 0) or cfg.dim // cfg.n_heads
    q, k, v = x @ _f32(p["wq"]), x @ _f32(p["wk"]), x @ _f32(p["wv"])
    if multiplier is not None:      # (``_attend_block`` divides by sqrt(hd))
        q = q * (multiplier * math.sqrt(hd))
    qk_norm = cfg.qk_norm if qk_norm is None else qk_norm
    if qk_norm == "whole":      # a WRONG model of per-head norms: one norm, the head's weight tiled
        q = _rms_norm(q, jnp.tile(_f32(p["q_norm"]["weight"]), cfg.n_heads), cfg.norm_eps)
        k = _rms_norm(k, jnp.tile(_f32(p["k_norm"]["weight"]), cfg.n_kv_heads), cfg.norm_eps)
    elif qk_norm is True:
        q = _rms_norm(q, p["q_norm"]["weight"], cfg.norm_eps)
        k = _rms_norm(k, p["k_norm"]["weight"], cfg.norm_eps)
    q, k = q.reshape(s, cfg.n_heads, hd), k.reshape(s, cfg.n_kv_heads, hd)
    if qk_norm == "head":
        q = _rms_norm(q, p["q_norm"]["weight"], cfg.norm_eps)
        k = _rms_norm(k, p["k_norm"]["weight"], cfg.norm_eps)
    elif qk_norm == "head_tiled":   # a WRONG model of the whole-projection norm: a norm a head
        q = _rms_norm(q, _f32(p["q_norm"]["weight"]).reshape(cfg.n_heads, hd), cfg.norm_eps)
        k = _rms_norm(k, _f32(p["k_norm"]["weight"]).reshape(cfg.n_kv_heads, hd), cfg.norm_eps)
    theta = cfg.rope_theta if rope_theta_wrong is None else rope_theta_wrong
    if theta is not None and rotary:    # None: no rotary embedding at all
        rotary = hd if rotary_all else int(getattr(cfg, "partial_rotary_factor", 1.0) * hd)
        q = jnp.concatenate([_rope(q[..., :rotary], theta), q[..., rotary:]], axis=-1)
        k = jnp.concatenate([_rope(k[..., :rotary], theta), k[..., rotary:]], axis=-1)
    v = v.reshape(s, cfg.n_kv_heads, hd)

    def attend(start):
        end = min(start + block, s)
        # the keys a row of the block may see: none behind the first row's window
        lo = max(start - window + 1, 0) if window else 0
        return _one_compile_at_a_time(
            ("attend", end - start, end - lo, q.shape[1:], k.shape[1:], window),
            lambda: _attend_block(q[start:end], k[lo:end], v[lo:end], start, lo, window=window))

    out = _in_threads(attend, list(range(0, s, block)))
    out = jnp.concatenate(out)
    if "wq_gate" in p and gate:
        out = out * jax.nn.sigmoid(x @ _f32(p["wq_gate"]))
    return out @ _f32(p["wo"])


def _latent_attention(p: dict, x, cfg, scale_mscale: bool = True, block: int = 512,
                      q_norm: bool = True):
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
    if getattr(cfg, "q_lora_rank", 0):
        c_q = x @ _f32(p["wq_a"])
        if q_norm:
            c_q = _rms_norm(c_q, p["q_norm"]["weight"], cfg.norm_eps)
        q = (c_q @ _f32(p["wq_b"])).reshape(s, heads, dn + dr)
    else:
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


def _causal_taps(z, taps, wrong: dict, seen: Optional[list] = None):
    """Depthwise causal taps over the whole sequence ``z`` [s, C] as an
    explicit shifted sum; ``taps`` [C, L], the last tap weighs the row itself.
    ``seen`` collects z (what a served path keeps the last rows of, as its
    state)."""
    s, d = z.shape
    if wrong["taps_reversed"]:
        taps = taps[:, ::-1]
    taps_n = taps.shape[1]
    t = jnp.arange(s)
    v = jnp.zeros_like(z)
    for j in range(taps_n):
        back = taps_n - 1 - j                   # this tap reads z_{t - back}
        shifted = jnp.concatenate([jnp.zeros((back, d)), z[:s - back]])[:s]
        if wrong["conv_reset_every"]:           # WRONG: nothing crosses a chunk's start
            every = wrong["conv_reset_every"]
            shifted = jnp.where((t // every == (t - back) // every)[:, None], shifted, 0.0)
        if wrong["conv_state_pad"] is not None and back:
            # WRONG: rows from n on read, across n, a padded chunk's last rows
            n, pad_rows = wrong["conv_state_pad"][0], wrong["conv_state_pad"][2][len(seen)]
            stale = pad_rows[jnp.clip(pad_rows.shape[0] - (n - (t - back)), 0, pad_rows.shape[0] - 1)]
            shifted = jnp.where(((t >= n) & (t - back < n))[:, None], stale, shifted)
        v = v + taps[:, j] * shifted
    if seen is not None:
        seen.append(z)
    return v


def _short_conv(p: dict, x, cfg, wrong: dict, seen: Optional[list] = None):
    """LFM2's gated short convolution over the whole sequence ``x`` [s, C]."""
    d = x.shape[1]
    bcx = x @ _f32(p["in_proj"])
    gate_b, gate_c, xs = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    v = _causal_taps(gate_b * xs if wrong["gate_b"] else xs, _f32(p["taps"]), wrong, seen)
    return ((gate_c * v) if wrong["gate_c"] else v) @ _f32(p["out_proj"])


@partial(jax.jit, static_argnames=("round_bf16",))
def _delta_scan(q, k, v, g, beta, reset, round_bf16: bool = False):
    """The gated delta rule as a scan over tokens from S = 0: ``q`` / ``k``
    [s, H, dk], ``v`` [s, H, dv], ``g`` / ``beta`` [s, H], ``reset`` [s] bool
    (a WRONG model zeroes S before such a token) -> o [s, H, dv]."""
    def step(S, row):
        q_t, k_t, v_t, g_t, b_t, reset_t = row
        S = jnp.where(reset_t, 0.0, S) * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        if round_bf16:
            # not ``.astype(bfloat16).astype(float32)``: the TPU compiler keeps the
            # excess precision of such a pair, and the wrong model would be the right one
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    with jax.default_matmul_precision("highest"):
        S0 = jnp.zeros((k.shape[1], k.shape[2], v.shape[2]), jnp.float32)
        return jax.lax.scan(step, S0, (q, k, v, g, beta, reset))[1]


def _gated_delta_net(p: dict, x, cfg, wrong: dict, seen: Optional[list] = None):
    """Qwen3-Next's Gated DeltaNet over the whole sequence ``x`` [s, C]: the
    recurrence token by token (the served path runs a chunked form in its
    prefill and keeps S and three rows of [q ; k ; v] between calls)."""
    s = x.shape[0]
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = hk * dk, hv * dv
    qkvz, ba = x @ _f32(p["in_proj_qkvz"]), x @ _f32(p["in_proj_ba"])
    mixed = _causal_taps(qkvz[:, :2 * key_dim + value_dim], _f32(p["conv1d"]), wrong, seen)
    if wrong["gdn_silu"]:
        mixed = jax.nn.silu(mixed)
    q = mixed[:, :key_dim].reshape(s, hk, dk)
    k = mixed[:, key_dim:2 * key_dim].reshape(s, hk, dk)
    v = mixed[:, 2 * key_dim:].reshape(s, hv, dv)
    if wrong["gdn_l2norm"]:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    if wrong["gdn_q_scale"]:
        q = q * dk ** -0.5
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv]) if wrong["gdn_beta"] else jnp.ones((s, hv))
    if getattr(cfg, "linear_allow_neg_eigval", False) and wrong["gdn_beta_doubled"]:
        beta = 2.0 * beta
    g = (-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[:, hv:] + _f32(p["dt_bias"]))
         if wrong["gdn_decay"] else jnp.zeros((s, hv)))
    every = wrong["gdn_reset_every"]
    reset = (jnp.arange(s) % every == 0) if every else jnp.zeros((s,), bool)
    o = _delta_scan(q, k, v, g, beta, reset, round_bf16=bool(wrong["gdn_state_bf16"]))
    o = _rms_norm(o, p["norm"]["weight"], cfg.norm_eps)
    if wrong["gdn_z_gate"]:
        o = o * jax.nn.silu(qkvz[:, 2 * key_dim + value_dim:].reshape(s, hv, dv))
    return o.reshape(s, value_dim) @ _f32(p["out_proj"])


@partial(jax.jit, static_argnames=("round_bf16",))
def _ssd_scan(x, dt, A, B, C, reset, round_bf16: bool = False):
    """Mamba-2's recurrence as a scan over tokens from h = 0: ``x`` [s, H, P],
    ``dt`` [s, H], ``A`` [H], ``B`` / ``C`` [s, H, N] (a group's row at each of
    its heads), ``reset`` [s] bool (a WRONG model zeroes h before such a token)
    -> (h after the last token [H, P, N], h_t C_t [s, H, P])."""
    def step(h, row):
        x_t, dt_t, b_t, c_t, reset_t = row
        h = (jnp.where(reset_t, 0.0, h) * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if round_bf16:   # (``lax.reduce_precision``: see ``_delta_scan``)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    with jax.default_matmul_precision("highest"):
        h0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[2]), jnp.float32)
        return jax.lax.scan(step, h0, (x, dt, B, C, reset))


def _mamba2(p: dict, u, cfg, wrong: dict, seen: Optional[list] = None,
            left: Optional[list] = None):
    """granite-4.0-h's Mamba-2 mixer over the whole sequence ``u`` [s, C]: the
    recurrence token by token (the served path runs a chunked form in its
    prefill and keeps h and three rows of xBC between calls). ``left``, a list:
    the h [H, P, N] the last token leaves is appended to it."""
    s = u.shape[0]
    H, P, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
    inner = H * P
    channels = inner + 2 * G * N
    zxbcdt = u @ _f32(p["in_proj"])
    z, xbc, dt = zxbcdt[:, :inner], zxbcdt[:, inner:inner + channels], zxbcdt[:, inner + channels:]
    mixed = _causal_taps(xbc, _f32(p["conv1d"]), wrong, seen)
    if "conv_bias" in p and wrong["ssd_conv_bias"]:
        mixed = mixed + _f32(p["conv_bias"])
    mixed = jax.nn.silu(mixed)
    if not wrong["ssd_conv_bc"]:     # WRONG: B and C as projected, the taps over x alone
        mixed = jnp.concatenate([mixed[:, :inner], xbc[:, inner:]], axis=1)
    x = mixed[:, :inner].reshape(s, H, P)
    B = jnp.repeat(mixed[:, inner:inner + G * N].reshape(s, G, N), H // G, axis=1)
    C = jnp.repeat(mixed[:, inner + G * N:].reshape(s, G, N), H // G, axis=1)
    a_log, dt_bias, skip = _f32(p["heads"])     # the tree's one leaf [3, H]
    dt = jax.nn.softplus(dt + dt_bias)
    every = wrong["ssd_reset_every"]
    reset = (jnp.arange(s) % every == 0) if every else jnp.zeros((s,), bool)
    h, y = _ssd_scan(x, dt, -jnp.exp(a_log), B, C, reset,
                     round_bf16=bool(wrong["ssd_state_bf16"]))
    if left is not None:
        left.append(h)
    if wrong["ssd_skip"]:
        y = y + skip[:, None] * x
    y = y.reshape(s, inner)
    if wrong["ssd_gate_after_norm"]:
        y = _rms_norm(y, p["norm"]["weight"], cfg.norm_eps) * jax.nn.silu(z)
    else:
        y = _rms_norm(y * jax.nn.silu(z), p["norm"]["weight"], cfg.norm_eps)
    return y @ _f32(p["out_proj"])


# SambaY's pieces are jitted a piece a layer KIND (a mixer, the gated MLP behind its
# norm), not run op by op: an eager forward compiles every op of every shape on its
# first call, ~250 small compiles that took 164 s beside a server that was compiling
# too (the chip's host, PR 55), where seven pieces take a quarter of it. The
# arithmetic and its order are what is written.
def _table_rows(leaf, tokens):
    """The table's rows ``tokens`` in float32: an int8 table's rows are gathered
    first and those alone dequantized (the same values as ``_f32(leaf)[tokens]``,
    without 2 GB of float32 table)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if hasattr(leaf, "q") and not leaf.out_major and leaf.scale.ndim == 1:
        return jnp.asarray(leaf.q)[tokens].astype(jnp.float32) * jnp.asarray(leaf.scale, jnp.float32)
    return _f32(leaf)[tokens]


def _layer_norm_rows(p: dict, x, eps, mean: bool, biased: bool):
    if mean:
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(p["weight"])
    return out + _f32(p["bias"]) if biased else out


_layer_norm_of = jax.jit(_layer_norm_rows, static_argnames=("mean", "biased"))


def _layer_norm(x, p: dict, eps: float, wrong: dict):
    """LayerNorm: the mean taken out, a weight and a bias."""
    return _layer_norm_of(p, x, eps, mean=bool(wrong["layer_norm_mean"]),
                          biased=wrong["bias_off"] != "norm")


@partial(jax.jit, static_argnames=("round_bf16",))
def _s6_scan(x, delta, A, B, C, reset, round_bf16: bool = False):
    """Mamba-1's recurrence as a scan over tokens from h = 0: ``x`` / ``delta`` [s, E],
    ``A`` [E, N], ``B`` / ``C`` [s, N], ``reset`` [s] bool (a WRONG model zeroes h before
    such a token) -> (h after the last token [E, N], h_t C_t [s, E])."""
    def step(h, row):
        x_t, d_t, b_t, c_t, reset_t = row
        h = (jnp.where(reset_t, 0.0, h) * jnp.exp(d_t[:, None] * A)
             + (d_t * x_t)[:, None] * b_t[None, :])
        if round_bf16:   # (``lax.reduce_precision``: see ``_delta_scan``)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, h @ c_t

    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32), (x, delta, B, C, reset))


@partial(jax.jit, static_argnames=("dims", "every", "round_bf16"))
def _mamba1_parts(p: dict, u, dims: tuple, every: Optional[int], round_bf16: bool):
    """-> (the mixer's output, h [E, N] after the last token, y + D x, y, the gated
    (y + D x) SiLU(z)): every reading of "the scan's output" a caller may want."""
    with jax.default_matmul_precision("highest"):
        s = u.shape[0]
        E, N, R = dims
        xz = u @ _f32(p["in_proj"])
        x, z = xz[:, :E], xz[:, E:]
        taps = _f32(p["conv1d"])
        back = taps.shape[1] - 1
        padded = jnp.concatenate([jnp.zeros((back, E), jnp.float32), x])
        x = sum(taps[:, j] * padded[j:j + s] for j in range(back + 1))
        if "conv_bias" in p:
            x = x + _f32(p["conv_bias"])
        x = jax.nn.silu(x)
        dbc = x @ _f32(p["x_proj"])
        delta = jax.nn.softplus(dbc[:, :R] @ _f32(p["dt_proj"]) + _f32(p["b_dt"]))
        reset = (jnp.arange(s) % every == 0) if every else jnp.zeros((s,), bool)
        h, y = _s6_scan(x, delta, -jnp.exp(_f32(p["A_log_t"])).T, dbc[:, R:R + N],
                        dbc[:, R + N:], reset, round_bf16=round_bf16)
        skipped = y + _f32(p["D"]) * x
        gated = skipped * jax.nn.silu(z)
        return gated @ _f32(p["out_proj"]), h, skipped, y, gated


def _mamba1(p: dict, u, cfg, wrong: dict, left: Optional[list] = None,
            handed: Optional[list] = None):
    """Mamba-1's mixer over the whole sequence ``u`` [s, C], the recurrence token by token.
    ``left``, a list: the h [E, N] the last token leaves is appended to it; ``handed``, a
    list: the m a gated memory unit reads (the scan's output before the gate)."""
    out, h, skipped, y, gated = _mamba1_parts(
        p, u, (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank),
        wrong["s6_reset_every"], bool(wrong["s6_state_bf16"]))
    if left is not None:
        left.append(h)
    if handed is not None:
        handed.append(gated if wrong["gmu_memory_gated"]
                      else (skipped if wrong["gmu_memory_skip"] else y))
    return out


@partial(jax.jit, static_argnames=("heads", "biased"))
def _project_heads(x, w, b, heads: int, biased: bool):
    """(x W + b) as [s, heads, d]."""
    with jax.default_matmul_precision("highest"):
        out = x @ _f32(w)
        if biased and b is not None:
            out = out + _f32(b)
        return out.reshape(x.shape[0], heads, -1)


@partial(jax.jit, static_argnames=("subln", "scaled", "biased"))
def _diff_epilogue(p: dict, a1, a2, init, eps, subln: bool, scaled: bool, biased: bool):
    """W_o [(1 - lambda_init) w * RMSNorm(a1 - lambda a2)] + b_o; ``init`` (lambda_init) is
    an argument, so the layers share the piece."""
    with jax.default_matmul_precision("highest"):
        lq1, lk1, lq2, lk2 = _f32(p["lambdas"])
        o = a1 - (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init) * a2
        if subln:
            o = _rms_norm(o, p["subln"]["weight"], eps)
        if scaled:
            o = o * (1.0 - init)
        out = o.reshape(o.shape[0], -1) @ _f32(p["wo"])
        return out + _f32(p["bo"]) if biased and "bo" in p else out


def _diff_attention(p: dict, x, cfg, layer: int, wrong: dict, window: int = 0, kv=None,
                    made: Optional[list] = None, block: int = 256):
    """Differential attention over the whole sequence ``x`` [s, C] by the equations, two
    softmaxes a pair of heads. ``kv``: the (k, v) [s, G, d] of ANOTHER layer (a cross
    layer: it makes none); ``made``, a list: this layer's own (k, v) are appended."""
    s = x.shape[0]
    H, G, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    biased = wrong["bias_off"] != "attention"
    q = _project_heads(x, p["wq"], p.get("bq"), H, biased)
    if kv is None:
        kv = (_project_heads(x, p["wk"], p.get("bk"), G, biased),
              _project_heads(x, p["wv"], p.get("bv"), G, biased))
        if made is not None:
            made.append(kv)
    k, v = kv
    if wrong["diff_pairs"] == "halves":     # WRONG: head j with head j + half
        q1, q2, k1, k2 = q[:, :H // 2], q[:, H // 2:], k[:, :G // 2], k[:, G // 2:]
        v = jnp.concatenate([v[:, :G // 2], v[:, G // 2:]], axis=-1)
    else:
        q1, q2, k1, k2 = q[:, 0::2], q[:, 1::2], k[:, 0::2], k[:, 1::2]
        v = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)

    def attend(job):
        qs, ks, start = job
        end = min(start + block, s)
        lo = max(start - window + 1, 0) if window else 0
        return _one_compile_at_a_time(
            ("attend", end - start, end - lo, qs.shape[1:], ks.shape[1:], v.shape[1:], window),
            lambda: _attend_block(qs[start:end], ks[lo:end], v[lo:end], start, lo, window=window))

    starts = list(range(0, s, block))
    out = _in_threads(attend, [(q1, k1, t) for t in starts] + [(q2, k2, t) for t in starts])
    a1 = jnp.concatenate(out[:len(starts)]).reshape(s, H // 2, 2 * d)
    a2 = jnp.concatenate(out[len(starts):]).reshape(s, H // 2, 2 * d)
    init = 0.8 - 0.6 * math.exp(-0.3 * (0 if wrong["lambda_init_layer0"] else layer))
    return _diff_epilogue(p, a1, a2, jnp.float32(init), cfg.norm_eps, bool(wrong["diff_subln"]),
                          bool(wrong["diff_scale"]), biased)


@jax.jit
def _gmu(p: dict, u, memory):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(u @ _f32(p["in_proj"])) * memory) @ _f32(p["out_proj"])


@partial(jax.jit, static_argnames=("mean", "biased"))
def _join_and_ffn(layer: dict, x, mixed, eps, mean: bool, biased: bool):
    """h = x + mixed;  h + MLP(LayerNorm(h))."""
    with jax.default_matmul_precision("highest"):
        x = x + mixed
        f = layer["ffn"]
        normed = _layer_norm_rows(layer["ffn_norm"], x, eps, mean, biased)
        return x + _swiglu(normed, _f32(f["w1"]), _f32(f["w2"]), _f32(f["w3"]))


def _sambay_hidden(p: dict, cfg: Any, tokens, wrong: dict, blocks: Optional[int] = None,
                   left: Optional[list] = None):
    """SambaY's stack (cfg.layer_types with "s6" / "gmu" / "cross_attention" layers) over
    the whole sequence: the hidden state [s, C] after the last block (after the first
    ``blocks``, where given; ``left`` then takes the h of THAT layer, an s6 one, and the
    block's output is not computed)."""
    # (an int8 tree's leaves go into the jitted pieces as they are: a pytree node)
    from seldon_core_tpu.ops.quantize import _register_pytree

    _register_pytree()
    x = _table_rows(p["tok_embeddings"], tokens)
    memory_layer = (cfg.memory_source if wrong["gmu_memory_layer"] is None
                    else wrong["gmu_memory_layer"])
    memory = source_kv = None
    window = wrong["window_wrong"] or cfg.sliding_window
    mean, biased = bool(wrong["layer_norm_mean"]), wrong["bias_off"] != "norm"
    for i, kind in enumerate(cfg.layer_types):
        layer = p[f"layer_{i}"]
        normed = _layer_norm(
            x, layer["operator_norm" if kind in ("s6", "gmu") else "attention_norm"],
            cfg.norm_eps, wrong)
        if blocks is not None and i == blocks:
            _mamba1(layer["s6"], normed, cfg, wrong, left=left)
            return x
        if kind == "s6":
            handed = [] if i == memory_layer else None
            mixed = _mamba1(layer["s6"], normed, cfg, wrong, handed=handed)
            if handed:
                memory, = handed
        elif kind == "gmu":
            mixed = _gmu(layer["gmu"], normed, memory)
        elif kind == "cross_attention":
            kv = source_kv
            if wrong["cross_kv_own"]:       # WRONG: of the layer's OWN input
                source = p[f"layer_{cfg.kv_source}"]["attention"]
                kv = tuple(_project_heads(normed, source[w], source[b], cfg.n_kv_heads, True)
                           for w, b in (("wk", "bk"), ("wv", "bv")))
            mixed = _diff_attention(layer["attention"], normed, cfg, i, wrong, kv=kv)
        else:
            made = [] if i == cfg.kv_source else None
            mixed = _diff_attention(layer["attention"], normed, cfg, i, wrong,
                                    window if kind == "sliding_attention" else 0, made=made)
            if made:
                source_kv, = made
        x = _join_and_ffn({"ffn": layer["ffn"], "ffn_norm": layer["ffn_norm"]}, x, mixed,
                          cfg.norm_eps, mean, biased)
    return x


def _is_sambay(cfg: Any) -> bool:
    return bool(set(getattr(cfg, "layer_types", None) or ()) & {"s6", "gmu", "cross_attention"})


def _final_norm(p: dict, cfg: Any, x, wrong: dict):
    if getattr(cfg, "norm", "rms") == "layer":
        return _layer_norm(x, p["norm"], cfg.norm_eps, wrong)
    return _rms_norm(x, p["norm"]["weight"], cfg.norm_eps)


EXPERT_ROWS = 64     # an expert's tokens are computed in whole buckets of this many rows


def _swiglu(x, w1, w2, w3, relu: bool = False):
    """act(x W1) * (x W3), then W2: SiLU (SwiGLU), or ReLU (ReGLU: cfg.ffn_act "relu")."""
    return ((jax.nn.relu if relu else jax.nn.silu)(x @ w1) * (x @ w3)) @ w2


@partial(jax.jit, static_argnames=("relu",), donate_argnums=(0,))
def _add_expert(out, x, share, took, n, w1, w2, w3, e, relu: bool = False):
    """``out`` plus expert ``e`` of the stacks on rows ``took`` of ``x`` (the
    first ``n`` of them count; the rest pad a bucket), each weighed by its
    ``share``: ONE compiled piece a bucket size, where the eager ops would be
    a dozen compiles each. ``out`` is donated: the caller holds the sum alone,
    and a copy of [s, dim] an expert is most of a long forward's expert time."""
    weight = jnp.where(jnp.arange(took.shape[0]) < n, share[took], 0.0)
    return out.at[took].add(weight[:, None] * _swiglu(
        x[took], _f32(w1, e), _f32(w2, e), _f32(w3, e), relu))


def _experts(p: dict, x, cfg, leave_out_rank: Optional[int], shared: bool = True,
             select_bias: bool = True, router_score: Optional[str] = None, follow=None,
             shared_gate: bool = True, leave_out_held: bool = False, router_x=None,
             relu: bool = False, renormalize: Optional[bool] = None):
    """The expert FFN of one layer, and what the router chose: ``experts``
    [s, k] largest weight first, their ``weights`` [s, k], and ``margin`` [s],
    by how much the last chosen score (with its selection bias) beats the
    best one not chosen (a tie the served path's bf16 activations may break
    the other way). ``follow`` [t, k] are the experts the SERVED path took for
    the first t rows: those rows take them too, weighed by this side's own
    scores, and ``behind`` [s] says by how much this side's last choice beats
    the worst expert followed (0 where both chose the same four; the size of
    the served noise where a near-tie fell the other way; more is a served
    path that chooses by another rule). ``router_x``: what the router
    multiplies where it is not ``x`` (the block's input); ``relu``: ReGLU
    experts; ``renormalize``: cfg.router_renormalize overruled."""
    n, k = cfg.n_experts, min(cfg.n_experts_per_token, cfg.n_experts)
    logits = (x if router_x is None else router_x) @ _f32(p["router"])
    sigmoid = (router_score or getattr(cfg, "router_score", "softmax")) == "sigmoid"
    probs = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    pick = probs
    if getattr(cfg, "router_bias", False) and select_bias:
        pick = probs + _f32(p["router_bias"])      # chooses; weighs nothing
    ranked_pick, ranked = jax.lax.top_k(pick, min(k + 1, n))
    margin = ranked_pick[:, k - 1] - ranked_pick[:, k] if k < n else jnp.full(x.shape[:1], jnp.inf)
    took, behind = ranked[:, :k], jnp.zeros(x.shape[:1])
    if follow is not None:
        took = jnp.concatenate([jnp.asarray(follow, took.dtype), took[len(follow):]])
        behind = ranked_pick[:, k - 1] - jnp.min(jnp.take_along_axis(pick, took, axis=-1), axis=-1)
    weights = jnp.take_along_axis(probs, took, axis=-1)
    by_weight = jnp.argsort(-weights, axis=-1)
    experts = jnp.take_along_axis(took, by_weight, axis=-1)
    weights = jnp.take_along_axis(weights, by_weight, axis=-1)
    if cfg.router_renormalize if renormalize is None else renormalize:
        eps = getattr(cfg, "router_renormalize_eps", None)
        eps = (1e-20 if sigmoid else 0.0) if eps is None else eps
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    weights = weights * getattr(cfg, "routed_scaling_factor", 1.0)
    used = weights if leave_out_rank is None else weights.at[:, leave_out_rank].set(0.0)
    # the experts whose weights are here (all, without a share): a chosen
    # expert that lies elsewhere adds nothing
    first = getattr(cfg, "experts_first", 0)
    held = getattr(cfg, "experts_held", 0) or n
    here = (experts >= first) & (experts < first + held)
    if leave_out_held:      # WRONG: the held expert each token weighs most (they are sorted)
        top = jnp.argmax(here, axis=-1)
        used = jnp.where(jnp.arange(k)[None, :] == top[:, None], 0.0, used)
    # who took which expert, read ONCE (a read an expert is a wait an expert:
    # 1,536 of them in a 12-layer forward over 128 held experts)
    chosen, weight = np.asarray(experts), np.asarray(used)
    # the layer's stacks on the device ONCE (a tree taken to the host, as the
    # chip check holds it, would cross again for every expert)
    w1, w2, w3 = jax.device_put((p["w1"], p["w2"], p["w3"]))

    def experts_of(lane):
        """The sum of every ``lanes``-th expert from ``lane`` on, a loop."""
        out = jnp.zeros_like(x)
        for e in range(first + lane, first + held, lanes):
            # [s]; 0 = not chosen (a token takes an expert at most once: no sum is rounded)
            share = np.where(chosen == e, weight, np.float32(0.0)).sum(axis=-1)
            # the tokens that took expert e, and no other: in whole buckets of
            # EXPERT_ROWS (the last repeated at weight 0), so that a forward
            # compiles a handful of shapes and not one an expert a layer
            rows = np.flatnonzero(share > 0)
            if len(rows):
                took = np.pad(rows, (0, -len(rows) % EXPERT_ROWS), mode="edge")
                sums = out      # (the donated sum: bound here, not in the closure's cell)
                out = _one_compile_at_a_time(
                    ("expert", x.shape, len(took), relu, jax.tree.structure(w1)),
                    lambda: _add_expert(sums, x, share, took, len(rows), w1, w2, w3, e - first,
                                        relu=relu))
        return out

    # a short sequence's experts are one loop; a long one's go a few loops side by side
    lanes = 8 if x.shape[0] >= 1024 else 1
    out = sum(_in_threads(experts_of, list(range(lanes))))
    if "shared" in p and shared:
        f = p["shared"]
        y = _swiglu(x, _f32(f["w1"]), _f32(f["w2"]), _f32(f["w3"]), relu)
        if "shared_gate" in p and shared_gate:
            y = jax.nn.sigmoid(x @ _f32(p["shared_gate"])) * y
        out = out + y
    return out, {"experts": experts, "weights": weights, "margin": margin, "behind": behind}


def _mix(p: dict, X, cfg, iters: int):
    """One sub-layer's hyper-connection maps from the streams ``X`` [s, n, C]:
    (u [s, C], H_post [s, n], H_res [s, n, n]); the Sinkhorn iterations are a
    plain loop over a [s, n, n] array."""
    s, n, _ = X.shape
    v = X.reshape(s, -1)
    m = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + cfg.norm_eps) * (v @ _f32(p["phi"]))
    a_pre, a_post, a_res = _f32(p["alpha"])
    h_pre = jax.nn.sigmoid(a_pre * m[:, :n] + _f32(p["b_pre"]))
    h_post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n] + _f32(p["b_post"]))
    mat = jnp.exp(jnp.clip(a_res * m[:, 2 * n:].reshape(s, n, n) + _f32(p["b_res"]),
                           -cfg.hc_res_clamp, cfg.hc_res_clamp))
    for _ in range(iters):
        mat = mat / (jnp.sum(mat, axis=2, keepdims=True) + cfg.hc_eps)    # rows
        mat = mat / (jnp.sum(mat, axis=1, keepdims=True) + cfg.hc_eps)    # then columns
    return jnp.einsum("si,sic->sc", h_pre, X), h_post, mat


def _block(layer: dict, x, cfg, moe: bool, routing: list, wrong: dict, follow=None,
           seen: Optional[list] = None, index: int = 0):
    """One decoder block on the residual ``x`` [s, C], or on the streams
    [s, n, C] where the layer has mixing parameters and ``streams`` is on. A
    layer that holds a ``conv`` (LFM2) or a ``linear_attn`` (Qwen3-Next) mixes
    tokens by it, not by attention; one that holds a ``mamba`` (granite-4.0-h)
    by Mamba-2's recurrence."""
    latent = getattr(cfg, "kv_lora_rank", 0) > 0
    mixed = x.ndim == 3
    iters = wrong["sinkhorn_iters"] if wrong["sinkhorn_iters"] is not None else getattr(
        cfg, "hc_sinkhorn_iters", 0)

    placement = wrong["norm_placement"] or getattr(cfg, "norm_placement", "pre")
    # what this layer's attention sees (cfg.layer_types / cfg.rope_layout), and
    # the wrong readings of each
    kinds, layout = getattr(cfg, "layer_types", None), getattr(cfg, "rope_layout", None)
    windowed = kinds is not None and index < len(kinds) and kinds[index] == "sliding_attention"
    window = 0 if wrong["window_off"] or not windowed else (
        wrong["window_wrong"] or cfg.sliding_window)
    laid = layout is None or index >= len(layout) or bool(layout[index])
    rotary = (laid and (wrong["rope_on_window"] or not windowed)) or (
        not laid and wrong["rope_on_global"])
    relu = (wrong["ffn_act"] or getattr(cfg, "ffn_act", "silu")) == "relu"
    router_x = x if (wrong["router_input"] or getattr(cfg, "router_input", "ffn_input")
                     ) == "layer_input" and not mixed else None

    # every branch times this before it joins the residual (Granite)
    joins = 1.0 if wrong["residual_multiplier_off"] else getattr(cfg, "residual_multiplier", 1.0)

    def sub_layer(x, name, norm, f):
        if placement == "branch":   # Olmo 2 / 3: the norm on the branch, none before it
            return x + joins * _rms_norm(f(x), layer[norm]["weight"], cfg.norm_eps)
        if not mixed:
            return x + joins * f(_rms_norm(x, layer[norm]["weight"], cfg.norm_eps))
        u, h_post, h_res = _mix(layer[name], x, cfg, iters)
        y = f(_rms_norm(u, layer[norm]["weight"], cfg.norm_eps))
        return jnp.einsum("sij,sjc->sic", h_res, x) + h_post[:, :, None] * y[:, None, :]

    def attention(n1):
        if latent:
            return _latent_attention(layer["attention"], n1, cfg, wrong["scale_mscale"],
                                     q_norm=wrong["q_norm"])
        return _attention(layer["attention"], n1, cfg, wrong["qk_norm"], wrong["attn_gate"],
                          wrong["rotary_all"], rope_theta_wrong=wrong["rope_theta_wrong"],
                          window=window, rotary=rotary,
                          multiplier=None if wrong["attention_multiplier_off"] else getattr(
                              cfg, "attention_multiplier", None))

    def ffn(n2):
        if moe:
            out, chose = _experts(layer["moe"], n2, cfg, wrong["leave_out_rank"], wrong["shared"],
                                  wrong["select_bias"], wrong["router_score"],
                                  None if follow is None else follow[:, len(routing)],
                                  wrong["shared_gate"], wrong["leave_out_held"],
                                  router_x, relu, wrong["renormalize"])
            routing.append(chose)
            return out
        f = layer["ffn"]
        return _swiglu(n2, _f32(f["w1"]), _f32(f["w2"]), _f32(f["w3"]), relu)

    if "conv" in layer:
        x = sub_layer(x, None, "operator_norm",
                      lambda n1: _short_conv(layer["conv"], n1, cfg, wrong, seen))
    elif "linear_attn" in layer:
        x = sub_layer(x, None, "operator_norm",
                      lambda n1: _gated_delta_net(layer["linear_attn"], n1, cfg, wrong, seen))
    elif "mamba" in layer:
        x = sub_layer(x, None, "operator_norm",
                      lambda n1: _mamba2(layer["mamba"], n1, cfg, wrong, seen))
    else:
        x = sub_layer(x, "attention_hc", "attention_norm", attention)
    return sub_layer(x, "ffn_hc", "ffn_norm", ffn)


def _enter(x, cfg, wrong: dict):
    n = getattr(cfg, "hc_mult", 0)
    return jnp.repeat(x[:, None, :], n, axis=1) if n > 1 and wrong["streams"] else x


def _leave(x):
    return jnp.sum(x, axis=1) if x.ndim == 3 else x


WRONG = {"leave_out_rank": None, "scale_mscale": True, "shared": True, "streams": True,
         "sinkhorn_iters": None, "select_bias": True, "router_score": None, "q_norm": True,
         "taps_reversed": False, "gate_b": True, "gate_c": True, "conv_reset_every": None,
         "conv_state_pad": None, "qk_norm": None,
         "gdn_decay": True, "gdn_beta": True, "gdn_l2norm": True, "gdn_reset_every": None,
         "gdn_silu": True, "gdn_z_gate": True, "gdn_state_bf16": False, "attn_gate": True,
         "rotary_all": False, "shared_gate": True, "leave_out_held": False,
         "gdn_beta_doubled": True, "gdn_q_scale": True, "norm_placement": None,
         "rope_theta_wrong": None,
         "window_off": False, "window_wrong": None, "rope_on_global": False,
         "rope_on_window": True, "router_input": None, "ffn_act": None, "renormalize": None,
         "ssd_state_bf16": False, "ssd_gate_after_norm": False, "attention_multiplier_off": False,
         "residual_multiplier_off": False, "ssd_skip": True, "ssd_conv_bias": True,
         "ssd_conv_bc": True, "ssd_reset_every": None,
         "s6_state_bf16": False, "s6_reset_every": None, "gmu_memory_gated": False,
         "gmu_memory_layer": None, "gmu_memory_skip": True, "cross_kv_own": False,
         "diff_pairs": "stripes", "lambda_init_layer0": False, "diff_subln": True,
         "diff_scale": True, "layer_norm_mean": True, "bias_off": None}


def _hidden(p: dict, cfg: Any, tokens, wrong: dict, follow=None, seen: Optional[list] = None,
            blocks: Optional[int] = None):
    """The main model's hidden state [s, C] after the last block (after the
    first ``blocks`` of them, where given) and the streams' exit, before the
    final norm; and the routing."""
    if _is_sambay(cfg):
        return _sambay_hidden(p, cfg, tokens, wrong, blocks), []
    if wrong["conv_state_pad"] is not None and len(wrong["conv_state_pad"]) == 2:
        # what each conv layer's LAST rows hold when the first n tokens are
        # padded with token 0 to m rows: a pass of its own
        n, m = wrong["conv_state_pad"]
        padded: list = []
        _hidden(p, cfg, list(tokens[:n]) + [0] * (m - n), {**wrong, "conv_state_pad": None},
                None if follow is None else follow[:0], padded)
        taps_n = next(_f32(leaf).shape[1] for i in range(cfg.n_layers)
                      for leaf in (p[f"layer_{i}"].get("conv", {}).get("taps"),
                                   p[f"layer_{i}"].get("linear_attn", {}).get("conv1d"),
                                   p[f"layer_{i}"].get("mamba", {}).get("conv1d"))
                      if leaf is not None)
        wrong = {**wrong, "conv_state_pad": (n, m, [z[-(taps_n - 1):] for z in padded])}
        seen = []
    latent = getattr(cfg, "kv_lora_rank", 0) > 0
    if getattr(cfg, "rope_scaling", None) and not latent:
        raise NotImplementedError("the reference has scaled RoPE for latent attention only")
    first_dense = getattr(cfg, "first_dense_layers", 0)
    routing: list = []
    x = _enter(_f32(p["tok_embeddings"])[jnp.asarray(tokens, jnp.int32)]
               * getattr(cfg, "embedding_multiplier", 1.0), cfg, wrong)
    for i in range(cfg.n_layers if blocks is None else blocks):
        x = _block(p[f"layer_{i}"], x, cfg, cfg.n_experts > 0 and i >= first_dense, routing, wrong,
                   follow, seen, i)
    return _leave(x), routing


def _head(p: dict, cfg: Any):
    return _f32(p["tok_embeddings"]).T if cfg.tie_embeddings else _f32(p["lm_head"])


def forward(params: Any, cfg: Any, tokens, rows=slice(None), follow=None, **wrong):
    """``tokens`` [s] -> (logits [s, vocab] float32, routing): ``routing`` has
    one entry per MoE layer (``_experts``), and is empty for a dense model.

    ``params`` is the tree models/transformer.py's Transformer takes (with or
    without the outer "params" key); ``cfg`` is anything with its fields (a
    TransformerConfig will do). The keywords of ``WRONG`` compute a WRONG
    model, for showing that a tolerance is tight: ``leave_out_rank`` (every
    token loses its rank-th expert, 0 the one of largest weight),
    ``scale_mscale=False`` (latent attention without YaRN's m^2 on the softmax
    scale), ``shared=False`` (no shared experts), and the hyper-connection and
    V3 ones the module's docstring lists. ``rows`` selects the positions whose
    logits are returned (a 16 k context times a 100 k vocabulary is more
    float32 than it is worth keeping). ``follow`` [t, n_moe_layers, k] int: the
    experts the served path took for the first t tokens (a probe's "routing",
    transport/rest.py): this forward takes the same ones there, so that the
    comparison holds the arithmetic and not which way a near-tie fell; what
    the served path may NOT do, choose by another rule, shows in the
    routing's ``behind`` (``_experts``)."""
    unknown = set(wrong) - set(WRONG)
    if unknown:
        raise TypeError(f"unknown keywords {sorted(unknown)}; the wrong models are {sorted(WRONG)}")
    wrong = {**WRONG, **wrong}
    p = params.get("params", params)
    with jax.default_matmul_precision("highest"):
        x, routing = _hidden(p, cfg, tokens, wrong, follow)
        x = _final_norm(p, cfg, x[rows], wrong)
        return x @ _head(p, cfg) / getattr(cfg, "logits_scaling", 1.0), routing


def ssd_state(params: Any, cfg: Any, tokens, layer: int, **wrong):
    """The h [H, P, N] float32 that mamba layer ``layer`` holds after ``tokens``
    [s], from zeros, token by token: what the served path's cache is compared
    with where a probe reads it back (transport/rest.py "state"). The keywords
    are ``forward``'s: ``ssd_state_bf16=True`` rounds h to bf16 after every
    token, which is what a cache that held it in bf16 would do."""
    wrong = {**WRONG, **wrong}
    p = params.get("params", params)
    with jax.default_matmul_precision("highest"):
        x, _ = _hidden(p, cfg, tokens, wrong, blocks=layer)
        mixer, left = p[f"layer_{layer}"], []
        _mamba2(mixer["mamba"], _rms_norm(x, mixer["operator_norm"]["weight"], cfg.norm_eps),
                cfg, wrong, left=left)
        return left[0]


def s6_state(params: Any, cfg: Any, tokens, layer: int, **wrong):
    """The h [E, N] float32 that s6 layer ``layer`` holds after ``tokens`` [s], from zeros,
    token by token (``ssd_state``'s counterpart): ``s6_state_bf16=True`` rounds h to bf16
    after every token, which is what a cache that held it in bf16 would do."""
    wrong = {**WRONG, **wrong}
    p = params.get("params", params)
    with jax.default_matmul_precision("highest"):
        left: list = []
        _sambay_hidden(p, cfg, tokens, wrong, blocks=layer, left=left)
        return left[0]


def forward_mtp(params: Any, cfg: Any, tokens):
    """The MTP module's logits [s - 1, vocab] over ``tokens`` [s]: row t, from
    the main model's hidden state of token t and the embedding of token t + 1,
    predicts token t + 2."""
    p = params.get("params", params)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        hidden, _ = _hidden(p, cfg, tokens[:-1], WRONG)
        m = p["mtp"]
        both = jnp.concatenate(
            [_rms_norm(hidden, m["hnorm"]["weight"], cfg.norm_eps),
             _rms_norm(_f32(p["tok_embeddings"])[tokens[1:]], m["enorm"]["weight"], cfg.norm_eps)],
            axis=-1)
        x = _block(m["block"], _enter(both @ _f32(m["eh_proj"]), cfg, WRONG), cfg,
                   cfg.n_experts > 0, [], WRONG)
        return _rms_norm(_leave(x), m["norm"]["weight"], cfg.norm_eps) @ _head(p, cfg)


def expert_token_counts(routing: list, n_experts: int, rows=slice(None)):
    """[n_experts] tokens routed to each expert over all layers, for the
    positions ``rows`` selects: what the served path's counters must equal."""
    counts = jnp.zeros((n_experts,), jnp.int32)
    for layer in routing:
        counts = counts + jnp.bincount(
            layer["experts"][rows].reshape(-1), length=n_experts).astype(jnp.int32)
    return counts
