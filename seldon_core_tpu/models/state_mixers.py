"""The state layers' token mixers: the layers of a hybrid model that keep a fixed
block of STATE a sequence and no pages (``layer_types`` "conv", "linear_attention",
"mamba", "s6", "gmu"): ``ShortConv`` over ``short_conv`` (the depthwise taps and
state rule the recurrences share), ``GatedDeltaNet`` over ``gated_delta_rule``
and ops/gated_delta.py, ``Mamba2Mixer`` over ops/ssd.py, ``Mamba1Mixer`` and
``GatedMemoryUnit`` over ops/selective_scan.py.

Each makes a call's rows and its new state and hands the state to models/cache.py
(``put_state``). ``TransformerBlock`` (transformer.py, beside this file) picks one
by the layer's kind and imports this module; nothing here imports that one:
``TransformerConfig`` is an annotation alone, never evaluated (PEP 563).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from seldon_core_tpu.models.cache import (
    PAD_POS,
    pack_state,
    put_state,
    state_lane_heads,
    state_rows,
    unpack_state,
)
from seldon_core_tpu.models.leaves import RMSNorm, param_with_axes, small_leaf_init


def short_conv(z: jnp.ndarray, taps: jnp.ndarray, state: Optional[jnp.ndarray],
               positions: jnp.ndarray, valid: jnp.ndarray):
    """The depthwise causal taps of LFM2's short convolution over the rows of
    one call, and the state each sequence leaves behind. ONE function for
    every call shape: a decode step (s = 1), a prefill chunk (one sequence,
    padded) and the cache-less forward.

    ``z`` [b, s, d] (the gated input B * X); ``taps`` [d, L] float32, tap j
    weighs z_{t - (L-1) + j} (so the LAST tap weighs the row itself: the order
    of ``torch.nn.Conv1d``'s weight); ``state`` [b, L-1, d] = the sequence's
    last L-1 values of z before this call, oldest first (None = none);
    ``positions`` [b, s] absolute positions; ``valid`` [b, s] bool, the rows
    that are tokens, which are a PREFIX of each sequence's rows (prompts are
    right-padded; a step's one row is a token or is not).

    A state row that would lie before position 0 reads as zero
    (``position - j >= 0``), so a sequence that starts needs no reset of its
    slot. Returns (v [b, s, d] float32, new_state [b, L-1, d] in z's dtype):
    the last L-1 values of z up to the last VALID row; a sequence with no
    valid row keeps its state as it came."""
    b, s, d = z.shape
    K = taps.shape[1] - 1
    if state is None:
        state = jnp.zeros((b, K, d), z.dtype)
    state = state.astype(z.dtype)
    # state row i is z at position p0 - K + i
    before_start = positions[:, :1] < (K - jnp.arange(K))[None, :]
    zz = jnp.concatenate([jnp.where(before_start[..., None], 0, state), z], axis=1)
    w = taps.astype(jnp.float32)
    v = sum(w[:, j] * zz[:, j:j + s].astype(jnp.float32) for j in range(K + 1))
    n = jnp.sum(valid, axis=1, dtype=jnp.int32)                              # [b]
    last = jnp.take_along_axis(zz, (n[:, None] + jnp.arange(K)[None, :])[..., None], axis=1)
    return v, jnp.where((n > 0)[:, None, None], last, state)


class ShortConv(nn.Module):
    """LFM2's gated short convolution (``transformers`` ``Lfm2ShortConv``), the
    token mixer of a "conv" layer:

        [B ; C ; X] = W_in u            W_in [dim, 3 dim], split in THAT order
        z_t = B_t * X_t ;  v_t = sum_j w_j z_{t-(L-1)+j} ;  out = W_out (C_t * v_t)

    W_in is ONE [dim, 3 dim] product. What a sequence keeps between calls is
    its last L-1 values of z, [L-1, dim], whatever its length: the cache entry
    of a conv layer is a ``StateEntry`` ``(state,)`` with state [rows, L-1,
    dim] in the serving dtype, rows = the dense cache's batch or the batcher's
    slots, read and written through models/cache.py ``state_rows`` /
    ``put_state`` (``state_slots``: which row each sequence continues).
    Without a cache: from zeros, returns (out, (state,)) as well."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, valid=None, cache=None, state_slots=None):
        cfg = self.cfg
        d, dt = cfg.dim, cfg.dtype
        w_in = param_with_axes("in_proj", nn.initializers.lecun_normal(), (d, 3 * d), jnp.float32,
                               axes=("embed", "conv_gates"))
        taps = param_with_axes("taps", small_leaf_init("taps"), (d, cfg.conv_L_cache), jnp.float32,
                               axes=("conv_channel", "conv_taps"))
        w_out = param_with_axes("out_proj", nn.initializers.lecun_normal(), (d, d), jnp.float32,
                                axes=("conv_channel", "embed"))
        if valid is None:
            valid = positions < PAD_POS
        with jax.named_scope("mix.conv.in"):
            bcx = x @ w_in.astype(dt)
        with jax.named_scope("mix.conv.taps"):
            gate_b, gate_c, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
            state, = state_rows(cache, state_slots, 1)
            v, new_state = short_conv(gate_b * xs, taps, state, positions, valid)
            new_cache = put_state(cache, state_slots, (new_state,))
            y = (gate_c.astype(jnp.float32) * v).astype(dt)
        with jax.named_scope("mix.conv.out"):
            return y @ w_out.astype(dt), new_cache


GDN_CHUNK = 64   # rows of a sub-chunk of the delta rule's chunked form


def _unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """(I + A)^-1 for strictly lower triangular ``a`` [..., c, c], by halves:
    the inverse of [[P, 0], [C, Q]] is [[P^-1, 0], [-Q^-1 C P^-1, Q^-1]], so
    from the 1 x 1 diagonal blocks (inverse 1) up, each of log2(c) levels fills
    the lower-left quarter of every diagonal block of twice the size, for all
    blocks at once as ``inv - inv (A . mask) inv`` (inv is block diagonal so
    far): 2 (log2(c) - 1) batched matmuls, as many as the product below took,
    in place of the c sequential rows of a forward substitution, and every
    product IS a block of the answer. (The product
    (I - A)(I + A^2)(I + A^4)... that stood here is the same in exact
    arithmetic, but its powers of A grow like binomials before they cancel:
    with keys that resemble each other and a decay near 1 it lost every digit
    in float32, PR 45.)"""
    c = a.shape[-1]
    hp = jax.lax.Precision.HIGHEST
    i = np.arange(c)

    def quarters(m):   # the lower-left quarters of the diagonal blocks of 2m rows
        return jnp.where((i[:, None] // (2 * m) == i[None, :] // (2 * m))
                         & (i[:, None] % (2 * m) >= m) & (i[None, :] % (2 * m) < m), a, 0.0)

    # blocks of two rows need no product: [[1, 0], [a, 1]]^-1 = [[1, 0], [-a, 1]]
    inv, m = jnp.eye(c, dtype=a.dtype) - quarters(1), 2
    while m < c:
        inv = inv - jnp.matmul(jnp.matmul(inv, quarters(m), precision=hp), inv, precision=hp)
        m *= 2
    return inv


def gated_delta_rule(q, k, v, g, beta, state, starts=None, kernel: bool = True):
    """The gated delta rule over the rows of one call, and the state each
    sequence leaves. ONE function for every call shape: a decode step
    (s = 1), a prefill chunk (one sequence, padded) and the cache-less forward.

    ``q`` / ``k`` [b, s, H, dk] float32 (L2-normalised a head, q scaled, the
    key heads repeated to the H value heads); ``v`` [b, s, H, dv]; ``g``
    [b, s, H] the LOG of each row's decay (<= 0); ``beta`` [b, s, H];
    ``state`` float32, S before the call's first row, in the CACHE's layout
    (models/cache.py ``pack_state``: [b, H / side, dk, side * dv], ``side``
    heads side by side along the lanes, read off the array's own shape;
    [b, H, dk, dv] where dv is whole lane tiles); ``starts`` [b] bool or None:
    the sequences whose S reads as ZEROS whatever
    ``state`` holds (a sequence that starts has no past). Per row:

        S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t

    A row with ``beta = 0`` and ``g = 0`` leaves S as it came (a padded row).
    Returns (o [b, s, H, dv] float32, S after the last row, laid out as it came).

    Computed in the CHUNKED form (the WY / UT transform of
    ``torch_chunk_gated_delta_rule`` and the flash-linear-attention kernel):
    sub-chunks of GDN_CHUNK rows; inside one, with G the running sum of g, the
    triangular system T = (I + tril(beta_i (k_i . k_j) e^{G_i - G_j}, -1))^-1
    gives every row's correction at once (W = T (beta k e^G), U = T (beta v));
    between sub-chunks S goes on in float32:

        V' = U - W S;  O = (q e^G) S + tril((q . k^T) e^{G_i - G_j}) V'
        S <- e^{G_last} S + (k e^{G_last - G})^T V'

    At s = 1 this IS the recurrence (T = 1), written so that S is multiplied
    elementwise and reduced, not handed to the MXU a [128, 128] block a head:
    the step is bound by S's bytes. In a program LOWERED for a TPU (and where
    ``kernel``: not on a mesh) it is the repo's kernel (ops/gated_delta.py: S
    read once and written once, in its own buffer), chosen by
    ``jax.lax.platform_dependent`` as ``MoEFFN`` chooses its grouped matmul;
    the expression, which XLA makes two passes over S, everywhere else and for
    a state that is not whole tiles (``plan``). The step's kernel reads and
    writes the state AS IT LIES; the expression and the chunked form unpack it
    (a chunk's one sequence: 2 MB a layer) and pack what they leave. float32
    throughout, the matmuls at the
    highest precision: they are a thousandth of a chunk's FLOPs."""
    b, s, H, dk = k.shape
    hp = jax.lax.Precision.HIGHEST
    side = H // state.shape[1]
    if starts is None:
        starts = jnp.zeros((b,), bool)
    if s == 1:
        from seldon_core_tpu.ops.gated_delta import gated_delta_step, plan

        def step_expression():
            S = jnp.where(starts[:, None, None, None], 0.0, unpack_state(state, side))
            q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]                       # [b, H, d]
            decay = jnp.exp(g[:, 0])[..., None]                          # [b, H, 1]
            # S^T k and S^T q out of ONE pass over S; o = e^g S^T q + (k . q) d
            sk = jnp.sum(S * k1[..., None], axis=-2)
            sq = jnp.sum(S * q1[..., None], axis=-2)
            d = beta[:, 0][..., None] * (v1 - decay * sk)
            new_state = S * decay[..., None] + k1[..., None] * d[..., None, :]
            return (decay * sq + jnp.sum(k1 * q1, axis=-1, keepdims=True) * d,
                    pack_state(new_state, side))

        walk = plan(H, dk, v.shape[-1]) if kernel else None

        def step_kernel():
            return gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                                    starts, walk, interpret=False)

        if walk is None:
            o, new_state = step_expression()
        else:
            o, new_state = jax.lax.platform_dependent(tpu=step_kernel, default=step_expression)
        return o[:, None], new_state
    state = jnp.where(starts[:, None, None, None], 0.0, unpack_state(state, side))
    c = min(GDN_CHUNK, s)
    pad = -s % c
    if pad:   # rows that change nothing
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (g, beta))
    n = (s + pad) // c

    def chunks(x):   # [b, n * c, H, ...] -> [n, b, H, c, ...]
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                                       # [n, b, H, c]
    lower = jnp.tril(jnp.ones((c, c), bool))
    # e^{G_i - G_j} for j <= i (the exponent is <= 0 there; masked BEFORE the
    # exponential, so nothing overflows above the diagonal)
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    k_beta = k * beta[..., None]
    a = jnp.einsum("...ik,...jk->...ij", k_beta, k, precision=hp) * decay
    T = _unit_lower_inverse(jnp.where(jnp.tril(lower, -1), a, 0.0))
    U = jnp.matmul(T, v * beta[..., None], precision=hp)
    W = jnp.matmul(T, k_beta * jnp.exp(G)[..., None], precision=hp)
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=hp) * decay
    q_in = q * jnp.exp(G)[..., None]
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])[..., None, None]

    def sub_chunk(S, xs):
        U_i, W_i, qk_i, q_i, k_i, last_i = xs
        v_new = U_i - jnp.matmul(W_i, S, precision=hp)
        o = jnp.matmul(q_i, S, precision=hp) + jnp.matmul(qk_i, v_new, precision=hp)
        S = last_i * S + jnp.einsum("...ck,...cv->...kv", k_i, v_new, precision=hp)
        return S, o

    new_state, o = jax.lax.scan(sub_chunk, state, (U, W, qk, q_in, k_out, last),
                                unroll=min(n, 4))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(b, n * c, H, -1)   # [b, s, H, dv]
    return o[:, :s], pack_state(new_state, side)


def gdn_step_walk(cfg: "TransformerConfig") -> Optional[Any]:
    """How the decode step's delta rule goes through the repo's kernel IN A
    PROGRAM LOWERED FOR A TPU (ops/gated_delta.py ``Plan``), or None where it is
    the expression (two passes over S) there too: a mesh, a state that is not
    whole tiles. The facts ``gated_delta_rule`` itself decides by, for the
    loop's ``seldon_llm_gdn_step_path``."""
    from seldon_core_tpu.ops.gated_delta import plan

    if cfg.mesh is not None or not cfg.layers_of("linear_attention"):
        return None
    return plan(cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim)


def l2_normalize(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class GatedDeltaNet(nn.Module):
    """Qwen3-Next's Gated DeltaNet (``transformers`` ``Qwen3NextGatedDeltaNet``),
    the token mixer of a "linear_attention" layer. With Hk key heads of dk and
    Hv value heads of dv (each key head serves Hv / Hk value heads):

        [q ; k ; v ; z] = W_qkvz u     [b ; a] = W_ba u      (held in THAT order:
                                       the checkpoint interleaves them by key head)
        [q ; k ; v] <- SiLU(causal depthwise taps over the channels of [q ; k ; v])
        q, k <- L2-normalised a head (eps 1e-6), q * dk^-1/2
        beta = sigmoid(b)     g = -exp(A_log) * softplus(a + dt_bias)     a value head
               (2 sigmoid(b) where cfg.linear_allow_neg_eigval: Olmo-Hybrid, whose
               separate q / k / v / gate and b / a projections are held as these
               two stacked leaves, Hk = Hv and dk != dv)
        o = gated_delta_rule(q, k, v, g, beta, S)
        out = W_out (RMSNorm_dv(o) * w * SiLU(z))       a value head

    What a sequence keeps between calls, whatever its length: the last
    taps - 1 rows of [q ; k ; v] BEFORE the convolution, in the serving dtype
    (``short_conv``'s state and rule), and S [dk, dv] a value head in float32,
    as the published implementation holds it: the cache entry is the 2-tuple
    ``(conv_state [rows, taps - 1, 2 Hk dk + Hv dv], S)``, S in the layout
    models/cache.py gives it (``pack_state``: [rows, Hv, dk, dv], or heads side
    by side along the lanes where dv is no whole lane tile).
    ``state_slots`` is ShortConv's. A sequence that starts (its first row at
    position 0) reads S as zeros, so admission resets nothing; a row that is
    no token has beta = 0 and g = 0 and leaves S as it came. Without a cache:
    from zeros, returns (out, (conv_state, S)) as well."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, valid=None, cache=None, state_slots=None):
        cfg = self.cfg
        d, dt = cfg.dim, cfg.dtype
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        key_dim, value_dim = hk * dk, hv * dv
        channels = 2 * key_dim + value_dim
        w_qkvz = param_with_axes("in_proj_qkvz", nn.initializers.lecun_normal(),
                                 (d, channels + value_dim), jnp.float32,
                                 axes=("embed", "gdn_proj"))
        w_ba = param_with_axes("in_proj_ba", nn.initializers.lecun_normal(), (d, 2 * hv),
                               jnp.float32, axes=("embed", "gdn_gates"))
        taps = param_with_axes("conv1d", small_leaf_init("conv1d"),
                               (channels, cfg.linear_conv_kernel_dim), jnp.float32,
                               axes=("gdn_channel", "conv_taps"))
        a_log = param_with_axes("A_log", small_leaf_init("A_log"), (hv,), jnp.float32,
                                axes=("gdn_scalar",))
        dt_bias = param_with_axes("dt_bias", small_leaf_init(cfg.small_leaf("dt_bias")), (hv,),
                                  jnp.float32, axes=("gdn_scalar",))
        w_out = param_with_axes("out_proj", nn.initializers.lecun_normal(), (value_dim, d),
                                jnp.float32, axes=("gdn_value", "embed"))
        b, s, _ = x.shape
        if valid is None:
            valid = positions < PAD_POS
        with jax.named_scope("mix.gdn.in"):
            qkvz = x @ w_qkvz.astype(dt)
            # the gates' 2 Hv values a row stay float32 out of the product: g is
            # -A softplus(a + dt_bias) with A up to 16, and a rounding of a to
            # bf16 is a rounding of the DECAY
            ba = jnp.matmul(x, w_ba.astype(dt), preferred_element_type=jnp.float32)
        conv_state, state = state_rows(cache, state_slots, 2)
        with jax.named_scope("mix.gdn.conv"):
            mixed, new_conv = short_conv(qkvz[..., :channels], taps, conv_state, positions, valid)
            mixed = jax.nn.silu(mixed)                                   # float32
        with jax.named_scope("mix.gdn.rule"):
            rep = hv // hk
            q = l2_normalize(mixed[..., :key_dim].reshape(b, s, hk, dk)) * dk ** -0.5
            k = l2_normalize(mixed[..., key_dim:2 * key_dim].reshape(b, s, hk, dk))
            q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
            v = mixed[..., 2 * key_dim:].reshape(b, s, hv, dv)
            live = valid[..., None]
            beta = jnp.where(live, jax.nn.sigmoid(ba[..., :hv]), 0.0)
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = jnp.where(live, -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias), 0.0)
            if state is None:
                side = state_lane_heads(cfg)
                state = jnp.zeros((b, hv // side, dk, side * dv), jnp.float32)
            # a sequence that starts here has no past (a row that is no token
            # starts nothing: a slot's S may be a chunk's to write meanwhile)
            starts = (positions[:, 0] == 0) & valid[:, 0]
            o, new_state = gated_delta_rule(q, k, v, g, beta, state, starts,
                                            kernel=gdn_step_walk(cfg) is not None)
        new_cache = put_state(cache, state_slots, (new_conv, new_state))
        with jax.named_scope("mix.gdn.out"):
            normed = RMSNorm(dv, cfg.norm_eps, "head_norm", name="norm")(o)
            z = qkvz[..., channels:].reshape(b, s, hv, dv).astype(jnp.float32)
            y = (normed * jax.nn.silu(z)).astype(dt).reshape(b, s, value_dim)
            return y @ w_out.astype(dt), new_cache


def ssd_step_blocks(cfg: "TransformerConfig") -> Optional[Any]:
    """How the decode step's state-space recurrence goes through the repo's
    kernel IN A PROGRAM LOWERED FOR A TPU (ops/ssd.py ``Plan``), or None where
    it is the expression (a further pass over h for h C) there too: a state
    that is not whole tiles. ``ssd``'s own ``plan`` at the model's sizes, for
    the loop's ``seldon_llm_ssd_step_path``."""
    from seldon_core_tpu.ops.ssd import plan

    if not cfg.layers_of("mamba"):
        return None
    return plan(cfg.mamba_n_heads, cfg.mamba_n_groups, cfg.mamba_d_head, cfg.mamba_d_state)


class Mamba2Mixer(nn.Module):
    """Mamba-2's selective state-space mixer as granite-4.0-h computes it
    (``transformers`` ``GraniteMoeHybridMambaLayer.torch_forward``,
    ``GraniteMoeHybridRMSNormGated``), the token mixer of a "mamba" layer. With H
    heads of P (d_inner = H P), G groups of heads and a state of N:

        [z ; xBC ; dt] = W_in u              d_inner + (d_inner + 2 G N) + H columns, no bias
        xBC <- SiLU(causal depthwise taps over the channels of xBC + conv bias)
        [x ; B ; C] = xBC                     x [H, P]; B, C [G, N]
        Delta = softplus(dt + dt_bias)        A = -exp(A_log)            a head; the leaf
                                              ``heads`` [3, H] = [A_log ; dt_bias ; D]
        h <- e^(Delta A) h + (Delta x) B^T    y = h C + D x              ops/ssd.py ``ssd``
        out = W_out (w * RMSNorm_{d_inner}(y * SiLU(z)))     the gate BEFORE the norm, and
                                                             ONE norm over all d_inner channels

    The whole projection leaves the product float32 (dt is a decay's exponent
    and z goes into float32 arithmetic; xBC is rounded to the serving dtype
    where it meets the taps, as the rows a sequence keeps are). What a sequence
    keeps between calls, whatever its length: the last taps - 1 rows of xBC
    BEFORE the convolution, in the serving dtype (``short_conv``'s state and
    rule), and h [P, N] a head in float32, as the published implementation
    holds it: the cache entry is the 2-tuple ``(conv_state [rows, taps - 1,
    d_inner + 2 G N], h)``, h in the layout models/cache.py gives it (a head's
    h TRANSPOSED, heads side by side along the lanes: [rows, H / side, N,
    side * P], the step kernel's; granite's [rows, 32, 128, 128]).
    ``state_slots`` is ShortConv's. A sequence that starts
    (its first row at position 0) reads h as zeros, so admission resets
    nothing; a row that is no token has Delta = 0 and leaves h as it came.
    Without a cache: from zeros, returns (out, (conv_state, h)) as well."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, valid=None, cache=None, state_slots=None):
        from seldon_core_tpu.ops.gated_delta import heads_a_lane_row
        from seldon_core_tpu.ops.ssd import ssd

        cfg = self.cfg
        d, dt = cfg.dim, cfg.dtype
        H, P, N, G = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups
        inner, channels = H * P, H * P + 2 * G * N
        w_in = param_with_axes("in_proj", nn.initializers.lecun_normal(),
                               (d, inner + channels + H), jnp.float32,
                               axes=("embed", "ssd_proj"))
        taps = param_with_axes("conv1d", small_leaf_init("conv1d"), (channels, cfg.mamba_d_conv),
                               jnp.float32, axes=("ssd_channel", "conv_taps"))
        conv_bias = param_with_axes("conv_bias", small_leaf_init("conv_bias"), (channels,),
                                    jnp.float32, axes=("ssd_scalar",)) if cfg.mamba_conv_bias else 0.0
        # A_log, dt_bias and D a head as ONE leaf [3, H]: a leaf of 64 values is
        # a copy into on-chip memory a layer a step, and each waited ~16 us
        # behind the weights' prefetches (1.7 ms of a 33.4 ms step of 36 layers;
        # with one leaf most of that wait moved on to the next small copy in the
        # queue, the norm's weight: PERF.md section 6, PR 53)
        a_log, dt_bias, skip = param_with_axes(
            "heads", small_leaf_init("heads"), (3, H), jnp.float32, axes=("ssd_scalar", "ssd_head"))
        w_out = param_with_axes("out_proj", nn.initializers.lecun_normal(), (inner, d),
                                jnp.float32, axes=("ssd_inner", "embed"))
        b, s, _ = x.shape
        if valid is None:
            valid = positions < PAD_POS
        with jax.named_scope("mix.ssd.in"):
            zxbcdt = jnp.matmul(x, w_in.astype(dt), preferred_element_type=jnp.float32)
        conv_state, state = state_rows(cache, state_slots, 2)
        with jax.named_scope("mix.ssd.conv"):
            # the taps read xBC in the serving dtype, as the rows a sequence
            # keeps are held: a row reads the same whichever call it is read in
            mixed, new_conv = short_conv(zxbcdt[..., inner:inner + channels].astype(dt), taps,
                                         conv_state, positions, valid)
            mixed = jax.nn.silu(mixed + conv_bias)                       # float32
        with jax.named_scope("mix.ssd.rule"):
            step = jnp.where(valid[..., None],
                             jax.nn.softplus(zxbcdt[..., inner + channels:] + dt_bias), 0.0)
            if state is None:
                side = heads_a_lane_row(H, P)
                state = jnp.zeros((b, H // side, N, side * P), jnp.float32)
            # a sequence that starts here has no past (a row that is no token
            # starts nothing: a slot's h may be a chunk's to write meanwhile)
            starts = (positions[:, 0] == 0) & valid[:, 0]
            y, new_state = ssd(mixed[..., :inner].reshape(b, s, H, P), step, -jnp.exp(a_log),
                               mixed[..., inner:inner + G * N].reshape(b, s, G, N),
                               mixed[..., inner + G * N:].reshape(b, s, G, N), skip, state,
                               starts)
        new_cache = put_state(cache, state_slots, (new_conv, new_state))
        with jax.named_scope("mix.ssd.out"):
            gated = y.reshape(b, s, inner) * jax.nn.silu(zxbcdt[..., :inner])
            normed = RMSNorm(inner, cfg.norm_eps, "head_norm", name="norm")(gated)
            return normed.astype(dt) @ w_out.astype(dt), new_cache


def dt_proj_init(key, shape, dtype=jnp.float32):
    """Mamba-1's published init of the step's up-projection: U(+- rank^-1/2)."""
    bound = float(shape[0]) ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba1Mixer(nn.Module):
    """Mamba-1's selective-scan mixer (arXiv 2312.00752; ``transformers``
    ``MambaMixer.slow_forward``), the token mixer of an "s6" layer. With E =
    cfg.mamba_d_inner channels, a state of N, a step bottleneck of R:

        [x ; z] = W_in u                        W_in [dim, 2 E], no bias
        x <- SiLU(causal depthwise taps over the E channels + conv bias)
        [dt ; B ; C] = W_x x                    W_x [E, R + 2 N]
        Delta = softplus(W_dt dt + b_dt)        W_dt [R, E], a channel
        A = -exp(A_log)                         a (state, channel) pair; the leaf
                                                ``A_log_t`` [N, E] is held as h is
        h <- e^(Delta A) h + Delta B x;  y = sum_n C h + D x     ops/selective_scan.py
        out = W_out (y * SiLU(z))               W_out [E, dim]

    Every product leaves float32 (Delta is a decay's exponent; x is rounded to
    the serving dtype where it meets the taps, as the rows a sequence keeps
    are). What a sequence keeps between calls, whatever its length: the last
    taps - 1 rows of x BEFORE the convolution, in the serving dtype
    (``short_conv``'s state and rule), and h [N, E] in float32: the cache entry
    is the 2-tuple ``(conv_state [rows, taps - 1, E], h [rows, N, E])``.
    ``state_slots`` is ShortConv's. A sequence that starts (its first row at
    position 0) reads h as zeros, so admission resets nothing; a row that is
    no token has Delta = 0 and leaves h as it came. ``hands_up`` (the layer is
    cfg.memory_source): also returns m = y, the scan's output BEFORE the gate
    with D x in it, float32 [b, s, E]: what every "gmu" layer above reads.
    Without a cache: from zeros, returns (out, (conv_state, h)[, m]) as well."""

    cfg: TransformerConfig
    hands_up: bool = False

    @nn.compact
    def __call__(self, x, positions, valid=None, cache=None, state_slots=None):
        from seldon_core_tpu.ops.selective_scan import selective_scan

        cfg = self.cfg
        d, dt, f32 = cfg.dim, cfg.dtype, jnp.float32
        E, N, R = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        lecun = nn.initializers.lecun_normal()
        w_in = param_with_axes("in_proj", lecun, (d, 2 * E), f32, axes=("embed", "ssd_proj"))
        taps = param_with_axes("conv1d", small_leaf_init("conv1d"), (E, cfg.mamba_d_conv), f32,
                               axes=("ssd_channel", "conv_taps"))
        conv_bias = param_with_axes("conv_bias", small_leaf_init("conv_bias"), (E,), f32,
                                    axes=("ssd_scalar",)) if cfg.mamba_conv_bias else 0.0
        w_x = param_with_axes("x_proj", lecun, (E, R + 2 * N), f32, axes=("ssd_inner", "ssd_proj"))
        w_dt = param_with_axes("dt_proj", dt_proj_init, (R, E), f32, axes=("ssd_rank", "ssd_inner"))
        b_dt = param_with_axes("b_dt", small_leaf_init("b_dt"), (E,), f32, axes=("ssd_scalar",))
        a_log = param_with_axes("A_log_t", small_leaf_init("A_log_t"), (N, E), f32,
                                axes=("ssd_scalar", "ssd_channel"))
        skip = param_with_axes("D", small_leaf_init("D"), (E,), f32, axes=("ssd_scalar",))
        w_out = param_with_axes("out_proj", lecun, (E, d), f32, axes=("ssd_inner", "embed"))
        b, s, _ = x.shape
        if valid is None:
            valid = positions < PAD_POS
        with jax.named_scope("mix.s6.in"):
            xz = jnp.matmul(x, w_in.astype(dt), preferred_element_type=f32)
        conv_state, state = state_rows(cache, state_slots, 2)
        with jax.named_scope("mix.s6.conv"):
            # the taps read x in the serving dtype, as the rows a sequence keeps
            # are held: a row reads the same whichever call it is read in
            mixed, new_conv = short_conv(xz[..., :E].astype(dt), taps, conv_state, positions, valid)
            u = jax.nn.silu(mixed + conv_bias)                                # float32
        with jax.named_scope("mix.s6.scan"):
            dbc = jnp.matmul(u.astype(dt), w_x.astype(dt), preferred_element_type=f32)
            step = jax.nn.softplus(
                jnp.matmul(dbc[..., :R].astype(dt), w_dt.astype(dt), preferred_element_type=f32)
                + b_dt)
            step = jnp.where(valid[..., None], step, 0.0)
            if state is None:
                state = jnp.zeros((b, N, E), f32)
            # a sequence that starts here has no past (a row that is no token
            # starts nothing: a slot's h may be a chunk's to write meanwhile)
            starts = (positions[:, 0] == 0) & valid[:, 0]
            y, new_state = selective_scan(u, step, -jnp.exp(a_log), dbc[..., R:R + N],
                                          dbc[..., R + N:], skip, state, starts)
        new_cache = put_state(cache, state_slots, (new_conv, new_state))
        with jax.named_scope("mix.s6.out"):
            out = (y * jax.nn.silu(xz[..., E:])).astype(dt) @ w_out.astype(dt)
        return (out, new_cache, y) if self.hands_up else (out, new_cache)


class GatedMemoryUnit(nn.Module):
    """SambaY's gated memory unit (arXiv 2507.06607), the token mixer of a "gmu"
    layer: GMU(u, m) = W_2 (SiLU(W_1 u) * m), W_1 [dim, E], W_2 [E, dim], no
    bias; ``memory`` m [b, s, E] float32 is the SAME rows' scan output of the
    layer cfg.memory_source in the SAME call, so the layer keeps nothing."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.cfg
        lecun = nn.initializers.lecun_normal()
        w1 = param_with_axes("in_proj", lecun, (cfg.dim, cfg.mamba_d_inner), jnp.float32,
                             axes=("embed", "ssd_proj"))
        w2 = param_with_axes("out_proj", lecun, (cfg.mamba_d_inner, cfg.dim), jnp.float32,
                             axes=("ssd_inner", "embed"))
        with jax.named_scope("mix.gmu"):
            gate = jnp.matmul(x, w1.astype(cfg.dtype), preferred_element_type=jnp.float32)
            return (jax.nn.silu(gate) * memory).astype(cfg.dtype) @ w2.astype(cfg.dtype)
