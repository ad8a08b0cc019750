"""Pallas TPU kernel: fused residual-add + RMSNorm for LLM decode.

Why this kernel exists: the round-5 decode profile (benchmarks/
DECODE_NOTES.md) attributes 18% of device time to ~899 RMSNorm-rooted
fusion clusters averaging 7.5 us each on [8, 2048] tensors that should take
<1 us of bandwidth — at batch 8 the decode step is per-op-overhead-bound,
and the named lever is fewer/larger kernels per step. Each transformer
block runs ``x = x + h`` followed by ``rms_norm(x)``: two HBM round trips
of the activation. This kernel computes both in ONE pass — read x and h
once, write the residual sum and the normed activation once, the f32
mean-of-squares reduction entirely in VMEM.

Numerics contract: the residual add happens in the model dtype, the norm
in f32 over the added value, the weight multiply in f32, the result cast
back to the model dtype — the dtype chain of ``rms_norm(x + h, w, eps)``
from models/transformer.py. The residual sum is bit-equal to the unfused
graph; the normed output is within 1 bf16 ulp of it, not bit-equal (v5e,
PR 21: max |diff| 2^-6 at [8, 4096] and [8, 2048] bf16 — PERF.md), so
``TransformerConfig.fused_norm`` can change a sampled token.

The kernel is the only implementation behind this entry point: Mosaic
compiles it on a TPU (a compile error there is raised, never papered over
with the XLA expression), and every other backend runs the same body under
the Pallas interpreter (``ops.pallas_interpret_default``).
``residual_rmsnorm_ref`` is what the parity tests compare against.
"""

from __future__ import annotations

import functools


def residual_rmsnorm_ref(x, h, weight, eps: float):
    """Pure-XLA reference: (y, rms_norm(y, weight, eps)) with y = x + h.
    Identical op chain to the unfused TransformerBlock path."""
    import jax
    import jax.numpy as jnp

    y = x + h
    y32 = y.astype(jnp.float32)
    norm = y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, axis=-1, keepdims=True) + eps)
    return y, (norm * weight).astype(y.dtype)


def _kernel(d_real: int, eps: float, x_ref, h_ref, w_ref, y_ref, o_ref):
    import jax
    import jax.numpy as jnp

    y = x_ref[...] + h_ref[...]  # residual add in the model dtype
    y_ref[...] = y
    y32 = y.astype(jnp.float32)
    # sum/d_real, not mean: the lane dim may be zero-padded to 128 and the
    # padded columns must not dilute the divisor (zeros already add nothing
    # to the sum)
    ms = jnp.sum(y32 * y32, axis=-1, keepdims=True) * (1.0 / d_real)
    normed = y32 * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (normed * w_ref[...].astype(jnp.float32)[None, :]).astype(o_ref.dtype)


def fused_residual_rmsnorm(x, h, weight, eps: float,
                           interpret: bool | None = None):
    """x, h: [..., d] activations; weight: [d] f32. Returns
    (y, normed) = (x + h, rms_norm(x + h, weight, eps)), both in x.dtype.

    One Pallas pass (one HBM read of x/h, one write of each output).
    ``interpret=None`` compiles the kernel on a TPU and interprets it on
    any other backend; pass a bool to force either.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from seldon_core_tpu.ops import pallas_interpret_default

    d = x.shape[-1]
    assert h.shape == x.shape and weight.shape == (d,), (x.shape, h.shape, weight.shape)
    if interpret is None:
        interpret = pallas_interpret_default()

    lead = x.shape[:-1]
    x2 = x.reshape(-1, d)
    h2 = h.reshape(-1, d)
    m = x2.shape[0]
    # sublane tile shrinks for small (decode) batches but stays a multiple
    # of the min f32 tile (8); lane dim pads to 128 for Mosaic tiling
    tm = 256 if m >= 256 else max(8, 1 << max(m - 1, 0).bit_length())
    pm = -(-m // tm) * tm
    pd = -(-d // 128) * 128
    if (pm, pd) != (m, d):
        x2 = jnp.pad(x2, ((0, pm - m), (0, pd - d)))
        h2 = jnp.pad(h2, ((0, pm - m), (0, pd - d)))
    w = weight.astype(jnp.float32)
    if pd != d:
        w = jnp.pad(w, (0, pd - d))

    y, o = pl.pallas_call(
        functools.partial(_kernel, d, float(eps)),
        grid=(pm // tm,),
        in_specs=[
            pl.BlockSpec((tm, pd), lambda i: (i, 0)),
            pl.BlockSpec((tm, pd), lambda i: (i, 0)),
            pl.BlockSpec((pd,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((tm, pd), lambda i: (i, 0)),
            pl.BlockSpec((tm, pd), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pm, pd), x.dtype),
            jax.ShapeDtypeStruct((pm, pd), x.dtype),
        ],
        interpret=interpret,
    )(x2, h2, w)
    if (pm, pd) != (m, d):
        y, o = y[:m, :d], o[:m, :d]
    return y.reshape(*lead, d), o.reshape(*lead, d)


__all__ = [
    "fused_residual_rmsnorm",
    "residual_rmsnorm_ref",
]
