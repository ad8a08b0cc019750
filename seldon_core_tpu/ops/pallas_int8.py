"""Pallas TPU kernel: int8 weight-only matmul with in-kernel dequantization.

The serving path for weight-only int8 (ops/quantize.py) relies on XLA to
fuse the convert+multiply dequant into the consuming matmul. This kernel is
the explicit-control variant of that contract — the weight tile crosses
HBM->VMEM as int8 (half the bytes of bf16), is dequantized in VMEM
registers, and feeds the MXU per (M, N) grid tile with f32 accumulation —
the quantization-kernel pattern from the TPU Pallas playbook. Its role: an
explicit-control experiment (``int8_dense`` / ``int8_matmul``) for
validating/benching the XLA fusion path against a known-good explicit
schedule. The public serving entry point (``ops.quantize.quantized_matmul``)
uses the fused XLA expression — the round-4 decision bench (earlier
harness) measured this kernel at 0.55-0.79x XLA on the decode GEMM shapes,
so swapping it into the model families stays gated on a benchmark win that
hasn't materialised.

``int8_matmul`` pads all dims to MXU-friendly tiles. Mosaic compiles the
kernel on a TPU and a compile error there is raised (v5e, PR 21: at
[8, 4096] x [4096, 4096] it does not compile — the ``(128,)`` scale block
fails Mosaic's layout check against XLA's ``T(1024)`` layout of
``f32[4096]``; PERF.md has the message); every other backend runs the same
body under the Pallas interpreter (``ops.pallas_interpret_default``).
"""

from __future__ import annotations

import functools


def _kernel(x_ref, q_ref, s_ref, o_ref):
    import jax.numpy as jnp

    # dequant in VMEM: int8 tile -> f32, scaled per output channel
    w = q_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _tile_sizes(m: int, n: int):
    # lane dim is fixed at 128; sublane tile shrinks for small batches but
    # stays a multiple of the f32 min tile (8)
    tm = 128 if m >= 128 else max(8, 1 << max(m - 1, 0).bit_length())
    return tm, 128


def int8_matmul(x, q, scale, out_dtype=None, interpret: bool | None = None):
    """x [M, K] float; q [K, N] int8; scale [N] f32 -> [M, N].

    Equivalent to ``x @ (q * scale)`` with f32 accumulation; the weight
    tiles stream into VMEM as int8. ``interpret=None`` compiles the kernel
    on a TPU and interprets it on any other backend; pass a bool to force
    either.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from seldon_core_tpu.ops import pallas_interpret_default

    m, k = x.shape
    kq, n = q.shape
    assert k == kq and scale.shape == (n,), (x.shape, q.shape, scale.shape)
    out_dtype = out_dtype or x.dtype
    if interpret is None:
        interpret = pallas_interpret_default()

    tm, tn = _tile_sizes(m, n)
    pm = -(-m // tm) * tm
    pn = -(-n // tn) * tn
    # K is the int8 sublane dim of q and the lane dim of x: pad to 128 so
    # Mosaic tiling holds for any K (zero rows/cols contribute nothing)
    pk = -(-k // 128) * 128
    xp = jnp.pad(x, ((0, pm - m), (0, pk - k))) if (pm, pk) != (m, k) else x
    qp = jnp.pad(q, ((0, pk - k), (0, pn - n))) if (pk, pn) != (k, n) else q
    sp = jnp.pad(scale, (0, pn - n)) if pn != n else scale

    out = pl.pallas_call(
        _kernel,
        grid=(pm // tm, pn // tn),
        in_specs=[
            pl.BlockSpec((tm, pk), lambda i, j: (i, 0)),
            pl.BlockSpec((pk, tn), lambda i, j: (0, j)),
            pl.BlockSpec((tn,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), out_dtype),
        interpret=interpret,
    )(xp, qp, sp)
    return out[:m, :n]


def int8_dense(x, qt, out_dtype=None):
    """Apply a quantized kernel (ops.quantize.QuantizedTensor holding a
    [K, N] weight) to activations [..., K] — reshapes to 2-D around the
    kernel so any leading batch structure works. Output dtype defaults to
    the weight's original dtype (matching dequantize_params semantics)."""
    out_dtype = out_dtype or qt.orig_dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape((-1, k)) if lead else x.reshape((1, k))
    out = int8_matmul(x2, qt.q.T if qt.out_major else qt.q, qt.scale,
                      out_dtype=out_dtype)
    n = out.shape[-1]
    return out.reshape((*lead, n)) if lead else out.reshape((n,))
