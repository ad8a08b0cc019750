"""Pallas-fused ResNet identity-residual chains for TPU serving.

Why this kernel exists: the single-chip ResNet-50 serving profile of the
earlier harness attributed ~79% of leaf device time to
*elementwise* fusion clusters rooted at residual-add/relu over the 56x56
activations — XLA on this backend leaves each relu / residual-add as its own
HBM round trip instead of folding it into the conv epilogues. An identity
bottleneck block (1x1 -> relu -> 3x3 -> relu -> 1x1 -> +residual -> relu)
over a (56, 56, 256) activation streams the ~1.6 MB/image input tensor many
times in that regime. This kernel computes the ENTIRE block — and optionally
a chain of consecutive identity blocks — per batch image inside VMEM: one
HBM read of x, one HBM write of the result, weights resident.

Shapes follow the folded-BN inference model (`models/resnet.py`,
``fold_batchnorm``): convs carry biases, BN is gone. Only *identity* blocks
(residual.shape == output.shape, stride 1) qualify; the strided/projection
block that opens each stage stays on XLA.

The 3x3 conv is expressed MXU-natively as 9 shifted (H*W, F) @ (F, F)
matmuls over the flattened spatial dim. Vertical out-of-range taps land in
an explicit zero-pad region of the flattened buffer; horizontal wraps (row
h, col 55 shifted +1 would alias row h+1, col 0) are killed by a per-shift
column mask — bit-equivalent to SAME zero padding.

Reference parity target: torch/CUDA frameworks hand-fuse these chains the
same way (reference seldon-core has no kernel tier at all — its model
runtimes inherit cuDNN fusion); here the fusion is explicit because the
measured XLA schedule leaves the bandwidth on the table.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_param_list(blocks: Sequence[dict]) -> list:
    """Flatten per-block folded params into the kernel's operand order.

    Each block contributes (w1, b1, w2, b2, w3, b3) with shapes
    w1 (C, F), b1 (F,), w2 (3, 3, F, F), b2 (F,), w3 (F, C), b3 (C,).
    w2 is flattened to (9F, F) — the im2col operand, tap-major to match the
    kernel's tap concatenation order; biases to (1, n) for 2D layout.
    """
    out = []
    for blk in blocks:
        f = blk["w1"].shape[1]
        c = blk["w1"].shape[0]
        if blk["w2"].shape[:2] != (3, 3):
            raise ValueError(f"3x3 conv expected, got {blk['w2'].shape}")
        out.extend(
            [
                blk["w1"],
                blk["b1"].reshape(1, f),
                blk["w2"].reshape(9 * f, f),
                blk["b2"].reshape(1, f),
                blk["w3"],
                blk["b3"].reshape(1, c),
            ]
        )
    return out


def _chunking(hw: int) -> tuple:
    """(n_chunks, rows-per-chunk) for the in-kernel matmul row chunking."""
    n_chunks = max(1, hw // 1024)
    while hw % n_chunks:
        n_chunks -= 1
    return n_chunks, hw // n_chunks


def _chain_kernel(h: int, w: int, n_blocks: int, *refs):
    """One grid program = `group` batch images through `n_blocks` identity
    blocks. The images are stacked along the flattened row axis; per-shift
    row/col masks stop 3x3 taps from bleeding across image seams or
    wrapping around row ends (bit-equivalent to SAME zero padding).

    refs layout: x_ref, (w1, b1, w2, b2, w3, b3) * n_blocks, out_ref,
    im2col scratch (rows, 9F). x_ref/out_ref block shape:
    (1, group*H*W, C); h/w are PER-IMAGE dims.
    """
    x_ref = refs[0]
    out_ref = refs[-2]
    im2col_ref = refs[-1]

    x = x_ref[0]  # (group*HW, C) bf16
    hw = x.shape[0]
    dtype = x.dtype

    # Validity masks per tap offset: the tap for OUTPUT position (row, col)
    # reads flat index + dh*w + dw, which aliases a wrong row (horizontal
    # wrap) or a neighboring image (vertical seam) unless row+dh and col+dw
    # are in-bounds for THIS image. With one image per program (hw == h*w)
    # vertical out-of-range taps land in the explicit zero padding, so row
    # masks are only needed for multi-image seams.
    flat = jax.lax.broadcasted_iota(jnp.int32, (hw, 1), 0)
    col = flat % w
    col_ok = {-1: col >= 1, 0: None, 1: col <= w - 2}
    if hw == h * w:
        row_ok = {-1: None, 0: None, 1: None}
    else:
        row = (flat // w) % h
        row_ok = {-1: row >= 1, 0: None, 1: row <= h - 2}

    def tap_mask(dh, dw):
        ok = None
        for part in (row_ok[dh], col_ok[dw]):
            if part is not None:
                ok = part if ok is None else jnp.logical_and(ok, part)
        return None if ok is None else ok.astype(dtype)

    # Row-chunked matmuls: a full (HW, C) f32 intermediate is 3.2 MB at
    # 3136x256 and the un-chunked kernel blows the 16 MB scoped-VMEM stack
    # (measured: 19.02M). Chunking the 1x1 dots and casting to bf16 eagerly
    # keeps live f32 transients to one chunk.
    n_chunks, rows = _chunking(hw)

    def chunked_matmul_bf16(a, w_ref, b_ref, relu, extra=None):
        """relu(a @ w + b [+ extra]) computed per row-chunk, bf16 out."""
        outs = []
        for ci in range(n_chunks):
            part = jnp.dot(
                a[ci * rows:(ci + 1) * rows, :], w_ref[:],
                preferred_element_type=jnp.float32,
            )
            part = (part + b_ref[:]).astype(dtype)
            if extra is not None:
                part = part + extra[ci * rows:(ci + 1) * rows, :]
            if relu:
                part = jnp.maximum(part, 0.0)
            outs.append(part)
        return outs[0] if n_chunks == 1 else jnp.concatenate(outs, axis=0)

    for i in range(n_blocks):
        w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref = refs[1 + 6 * i : 7 + 6 * i]

        # --- 1x1 reduce: (HW, C) @ (C, F) -> relu -> bf16
        y1 = chunked_matmul_bf16(x, w1_ref, b1_ref, relu=True)  # (HW, F)

        # --- 3x3 conv in im2col form, row-chunked: the 9 taps concatenate
        # along lanes into (rows, 9F) and ONE (rows, 9F) @ (9F, F) matmul
        # replaces 9 skinny K=F matmuls — at F=64 the skinny form fills only
        # a quarter of the 128x128 MXU (K=64, N=64) while im2col's K=9F
        # streams full K tiles (measured: the 9-tap form lost 23% vs XLA on
        # the 56x56 chain; see benchmarks/MFU_NOTES.md round-5 log). The
        # kernel operand is reshaped to (9F, F) outside the kernel. Zero
        # rows above/below keep the shifted slices in bounds; the masks
        # above supply the actual SAME-padding semantics.
        f = y1.shape[1]
        y1p = jnp.concatenate(
            [jnp.zeros((w + 1, f), dtype), y1, jnp.zeros((w + 1, f), dtype)], axis=0
        )
        w2flat = w2_ref[:]  # (9F, F), pre-flattened tap-major
        y2_parts = []
        for ci in range(n_chunks):
            # Stage taps through the im2col scratch ref: a vector concat of
            # differently-shifted slices is unsupported (Mosaic: "offset
            # mismatch on non-concat dimension"); stores normalize layout.
            for dh in (-1, 0, 1):
                for dw in (-1, 0, 1):
                    shift = dh * w + dw
                    lo = w + 1 + shift + ci * rows  # static: lowers as
                    tap = y1p[lo:lo + rows, :]  # lax.slice (dynamic_slice
                    # has no Pallas TPU lowering)
                    m = tap_mask(dh, dw)
                    if m is not None:
                        tap = tap * m[ci * rows:(ci + 1) * rows, :]
                    k = 3 * (dh + 1) + (dw + 1)
                    im2col_ref[:, k * f:(k + 1) * f] = tap
            acc = jnp.dot(
                im2col_ref[:], w2flat,
                preferred_element_type=jnp.float32,
            )
            y2_parts.append(
                jnp.maximum(acc + b2_ref[:], 0.0).astype(dtype)
            )
        y2 = y2_parts[0] if n_chunks == 1 else jnp.concatenate(y2_parts, axis=0)

        # --- 1x1 expand + residual + relu (residual add in bf16, matching
        # the folded flax graph's dtype chain)
        x = chunked_matmul_bf16(y2, w3_ref, b3_ref, relu=False, extra=x)
        x = jnp.maximum(x, 0.0)

    out_ref[0] = x


def fused_identity_chain(
    x: jax.Array,
    blocks: Sequence[dict],
    *,
    group: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Run consecutive folded-BN identity bottleneck blocks as ONE Pallas
    kernel: per batch image, one HBM read of x and one HBM write of the
    final activation; every intermediate lives in VMEM.

    x: (B, H, W, C) activations (bf16 recommended).
    blocks: per-block folded params, dicts with w1 (C,F), b1, w2 (3,3,F,F),
        b2, w3 (F,C), b3 — see fold_batchnorm (models/resnet.py).
    group: batch images per grid program (raise for small spatial dims so
        the matmul M stays MXU-sized; B % group must be 0).
    """
    b, h, w, c = x.shape
    if b % group:
        raise ValueError(f"batch {b} not divisible by group {group}")
    params = _block_param_list(blocks)
    n_blocks = len(blocks)

    x2d = x.reshape(b // group, group * h * w, c)
    grid = (b // group,)
    data_spec = pl.BlockSpec(
        (1, group * h * w, c), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    w_specs = [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in params]

    # Cost estimate: per image per block, 2*HW*C*F (x2) + 2*HW*9*F*F flops;
    # bytes ~= one read + one write of (HW, C) per chain end-to-end.
    f = blocks[0]["w1"].shape[1]
    flops = 2 * b * h * w * (2 * c * f + 9 * f * f) * n_blocks
    bytes_accessed = 2 * b * h * w * c * x.dtype.itemsize

    # Multi-block chains keep each block's transients live on the Mosaic
    # stack (measured: ~8M/block at 56x56x256, vs the 16M default scoped
    # limit); the chip accepts far larger scoped VMEM (the r4 flag sweep ran
    # XLA at a 128 MiB scoped limit), so raise the cap with the chain depth.
    compiler_params = None
    if not interpret and n_blocks > 1:
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=min(128, 16 + 10 * n_blocks) * 1024 * 1024
        )
    _, chunk_rows = _chunking(group * h * w)
    out = pl.pallas_call(
        partial(_chain_kernel, h, w, n_blocks),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x.dtype),
        grid=grid,
        in_specs=[data_spec] + w_specs,
        out_specs=data_spec,
        scratch_shapes=[pltpu.VMEM((chunk_rows, 9 * f), x.dtype)],
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=bytes_accessed, transcendentals=0
        ),
        compiler_params=compiler_params,
        interpret=interpret,
    )(x2d, *params)
    return out.reshape(b, h, w, c)


def identity_chain_ref(x: jax.Array, blocks: Sequence[dict]) -> jax.Array:
    """Pure-XLA reference for the fused chain (same numerics contract:
    f32 matmul accumulation, bf16 handoffs, SAME-padded 3x3)."""
    dtype = x.dtype
    for blk in blocks:
        y = jnp.maximum(
            jnp.einsum("bhwc,cf->bhwf", x, blk["w1"],
                       preferred_element_type=jnp.float32)
            + blk["b1"],
            0.0,
        ).astype(dtype)
        y = jnp.maximum(
            jax.lax.conv_general_dilated(
                y.astype(dtype),
                blk["w2"].astype(dtype),
                (1, 1),
                ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32,
            )
            + blk["b2"],
            0.0,
        ).astype(dtype)
        y = (
            jnp.einsum("bhwf,fc->bhwc", y, blk["w3"],
                       preferred_element_type=jnp.float32)
            + blk["b3"]
        ).astype(dtype)
        x = jnp.maximum(x + y, 0.0)
    return x


def _is_identity_block(scope: dict) -> bool:
    return "conv_proj" not in scope


def folded_block_params(scope: dict) -> dict:
    """Map one folded BottleneckBlock_* param scope to the kernel's dict."""
    return {
        "w1": scope["Conv_0"]["kernel"].reshape(
            scope["Conv_0"]["kernel"].shape[-2:]
        ),
        "b1": scope["Conv_0"]["bias"],
        "w2": scope["Conv_1"]["kernel"],
        "b2": scope["Conv_1"]["bias"],
        "w3": scope["Conv_2"]["kernel"].reshape(
            scope["Conv_2"]["kernel"].shape[-2:]
        ),
        "b3": scope["Conv_2"]["bias"],
    }


__all__ = [
    "fused_identity_chain",
    "identity_chain_ref",
    "folded_block_params",
]
