"""Dedicated ResNet serving forward over folded-BN params.

`resnet_serve_forward` is a pure function over the param dict produced by
``fold_batchnorm`` (models/resnet.py) — no flax module tracing on the hot
path — with an optional Pallas tier: consecutive *identity* bottleneck
blocks (the 12 of 16 blocks in ResNet-50 with no projection/stride) run as
single fused kernels (`ops/fused_resnet.fused_identity_chain`), one HBM
read + one write per chain instead of XLA's per-op elementwise round trips
(the earlier harness's profile attributed ~79% of device time there).

Numerics match the ``fused=True`` flax module: bf16 conv compute, bf16 bias
adds, f32 head. Parity-tested against ``model.apply`` in
tests/test_fused_resnet.py.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops.fused_resnet import (
    _is_identity_block,
    folded_block_params,
    fused_identity_chain,
)

# Preferred images-per-program by spatial size: keeps the fused kernel's
# matmul M dimension MXU-sized as the activations shrink, while the
# per-program VMEM footprint stays ~1.6 MB (56x56x256 ~= 2x 28x28x512 ...).
_PREFERRED_GROUP = {56: 1, 28: 2, 14: 4, 7: 8}


def _largest_group(batch: int, preferred: int) -> int:
    g = min(preferred, batch)
    while batch % g:
        g -= 1
    return g


def _conv(x, kernel, bias, strides=(1, 1), padding=((0, 0), (0, 0))):
    dtype = x.dtype
    y = jax.lax.conv_general_dilated(
        x,
        kernel.astype(dtype),
        strides,
        padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + bias.astype(dtype)


def _bottleneck(x, scope, strides):
    y = jnp.maximum(_conv(x, scope["Conv_0"]["kernel"], scope["Conv_0"]["bias"]), 0)
    y = jnp.maximum(
        _conv(y, scope["Conv_1"]["kernel"], scope["Conv_1"]["bias"], strides,
              ((1, 1), (1, 1))),
        0,
    )
    y = _conv(y, scope["Conv_2"]["kernel"], scope["Conv_2"]["bias"])
    residual = x
    if "conv_proj" in scope:
        residual = _conv(x, scope["conv_proj"]["kernel"], scope["conv_proj"]["bias"],
                         strides)
    return jnp.maximum(residual + y, 0)


def resnet_serve_forward(
    variables: dict,
    x: jax.Array,
    *,
    stage_sizes: Sequence[int] = (3, 4, 6, 3),
    dtype=jnp.bfloat16,
    pallas_stages: Sequence[int] = (),
    interpret: bool = False,
) -> jax.Array:
    """Forward pass over ``fold_batchnorm`` params (ResNet-50 default).

    pallas_stages: stage indices (0-based) whose identity blocks run as
    fused Pallas chains; () reproduces the pure-XLA folded graph.
    """
    params = variables["params"]
    x = x.astype(dtype)
    x = _conv(x, params["conv_init"]["kernel"], params["conv_init"]["bias"],
              (2, 2), ((3, 3), (3, 3)))
    x = jnp.maximum(x, 0)
    x = jax.lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(dtype, jnp.floating) else 0,
        jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )

    block_idx = 0
    for i, n_blocks in enumerate(stage_sizes):
        scopes = [params[f"BottleneckBlock_{block_idx + j}"] for j in range(n_blocks)]
        block_idx += n_blocks
        # Opening block always projects (channel widening; stride 2 for i>0).
        x = _bottleneck(x, scopes[0], (2, 2) if i > 0 else (1, 1))
        identity = scopes[1:]
        if i in pallas_stages and identity:
            if not all(_is_identity_block(s) for s in identity):
                raise ValueError(
                    f"stage {i}: pallas_stages requires projection-free "
                    "non-opening blocks; a conv_proj would be silently "
                    "dropped by the fused kernel"
                )
            group = _largest_group(x.shape[0], _PREFERRED_GROUP.get(x.shape[1], 1))
            x = fused_identity_chain(
                x, [folded_block_params(s) for s in identity], group=group,
                interpret=interpret,
            )
        else:
            for scope in identity:
                x = _bottleneck(x, scope, (1, 1))

    x = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    return x.astype(jnp.float32) @ head["kernel"].astype(jnp.float32) + head[
        "bias"
    ].astype(jnp.float32)


__all__ = ["resnet_serve_forward"]
