"""Parameter leaves and the norms: what transformer.py and state_mixers.py both
stand on, and which imports neither (``TransformerConfig`` is an annotation alone,
never evaluated: PEP 563): ``param_with_axes`` (how a module declares a leaf),
``RMSNorm`` / ``LayerNorm`` and the two functions that pick between them by
``cfg.norm``, the leaves that stay float32 in every tree (``FLOAT32_AXES``) and
the seeded init of the small ones by leaf name (``SMALL_LEAF_INIT``).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

param_with_axes = nn_partitioning.param_with_axes


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * weight).astype(x.dtype)


class RMSNorm(nn.Module):
    dim: int
    eps: float = 1e-5
    axis: str = "embed"

    @nn.compact
    def __call__(self, x=None):
        """x=None returns the bare weight (the same param path: ``normed_by``
        norms with it where no module may be made)."""
        w = param_with_axes("weight", nn.initializers.ones_init(), (self.dim,), jnp.float32, axes=(self.axis,))
        if x is None:
            return w
        return rms_norm(x, w, self.eps)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    norm = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (norm * weight + bias).astype(x.dtype)


class LayerNorm(nn.Module):
    """LayerNorm over the last axis: the mean taken out, a weight AND a bias
    (cfg.norm "layer": Phi-4-mini-flash). The bias is a float32 leaf of every
    tree (FLOAT32_AXES "norm_bias"), seeded normal(0, 0.02) and not zeros, so
    that a bias left out is seen."""

    dim: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x=None):
        """x=None returns the bare (weight, bias), as ``RMSNorm``'s."""
        w = param_with_axes("weight", nn.initializers.ones_init(), (self.dim,), jnp.float32,
                            axes=("embed",))
        bias = param_with_axes("bias", small_leaf_init("bias"), (self.dim,), jnp.float32,
                               axes=("norm_bias",))
        if x is None:
            return w, bias
        return layer_norm(x, w, bias, self.eps)


def block_norm(cfg: "TransformerConfig", name: str):
    """The norm of a block's sub-layer or the model's last: cfg.norm's."""
    if cfg.norm == "layer":
        return LayerNorm(cfg.dim, cfg.norm_eps, name=name)
    return RMSNorm(cfg.dim, cfg.norm_eps, name=name)


def normed_by(cfg: "TransformerConfig", weights, x: jnp.ndarray) -> jnp.ndarray:
    """``block_norm(cfg, name)(x)`` from the module's bare ``weights`` (its
    ``__call__()``): where no module may be made (a conditional's branch)."""
    if cfg.norm == "layer":
        return layer_norm(x, *weights, cfg.norm_eps)
    return rms_norm(x, weights, cfg.norm_eps)


# Leaves that stay float32 in every tree (never int8, never cast to the
# serving dtype): the stream mixing's maps, scalars and biases and the router's
# selection bias. They are told apart by a logical axis (FLOAT32_AXES:
# parallel/sharding.py ``float32_leaves``), and their seeded init is here, by
# leaf name, for the module's own init and for the server's streamed one:
# normal(mean, std); std None = 1 / sqrt(fan_in). The sizes are chosen to be
# VISIBLE in the logits (tests/test_reference_xing4.py's wrong references)
# and such that the configured 20 Sinkhorn iterations converge: H_pre spread
# over (0.25, 0.75), H_post over (0.5, 1.5), the residual matrix's logarithm
# a_res m_res + B_res of spread (0.7^2 + 0.5^2)^1/2 = 0.86 (a seeded doubly
# stochastic matrix far from both I and 1/n, whose rows sum to 1 within 2e-6
# after 20 iterations; at a spread of 1.4 three tokens in a hundred are still
# 1e-4 off), a selection bias about the spread of the top sigmoid scores.
# The short convolution's taps are normal(0, 1/sqrt(taps)), so that a conv
# layer's output has its input's size; a per-head q / k norm weight is ones.
# Gated DeltaNet's (Qwen3-Next's published init where it has one): the four
# taps a channel normal(0, 1/2) likewise, ``dt_bias`` ones, the gated norm's
# weight ones, ``A_log`` = log of uniform(0, 16) (LOG_UNIFORM: the pair is the
# range); the shared expert's scalar gate normal(0, 1/sqrt(dim)). The layer's
# own dt_bias (flash-linear-attention's GatedDeltaNet; ``cfg.linear_dt_bias``
# "range"): softplus^-1 of a step dt = exp(U(log 0.001, log 0.1)) (DT_RANGE).
# Mamba-2's (Mamba2Mixer; the layer's PUBLISHED initialisation, ``Mamba2``'s, not
# the installed modeling file's placeholders, whose dt_bias ones forgets within
# two tokens), the three values a head as the ONE leaf ``heads`` [3, heads]
# (SSD_HEADS): ``A_log`` = log(1 .. heads) (no draw), ``dt_bias`` by DT_RANGE over
# (0.001, 0.1) (the file's time_step_min / _max), ``D`` ones; the taps as Gated
# DeltaNet's and the convolution's bias normal(0, 1/2) likewise.
# Mamba-1's (Mamba1Mixer; ``Mamba``'s published initialisation): ``A_log_t`` =
# log(1 .. N) a channel (S6_A: no draw; held [N, channels], the state's layout),
# ``b_dt`` by DT_RANGE over (0.001, 0.1), ``D`` ones, the taps and the
# convolution's bias as Mamba-2's. Differential attention's four lambda vectors
# (``lambdas`` [4, head_dim]) normal(0, 0.1); the sub-norm's weight ones. A
# projection's or a LayerNorm's ``bias`` normal(0, 0.02), NOT zeros: a seeded
# model whose biases are zeros cannot show a bias that is left out.
FLOAT32_AXES = ("hc_maps", "expert_select", "conv_taps", "head_norm", "gdn_scalar", "expert_gate",
                "ssd_scalar", "norm_bias", "attn_bias")
LOG_UNIFORM = "log of uniform"
SSD_HEADS = "A_log, dt_bias and D a head, stacked"
S6_A = "log of 1 .. N, a channel"
DT_RANGE = "softplus inverse of a log-uniform step"
SMALL_LEAF_INIT = {
    "phi": (0.0, None), "alpha": (0.7, 0.05), "b_pre": (0.0, 0.5), "b_post": (0.0, 0.5),
    "b_res": (0.0, 0.5), "router_bias": (0.0, 0.1), "taps": (0.0, 3 ** -0.5),
    "weight": (1.0, 0.0), "conv1d": (0.0, 0.5), "dt_bias": (1.0, 0.0),
    "A_log": (LOG_UNIFORM, (0.0, 16.0)), "shared_gate": (0.0, None),
    "dt_bias_range": (DT_RANGE, (1e-3, 1e-1)),
    "heads": (SSD_HEADS, (1e-3, 1e-1)), "conv_bias": (0.0, 0.5),
    "A_log_t": (S6_A, None), "b_dt": (DT_RANGE, (1e-3, 1e-1)), "D": (1.0, 0.0),
    "lambdas": (0.0, 0.1), "bias": (0.0, 0.02),
}


def draw_small_leaf(name: str, key, shape) -> jnp.ndarray:
    mean, std = SMALL_LEAF_INIT[name]
    if mean == LOG_UNIFORM:
        low, high = std
        return jnp.log(jnp.maximum(
            jax.random.uniform(key, shape, jnp.float32, low, high), 1e-6))
    if mean == SSD_HEADS:
        heads = shape[1]
        return jnp.stack([jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                          draw_small_leaf("dt_bias_range", key, (heads,)),
                          jnp.ones((heads,), jnp.float32)])
    if mean == S6_A:
        states = shape[0]
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, states + 1, dtype=jnp.float32))[:, None], shape)
    if mean == DT_RANGE:
        low, high = std
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(low), math.log(high)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if std is None:
        std = float(shape[0]) ** -0.5
    return mean + std * jax.random.normal(key, shape, jnp.float32)


def small_leaf_init(name: str):
    return lambda key, shape, dtype=jnp.float32: draw_small_leaf(name, key, shape).astype(dtype)
