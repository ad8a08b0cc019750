"""Pallas TPU kernel: one decode step of the gated delta rule, every slot's
matrix state read ONCE and written ONCE.

``GatedDeltaNet`` (models/state_mixers.py) keeps a float32 matrix S [dk, dv] a
value head a sequence and, a decode step, a sequence:

    S <- e^g S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

As XLA ops the step is two passes over S (the two reductions S^T k and S^T q
in one fusion, the update in another: S read twice and written once), 5.8 ms a
step of 64 slots x 9 layers x 2.1 MB where the bytes of one read and one write
are 2.95 ms (v5e, PR 38: ``gdn_state_roofline`` 51 %). Here a grid step holds a
block of heads' S in VMEM, computes both reductions, the correction and the
output from it and writes the updated block back into the SAME buffer
(``input_output_aliases``): the state's bytes once each way.

All of it is VPU work on [dk, lanes] tiles (S is float32 and stays float32; an
MXU pass would load a 128 x 128 block of S as weights to multiply ONE row by
it): k and q arrive as COLUMNS ([dk, heads of the block], made outside, a
megabyte) and are broadcast along the lanes, v / d / o are rows.

THE STATE'S LAYOUT is the cache's (``pack_state`` below): where a
head's dv is no whole number of 128-lane tiles, ``heads_a_lane_row`` heads lie
SIDE BY SIDE along the lanes, [slots, H / side, dk, side * dv], so that the
array holds no padded lane in HBM (Olmo-Hybrid's 30 heads of [96, 192]: 15
units of [96, 384], twelve sublane tiles by three lane tiles; held [30, 96,
192] the chip would tile 192 lanes to 256, a third more bytes every step). A
unit's k and q are then its heads' columns, each spread over its own dv lanes
(a select on the lane index); everything else is the same rows and tiles. At
side = 1 (Qwen3-Next's [128, 128]) the kernel is what it was.

BYTES a step moves a slot a layer at Olmo-Hybrid's (30, 96, 192), five units
a grid step (three grid steps a slot): S once each way 2 x 30 x 96 x 192 x 4 =
4,423,680 B, which is the model's own (no padding); the columns [96, 20] as the
chip tiles them (20 lanes to 128) 3 x 49,152 B; the rows [20, 384] (to 24
sublanes) 3 x 36,864 B; o 3 x [5 -> 8, 384] x 4 = 36,864 B: 4.72 MB, 1.07 x
the state's bytes (Qwen3-Next's (32, 128, 128): 4.19 MB of S, 4.42 MB in all).

Numerics are the expression's but for the order of the sums over dk.
tests/test_gated_delta.py holds it to ``gated_delta_rule`` (s = 1) under the
Pallas interpreter; a program lowered for a TPU compiles it (Mosaic raises what
it refuses).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

# the name the device trace shows for the kernel
KERNEL_NAME = "gated_delta_step"
# one block of S (heads x [dk, dv] float32) is at most this: in and out, each
# double-buffered, are four of them in VMEM
STATE_BLOCK_BYTES = 1 << 20


class Plan(NamedTuple):
    heads: int      # value heads a grid step holds
    side: int = 1   # of which this many lie side by side along the lanes (a unit)


def heads_a_lane_row(heads: int, dv: int) -> int:
    """How many heads' [dk, dv] lie side by side along the lanes of the state
    array: the fewest that make whole 128-lane tiles, 1 where dv is whole tiles
    already or the heads do not divide into such rows (the array is then held a
    head a row, padded as the chip tiles it)."""
    side = 128 // math.gcd(dv, 128)
    return side if heads % side == 0 else 1


def pack_state(S, side: int):
    """A layer's matrix state as the cache holds it: [b, H, dk, dv] ->
    [b, H / side, dk, side * dv], ``side`` heads side by side along the lanes
    (``heads_a_lane_row``), so that a head whose dv is no whole 128-lane tile
    leaves no padded lane in HBM. At side 1 the array as it is."""
    if side == 1:
        return S
    b, H, dk, dv = S.shape
    return S.reshape(b, H // side, side, dk, dv).swapaxes(2, 3).reshape(
        b, H // side, dk, side * dv)


def unpack_state(S, side: int):
    """``pack_state``'s inverse: [b, H / side, dk, side * dv] -> [b, H, dk, dv]."""
    if side == 1:
        return S
    b, units, dk, lanes = S.shape
    return S.reshape(b, units, dk, side, lanes // side).swapaxes(2, 3).reshape(
        b, units * side, dk, lanes // side)


def plan(heads: int, dk: int, dv: int) -> Optional[Plan]:
    """How many heads' S a grid step holds, from static shapes; None for a
    shape the kernel does not take: a unit (``heads_a_lane_row`` heads side by
    side) [dk, side * dv] that is not whole (8, 128) float32 tiles."""
    if heads < 1:
        return None
    side = heads_a_lane_row(heads, dv)
    if dk % 8 or (side * dv) % 128:
        return None
    units = heads // side
    fit = max(1, STATE_BLOCK_BYTES // (dk * side * dv * 4))
    blocks = [u for u in range(1, min(units, fit) + 1) if units % u == 0]
    # the rows' block [4 * units, lanes] in whole sublane tiles (or all the
    # units) where the heads allow it; else the most units that divide them
    whole = [u for u in blocks if u % 8 == 0 or u == units]
    return Plan(max(whole or blocks) * side, side)


def _kernel(hb: int, side: int, cols_ref, rows_ref, s_ref, o_ref, s_out_ref):
    import jax
    import jax.numpy as jnp

    dk, lanes = s_ref.shape[-2:]
    dv = lanes // side
    cols = cols_ref[0, 0]                      # [dk, 2 hb side]: k's columns, then q's
    if side > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, lanes), 1)

    def spread(first):     # a unit's columns, each over its own head's dv lanes
        out = jnp.broadcast_to(cols[:, first + side - 1:first + side], (dk, lanes))
        for p in range(side - 2, -1, -1):
            out = jnp.where(lane < (p + 1) * dv,
                            jnp.broadcast_to(cols[:, first + p:first + p + 1], (dk, lanes)), out)
        return out

    for i in range(hb):                        # static: the units of this block
        k, q = spread(i * side), spread((hb + i) * side)
        v = rows_ref[0, 0, i:i + 1, :]                             # [1, lanes] rows
        decay = rows_ref[0, 0, hb + i:hb + i + 1, :]
        beta = rows_ref[0, 0, 2 * hb + i:2 * hb + i + 1, :]
        keep = rows_ref[0, 0, 3 * hb + i:3 * hb + i + 1, :]
        S = jnp.where(keep > 0.5, s_ref[0, 0, i], 0.0)             # a sequence that starts has no past
        sk = jnp.sum(S * k, axis=0, keepdims=True)
        sq = jnp.sum(S * q, axis=0, keepdims=True)
        kq = jnp.sum(k * q, axis=0, keepdims=True)                 # k . q in every lane
        d = beta * (v - decay * sk)
        s_out_ref[0, 0, i] = S * decay + k * d
        o_ref[0, 0, i:i + 1, :] = decay * sq + kq * d


def gated_delta_step(q, k, v, g, beta, state, starts, walk: Plan,
                     interpret: bool | None = None):
    """``q`` / ``k`` [b, H, dk], ``v`` [b, H, dv], ``g`` / ``beta`` [b, H]
    float32 (one row a sequence; g the LOG of the decay), ``state`` float32 in
    the cache's layout [b, H / side, dk, side * dv] (``walk.side`` heads side by
    side along the lanes; [b, H, dk, dv] at side 1), ``starts`` [b] bool (a
    sequence whose S reads as zeros) -> (o [b, H, dv], new state in the same
    layout), the state updated in its own buffer. ``walk`` = ``plan(H, dk, dv)``.
    ``interpret=None`` compiles the kernel on a TPU and interprets it on any
    other backend; pass a bool to force either."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops import pallas_interpret_default

    b, H, dk = k.shape
    dv = v.shape[-1]
    side = walk.side
    hb, lanes = walk.heads // side, side * dv       # units a grid step, a unit's lanes
    G = H // walk.heads
    if interpret is None:
        interpret = pallas_interpret_default()

    def columns(x):     # [b, H, dk] -> [b, G, dk, hb side]
        return jnp.swapaxes(x.reshape(b, G, hb * side, dk), 2, 3)

    def rows(x):        # [b, H] -> [b, G, hb, lanes], a head's value in each of its lanes
        return jnp.broadcast_to(x.reshape(b, G, hb, side, 1),
                                (b, G, hb, side, dv)).reshape(b, G, hb, lanes)

    f32 = jnp.float32
    cols = jnp.concatenate([columns(k.astype(f32)), columns(q.astype(f32))], axis=-1)
    keep = jnp.broadcast_to(~starts[:, None], (b, H)).astype(f32)
    packed = jnp.concatenate(
        [v.astype(f32).reshape(b, G, hb, lanes), rows(jnp.exp(g.astype(f32))),
         rows(beta.astype(f32)), rows(keep)], axis=2)                  # [b, G, 4 hb, lanes]
    block = hb * dk * lanes * 4
    o, new_state = pl.pallas_call(
        functools.partial(_kernel, hb, side),
        out_shape=(jax.ShapeDtypeStruct((b, G, hb, lanes), f32),
                   jax.ShapeDtypeStruct((b, G, hb, dk, lanes), f32)),
        grid=(b, G),
        in_specs=[pl.BlockSpec((1, 1, dk, 2 * hb * side), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, 4 * hb, lanes), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, hb, dk, lanes), lambda i, j: (i, j, 0, 0, 0))],
        out_specs=(pl.BlockSpec((1, 1, hb, lanes), lambda i, j: (i, j, 0, 0)),
                   pl.BlockSpec((1, 1, hb, dk, lanes), lambda i, j: (i, j, 0, 0, 0))),
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(4 * block + (16 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=b * H * 8 * dk * dv, transcendentals=0,
            bytes_accessed=2 * b * H * dk * dv * 4),
        interpret=interpret,
        name=KERNEL_NAME,
    )(cols, packed, state.reshape(b, G, hb, dk, lanes))
    return o.reshape(b, H, dv), new_state.reshape(b, H // side, dk, lanes)


__all__ = ["KERNEL_NAME", "Plan", "gated_delta_step", "heads_a_lane_row", "pack_state", "plan",
           "unpack_state"]
