"""Pallas TPU kernel: one decode step of the gated delta rule, every slot's
matrix state read ONCE and written ONCE.

``GatedDeltaNet`` (models/transformer.py) keeps a float32 matrix S [dk, dv] a
value head a sequence and, a decode step, a sequence:

    S <- e^g S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

As XLA ops the step is two passes over S (the two reductions S^T k and S^T q
in one fusion, the update in another: S read twice and written once), 5.8 ms a
step of 64 slots x 9 layers x 2.1 MB where the bytes of one read and one write
are 2.95 ms (v5e, PR 38: ``gdn_state_roofline`` 51 %). Here a grid step holds a
block of heads' S in VMEM, computes both reductions, the correction and the
output from it and writes the updated block back into the SAME buffer
(``input_output_aliases``): the state's bytes once each way.

All of it is VPU work on [dk, dv] tiles (S is float32 and stays float32; an
MXU pass would load a 128 x 128 block of S as weights to multiply ONE row by
it): k and q arrive as COLUMNS ([dk, heads of the block], made outside, a
megabyte) and are broadcast along the lanes, v / d / o are rows.

Numerics are the expression's but for the order of the sums over dk.
tests/test_gated_delta.py holds it to ``gated_delta_rule`` (s = 1) under the
Pallas interpreter; a program lowered for a TPU compiles it (Mosaic raises what
it refuses).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

# the name the device trace shows for the kernel
KERNEL_NAME = "gated_delta_step"
# one block of S (heads x [dk, dv] float32) is at most this: in and out, each
# double-buffered, are four of them in VMEM
STATE_BLOCK_BYTES = 1 << 20


class Plan(NamedTuple):
    heads: int      # value heads a grid step holds


def plan(heads: int, dk: int, dv: int) -> Optional[Plan]:
    """How many heads' S a grid step holds, from static shapes; None for a
    shape the kernel does not take: [dk, dv] that is not whole (8, 128)
    float32 tiles with dk a whole lane tile too (k and q are broadcast from
    its columns)."""
    if dk % 128 or dv % 128 or heads < 1:
        return None
    fit = max(1, STATE_BLOCK_BYTES // (dk * dv * 4))
    # the rows' block [4 * block, dv] wants whole sublane tiles (or all the heads)
    blocks = [h for h in range(1, min(heads, fit) + 1)
              if heads % h == 0 and (h % 8 == 0 or h == heads)]
    return Plan(max(blocks)) if blocks else None


def _kernel(hb: int, cols_ref, rows_ref, s_ref, o_ref, s_out_ref):
    import jax.numpy as jnp

    dk, dv = s_ref.shape[-2:]
    cols = cols_ref[0, 0]                      # [dk, 2 hb]: k's columns, then q's
    for i in range(hb):                        # static: the heads of this block
        k = jnp.broadcast_to(cols[:, i:i + 1], (dk, dv))
        q = jnp.broadcast_to(cols[:, hb + i:hb + i + 1], (dk, dv))
        v = rows_ref[0, 0, i:i + 1, :]                             # [1, dv] rows
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
    float32 (one row a sequence; g the LOG of the decay), ``state``
    [b, H, dk, dv] float32, ``starts`` [b] bool (a sequence whose S reads as
    zeros) -> (o [b, H, dv], new state), the state updated in its own buffer.
    ``walk`` = ``plan(H, dk, dv)``. ``interpret=None`` compiles the kernel on a
    TPU and interprets it on any other backend; pass a bool to force either."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops import pallas_interpret_default

    b, H, dk = k.shape
    dv = v.shape[-1]
    hb = walk.heads
    G = H // hb
    if interpret is None:
        interpret = pallas_interpret_default()

    def columns(x):     # [b, H, dk] -> [b, G, dk, hb]
        return jnp.swapaxes(x.reshape(b, G, hb, dk), 2, 3)

    def rows(x):        # [b, H] -> [b, G, hb, dv], the value in every lane
        return jnp.broadcast_to(x.reshape(b, G, hb, 1), (b, G, hb, dv))

    f32 = jnp.float32
    cols = jnp.concatenate([columns(k.astype(f32)), columns(q.astype(f32))], axis=-1)
    keep = jnp.broadcast_to(~starts[:, None], (b, H)).astype(f32)
    packed = jnp.concatenate(
        [v.astype(f32).reshape(b, G, hb, dv), rows(jnp.exp(g.astype(f32))),
         rows(beta.astype(f32)), rows(keep)], axis=2)                  # [b, G, 4 hb, dv]
    block = hb * dk * dv * 4
    o, new_state = pl.pallas_call(
        functools.partial(_kernel, hb),
        out_shape=(jax.ShapeDtypeStruct((b, G, hb, dv), f32),
                   jax.ShapeDtypeStruct((b, G, hb, dk, dv), f32)),
        grid=(b, G),
        in_specs=[pl.BlockSpec((1, 1, dk, 2 * hb), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, 4 * hb, dv), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, hb, dk, dv), lambda i, j: (i, j, 0, 0, 0))],
        out_specs=(pl.BlockSpec((1, 1, hb, dv), lambda i, j: (i, j, 0, 0)),
                   pl.BlockSpec((1, 1, hb, dk, dv), lambda i, j: (i, j, 0, 0, 0))),
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(4 * block + (16 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=b * H * 8 * dk * dv, transcendentals=0,
            bytes_accessed=2 * b * H * dk * dv * 4),
        interpret=interpret,
        name=KERNEL_NAME,
    )(cols, packed, state.reshape(b, G, hb, dk, dv))
    return o.reshape(b, H, dv), new_state.reshape(b, H, dk, dv)


__all__ = ["KERNEL_NAME", "Plan", "gated_delta_step", "plan"]
