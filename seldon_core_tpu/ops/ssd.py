"""Mamba-2's selective state-space recurrence (SSD), and the Pallas TPU kernel
of its decode step: every slot's state read ONCE and written ONCE.

``Mamba2Mixer`` (models/state_mixers.py) keeps a float32 matrix h [P, N] a head
a sequence (P the head's width, N the state's: granite-4.0-h-micro's [64, 128],
64 heads) and, a token, a head:

    h <- e^(dt A) h + (dt x) B^T;    y = h C + D x

with dt > 0 a head a token, A < 0 a head, x [P] a head, B and C [N] a GROUP of
heads, D a head. No correction term, no key normalisation, no beta: another
rule than ops/gated_delta.py's, with another chunked form (a decay-masked
product; no triangular inverse).

``ssd`` is ONE function for every call shape: a decode step (s = 1), a prefill
chunk and the cache-less forward. float32 throughout.

THE CHUNK (s > 1): sub-chunks of SSD_CHUNK rows; inside one, with a_t = dt_t A,
G its running sum and L_ij = e^(G_i - G_j) for i >= j:

    Y   = (L o (C B^T)) (dt x) + (e^G C) h_0 + D x
    h_Q = e^(G_Q) h_0 + sum_j e^(G_Q - G_j) (dt_j x_j) B_j^T

and h goes on in float32 between sub-chunks. Differences of G are formed in
float32 BEFORE the exponential, masked before it too (a product of powers lost
every float32 digit under slow decays: PR 45). A row with dt = 0 (no token)
decays nothing and adds nothing.

THE STEP (s = 1) is bound by h's bytes (2 MB a slot a layer). As XLA ops it is
the update in one fusion and the reduction h C in another: h read twice and
written once. The kernel here holds a block of heads' h in VMEM, updates it,
reduces the UPDATED block against C and writes it back into the SAME buffer
(``input_output_aliases``): the state's bytes once each way.

THE STATE'S LAYOUT is the cache's (ops/gated_delta.py ``pack_state``, the delta
rule's): a head's h is held TRANSPOSED, [N, P], and ``heads_a_lane_row`` heads
lie SIDE BY SIDE along the lanes, [slots, H / side, N, side * P] (granite's 64
heads of [64, 128]: 32 units of [128, 128], whole (8, 128) float32 tiles, the
model's own bytes). With N along the SUBLANES everything a token brings is
cheap VPU work on such tiles: B and C are COLUMNS of the block's group ([N, 2],
broadcast along the lanes ONCE a grid step), dt x and the decay are ROWS ([1,
side * P]: a head's values over its own P lanes, broadcast along the sublanes
as a row is read), and y = h C is a sum over SUBLANES (adds across registers),
stored as a row. (Held [P, N] a head, which the first kernel of this PR did,
the column dt x needs a lane broadcast a register and h C a lane reduction a
register, and y a masked one-lane store: the kernel then ran at 55 % of the
HBM's rate, 34.8 ms of a 96-slot step's 36 layers where the bytes are 17.7:
PERF.md section 6, PR 53.)

BYTES a step moves a slot a layer at (64 heads, 64, 128), 16 units a grid step
(two grid steps a slot): h once each way 2 x 64 x 64 x 128 x 4 = 4,194,304 B;
the columns [128, 2] (2 lanes tiled to 128) 2 x 65,536 B; the rows [32, 128] 2
x 16,384 B; y [16, 128] 2 x 8,192 B: 4.37 MB, 1.04 x the state's.

Numerics are the expression's but for the order of the sum over N.
tests/test_ssd.py holds the kernel to the expression under the Pallas
interpreter and the chunked form to the recurrence row by row; a program
lowered for a TPU compiles it (Mosaic raises what it refuses).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from seldon_core_tpu.ops.gated_delta import heads_a_lane_row, pack_state, unpack_state

# the name the device trace shows for the kernel
KERNEL_NAME = "ssd_step"
# one block of h (units x [N, side * P] float32) is at most this: in and out,
# each double-buffered, are four of them in VMEM
STATE_BLOCK_BYTES = 1 << 20
SSD_CHUNK = 128   # rows of a sub-chunk of the chunked form


class Plan(NamedTuple):
    heads: int      # heads a grid step holds
    side: int = 1   # of which this many lie side by side along the lanes (a unit)


def plan(heads: int, groups: int, p: int, n: int) -> Optional[Plan]:
    """How many heads' h a grid step of the step kernel holds, from static
    shapes; None for a shape the kernel does not take: a unit
    (``heads_a_lane_row`` heads side by side) [N, side * P] that is not whole
    (8, 128) float32 tiles. A block lies within ONE group of heads (its B and C
    are one pair of columns)."""
    if heads < 1 or groups < 1 or heads % groups:
        return None
    side = heads_a_lane_row(heads, p)
    a_group = heads // groups
    if n % 8 or (side * p) % 128 or a_group % side:
        return None
    units = a_group // side
    fit = max(1, STATE_BLOCK_BYTES // (n * side * p * 4))
    blocks = [u for u in range(1, min(units, fit) + 1) if units % u == 0]
    # the rows' block [2 * units, lanes] in whole sublane tiles (or all the
    # group's units) where the heads allow it; else the most units that divide them
    whole = [u for u in blocks if u % 8 == 0 or u == units]
    return Plan(max(whole or blocks) * side, side)


def _kernel(units: int, cols_ref, rows_ref, h_ref, y_ref, h_out_ref):
    n, lanes = h_ref.shape[-2:]
    # B and C of the block's group, along the lanes: once a grid step
    b_cols = jnp.broadcast_to(cols_ref[0, 0, :, 0:1], (n, lanes))
    c_cols = jnp.broadcast_to(cols_ref[0, 0, :, 1:2], (n, lanes))
    for i in range(units):                     # static: the units of this block
        dtx = rows_ref[0, 0, i:i + 1, :]                                # [1, lanes] rows
        decay = rows_ref[0, 0, units + i:units + i + 1, :]
        # a decay of 0 is a sequence that starts: h reads as zeros whatever it holds
        h = jnp.where(decay > 0.0, h_ref[0, 0, i], 0.0) * decay + b_cols * dtx
        h_out_ref[0, 0, i] = h
        y_ref[0, 0, i:i + 1, :] = jnp.sum(h * c_cols, axis=0, keepdims=True)


def ssd_step(x, dt, A, B, C, state, starts, walk: Plan, interpret: bool | None = None):
    """``x`` [b, H, P], ``dt`` [b, H], ``A`` [H], ``B`` / ``C`` [b, G, N], all
    float32; ``state`` float32 in the cache's layout [b, H / side, N, side * P]
    (``walk.side`` heads' TRANSPOSED h side by side along the lanes); ``starts``
    [b] bool (a sequence whose h reads as zeros) -> (h' C [b, H, P], h' in the
    same layout), the state updated in its own buffer; the skip D x is the
    caller's. ``walk`` = ``plan(H, G, P, N)``. ``interpret=None`` compiles the
    kernel on a TPU and interprets it on any other backend; pass a bool to
    force either."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops import pallas_interpret_default

    b, H, P = x.shape
    G, N = B.shape[1:]
    side = walk.side
    units, lanes = walk.heads // side, side * P     # units a grid step, a unit's lanes
    blocks, blocks_a_group = H // walk.heads, H // G // walk.heads
    if interpret is None:
        interpret = pallas_interpret_default()
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.where(starts[:, None], 0.0, jnp.exp(dt * A.astype(f32)))          # [b, H]
    rows = jnp.concatenate(
        [(x.astype(f32) * dt[..., None]).reshape(b, blocks, units, lanes),
         jnp.broadcast_to(decay[..., None], (b, H, P)).reshape(b, blocks, units, lanes)],
        axis=2)                                                                   # [b, blocks, 2 units, lanes]
    cols = jnp.stack([B.astype(f32), C.astype(f32)], axis=-1)                     # [b, G, N, 2]
    block = units * N * lanes * 4
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, units),
        out_shape=(jax.ShapeDtypeStruct((b, blocks, units, lanes), f32),
                   jax.ShapeDtypeStruct((b, blocks, units, N, lanes), f32)),
        grid=(b, blocks),
        in_specs=[pl.BlockSpec((1, 1, N, 2), lambda i, j: (i, j // blocks_a_group, 0, 0)),
                  pl.BlockSpec((1, 1, 2 * units, lanes), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, 1, units, N, lanes), lambda i, j: (i, j, 0, 0, 0))],
        out_specs=(pl.BlockSpec((1, 1, units, lanes), lambda i, j: (i, j, 0, 0)),
                   pl.BlockSpec((1, 1, units, N, lanes), lambda i, j: (i, j, 0, 0, 0))),
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(4 * block + (16 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=b * H * 5 * P * N, transcendentals=0,
            bytes_accessed=2 * b * H * P * N * 4),
        interpret=interpret,
        name=KERNEL_NAME,
    )(cols, rows, state.reshape(b, blocks, units, N, lanes))
    return y.reshape(b, H, P), new_state.reshape(b, H // side, N, lanes)


@jax.jit
def ssd(x, dt, A, B, C, D, state, starts=None):
    """The recurrence over the rows of one call, and the state each sequence
    leaves. ``x`` [b, s, H, P]; ``dt`` [b, s, H] (the step, > 0; 0 for a row
    that is no token); ``A`` [H] (< 0); ``B`` / ``C`` [b, s, G, N] (G groups of
    H / G heads); ``D`` [H]; ``state`` float32, h before the call's first row
    in the CACHE's layout (a head's h transposed, [N, P], ``side`` heads side by
    side along the lanes: [b, H / side, N, side * P], ``side`` read off the
    array's own shape); ``starts`` [b] bool or None: the sequences whose h
    reads as ZEROS whatever ``state`` holds (a sequence that starts has no
    past). float32. Returns (y [b, s, H, P], h after the last row, laid out as
    it came).

    In a program LOWERED for a TPU the step is the kernel above, on the state AS
    IT LIES, chosen by ``jax.lax.platform_dependent`` as ``gated_delta_rule``
    chooses its own; the expression everywhere else and for a state that is not
    whole tiles (``plan``). The expression and the chunked form unpack the state (a chunk's
    one sequence: 2 MB a layer) and pack what they leave. A jitted function of
    its own, so a program's layers share ONE trace of it (a trace a layer of
    the kernel's body was 37 s of a cold start: PERF.md section 6, PR 53)."""
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    side = H // state.shape[1]
    f32 = jnp.float32
    x, dt, A, B, C, D, state = (v.astype(f32) for v in (x, dt, A, B, C, D, state))
    if starts is None:
        starts = jnp.zeros((b,), bool)
    skip = D[:, None] * x

    def apart():     # h^T a head, [b, G, R, N, P], zeros for a sequence that starts
        h = jnp.where(starts[:, None, None, None], 0.0, unpack_state(state, side))
        return h.reshape(b, G, R, N, P)

    def together(h):
        return pack_state(h.reshape(b, H, N, P), side)

    if s == 1:
        def step_expression():
            decay = jnp.exp(dt[:, 0] * A).reshape(b, G, R, 1, 1)
            dtx = (x[:, 0] * dt[:, 0, :, None]).reshape(b, G, R, 1, P)
            h = apart() * decay + B[:, 0, :, None, :, None] * dtx
            y = jnp.sum(h * C[:, 0, :, None, :, None], axis=-2)                   # [b, G, R, P]
            return y.reshape(b, H, P), together(h)

        walk = plan(H, G, P, N)

        def step_kernel():
            return ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], state, starts, walk,
                            interpret=False)

        if walk is None:
            y, new_state = step_expression()
        else:
            y, new_state = jax.lax.platform_dependent(tpu=step_kernel, default=step_expression)
        return y[:, None] + skip, new_state
    hp = jax.lax.Precision.HIGHEST
    c = min(SSD_CHUNK, s)
    pad = -s % c
    n = (s + pad) // c

    def chunks(v):   # [b, s, ...] -> [n, b, c, ...]; the padding: rows that change nothing
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((b, n, c) + v.shape[2:]), 1, 0)

    # heads as [G, R]: B and C are a group's, never repeated to the heads
    dtx = chunks((x * dt[..., None]).reshape(b, s, G, R, P))
    a = chunks((dt * A).reshape(b, s, G, R))
    lower = jnp.tril(jnp.ones((c, c), bool))

    def sub_chunk(h, xs):
        dtx_i, a_i, B_i, C_i = xs            # [b, c, G, R, P], [b, c, G, R], [b, c, G, N] x 2
        g = jnp.moveaxis(jnp.cumsum(a_i, axis=1), 1, -1)                          # [b, G, R, c]
        # e^{G_i - G_j} for j <= i: the difference first, masked BEFORE the
        # exponential (the exponent is <= 0 there; nothing overflows above)
        L = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bgij", C_i, B_i, precision=hp)
        y = jnp.einsum("bgrij,bjgrp->bigrp", L * cb[:, :, None], dtx_i, precision=hp)
        y = y + (jnp.einsum("bign,bgrnp->bigrp", C_i, h, precision=hp)
                 * jnp.moveaxis(jnp.exp(g), -1, 1)[..., None])
        to_end = jnp.moveaxis(jnp.exp(g[..., -1:] - g), -1, 1)                    # [b, c, G, R]
        h = (jnp.exp(g[..., -1])[..., None, None] * h
             + jnp.einsum("bjgn,bjgrp->bgrnp", B_i, dtx_i * to_end[..., None], precision=hp))
        return h, y

    new_state, y = jax.lax.scan(sub_chunk, apart(), (dtx, a, chunks(B), chunks(C)),
                                unroll=min(n, 4))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * c, H, P)[:, :s]
    return y + skip, together(new_state)


__all__ = ["KERNEL_NAME", "Plan", "SSD_CHUNK", "plan", "ssd", "ssd_step"]
