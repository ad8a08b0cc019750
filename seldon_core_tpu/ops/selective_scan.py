"""Mamba-1's selective scan (S6), and the Pallas TPU kernel of a chunk's scan:
h stays on chip across the chunk's rows.

``Mamba1Mixer`` (models/state_mixers.py) keeps a float32 h [N, d] a sequence (d
the mixer's channels, N the state's: Phi-4-mini-flash's [16, 5120]) and, a
token, for EVERY (channel, state) pair on its own:

    h[n, c] <- e^(Delta[c] A[n, c]) h[n, c] + Delta[c] B[n] x[c]
    y[c]    =  sum_n C[n] h[n, c] + D[c] x[c]

with Delta > 0 a channel a token, A < 0 a (state, channel) pair, B and C [N] a
token, D a channel. Where Mamba-2 (ops/ssd.py) decays a head's whole matrix by
ONE scalar, and so has a chunked form (a decay-masked matrix product), here
every pair decays at its own rate: there is NO matrix form, and a chunk IS the
scan over its rows.

``selective_scan`` is ONE function for every call shape: a decode step (s = 1),
a prefill chunk and the cache-less forward. float32 throughout.

THE STEP (s = 1) is one elementwise read-modify-write of h and a sum over N:
plain ``jax.numpy`` (h is read once and written once in one fusion, 10.5 MB a
layer at 32 slots of [16, 5120]).

THE CHUNK (s > 1) as ``lax.scan`` is s small launches a layer (1,024 for a wide
chunk). In a program LOWERED for a TPU it is the kernel below (chosen by
``jax.lax.platform_dependent``, as ``ssd`` chooses its own): a grid step holds
a block of channels' h [N, lanes] in VMEM scratch across the time blocks of the
chunk (the time axis is the grid's last, ``arbitrary``), and walks its rows
eight at a time:

    rows  x, Delta [8, lanes]           one aligned load each, a row broadcast
                                        along the sublanes as it is used
    cols  B^T, C^T [N, 8]               laid out [s / 8, N, 8] OUTSIDE (a
                                        megabyte): a column broadcast along the
                                        lanes once a row
    h     <- exp(Delta A) h + (Delta x) B     [N, lanes] registers
    y     =  sum over the SUBLANES of C h     adds across registers, stored
                                              eight rows at a time

THE STATE'S LAYOUT is the cache's: [slots, N, d], the states along the sublanes
and the channels along the lanes (PR 53's lesson: held [d, N], B_t would need a
lane broadcast a register and y a lane reduction a register).

BYTES a chunk moves a layer: x, Delta and y [s, d] float32 once each, B^T and
C^T (tiled to 128 lanes in VMEM, 8 in HBM), A [N, d] once a channel block a
batch row, h once each way. FLOPs: 3 x 2 x d x N a row for the recurrence (one
exp a pair a row beside them).

Numerics are the scan's but for the order of the sum over N.
tests/test_selective_scan.py holds the kernel to ``lax.scan`` under the Pallas
interpreter; a program lowered for a TPU compiles it (Mosaic raises what it
refuses).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# the name the device trace shows for the kernel
KERNEL_NAME = "s6_chunk_scan"
ROWS = 8            # rows a pass of the kernel's loop walks: one sublane tile
LANE_BLOCKS = (512, 256, 128)
TIME_BLOCKS = (256, 128, 64, 32, 16, 8)


class Plan(NamedTuple):
    lanes: int    # channels a grid step holds
    rows: int     # rows a grid step walks (h stays in scratch between them)


def plan(s: int, d: int, n: int) -> Optional[Plan]:
    """How the kernel walks a call of ``s`` rows, ``d`` channels and a state of
    ``n``, from static shapes; None for a shape it does not take (rows that are
    no whole sublane tiles, channels that are no whole lane tiles)."""
    if s < ROWS or s % ROWS or n % 8 or d % 128:
        return None
    return Plan(next(w for w in LANE_BLOCKS if d % w == 0),
                next(r for r in TIME_BLOCKS if s % r == 0))


def _kernel(rows: int, x_ref, delta_ref, a_ref, b_ref, c_ref, h_ref, y_ref, h_out_ref, h_scr):
    from jax.experimental import pallas as pl

    n, lanes = a_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = h_ref[0]

    A = a_ref[...]

    def eight_rows(i, h):
        r = pl.multiple_of(i * ROWS, ROWS)
        x8, d8 = x_ref[0, pl.ds(r, ROWS), :], delta_ref[0, pl.ds(r, ROWS), :]
        b8, c8 = b_ref[0, i], c_ref[0, i]                                      # [N, 8]
        ys = []
        for j in range(ROWS):                   # static: a column is a static lane slice
            dj = jnp.broadcast_to(d8[j:j + 1, :], (n, lanes))
            dx = jnp.broadcast_to(d8[j:j + 1, :] * x8[j:j + 1, :], (n, lanes))
            h = jnp.exp(dj * A) * h + dx * jnp.broadcast_to(b8[:, j:j + 1], (n, lanes))
            ys.append(jnp.sum(h * jnp.broadcast_to(c8[:, j:j + 1], (n, lanes)),
                              axis=0, keepdims=True))
        y_ref[0, pl.ds(r, ROWS), :] = jnp.concatenate(ys, axis=0)
        return h

    h = jax.lax.fori_loop(0, rows // ROWS, eight_rows, h_scr[...])
    h_scr[...] = h
    h_out_ref[0] = h


def scan_kernel(x, delta, A, B, C, state, walk: Plan, interpret: bool | None = None):
    """``x`` / ``delta`` [b, s, d], ``A`` [N, d], ``B`` / ``C`` [b, s, N], ``state``
    [b, N, d] (h before the first row), all float32 -> (sum_n C h [b, s, d], h
    after the last row [b, N, d], in the state's own buffer). The skip D x and a
    sequence that starts (h read as zeros) are the caller's. ``walk`` =
    ``plan(s, d, N)``. ``interpret=None`` compiles the kernel on a TPU and
    interprets it on any other backend; pass a bool to force either."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops import pallas_interpret_default

    b, s, d = x.shape
    n = A.shape[0]
    lanes, rows = walk
    if interpret is None:
        interpret = pallas_interpret_default()
    f32 = jnp.float32

    def columns(v):     # [b, s, N] -> [b, s / 8, N, 8]: eight rows' columns side by side
        return jnp.swapaxes(v.astype(f32).reshape(b, s // ROWS, ROWS, n), 2, 3)

    row_block = pl.BlockSpec((1, rows, lanes), lambda i, j, t: (i, t, j))
    col_block = pl.BlockSpec((1, rows // ROWS, n, ROWS), lambda i, j, t: (i, t, 0, 0))
    state_block = pl.BlockSpec((1, n, lanes), lambda i, j, t: (i, 0, j))
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, rows),
        out_shape=(jax.ShapeDtypeStruct((b, s, d), f32), jax.ShapeDtypeStruct((b, n, d), f32)),
        grid=(b, d // lanes, s // rows),
        in_specs=[row_block, row_block, pl.BlockSpec((n, lanes), lambda i, j, t: (0, j)),
                  col_block, col_block, state_block],
        out_specs=(row_block, state_block),
        scratch_shapes=[pltpu.VMEM((n, lanes), f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=b * s * d * n * 6, transcendentals=b * s * d * n,
            bytes_accessed=(3 * b * s * d + 2 * b * n * d + 2 * b * s * n) * 4),
        interpret=interpret,
        name=KERNEL_NAME,
    )(x.astype(f32), delta.astype(f32), A.astype(f32), columns(B), columns(C), state.astype(f32))
    return y, new_state


def scan_rows(x, delta, A, B, C, state):
    """The recurrence as ``lax.scan`` over the rows: the kernel's operands and
    results, on every backend."""
    def row(h, xs):
        x_t, d_t, b_t, c_t = xs                          # [b, d], [b, d], [b, N], [b, N]
        h = (jnp.exp(d_t[:, None, :] * A) * h
             + (d_t * x_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    new_state, y = jax.lax.scan(
        row, state, tuple(jnp.swapaxes(v, 0, 1) for v in (x, delta, B, C)))
    return jnp.swapaxes(y, 0, 1), new_state


@jax.jit
def selective_scan(x, delta, A, B, C, D, state, starts=None):
    """The recurrence over the rows of one call, and the state each sequence
    leaves. ``x`` [b, s, d]; ``delta`` [b, s, d] (the step, > 0; 0 for a row
    that is no token: it decays nothing and adds nothing); ``A`` [N, d] (< 0:
    the TRANSPOSE of the published [d, N], the state's own layout); ``B`` /
    ``C`` [b, s, N]; ``D`` [d]; ``state`` [b, N, d] float32, h before the
    call's first row; ``starts`` [b] bool or None: the sequences whose h reads
    as ZEROS whatever ``state`` holds (a sequence that starts has no past).
    float32. Returns (y [b, s, d], h after the last row [b, N, d]).

    A jitted function of its own, so a program's layers share ONE trace of it."""
    b, s, d = x.shape
    f32 = jnp.float32
    x, delta, A, B, C, D, state = (v.astype(f32) for v in (x, delta, A, B, C, D, state))
    if starts is not None:
        state = jnp.where(starts[:, None, None], 0.0, state)
    if s == 1:
        h = (jnp.exp(delta[:, 0, None, :] * A) * state
             + (delta[:, 0] * x[:, 0])[:, None, :] * B[:, 0, :, None])
        y = jnp.sum(h * C[:, 0, :, None], axis=1)[:, None]
        return y + D * x, h
    walk = plan(s, d, A.shape[0])
    if walk is None:
        y, new_state = scan_rows(x, delta, A, B, C, state)
    else:
        y, new_state = jax.lax.platform_dependent(
            tpu=lambda: scan_kernel(x, delta, A, B, C, state, walk, interpret=False),
            default=lambda: scan_rows(x, delta, A, B, C, state))
    return y + D * x, new_state


__all__ = ["KERNEL_NAME", "Plan", "plan", "scan_kernel", "scan_rows", "selective_scan"]
