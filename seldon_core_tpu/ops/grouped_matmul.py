"""Pallas TPU kernel: the grouped matmul of the routed experts, int8 stacks
read as int8, walked in a row tile sized to the groups.

``MoEFFN`` (models/transformer.py) sorts its (token, expert) rows by expert
and multiplies each expert's run of rows by that expert's matrix.
``jax.lax.ragged_dot`` does that on a TPU through XLA's own Mosaic kernel,
tiled (256, 512, 512): every (expert, row tile) pair it visits multiplies a
whole 256-row tile, twelve grid steps of a 512 x 512 weight tile each, and a
served group is 4-30 rows (v5e, PR 29: 40.6 ms of a 61.9 ms DeepSeek chunk,
13 % of what its bytes and FLOPs need). This kernel is on the scheme of
``jax.experimental.pallas.ops.tpu.megablox.gmm`` (the visit list as
scalar-prefetch operands; a row tile that straddles groups is visited once
per group under a store mask), with what that one refuses and what the
served shapes want:

- ``rhs`` [e, k, n] is taken as it is held, int8 or floating. A visit's
  weight block is an expert's whole [k, n] matrix where that fits (it does
  at the served widths), so it crosses HBM -> VMEM once per touched expert,
  as int8 (a block whose index does not change between two visits is not
  moved again), and is converted to the activations' dtype IN VMEM. No
  floating copy of a stack exists in HBM.
- the per-expert scale [e, n] multiplies the float32 product in the
  epilogue (a (1, n) block of an [e, 1, n] array: 2-D, PR 21's 1-D scale
  block is what Mosaic refused).
- rows that belong to no group (dead slots, padding: they sort behind the
  last group) are written as zeros, by visits that multiply nothing.
- the row tile follows the call's static shapes (``row_tile``), and a visit
  of the 128-row tile multiplies the run of aligned 32-row blocks that holds
  the rows it owns, not the tile (``sub_block``).

Numerics are ``ragged_dot``'s: int8 -> bf16 is exact, products accumulate in
float32, the scale multiplies the product.

Mosaic compiles the kernel on a TPU (a compile error there is raised); other
backends run the same body under the Pallas interpreter
(``ops.pallas_interpret_default``), which is how tier-1 holds it to
``ragged_dot`` (tests/test_grouped_matmul.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

# the name the device trace shows for the kernel and, among their operands,
# for the fusions that read it: perf/scope_times.py finds the expert FFN by
# the substring "ragged-dot" ('-' and '_' folded)
KERNEL_NAME = "ragged_dot_int8"
ROW_TILES = (16, 32, 64, 128)
# one weight block (an expert's [k, tn] strip as it is held) is at most this
WEIGHT_BLOCK_BYTES = 4 << 20
# the rows of an aligned block of a large tile (whole bf16 sublane tiles: a
# multiple of 16); see ``sub_block``
SUB_BLOCK = 32


def row_tile(m: int, n_groups: int) -> int:
    """Rows of a tile for ``m`` sorted rows over ``n_groups`` groups, from the
    mean group ``m / n_groups`` alone: the smallest of ROW_TILES that covers
    sixteen mean groups, but no more rows than 64 or one mean group, whichever
    is larger. Up to 64 rows a visit hides under the DMA of the next expert's
    block, so a tile that large costs nothing where the rows fill it; beyond
    that a larger tile pays only where single groups fill it; and where a call
    has fewer rows than experts most of a large tile is padding
    (docs/performance.md "The grouped matmul" has the chip's tables)."""
    mean = m / max(n_groups, 1)
    want = min(16 * mean, max(64, mean))
    return next((tile for tile in ROW_TILES if tile >= want), ROW_TILES[-1])


def sub_block(rows: int) -> int:
    """Rows of the aligned blocks a visit of a ``rows``-row tile multiplies in:
    the run of them that holds the rows the visit owns. Up to 64 rows the
    block is the tile (such a visit hides under the DMA of the next expert's
    weights: every decode step and 256-row chunk keeps the one product it
    had); in the 128-row tile of the 1,024-row chunks it is SUB_BLOCK, where a
    collapsed router's small groups would each pay for 128 rows
    (docs/performance.md "The grouped matmul": granules of 16, 32 and 64 and
    nested blocks, on the chip)."""
    return SUB_BLOCK if rows > 64 else rows


def _blocks(rows: int, tile, lo, hi):
    """(first, count) of the ``sub_block(rows)``-row blocks of tile ``tile``
    that hold a row of ``[lo, hi)``: what a visit multiplies (none where it
    owns no row). Scalars in the kernel, arrays beside the visit list."""
    import jax
    import jax.numpy as jnp

    block = jnp.int32(sub_block(rows))
    origin = tile * rows
    r0, r1 = jnp.clip(lo - origin, 0, rows), jnp.clip(hi - origin, 0, rows)
    first = jax.lax.div(r0, block)    # (nothing negative: no floor's sign chain)
    return first, jax.lax.div(r1 + (block - 1), block) - first


class Visits(NamedTuple):
    """The (group, row tile) pairs one call walks, in the order it walks
    them, as the kernel's scalar-prefetch operands: visit ``i`` multiplies
    row tile ``tile[i]`` by expert ``expert[i]`` and owns rows
    ``[lo[i], hi[i])`` of it. A tile wholly behind the last group gets one
    visit with no rows, which writes its zeros. ``count`` is how many there
    are (the grid's length); ``rows`` is the static tile; ``multiplied`` is
    how many rows the kernel multiplies over the list: ``count x rows`` where
    a visit multiplies its tile (the visits without rows counted as they
    always were), the live visits' blocks where it multiplies ``sub_block``s."""

    tile: "jax.Array"
    expert: "jax.Array"
    lo: "jax.Array"
    hi: "jax.Array"
    count: "jax.Array"
    rows: int
    multiplied: "jax.Array"


def make_visits(group_sizes, m: int, rows: int) -> Visits:
    """``group_sizes`` [e] int32 (their sum may be under ``m``), ``m`` sorted
    rows, ``rows`` a tile: the visit list. A group visits every tile it has
    a row in; an empty group visits none."""
    import jax
    import jax.numpy as jnp

    e = group_sizes.shape[0]
    tiles = -(-m // rows)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    total = ends[-1]
    first = starts // rows
    n_visits = jnp.where(sizes > 0, (ends - 1) // rows - first + 1, 0)
    # "group e": the tiles no group has a row in
    dead_first = jax.lax.div(total + (rows - 1), jnp.int32(rows))   # total >= 0: no floor's sign chain
    first = jnp.concatenate([first, dead_first[None]])
    visit_ends = jnp.cumsum(jnp.concatenate([n_visits, (tiles - dead_first)[None]]))
    visit_starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), visit_ends[:-1]])
    # a live group's visits are at most tiles + e - 1, the dead tiles' the rest
    i = jnp.arange(tiles + e, dtype=jnp.int32)
    # (all comparisons at once: the default's binary search is a device loop,
    # seven trips of eight ops a call)
    g = jnp.minimum(jnp.searchsorted(visit_ends, i, side="right", method="compare_all"),
                    e).astype(jnp.int32)
    live = g < e
    # a visit without rows keeps the weights that are there: no block moves
    last_live = jnp.max(jnp.where(sizes > 0, jnp.arange(e, dtype=jnp.int32), 0))
    gl = jnp.minimum(g, e - 1)
    tile = jnp.minimum(first[g] + i - visit_starts[g], tiles - 1)
    lo, hi = jnp.where(live, starts[gl], 0), jnp.where(live, ends[gl], 0)
    count = visit_ends[-1]
    multiplied = count * rows
    if sub_block(rows) != rows:
        multiplied = jnp.sum(_blocks(rows, tile, lo, hi)[1]) * sub_block(rows)
    return Visits(tile=tile, expert=jnp.where(live, gl, last_live), lo=lo, hi=hi,
                  count=count, rows=rows, multiplied=multiplied)


def _kernel(rows: int, tile, expert, lo, hi, lhs_ref, w_ref, *refs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del expert   # the index maps' (which weight block is in w_ref)
    *s_ref, out_ref = refs   # the scale's block, where there is one
    i = pl.program_id(1)

    @pl.when((i == 0) | (tile[i] != tile[jnp.maximum(i - 1, 0)]))
    def _zero():      # rows of the tile that no visit owns stay zero
        out_ref[...] = jnp.zeros_like(out_ref)

    def multiply(start=None, size=rows):
        """The product over the tile, or over ``size`` of its rows from ``start``."""
        at = ... if start is None else (pl.ds(start, size), slice(None))
        # the weight block is converted where it lies, in VMEM, by every
        # visit: the conversion hides under the MXU's passes, and a converted
        # copy kept for an expert's further visits measured 5-20 % slower
        product = jnp.dot(lhs_ref[at], w_ref[...].astype(lhs_ref.dtype),
                          preferred_element_type=jnp.float32)
        if s_ref:
            product = product * s_ref[0][...]
        first_row = tile[i] * rows if start is None else tile[i] * rows + start
        row = first_row + jax.lax.broadcasted_iota(jnp.int32, product.shape, 0)
        out_ref[at] = jnp.where((row >= lo[i]) & (row < hi[i]), product, out_ref[at])

    block = sub_block(rows)
    if block == rows:
        pl.when(hi[i] > lo[i])(multiply)
        return
    # the run of blocks that holds the visit's rows, one branch a length of
    # the run: ONE product whatever the length (a product for each 64-row
    # half that holds a row measured 3-4 % slower than a run of 64-row blocks,
    # one for each 32-row quarter 25 % slower: each converts the weights again)
    first, count = _blocks(rows, tile[i], lo[i], hi[i])
    start = pl.multiple_of(first * block, block)
    for n in range(1, rows // block):
        pl.when(count == n)(functools.partial(multiply, start, n * block))
    pl.when(count == rows // block)(multiply)


def grouped_matmul(lhs, rhs, visits: Visits, scale=None, interpret: bool | None = None):
    """``lhs`` [m, k] rows sorted by group; ``rhs`` [e, k, n] as held (int8 or
    floating); ``scale`` [e, n] float32 or None -> float32 [m, n]:
    ``lhs[group g's rows] @ rhs[g] * scale[g]``, zeros for rows in no group.
    ``visits`` = ``make_visits(group_sizes, m, row_tile(m, e))``, shared by
    the calls over the same rows. ``interpret=None`` compiles the kernel on a
    TPU and interprets it on any other backend; pass a bool to force either."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops import pallas_interpret_default

    m, k = lhs.shape
    e, kr, n = rhs.shape
    assert k == kr and (scale is None or scale.shape == (e, n)), (lhs.shape, rhs.shape)
    if interpret is None:
        interpret = pallas_interpret_default()
    rows = visits.rows
    padded = -(-m // rows) * rows
    if padded != m:   # no served shape: their tiles divide their rows
        lhs = jnp.pad(lhs, ((0, padded - m), (0, 0)))
    # an expert's whole [k, n] as one block where it fits, else strips of
    # whole 128-lane tiles
    tn = n
    if k * n * rhs.dtype.itemsize > WEIGHT_BLOCK_BYTES:
        tn = max(128, WEIGHT_BLOCK_BYTES // (k * rhs.dtype.itemsize) // 128 * 128)
    strips = -(-n // tn)

    in_specs = [
        pl.BlockSpec((rows, k), lambda j, i, tile, *_: (tile[i], 0)),
        pl.BlockSpec((None, k, tn), lambda j, i, tile, expert, *_: (expert[i], 0, j))]
    operands = [lhs, rhs]
    if scale is not None:
        in_specs.append(
            pl.BlockSpec((None, 1, tn), lambda j, i, tile, expert, *_: (expert[i], 0, j)))
        operands.append(scale.reshape(e, 1, n))
    # two buffers of each block, the converted weights and the product
    vmem = (k * tn * (2 * rhs.dtype.itemsize + lhs.dtype.itemsize)
            + 2 * rows * k * lhs.dtype.itemsize + 4 * rows * tn * 4)
    out = pl.pallas_call(
        functools.partial(_kernel, rows),
        out_shape=jax.ShapeDtypeStruct((padded, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((rows, tn), lambda j, i, tile, *_: (tile[i], j)),
            grid=(strips, visits.count)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(vmem + (16 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * strips
                            + min(e, m) * k * n * rhs.dtype.itemsize + padded * n * 4)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(visits.tile, visits.expert, visits.lo, visits.hi, *operands)
    return out[:m] if padded != m else out


__all__ = ["KERNEL_NAME", "ROW_TILES", "Visits", "grouped_matmul", "make_visits", "row_tile"]
