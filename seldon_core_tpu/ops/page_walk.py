"""Pallas TPU kernel: an attention read of the paged pool that walks each
sequence's LIVE pages once, for both kinds of cached row.

The pool caches one row a token a layer in pages addressed through a block
table: latent attention's ``[c_t ; k^R_t]`` (one pool, no head axis:
ops/latent_attention.py) or grouped-query attention's K row and V row of
``n_kv_heads * head_dim`` values (two pools: ops/gqa_attention.py). The read as
an expression gathers ``pool[block_tables]`` into a copy of the WHOLE logical
view and multiplies all of it twice, whatever is live. This kernel visits what
a sequence has live, straight from the pool, once, under a running softmax:

- a VISIT is ``plan.pages`` consecutive entries of one sequence's block table
  (1,024 cached rows at the served 64-row page, 512 under a chunk's full
  query tiles, 128 where the products are a lane block's) against one tile of
  that sequence's query rows. A page is a block of the pool as it lies,
  fetched by its table entry (the table and the visit list are
  scalar-prefetch operands); no copy of the view exists.
- a sequence's LIVE pages are those up to its queries' largest valid
  position. Entries behind them are read as NULL_PAGE (whose positions are
  PAD_POS forever), so a page behind the live ones is never fetched. A
  sequence with no valid query (an empty slot of the static-shape step) makes
  no visit and its rows come out zero. The grid's length is the number of
  visits, computed on the device from ``positions``. (An empty slot's visit
  fetched nothing but cost a grid step over all the page operands, ~2 us
  with thirty-two of them: 54 of the 80 us a layer of a Mistral chat step
  with 27 of 32 slots empty, v5e, PR 36.)
- the mask is the expression's ONE predicate, ``pos <= position``, on the
  positions cached beside the rows: causality, empty rows (PAD_POS), a
  half-filled page, rows a rejected draft left behind.
- a sliding-attention layer (``window`` > 0) has a FIRST live page as well:
  the one that holds the smallest position any query of the call may see
  (its smallest valid position - window + 1). Entries before it read
  NULL_PAGE too (the batcher gave those pages back) and no visit lies wholly
  before it; the predicate gains ``pos > position - window``, on the cached
  positions, so a row in a page not yet given back is masked as well. With
  ``window`` 0 the call is, operand for operand, the one it always was.
- the queries of a sequence are its ``s x H`` rows, each as wide as a cached
  row, against one shared row a token: the decode step (s = 1, every slot a
  sequence), the prefill chunk (one sequence, 256 x H rows in tiles of
  ``plan.q_tile``) and the speculative verify are one kernel. Scores contract
  over the whole row of the FIRST pool; the output is ``sum_t p_t v_t`` over
  the first ``out_dim`` values of the LAST pool's rows, a query row.
- a row of several LANE BLOCKS (``plan.blocks``: grouped-query attention's
  chunk, a block a KV head) is the same walk with one product a block INSIDE a
  visit: the visit's pages are fetched once, as whole rows, and each block's
  lanes of them meet that block's own query heads alone, under a running
  softmax a block. The blocks are never operands nor grid steps (a grid step a
  block is ``blocks`` times the page fetches, each 256-byte pieces of a row,
  and ~20 index maps for 0.7 us of products: 324 us a Mistral chunk's read at
  3,584 live rows against 146, v5e, PR 41). A block's query heads lie side by
  side in a token's row of the query operand as the projection made them, go
  one under another into the product and come back the same way: ``[b, s, H x
  W]`` in and out, no re-tiling copy around the call. The scores of such a
  product lie ``[cached rows, query rows]``, so a query row's statistics are
  lane-dense vectors and its maximum and sum run down the sublanes; and a
  visit whose every cached row every query row of the tile admits (all but
  those that hold the chunk's own rows) computes no predicate: the same sums,
  a third of the softmax's VPU work less. The row-wide form is the one block
  as wide as the row, its heads rows already, its kernel as it was.

Numerics are the expression's: bf16 operands, float32 scores, softmax
statistics and accumulator; nothing is approximated and no row the mask admits
is skipped. The probabilities are rounded to bf16 before their sum is divided
out (the expression rounds them after), so the two differ by bf16 roundings.

Mosaic compiles the kernel on a TPU; other backends run the same body under
the Pallas interpreter, which is how tier-1 holds it to the expressions over
the gathered view (tests/test_latent_attention.py, tests/test_gqa_page_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

# cached rows one visit carries, and the query rows (tokens x heads) it
# multiplies them by. A step's visit (16-32 query rows) costs ~2.2 us whatever
# it carries up to 1,024 latent rows (1.3 MB = 1.6 us of HBM time), so it
# carries that many; where the query rows fill a tile (a chunk) the visit is
# MXU-bound, half as many rows cost the same a row, and the last visit rounds
# less (docs/performance.md has the chip's table)
VISIT_ROWS = 1024
TILED_VISIT_ROWS = 512
QUERY_TILE = 512
# ... and where a visit multiplies a lane block a KV head by that head's query
# rows alone (``Plan.blocks``): the products of a block are small beside its
# softmax, so a visit takes all of a block's query rows it can (the rows are
# fetched once a tile of them) and few cached rows (a chunk's last visit is the
# one whose predicate is computed, and what it over-reads is multiplied too)
BLOCK_VISIT_ROWS = 128
BLOCK_QUERY_TILE = 2048
# the pages of a visit are operands of their own: no more of them than this
MAX_VISIT_PAGES = 32
# ... and no more bytes of rows than this, all pools together (on-chip memory
# holds them three times: both buffers of each page operand and the rows side
# by side). Mistral's K + V rows are 4 MB a 1,024-row visit, OLMoE's 8 MB
VISIT_BYTES = 8 << 20
LANES = 128


class Plan(NamedTuple):
    """How one call shape is walked: ``pages`` block-table entries a visit,
    ``q_tile`` query rows a visit, a cached row read as ``blocks`` lane blocks
    (each walked with its own query heads). From the call's static shapes
    alone."""

    pages: int
    q_tile: int
    blocks: int = 1

    def groups(self, n_pages: int) -> int:
        """Visits that cover a whole block-table row."""
        return -(-n_pages // self.pages)


def plan(s: int, heads: int, n_pages: int, page_size: int, row_dim: int,
         out_dim: int, pools: int = 1, blocks: int = 1) -> Optional[Plan]:
    """The walk of a call with ``s`` query tokens of ``heads`` heads a sequence
    over ``n_pages`` table entries of ``page_size`` rows ``row_dim`` wide in
    each of ``pools`` pools, or None where the kernel does not take the shape
    (the caller keeps the expression): the row and the ``out_dim`` values of it
    that are summed are whole 128-lane tiles, a page is whole bf16 sublane
    tiles and a visit's rows whole lane tiles, and the query rows divide into
    tiles. With ``blocks`` the row is that many lane blocks, each read by
    ``heads // blocks`` query heads of its own and summed whole (``out_dim`` is
    the row): the query rows of a product are one block's (whole bf16 sublane
    tiles of tokens a head, up to ``BLOCK_QUERY_TILE``) and a visit is
    ``BLOCK_VISIT_ROWS``."""
    tiled = blocks > 1
    if tiled and (row_dim % (blocks * LANES) or out_dim != row_dim or heads % blocks):
        return None
    q_rows = s * heads // blocks
    if row_dim % LANES or out_dim % LANES or not 0 < out_dim <= row_dim:
        return None
    tile = BLOCK_QUERY_TILE if tiled else QUERY_TILE
    if tiled and q_rows > tile and q_rows % tile:
        # a block's query heads do not fill the tile a whole number of times (7
        # heads a KV head: 3,584 rows of a 512-token chunk): the largest tile
        # under it that does, in whole bf16 sublane tiles of tokens a head
        unit = 16 * heads // blocks
        tile = next((t for t in range(tile // unit * unit, 0, -unit) if q_rows % t == 0), tile)
    visit_rows = BLOCK_VISIT_ROWS if tiled else TILED_VISIT_ROWS if q_rows >= tile else VISIT_ROWS
    visit_rows = min(visit_rows, VISIT_BYTES // (pools * row_dim * 2))
    per_visit = visit_rows // page_size
    if page_size % 16 or not 1 <= per_visit <= MAX_VISIT_PAGES:
        return None
    if q_rows % 16 or (q_rows > tile and q_rows % tile):
        return None
    if tiled and min(q_rows, tile) % (16 * heads // blocks):
        return None
    # whole lane tiles of rows (two 64-row pages make one), a short table too
    unit = LANES // math.gcd(LANES, page_size)
    if per_visit < unit:
        return None
    return Plan(pages=min(per_visit // unit * unit, -(-n_pages // unit) * unit),
                q_tile=min(q_rows, tile), blocks=blocks)


def live_pages(block_tables, positions, page_size: int):
    """[b] int32: the table entries a sequence's read has to visit, those up
    to its queries' largest valid position (0 where no query is valid:
    padding, or a slot nobody holds, whose table row is all TRASH_PAGE)."""
    import jax.numpy as jnp

    top = jnp.max(jnp.where(_valid_queries(block_tables, positions), positions, -1), axis=1)
    return jnp.minimum((top.astype(jnp.int32) + page_size) // page_size, block_tables.shape[1])


def _valid_queries(block_tables, positions):
    import jax.numpy as jnp

    from seldon_core_tpu.models.cache import PAD_POS, TRASH_PAGE

    p = positions.astype(jnp.int32)
    return (p >= 0) & (p < PAD_POS) & (block_tables[:, :1] != TRASH_PAGE)


def first_live_pages(block_tables, positions, page_size: int, window: int):
    """[b] int32: the FIRST table entry a sliding-attention layer's read has to
    visit, the page that holds the smallest position any valid query of the
    sequence may see (its smallest valid position - ``window`` + 1); 0 where no
    query is valid."""
    import jax.numpy as jnp

    from seldon_core_tpu.models.cache import PAD_POS

    low = jnp.min(jnp.where(_valid_queries(block_tables, positions), positions, PAD_POS), axis=1)
    low = jnp.where(low < PAD_POS, low.astype(jnp.int32), 0)
    return jnp.maximum(low - window + 1, 0) // page_size


def rows_visited(live_rows: int, page_size: int, walk: Plan, first_row: int = 0) -> int:
    """Cached rows the kernel multiplies for one sequence whose queries reach
    ``live_rows`` rows: whole visits (from the one that holds row ``first_row``:
    a sliding-attention layer's first row inside the window)."""
    rows = walk.pages * page_size
    return -(-live_rows // rows) * rows - first_row // rows * rows


class Visits(NamedTuple):
    """The (sequence, page group) pairs one call walks, in order, as the
    kernel's scalar-prefetch operands; ``count`` of them (the grid's length:
    at least one, which finishes nothing where no sequence has a live page).
    ``table`` is the block table with the entries behind each sequence's live
    pages read as NULL_PAGE, padded to whole visits, flat. With a first live
    page (a sliding-attention layer) ``start`` flags each sequence's first
    visit, which is then not its group 0, and the entries before the first
    live page read NULL_PAGE too."""

    seq: "jax.Array"
    group: "jax.Array"
    last: "jax.Array"
    live: "jax.Array"
    table: "jax.Array"
    count: "jax.Array"
    start: Optional["jax.Array"] = None


def make_visits(block_tables, live, walk: Plan, first=None) -> Visits:
    """``block_tables`` [b, n_pages] int32, ``live`` [b] (``live_pages``): a
    sequence visits the groups of ``walk.pages`` entries that hold a live
    page; with ``first`` [b] (``first_live_pages``) those from the group that
    holds its first live page on."""
    import jax.numpy as jnp

    from seldon_core_tpu.models.cache import NULL_PAGE

    b, n_pages = block_tables.shape
    groups = walk.groups(n_pages)
    padded = groups * walk.pages
    entry = jnp.arange(padded, dtype=jnp.int32)[None]
    table = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, padded - n_pages)),
                    constant_values=NULL_PAGE)
    table = jnp.where(entry < live[:, None], table, NULL_PAGE)
    n_visits = (-(-live // walk.pages)).astype(jnp.int32)
    if first is not None:
        table = jnp.where(entry >= first[:, None], table, NULL_PAGE)
        first_group = jnp.minimum(first // walk.pages, n_visits).astype(jnp.int32)
        n_visits = n_visits - first_group
    ends = jnp.cumsum(n_visits)
    i = jnp.arange(b * groups, dtype=jnp.int32)
    # (all comparisons at once: the default's binary search is a device loop)
    seq = jnp.minimum(jnp.searchsorted(ends, i, side="right", method="compare_all"),
                      b - 1).astype(jnp.int32)
    nth = jnp.clip(i - (ends - n_visits)[seq], 0, groups - 1)
    if first is None:
        return Visits(seq=seq, group=nth, last=(nth == n_visits[seq] - 1).astype(jnp.int32),
                      live=live.astype(jnp.int32), table=table.reshape(-1),
                      count=jnp.maximum(ends[-1], 1))
    return Visits(seq=seq, group=jnp.minimum(nth + first_group[seq], groups - 1),
                  last=(nth == n_visits[seq] - 1).astype(jnp.int32),
                  live=live.astype(jnp.int32), table=table.reshape(-1),
                  count=jnp.maximum(ends[-1], 1), start=(nth == 0).astype(jnp.int32))


def _kernel(walk: Plan, page_size: int, pools: int, fold: int, out_dim: int, scale: float,
            groups: int, window: int, seq, group, last, live, table, *refs):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del table   # the index maps' (which pages are in the page refs)
    if window:
        start, *refs = refs
    if walk.blocks > 1:
        top, low, *refs = refs
        if window:
            bottom, high, *refs = refs
    q_ref, qpos_ref, pos_ref, *refs = refs
    n_pages = pools * walk.pages
    page_refs = refs[:n_pages]
    out_ref = refs[n_pages]
    rows_refs = refs[n_pages + 1:n_pages + 1 + pools]
    m_ref, l_ref, acc_ref = refs[n_pages + 1 + pools:]
    v = pl.program_id(1)
    lowest = jnp.finfo(jnp.float32).min

    @pl.when(start[v] == 1 if window else group[v] == 0)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, lowest)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fetched():   # the visit's pages side by side, as the products read them
        for i, page in enumerate(page_refs):
            j = i % walk.pages
            rows_refs[i // walk.pages][j * page_size:(j + 1) * page_size, :] = page[...]

    def product(at, queries, keys, values, admitted, across: int):
        """One product of the visit into the running softmax ``at`` (all of it,
        or a block's): ``across`` is the axis of the scores the cached rows lie
        along; ``admitted`` makes the predicate's answer, or is None where the
        whole visit is admitted. Every operand is a function that reads it: each
        is read where it is used."""
        keys = keys()
        a, b = (queries(), keys) if across else (keys, queries())
        scores = jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if admitted is not None:
            admitted = admitted()
            scores = jnp.where(admitted, scores, lowest)
        m_old = m_ref[at]
        m_new = jnp.maximum(m_old, jnp.max(scores, axis=across, keepdims=True))
        shrink = jnp.exp(m_old - m_new)
        p = jnp.exp(scores - m_new)
        if admitted is not None:
            p = jnp.where(admitted, p, 0.0)
        l_ref[at] = shrink * l_ref[at] + jnp.sum(p, axis=across, keepdims=True)
        acc_ref[at] = shrink * acc_ref[at] + (
            jnp.dot(p.astype(keys.dtype), values(), preferred_element_type=jnp.float32) if across
            else jax.lax.dot_general(values(), p.astype(keys.dtype), (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32))
        m_ref[at] = m_new

    def context(at):   # a row that admitted nothing comes out zero
        total = l_ref[at]
        return acc_ref[at] / jnp.where(total > 0.0, total, 1.0)

    def predicate():   # the ONE: causality, empty rows (PAD_POS), padding
        if window:     # ... and a sliding-attention layer's lower bound
            return (pos_ref[...] <= qpos_ref[...]) & (pos_ref[...] > qpos_ref[...] - window)
        return pos_ref[...] <= qpos_ref[...]

    here = live[seq[v]] > group[v] * walk.pages
    if walk.blocks == 1:   # every query head a row as wide as the cached rows
        @pl.when(here)
        def _visit():
            fetched()
            product(..., lambda: q_ref[...], lambda: rows_refs[0][...],
                    lambda: rows_refs[-1][:, :out_dim], predicate, 1)

        @pl.when(last[v] == 1)
        def _finish():
            out_ref[...] = context(...).astype(out_ref.dtype)
        return

    # a row of lane blocks: one product a block, its ``fold`` query heads (side
    # by side in a token's row of q_ref) one under another. The scores lie
    # [cached rows, query rows]: a query row's maximum and sum run down the
    # sublanes and its statistics are lane-dense [1, q_tile] vectors (held the
    # other way every 8 query rows are a cross-lane reduce a statistic a visit,
    # more than the products cost). The blocks are a loop whose body is traced
    # ONCE and unrolled as it is lowered (one pass over eight blocks a fifth
    # slower, v5e, PR 41): tracing a body a block is start-up time of every
    # program that carries the kernel, which no compile cache serves.
    width = rows_refs[0].shape[1] // walk.blocks
    tokens = q_ref.shape[0]

    def lanes(g, of: int):
        return pl.ds(pl.multiple_of(g * of, of), of)

    def visit(masked: bool):
        fetched()
        admitted = predicate() if masked else None

        def block(g, carry):
            product(g,
                    lambda: jnp.concatenate(
                        [q_ref[:, lanes(g * fold + i, width)] for i in range(fold)], axis=0),
                    lambda: rows_refs[0][:, lanes(g, width)],
                    lambda: rows_refs[-1][:, lanes(g, width)],   # (summed whole)
                    (lambda: admitted) if masked else None, 0)
            return carry

        jax.lax.fori_loop(0, walk.blocks, block, 0, unroll=True)

    # a visit whose every cached row every query row of the tile admits (its
    # largest cached position no larger than the tile's smallest: all but a
    # chunk's last visits) computes no predicate: the same sums, a third of the
    # softmax's VPU work less
    whole = top[seq[v] * groups + group[v]] <= low[seq[v] * pl.num_programs(0) + pl.program_id(0)]
    if window:   # ... and its smallest cached position inside the window of the tile's LAST row
        whole &= (bottom[seq[v] * groups + group[v]]
                  > high[seq[v] * pl.num_programs(0) + pl.program_id(0)] - window)
    pl.when(here & whole)(functools.partial(visit, False))
    pl.when(here & jnp.logical_not(whole))(functools.partial(visit, True))

    @pl.when(last[v] == 1)
    def _finish():
        def block(g, carry):
            laid = context(g).T.astype(out_ref.dtype)
            for i in range(fold):
                out_ref[:, lanes(g * fold + i, out_dim)] = laid[i * tokens:(i + 1) * tokens]
            return carry

        jax.lax.fori_loop(0, walk.blocks, block, 0)


def page_walk_attention(q, pools, pos_pool, block_tables, positions, scale: float,
                        out_dim: int, walk: Plan, name: str, interpret: bool | None = None,
                        window: int = 0):
    """``q`` [b, s, H, W] query rows as wide as a cached row, in the pools'
    dtype; ``pools`` one or two arrays [pages, page_size, W] as held (the first
    scored, the last summed) / ``pos_pool`` [pages, page_size] int32;
    ``block_tables`` [b, n_pages]; ``positions`` [b, s] -> [b, s, H, out_dim] =
    softmax(scale q . rows, pos <= position) rows'[:, :out_dim] over the rows
    the tables name, in ``q``'s dtype (with ``window`` > 0 the predicate is
    position - window < pos <= position and the walk starts at each sequence's
    first live page). ``walk`` = ``plan(...)`` of the same
    shapes; ``name`` is the op's in a device trace. ``interpret=None`` compiles
    the kernel on a TPU and interprets it on any other backend; pass a bool to
    force either.

    The call is a jitted function of its own, so a program of many layers
    traces the kernel and its index maps (one a page operand) ONCE and not once
    a layer: 0.9 s a layer on the chip's host with Mistral's 64 page operands,
    33 s of every start of a 32-layer step program, which no compile cache
    serves (v5e, PR 36)."""
    import jax.numpy as jnp

    from seldon_core_tpu.ops import pallas_interpret_default

    if interpret is None:
        interpret = pallas_interpret_default()
    return _jitted_walk()(q, tuple(pools), pos_pool, jnp.asarray(block_tables, jnp.int32),
                          positions, scale=scale, out_dim=out_dim, walk=walk, name=name,
                          interpret=interpret, window=window)


@functools.cache
def _jitted_walk():
    import jax

    return jax.jit(_walk_pages,
                   static_argnames=("scale", "out_dim", "walk", "name", "interpret", "window"))


def _walk_pages(q, pools, pos_pool, bt, positions, *, scale, out_dim, walk, name, interpret,
                window=0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, heads, width = q.shape
    page_size = pos_pool.shape[1]
    n_pages = bt.shape[1]
    row = walk.blocks * width
    groups, rows = walk.groups(n_pages), walk.pages * page_size
    tiled = walk.blocks > 1
    # the row-wide form's query rows are (token, head), each against the whole
    # row; a lane block's are its tokens', its ``fold`` heads side by side in
    # each (the row of the operand is a token's heads as the projection made them)
    fold = heads // walk.blocks if tiled else 1
    tq, tokens = walk.q_tile, walk.q_tile // fold
    together = heads if tiled else 1    # heads a row of the query operand holds
    q = q.reshape(b, s * heads // together, together * width)
    q_tiles = q.shape[1] // tokens
    assert all(pool.shape[1:] == (page_size, row) for pool in pools), (q.shape, walk)
    assert q.shape[1] % tokens == 0 and tq == tokens * fold, (q.shape, walk)
    assert not tiled or out_dim == width, (q.shape, out_dim, walk)

    stats = (walk.blocks,) if tiled else ()   # a running softmax a block

    def of_sequence(t, v, seq, *_):
        return (seq[v], t, 0)

    def shaped(down, along):   # a lane block's scores lie [cached rows, query rows]
        return (along, down) if tiled else (down, along)

    first = first_live_pages(bt, positions, page_size, window) if window else None
    visits = make_visits(bt, live_pages(bt, positions, page_size), walk, first)
    # the positions cached beside the rows the visits fetch: 256 B a page
    pos_view = pos_pool[visits.table].reshape((b * groups,) + shaped(1, rows))
    if tiled:   # a tile's query rows are head-major: its tokens' positions once a head
        qpos = jnp.broadcast_to(positions.astype(jnp.int32).reshape(b, q_tiles, 1, tokens),
                                (b, q_tiles, fold, tokens)).reshape(b * q_tiles, 1, tq)
        qpos_spec = pl.BlockSpec((None, 1, tq), lambda t, v, seq, *_: (seq[v] * q_tiles + t, 0, 0))
    else:
        qpos = jnp.repeat(positions.astype(jnp.int32), heads, axis=1)[..., None]
        qpos_spec = pl.BlockSpec((None, tq, 1), of_sequence)
    # the largest position cached in a visit's rows, the smallest a tile's query
    # rows hold: where the one is no larger than the other the predicate admits
    # every pair of the visit
    bounds = ()
    if tiled:
        bounds = (jnp.max(pos_view.reshape(b * groups, rows), axis=1),
                  jnp.min(qpos.reshape(b * q_tiles, tq), axis=1))
        if window:
            # the smallest position cached in a visit's rows, the largest a tile's
            # VALID query rows hold (a padded row's result is nobody's)
            from seldon_core_tpu.models.cache import PAD_POS

            flat_q = qpos.reshape(b * q_tiles, tq)
            bounds += (jnp.min(pos_view.reshape(b * groups, rows), axis=1),
                       jnp.max(jnp.where(flat_q < PAD_POS, flat_q, -1), axis=1))
    if window:
        bounds = (visits.start,) + bounds

    def page_spec(j):
        return pl.BlockSpec(
            (None, page_size, row),
            lambda t, v, seq, group, last, live, table, *_:
                (table[(seq[v] * groups + group[v]) * walk.pages + j], 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, walk, page_size, len(pools), fold, out_dim, scale, groups,
                          window),
        out_shape=jax.ShapeDtypeStruct((b, q.shape[1], together * out_dim), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(bounds),
            in_specs=[
                pl.BlockSpec((None, tokens, q.shape[2]), of_sequence),
                qpos_spec,
                pl.BlockSpec((None,) + shaped(1, rows),
                             lambda t, v, seq, group, *_: (seq[v] * groups + group[v], 0, 0)),
                *[page_spec(j) for _ in pools for j in range(walk.pages)]],
            out_specs=pl.BlockSpec((None, tokens, together * out_dim), of_sequence),
            grid=(q_tiles, visits.count),
            scratch_shapes=[
                *[pltpu.VMEM((rows, row), pool.dtype) for pool in pools],  # the visit's rows
                pltpu.VMEM(stats + shaped(tq, 1), jnp.float32),            # running maximum
                pltpu.VMEM(stats + shaped(tq, 1), jnp.float32),            # running sum
                pltpu.VMEM(stats + shaped(tq, out_dim), jnp.float32)]),    # running products
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(visits.seq, visits.group, visits.last, visits.live, visits.table, *bounds,
      q, qpos, pos_view, *[pool for pool in pools for _ in range(walk.pages)])
    # a sequence no visit finished was never written
    out = jnp.where((visits.live > 0)[:, None, None], out, 0)
    return out.reshape(b, s, heads, out_dim)


__all__ = ["Plan", "Visits", "first_live_pages", "live_pages", "make_visits",
           "page_walk_attention", "plan", "rows_visited"]
