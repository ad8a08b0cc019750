"""Grouped-query attention's read of the paged pool, walking each sequence's
LIVE pages once: the shared page-walk kernel (ops/page_walk.py) over TWO pools.

``Attention`` (models/transformer.py) caches a token's K and V as one row each
of ``n_kv_heads * head_dim`` values, ``[pages, page_size, kvh * hd]``
(``TransformerConfig.kv_rows_flat``: every bf16 pool on one device),
in pages addressed through a block table. Its read as an expression gathers
``pool[block_tables]`` into copies of the WHOLE logical view of K and of V and
multiplies all of both, whatever is live (v5e, PR 35: 5.6 of a 16.1 ms Mistral
chat step with 4 % of the pool live, 71 % of OLMoE's device time). The kernel
visits what a sequence has live, straight from the two pools, once, under a
running softmax (the visits, the live pages, the one predicate and the
numerics are ops/page_walk.py's).

The decode step and the speculative verify (under one tile of query rows a
sequence) read ROW-WIDE: a query head of KV group g is laid into the g-th
``hd``-wide slot of a row-wide vector of zeros, scores contract over the whole K
row (the zeros add nothing), the context comes back as wide as a V row and head
h keeps its group's slot. The same sums as the per-head chain
(``grouped_query_attention``) but for their order, whatever ``hd`` and ``rep``
are; a visit multiplies ``n_kv_heads`` times the products it needs, and the K
and V rows pass through the MXU once either way, so the step stays bound by
their bytes.

A call whose query rows fill a tile (the prefill chunk: 256 x H rows) is bound
by those products, so its walk takes a LANE BLOCK a KV head (``gqa_plan``'s
``blocks``): a visit is two pages of K and of V rows as they lie (128 rows of
each, fetched once, as whole rows) and inside it one product a block, that
head's ``s x rep`` query rows (all of them, up to 2,048) against its ``hd``
lanes of the K rows: the per-head chain's own products over the live rows and
no others. Heads narrower than a lane tile (LFM2's 64) share a block of 128
lanes two by two, each query head laid into its own KV head's part of it as
the row-wide form lays it into the row (2x the products there, not 8x). The
pools stay as they are held, and the queries go in and the context comes out
``[b, s, H x hd]`` as the projections around the read hold them: no copy of
the view, no re-tiling copy that splits the heads out of it. One walk and one
laying for both forms: the row-wide form is the one block as wide as the row.
(ops/page_walk.py and docs/performance.md have the chip's table: why the
blocks are a loop inside the visit and not grid steps, and why the scores of a
block lie ``[cached rows, query rows]``.)

tests/test_gqa_page_attention.py holds both to ``grouped_query_attention`` over
the gathered view under the Pallas interpreter.
"""

from __future__ import annotations

import math

from seldon_core_tpu.ops.page_walk import LANES, QUERY_TILE, Plan, page_walk_attention, plan

# the name the device trace shows for the kernel
KERNEL_NAME = "gqa_page_attention"


def _row_wide_heads(s: int, heads: int) -> int:
    """The query heads a sequence's row-wide operand holds: ``heads`` and as
    many rows of zeros as make ``s`` of them whole bf16 sublane tiles (30 heads
    a token are 32 rows: two of nothing, read and dropped); fewer heads than one
    tile are left as they are (``plan`` refuses them)."""
    unit = 16 // math.gcd(16, s)
    return heads if heads < unit else -(-heads // unit) * unit


def gqa_plan(s: int, heads: int, n_kv_heads: int, head_dim: int, n_pages: int,
             page_size: int):
    """``plan`` of a call over K and V rows of ``n_kv_heads * head_dim``:
    row-wide under one tile of query rows a sequence, a lane block a KV head
    (or ``128 // head_dim`` neighbouring ones) from there."""
    row = n_kv_heads * head_dim
    if s * heads < QUERY_TILE:
        return plan(s, _row_wide_heads(s, heads), n_pages, page_size, row, row, pools=2)
    block = max(head_dim, LANES)
    if row % block or block % head_dim:
        return None
    return plan(s, heads, n_pages, page_size, row, row, pools=2, blocks=row // block)


def gqa_page_attention(q, k_pool, v_pool, pos_pool, block_tables, positions,
                       n_kv_heads: int, walk: Plan, interpret: bool | None = None,
                       window: int = 0):
    """``q`` [b, s, H, hd] rotated queries in the pools' dtype; ``k_pool`` /
    ``v_pool`` [pages, page_size, n_kv_heads * hd] / ``pos_pool`` [pages,
    page_size] int32 as held; ``block_tables`` [b, n_pages]; ``positions``
    [b, s] -> [b, s, H, hd] = softmax(hd^-0.5 q_h . k_g, pos <= position) v_g
    over the rows the tables name, head h reading KV head g = h // (H //
    n_kv_heads), in ``q``'s dtype; a sliding-attention layer's ``window`` > 0
    bounds the predicate below too (position - window < pos) and starts the
    walk at the first live page. ``walk`` = ``gqa_plan(...)`` of the same
    shapes. ``interpret=None`` compiles the kernel on a TPU and interprets it
    on any other backend; pass a bool to force either."""
    import jax.numpy as jnp

    b, s, heads, hd = q.shape
    held = n_kv_heads // walk.blocks        # KV heads a lane block holds
    block = held * hd
    ctx = q
    if held > 1:
        # a query head in its KV head's slot of the block, zeros beside it
        in_slot = (jnp.arange(heads)[:, None] // (heads // n_kv_heads) % held
                   == jnp.arange(held)[None, :])                                 # [H, slot]
        ctx = jnp.where(in_slot[:, :, None], q[:, :, :, None, :], 0).reshape(b, s, heads, block)
    pad = _row_wide_heads(s, heads) - heads if walk.blocks == 1 else 0
    if pad:
        ctx = jnp.pad(ctx, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ctx = page_walk_attention(ctx, (k_pool, v_pool), pos_pool, block_tables, positions,
                              hd**-0.5, block, walk, KERNEL_NAME, interpret,
                              window)[:, :, :heads]
    if held > 1:
        ctx = jnp.sum(jnp.where(in_slot[:, :, None], ctx.reshape(b, s, heads, held, hd), 0), axis=3)
    return ctx


__all__ = ["KERNEL_NAME", "gqa_page_attention", "gqa_plan"]
