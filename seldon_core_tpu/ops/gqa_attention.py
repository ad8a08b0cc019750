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

A query head of KV group g is laid into the g-th ``hd``-wide slot of a row-wide
vector of zeros: scores contract over the whole K row (the zeros add nothing),
the context comes back as wide as a V row and head h keeps its group's slot.
The same sums as the per-head chain (``grouped_query_attention``) but for their
order, whatever ``hd`` and ``rep`` are; a visit multiplies ``n_kv_heads`` times
the products it needs, and the K and V rows pass through the MXU once either
way, so the step stays bound by their bytes.

tests/test_gqa_page_attention.py holds it to ``grouped_query_attention`` over the
gathered view under the Pallas interpreter.
"""

from __future__ import annotations

from seldon_core_tpu.ops.page_walk import Plan, page_walk_attention, plan

# the name the device trace shows for the kernel
KERNEL_NAME = "gqa_page_attention"


def gqa_plan(s: int, heads: int, n_kv_heads: int, head_dim: int, n_pages: int,
             page_size: int):
    """``plan`` of a call over K and V rows of ``n_kv_heads * head_dim``."""
    row = n_kv_heads * head_dim
    return plan(s, heads, n_pages, page_size, row, row, pools=2)


def gqa_page_attention(q, k_pool, v_pool, pos_pool, block_tables, positions,
                       n_kv_heads: int, walk: Plan, interpret: bool | None = None):
    """``q`` [b, s, H, hd] rotated queries in the pools' dtype; ``k_pool`` /
    ``v_pool`` [pages, page_size, n_kv_heads * hd] / ``pos_pool`` [pages,
    page_size] int32 as held; ``block_tables`` [b, n_pages]; ``positions``
    [b, s] -> [b, s, H, hd] = softmax(hd^-0.5 q_h . k_g, pos <= position) v_g
    over the rows the tables name, head h reading KV head g = h // (H //
    n_kv_heads), in ``q``'s dtype. ``walk`` = ``gqa_plan(...)`` of the same
    shapes. ``interpret=None`` compiles the kernel on a TPU and interprets it
    on any other backend; pass a bool to force either."""
    import jax.numpy as jnp

    b, s, heads, hd = q.shape
    row = n_kv_heads * hd
    in_group = (jnp.arange(heads)[:, None] // (heads // n_kv_heads)
                == jnp.arange(n_kv_heads)[None, :])                              # [H, g]
    q_rows = jnp.where(in_group[:, :, None], q[:, :, :, None, :], 0).reshape(b, s, heads, row)
    ctx = page_walk_attention(q_rows, (k_pool, v_pool), pos_pool, block_tables, positions,
                              hd**-0.5, row, walk, KERNEL_NAME, interpret)
    ctx = ctx.reshape(b, s, heads, n_kv_heads, hd)
    return jnp.sum(jnp.where(in_group[:, :, None], ctx, 0), axis=3)


__all__ = ["KERNEL_NAME", "gqa_page_attention", "gqa_plan"]
