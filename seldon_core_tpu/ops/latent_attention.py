"""Latent attention's read of the paged pool, walking each sequence's LIVE pages
once: the shared page-walk kernel (ops/page_walk.py) over ONE pool.

``LatentAttention`` (models/transformer.py) caches one row ``[c_t ; k^R_t]`` a
token a layer, with no head axis, in pages addressed through a block table.
Its read as an expression gathers ``pool[block_tables]`` into a copy of the
WHOLE logical view (168 MB a layer at the served shapes) and multiplies all
of it twice, whatever is live: four passes over the view a layer, of which
14-38 % was live (v5e, PR 31: 11.7 of a 15.3 ms DeepSeek step, 7.1 of a 15.1
ms Xing4 step). The kernel visits what a sequence has live, straight from the
pool, once, under a running softmax (the visits, the live pages, the one
predicate and the numerics are ops/page_walk.py's). The queries of a sequence
are its ``s x H`` rows ``[q~_h ; q^R_h ; 0]`` (absorbed with W_UK by the
caller) against one shared row a token; scores contract over the whole row and
the output is ``sum_t p_t c_t`` a query row; W_UV stays outside.

tests/test_latent_attention.py holds it to ``absorbed_latent_attention`` over
the gathered view under the Pallas interpreter.
"""

from __future__ import annotations

from seldon_core_tpu.ops.page_walk import Plan, page_walk_attention, plan

# the name the device trace shows for the kernel
KERNEL_NAME = "latent_page_attention"


def latent_page_attention(q, pool, pos_pool, block_tables, positions, scale: float,
                          latent_dim: int, walk: Plan, interpret: bool | None = None):
    """``q`` [b, s, H, W] absorbed query rows ``[q~ ; q^R ; zeros]`` in the
    pool's dtype; ``pool`` [pages, page_size, W] / ``pos_pool`` [pages,
    page_size] int32 as held; ``block_tables`` [b, n_pages]; ``positions``
    [b, s] -> [b, s, H, latent_dim] = softmax(scale q . rows, pos <= position)
    rows[:, :latent_dim] over the rows the tables name, in ``q``'s dtype.
    ``walk`` = ``plan(...)`` of the same shapes. ``interpret=None`` compiles
    the kernel on a TPU and interprets it on any other backend; pass a bool
    to force either."""
    return page_walk_attention(q, (pool,), pos_pool, block_tables, positions, scale,
                               latent_dim, walk, KERNEL_NAME, interpret)


__all__ = ["KERNEL_NAME", "latent_page_attention", "plan"]
