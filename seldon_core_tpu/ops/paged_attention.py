"""Pallas kernel: paged-attention decode read — NOT on the serving path.

Serving reads the paged pool with the XLA gather in models/transformer.py
on every backend, the v5e included. This kernel does not lower for a TPU
(jax 0.9.0 / libtpu 0.0.34, confirmed on the chip in PR 21; PERF.md has the
compiler's words): the ``(1, page_size)`` position-pool block fails the
"last two dimensions divisible by 8 and 128" rule, and past that
``einsum("hd,phd->hp")`` becomes a ``tpu.dot_dimension_numbers`` with no
lhs non-contracting dimension. Until ROADMAP A3b rewrites or deletes it, it
runs only under ``interpret=True`` (its parity test, tests/test_paged_kv.py);
``interpret=False`` hands it to the compiler and raises what the compiler
says.

Why it was written: the paged KV cache (models/transformer.py
``init_paged_kv_caches`` + runtime/batcher.py block tables) bills HBM for
pages actually written instead of ``max_len`` per slot — but the XLA
gather read still GATHERS the full logical view ([slots, n_pages*page_size])
back into a contiguous buffer before the attention einsum, i.e. it buys
capacity, not bandwidth. This kernel does what the gather cannot: for each
(sequence, page) grid step it streams exactly ONE page of K/V from HBM into
VMEM — addressed through the scalar-prefetched block table, the
vLLM/PagedAttention design (Kwon et al., SOSP 2023) — and accumulates the
masked softmax online, so the decode step's KV traffic is the pages the
block tables name, never the provisioned maximum.

Numerics: masking uses the pooled position rows exactly like the dense path
(PAD_POS slots get ``finfo(f32).min`` logits, contributing exact zeros), and
the online-softmax accumulation runs in f32. The kernel is NOT bit-identical
to the XLA einsum (different reduction order); the bit-exactness contract of
paged-vs-dense serving (tests/test_paged_kv.py) is carried by the gather
read, which IS the dense chain (``grouped_query_attention``) on gathered
bytes. Kernel parity tests run interpret-mode under the ``pallas`` marker
with tolerances.
"""

from __future__ import annotations

import functools


def paged_attention_ref(q, cache, block_tables, positions):
    """Pure-XLA reference: gather the logical view through the block table
    (models/transformer.py ``gather_paged_view``) and run serving's own
    masked-softmax chain on it (``grouped_query_attention``, K/V kept
    n_kv_heads wide) — the SAME two functions serving calls, so the
    oracle cannot drift from what serves. q: [b, 1, h, hd]; cache: the paged
    3-tuple (bf16) or 5-tuple (int8) pool; block_tables: [b, n_pages];
    positions: [b, 1]. Returns [b, 1, h, hd] in q.dtype."""
    from seldon_core_tpu.models.transformer import (
        gather_paged_view,
        grouped_query_attention,
    )

    k_all, v_all, pos_view = gather_paged_view(cache, block_tables, q.dtype)
    mask = pos_view[:, None, :] <= positions[:, :, None]  # [b, s, L]
    return grouped_query_attention(q, k_all, v_all, mask)


def _kernel(quantized: bool, n_pages: int, scale: float,
            bt_ref, qpos_ref, *refs):
    """Grid (b, n_pages): sequence i accumulates the online softmax over its
    block-table pages j (sequential axis). Scratch carries the running max,
    normalizer and weighted-value accumulator between pages."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if quantized:
        (q_ref, kq_ref, ks_ref, vq_ref, vs_ref, pos_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
        k = kq_ref[0].astype(jnp.float32) * ks_ref[0][..., None]
        v = vq_ref[0].astype(jnp.float32) * vs_ref[0][..., None]
    else:
        q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref = refs
        k = k_ref[0].astype(jnp.float32)   # [ps, kvh, hd]
        v = v_ref[0].astype(jnp.float32)
    i, j = pl.program_id(0), pl.program_id(1)

    q = q_ref[0].astype(jnp.float32)       # [h, hd]
    pos = pos_ref[0]                       # [ps]
    h, hd = q.shape
    ps, kvh, _ = k.shape
    if kvh != h:                           # GQA: repeat KV up to q heads
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)

    neg = jnp.finfo(jnp.float32).min
    logits = jnp.einsum("hd,phd->hp", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = pos <= qpos_ref[i]              # [ps] — PAD_POS never attends
    logits = jnp.where(mask[None, :], logits, neg)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, neg)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = m_ref[:, 0]                   # [h]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])   # [h, ps]
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc = acc_ref[...] * alpha[:, None] + jnp.einsum(
        "hp,phd->hd", p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
    acc_ref[...] = acc

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, 0][:, None]).astype(o_ref.dtype)


def paged_attention(q, cache, block_tables, positions, *, interpret: bool):
    """q: [b, 1, h, hd]; cache: paged pool tuple (bf16 3-tuple or int8
    5-tuple, [pages, page_size, kvh, hd] buffers); block_tables: [b,
    n_pages] int32; positions: [b, 1] int32 query positions. Returns
    [b, 1, h, hd] in q.dtype.

    One Pallas pass per (sequence, page) streaming only block-table-named
    pages. ``interpret=True`` runs it under the Pallas interpreter (the
    parity test); ``interpret=False`` compiles it, which no TPU toolchain
    installed here accepts (module docstring) — the error is the
    compiler's, nothing stands in."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, hd = q.shape
    assert s == 1, "paged_attention is the decode (s=1) read"
    quantized = len(cache) == 5
    ps = cache[0].shape[1]
    n_pages = int(block_tables.shape[1])

    bt = jnp.asarray(block_tables, jnp.int32)
    qpos = jnp.asarray(positions, jnp.int32)[:, 0]  # [b]
    q3 = q[:, 0]                                    # [b, h, hd]

    def page_map(i, j, bt_ref, qpos_ref):
        return (bt_ref[i, j], 0, 0, 0)

    def scale_map(i, j, bt_ref, qpos_ref):
        return (bt_ref[i, j], 0, 0)

    def pos_map(i, j, bt_ref, qpos_ref):
        return (bt_ref[i, j], 0)

    def seq_map(i, j, bt_ref, qpos_ref):
        return (i, 0, 0)

    kvh = cache[0].shape[2]
    page_spec = lambda arr: pl.BlockSpec((1, ps, kvh, hd), page_map)  # noqa: E731
    if quantized:
        kq, ks, vq, vs, pos_pool = cache
        ins = [q3, kq, ks, vq, vs, pos_pool]
        in_specs = [
            pl.BlockSpec((1, h, hd), seq_map),
            page_spec(kq),
            pl.BlockSpec((1, ps, kvh), scale_map),
            page_spec(vq),
            pl.BlockSpec((1, ps, kvh), scale_map),
            pl.BlockSpec((1, ps), pos_map),
        ]
    else:
        k_pool, v_pool, pos_pool = cache
        ins = [q3, k_pool, v_pool, pos_pool]
        in_specs = [
            pl.BlockSpec((1, h, hd), seq_map),
            page_spec(k_pool),
            page_spec(v_pool),
            pl.BlockSpec((1, ps), pos_map),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # block tables + query positions
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, hd), seq_map),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),  # running max
            pltpu.VMEM((h, 128), jnp.float32),  # running normalizer
            pltpu.VMEM((h, hd), jnp.float32),   # weighted-value accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, quantized, n_pages, hd**-0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(bt, qpos, *ins)
    return out[:, None]


__all__ = [
    "paged_attention",
    "paged_attention_ref",
]
