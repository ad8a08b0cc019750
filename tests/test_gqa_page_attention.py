"""Grouped-query attention's live-page read (ops/gqa_attention.py) under the
Pallas interpreter, held to ``paged_attention_ref``: the gathered view through
``grouped_query_attention``, the expression every lowering that is not for a
TPU keeps. (That Mosaic takes the kernel at the served shapes is in
tests/test_kernel_lowering.py and tests/test_tpu_program.py; what it costs on
the chip is in PERF.md and docs/performance.md.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.transformer import (
    NULL_PAGE, PAD_POS, TRASH_PAGE, paged_attention_ref)
from seldon_core_tpu.ops.gqa_attention import gqa_page_attention, gqa_plan
from seldon_core_tpu.ops.page_walk import Plan, live_pages, make_visits, rows_visited

PAGE = 32
NOBODY = -1   # a slot nobody holds: its table row is all TRASH_PAGE


class Pool:
    """A paged K / V pool filled the way the batcher fills one: sequence i
    holds ``rows[i]`` rows of ``width`` values on pages in no order, each row's
    position cached beside it; ``allocated`` table entries are backed by pages
    (those behind the rows are reset: positions PAD_POS)."""

    def __init__(self, rows, n_pages, width, allocated=None, seed=0):
        rng = np.random.default_rng(seed)
        b = len(rows)
        self.n = 2 + b * n_pages
        self.k = np.asarray(rng.normal(size=(self.n, PAGE, width)), np.float32)
        self.v = np.asarray(rng.normal(size=(self.n, PAGE, width)), np.float32)
        self.pos = np.full((self.n, PAGE), PAD_POS, np.int32)
        self.tables = np.full((b, n_pages), NULL_PAGE, np.int32)
        free = iter(rng.permutation(np.arange(2, self.n)))
        for i, held in enumerate(rows):
            if held == NOBODY:
                self.tables[i] = TRASH_PAGE
                continue
            backed = max(-(-held // PAGE), (allocated or [0] * b)[i])
            for j in range(backed):
                page = self.tables[i, j] = next(free)
                n = int(np.clip(held - j * PAGE, 0, PAGE))
                self.pos[page, :n] = j * PAGE + np.arange(n)

    def arrays(self):
        return ((jnp.asarray(self.k, jnp.bfloat16), jnp.asarray(self.v, jnp.bfloat16),
                 jnp.asarray(self.pos)), jnp.asarray(self.tables))


def queries(b, s, heads, hd, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, heads, hd), jnp.float32
                             ).astype(jnp.bfloat16)


def by_kernel(q, cache, tables, positions, kvh, walk):
    return gqa_page_attention(q, *cache, tables, positions, kvh, walk, interpret=True)


def last_positions(rows, s):
    """Each sequence's queries are its last ``s`` rows (a step: the row just
    written), PAD_POS where it has fewer, 0 for a slot nobody holds."""
    out = np.full((len(rows), s), PAD_POS, np.int32)
    for i, held in enumerate(rows):
        n = min(s, max(held, 0))
        out[i, :n] = np.arange(held - n, held)
        if held == NOBODY:
            out[i] = 0
    return jnp.asarray(out)


CASES = {
    # name: (heads, KV heads, head_dim, query tokens, rows each sequence holds,
    #        table entries, pool kwargs, walk)
    "decode step, rep 4, heads of 128": (16, 4, 128, 1, [100, 37, 1, 380], 12, {}, Plan(4, 16)),
    "decode step, rep 4, heads of 64": (32, 8, 64, 1, [100, 37, 1, 380], 12, {}, Plan(4, 32)),
    "decode step, rep 1, heads of 128": (16, 16, 128, 1, [100, 37, 380], 12, {}, Plan(4, 16)),
    "decode step, rep 1, heads of 64": (16, 16, 64, 1, [100, 37, 380], 12, {}, Plan(4, 16)),
    "decode step by the rule's walk": (16, 4, 128, 1, [1500, 640, 2040], 64, {}, None),
    "speculative verify, PAD_POS behind a short draft": (
        16, 4, 128, 3, [100, 37, 2, 380], 12, {}, Plan(4, 48)),
    "speculative verify, rep 1, heads of 64": (16, 16, 64, 5, [100, 3, 380], 12, {}, Plan(4, 80)),
    "a half-filled last page and a full one": (
        16, 4, 128, 1, [PAGE * 3 + 1, PAGE * 4], 12, {}, Plan(2, 16)),
    "pages allocated ahead of the rows (PAD_POS rows)": (
        16, 4, 64, 1, [50, 200], 12, dict(allocated=[6, 12]), Plan(4, 16)),
    "a slot nobody holds between two that decode": (
        16, 4, 128, 1, [90, NOBODY, 260], 12, {}, Plan(4, 16)),
    "nobody holds any slot": (16, 4, 128, 1, [NOBODY, NOBODY], 12, {}, Plan(4, 16)),
    "table entries no visit divides": (16, 4, 128, 1, [100, 210], 7, {}, Plan(4, 16)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_live_page_read_is_the_chain_over_the_gathered_view(case):
    heads, kvh, hd, s, rows, n_pages, pool_kwargs, walk = CASES[case]
    walk = walk or gqa_plan(s, heads, kvh, hd, n_pages, PAGE)
    cache, tables = Pool(rows, n_pages, kvh * hd, **pool_kwargs).arrays()
    positions = last_positions(rows, s)
    q = queries(len(rows), s, heads, hd)
    want = np.asarray(paged_attention_ref(q, cache, tables, positions, kvh), np.float32)
    got = np.asarray(by_kernel(q, cache, tables, positions, kvh, walk), np.float32)
    assert got.shape == want.shape == (len(rows), s, heads, hd)
    assert np.all(np.isfinite(got))
    valid = np.asarray((positions < PAD_POS) & (tables[:, :1] != TRASH_PAGE))
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-2, rtol=2e-2)
    # a slot with no valid query makes no visit and comes out zero
    for i, held in enumerate(rows):
        if held == NOBODY:
            assert np.all(got[i] == 0.0)
    visits = int(make_visits(tables, live_pages(tables, positions, PAGE), walk).count)
    per_visit = walk.pages * PAGE
    assert visits == max(sum(-(-max(held, 0) // per_visit) for held in rows), 1)
    assert visits * per_visit == max(sum(rows_visited(max(held, 0), PAGE, walk) for held in rows),
                                     per_visit)


@pytest.mark.parametrize("s,heads,kvh,hd", [(1, 16, 4, 128), (3, 32, 8, 64), (1, 16, 16, 128)])
def test_pages_behind_the_live_ones_are_never_read(s, heads, kvh, hd):
    """Whatever lies on a sequence's pages behind its queries' largest
    position (pages allocated ahead, the rest of a longer table) changes
    nothing, NaN included, in K or in V: they are not fetched."""
    rows, n_pages, walk = [70, 200], 12, Plan(4, s * heads)
    state = Pool(rows, n_pages, kvh * hd, allocated=[9, 12])
    q = queries(len(rows), s, heads, hd)
    positions = last_positions(rows, s)
    clean = by_kernel(q, *state.arrays(), positions, kvh, walk)
    for i, held in enumerate(rows):
        for page in state.tables[i, -(-held // PAGE):]:
            if page != NULL_PAGE:
                state.k[page] = state.v[page] = np.nan
    state.k[TRASH_PAGE] = state.v[TRASH_PAGE] = np.nan
    dirty = by_kernel(q, *state.arrays(), positions, kvh, walk)
    assert np.all(np.isfinite(np.asarray(dirty, np.float32)))
    np.testing.assert_array_equal(np.asarray(clean, np.float32), np.asarray(dirty, np.float32))


@pytest.mark.parametrize("pools", [1, 2])
def test_rows_no_visit_wrote_are_zeroed_outside_the_kernel(monkeypatch, pools):
    """A sequence with no valid query makes no visit, so the kernel never
    writes its output block: on the chip the block holds whatever the memory
    held. The interpreter hands the kernel zeros there and cannot show it, so
    here the call's result is poisoned where no visit wrote, as the chip may
    leave it, for both callers (latent attention's one pool, K and V): the
    rows still come out zero, and the others are untouched."""
    from jax.experimental import pallas as pl

    from seldon_core_tpu.ops import page_walk
    from seldon_core_tpu.ops.latent_attention import latent_page_attention

    real = pl.pallas_call

    def leaving_unwritten_blocks_dirty(kernel, **kwargs):
        call = real(kernel, **kwargs)

        def run(seq, group, last, live, table, *operands):
            out = call(seq, group, last, live, table, *operands)
            return jnp.where((live > 0)[:, None, None], out, jnp.nan)

        return run

    rows, n_pages, heads, kvh, hd = [90, NOBODY, 260, NOBODY], 12, 16, 4, 128
    cache, tables = Pool(rows, n_pages, kvh * hd).arrays()
    positions = last_positions(rows, 1)

    def read():
        if pools == 2:
            return by_kernel(queries(len(rows), 1, heads, hd), cache, tables, positions, kvh,
                             Plan(4, heads))
        return latent_page_attention(queries(len(rows), 1, heads, kvh * hd), cache[0], cache[2],
                                     tables, positions, 0.1, 256, Plan(4, heads), interpret=True)

    clean = np.asarray(read(), np.float32)
    monkeypatch.setattr(pl, "pallas_call", leaving_unwritten_blocks_dirty)
    page_walk._jitted_walk.cache_clear()     # (a trace of its own, not the clean one's)
    try:
        dirty = np.asarray(read(), np.float32)
    finally:
        page_walk._jitted_walk.cache_clear()
    assert np.all(dirty[[1, 3]] == 0.0)
    np.testing.assert_array_equal(clean, dirty)


def test_the_walk_at_the_served_shapes():
    """A step's or a verify's query rows are one tile over 1,024 K and V rows
    a visit (sixteen 64-row pages); rows wider than 2,048 values take as many
    as 8 MB hold; plain multi-head attention (OLMoE) is the same walk. A
    chunk's query rows, the int8 pool, a mesh (whose pool of heads of 128
    keeps its head axis: ``TransformerConfig.kv_rows_flat``) and a row that is
    no whole lane tile have no walk (``Attention`` keeps the expression)."""
    from seldon_core_tpu.models.transformer import TransformerConfig, paged_read_walk

    bf16 = jnp.bfloat16
    mistral = TransformerConfig(dim=4096, n_heads=32, n_kv_heads=8, dtype=bf16)
    olmoe = TransformerConfig(dim=2048, n_heads=16, n_kv_heads=16, dtype=bf16)
    lfm2 = TransformerConfig(dim=2048, n_heads=32, n_kv_heads=8, dtype=bf16)
    wide = TransformerConfig(dim=8192, n_heads=64, n_kv_heads=32, dtype=bf16)
    assert paged_read_walk(mistral, 1, 16, 64, bf16) == Plan(16, 32)
    assert paged_read_walk(mistral, 1, 64, 64, bf16) == Plan(16, 32)
    assert paged_read_walk(mistral, 4, 64, 64, bf16) == Plan(16, 128)     # a verify
    assert mistral.kv_rows_flat and lfm2.kv_rows_flat and olmoe.kv_rows_flat
    assert paged_read_walk(olmoe, 1, 16, 64, bf16) == Plan(16, 16)         # n_kv_heads == n_heads
    assert paged_read_walk(lfm2, 1, 64, 64, bf16) == Plan(16, 32)
    assert paged_read_walk(wide, 1, 64, 64, bf16) == Plan(8, 64)           # 4,096-wide rows
    assert paged_read_walk(mistral, 1, 5, 64, bf16) == Plan(6, 32)         # a short table
    assert paged_read_walk(mistral, 256, 64, 64, bf16) is None             # a chunk
    assert paged_read_walk(mistral, 16, 64, 64, bf16) is None              # 512 query rows
    assert paged_read_walk(mistral, 1, 64, 64, jnp.int8) is None           # the int8 pool
    on_mesh = [dataclasses.replace(cfg, mesh=object()) for cfg in (mistral, lfm2)]
    assert [cfg.kv_rows_flat for cfg in on_mesh] == [False, True]          # narrow heads stay flat
    assert all(paged_read_walk(cfg, 1, 64, 64, bf16) is None for cfg in on_mesh)
    assert paged_read_walk(mistral, 1, 64, 8, bf16) is None                # toy pages
    narrow = TransformerConfig(dim=192, n_heads=16, n_kv_heads=4, dtype=bf16)   # 4 x 12 = 48
    assert paged_read_walk(narrow, 1, 64, 64, bf16) is None
    assert gqa_plan(1, 4, 2, 128, 64, 64) is None                          # four query rows


GQA_TOY = dict(vocab_size=96, dim=512, n_layers=2, n_heads=16, n_kv_heads=4, ffn_dim=64,
               max_seq_len=256, dtype="bfloat16")


@pytest.mark.parametrize("more", [{}, dict(n_kv_heads=8), dict(qk_norm="head")],
                         ids=["rep 4", "rep 2", "a norm per head"])
def test_attention_through_the_kernel_is_attention_through_the_expression(monkeypatch, more):
    """``Attention`` picks by the lowering platform (the kernel for a TPU, the
    expression elsewhere). Here the TPU's branch is taken by hand, its kernel
    under the interpreter, through a chunk of a prompt (whose query rows keep
    the expression either way) and two decode steps of the paged pool, beside
    a slot nobody holds: the same logits as the branch tier-1 otherwise runs,
    and the same pool."""
    import seldon_core_tpu.ops.gqa_attention as module
    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.models.transformer import init_paged_kv_caches

    model = get_model("transformer", **{**GQA_TOY, **more})
    cfg = model.cfg
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :4])
    tables = jnp.asarray([[5, 2, 7, NULL_PAGE], [TRASH_PAGE] * 4], jnp.int32)

    def serve():
        pools = init_paged_kv_caches(cfg, 8, 32)
        out = []
        positions = jnp.stack([jnp.arange(48), jnp.full((48,), PAD_POS)]).astype(jnp.int32)
        logits, pools = model.apply(params, tokens, positions=positions, caches=pools,
                                    block_tables=tables)
        out.append(logits[0])
        for step in range(2):
            positions = jnp.asarray([[48 + step], [0]], jnp.int32)
            logits, pools = model.apply(params, tokens[:, step:step + 1], positions=positions,
                                        caches=pools, block_tables=tables)
            out.append(logits[0])
        return np.concatenate([np.asarray(x, np.float32) for x in out]), pools

    want, want_pools = serve()
    kernel, calls = module.gqa_page_attention, []

    def interpreted(*args, interpret, **kw):
        calls.append(args[0].shape)
        return kernel(*args, interpret=True, **kw)

    monkeypatch.setattr(module, "gqa_page_attention", interpreted)
    monkeypatch.setattr(jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))
    got, got_pools = serve()
    assert calls == [(2, 1, 16, 32)] * 4   # two layers of two steps; the chunk has no walk
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    for got_layer, want_layer in zip(got_pools, want_pools):
        assert got_layer[0].shape == (8, 32, cfg.n_kv_heads * cfg.head_dim)
        np.testing.assert_array_equal(np.asarray(got_layer[2]), np.asarray(want_layer[2]))
        for rows, want_rows in zip(got_layer[:2], want_layer[:2]):
            np.testing.assert_allclose(np.asarray(rows[2:], np.float32),
                                       np.asarray(want_rows[2:], np.float32), atol=3e-2, rtol=3e-2)


def test_the_loop_counts_whole_visits_over_live_rows_for_a_gqa_model(monkeypatch):
    """``seldon_llm_attn_rows_read_total`` for a model that runs ``Attention``:
    the whole block-table view of every sequence where the expression serves
    (here on the CPU; a chunk; the int8 pool; a mesh), whole visits over the
    live rows where the kernel does, by the ONE rule the module itself takes. A
    model with conv layers asks it of its first PAGED layer."""
    from types import SimpleNamespace

    from seldon_core_tpu.models.transformer import TransformerConfig
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    cfg = TransformerConfig(dim=2048, n_heads=32, n_kv_heads=8, n_layers=2, dtype=jnp.bfloat16,
                            layer_types=("conv", "full_attention"))

    def loop(cfg, pool_dtype=jnp.bfloat16):
        pool = jnp.zeros((1,), pool_dtype)
        return SimpleNamespace(server=SimpleNamespace(_cfg=cfg), n_pages=64, page_size=64,
                               _caches=[(jnp.zeros((1,), jnp.float32),), (pool, pool, None)],
                               _read_walks={})

    def rows_read(loop, *args):
        loop._read_walk = lambda s: ContinuousBatcher._read_walk(loop, s)
        return ContinuousBatcher._rows_read(loop, *args)

    view = 64 * 64
    assert rows_read(loop(cfg), 1, [3000, 900], 32) == 32 * view        # here: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # 30 slots nobody holds count nothing: their visit fetches nothing
    assert rows_read(loop(cfg), 1, [3000, 900], 32) == 3 * 1024 + 1024
    assert rows_read(loop(cfg), 1, [1024, 1025, 1], 32) == 1024 + 2048 + 1024
    assert rows_read(loop(cfg), 256, [3000], 1) == view                  # a chunk: the view
    assert rows_read(loop(cfg, jnp.int8), 1, [3000, 900], 32) == 32 * view
    assert rows_read(loop(dataclasses.replace(cfg, mesh=object())), 1, [3000], 32) == 32 * view
