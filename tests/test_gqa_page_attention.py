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

from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS, TRASH_PAGE
from seldon_core_tpu.models.transformer import paged_attention_ref
from seldon_core_tpu.ops.gqa_attention import gqa_page_attention, gqa_plan
from seldon_core_tpu.ops.page_walk import Plan, live_pages, make_visits, rows_visited

PAGE = 32
NOBODY = -1   # a slot nobody holds: its table row is all TRASH_PAGE


class Pool:
    """A paged K / V pool filled the way the batcher fills one: sequence i
    holds ``rows[i]`` rows of ``width`` values on pages in no order, each row's
    position cached beside it; ``allocated`` table entries are backed by pages
    (those behind the rows are reset: positions PAD_POS)."""

    def __init__(self, rows, n_pages, width, allocated=None, seed=0, page_size=PAGE):
        rng = np.random.default_rng(seed)
        b = len(rows)
        self.n = 2 + b * n_pages
        self.k = np.asarray(rng.normal(size=(self.n, page_size, width)), np.float32)
        self.v = np.asarray(rng.normal(size=(self.n, page_size, width)), np.float32)
        self.pos = np.full((self.n, page_size), PAD_POS, np.int32)
        self.tables = np.full((b, n_pages), NULL_PAGE, np.int32)
        free = iter(rng.permutation(np.arange(2, self.n)))
        for i, held in enumerate(rows):
            if held == NOBODY:
                self.tables[i] = TRASH_PAGE
                continue
            backed = max(-(-held // page_size), (allocated or [0] * b)[i])
            for j in range(backed):
                page = self.tables[i, j] = next(free)
                n = int(np.clip(held - j * page_size, 0, page_size))
                self.pos[page, :n] = j * page_size + np.arange(n)

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
    # 30 heads of their own (Olmo-Hybrid): 32 query rows, two of zeros, read and dropped
    "decode step, 30 heads of 128, rep 1": (30, 30, 128, 1, [100, 37, 380], 12, {}, None),
    "speculative verify, 30 heads": (30, 30, 128, 3, [100, 2, 380], 12, {}, None),
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


# the served chunk shapes (PERF.md section 4): heads, KV heads, head_dim,
# tokens a chunk, table entries of 64-row pages, the plan's walk
CHUNKS = {
    "mistral docs / rerank": (32, 8, 128, 256, 64, Plan(2, 1024, 8)),
    "mistral chat, 128 tokens": (32, 8, 128, 128, 16, Plan(2, 512, 8)),
    "mistral chat, 256 tokens": (32, 8, 128, 256, 16, Plan(2, 1024, 8)),
    "olmoe: heads of their own": (16, 16, 128, 256, 16, Plan(2, 256, 16)),
    "lfm2: two heads of 64 a block": (32, 8, 64, 256, 64, Plan(2, 2048, 4)),
    "qwen3-next: heads of 256": (16, 2, 256, 256, 128, Plan(2, 2048, 2)),
}


@pytest.mark.parametrize("case", list(CHUNKS))
def test_a_chunks_read_a_head_block_is_the_chain_over_the_gathered_view(monkeypatch, case):
    """The prefill chunk's form (a lane block a KV head, one product a block
    inside a visit of whole rows, its group's query heads the lane slices of a
    token's row) at every served chunk shape, as a
    prompt's LAST chunk behind an offset: its second half padding (PAD_POS),
    beside a slot nobody holds. Held to the expression on every valid row;
    then again with everything the walk must not touch poisoned, NaN on the
    pages behind the live ones and on TRASH_PAGE, in K and in V, and the
    output blocks no visit wrote left dirty as the chip may leave them (the
    interpreter hands the kernel zeros): nothing changes, the padded rows stay
    finite, the slot nobody holds comes out zero."""
    from jax.experimental import pallas as pl

    from seldon_core_tpu.ops import page_walk

    heads, kvh, hd, s, n_pages, walk = CHUNKS[case]
    assert gqa_plan(s, heads, kvh, hd, n_pages, 64) == walk
    offset = min(3 * s, n_pages * 64 - s) + 7 if n_pages > 16 else s // 2
    valid_rows = s // 2 + 5
    held = offset + valid_rows
    state = Pool([held, NOBODY], n_pages, kvh * hd, allocated=[n_pages, 0], page_size=64)
    positions = np.full((2, s), PAD_POS, np.int32)
    positions[0, :valid_rows] = offset + np.arange(valid_rows)
    positions[1] = 0
    positions = jnp.asarray(positions)
    q = queries(2, s, heads, hd)

    cache, tables = state.arrays()
    want = np.asarray(paged_attention_ref(q, cache, tables, positions, kvh), np.float32)
    clean = np.asarray(by_kernel(q, cache, tables, positions, kvh, walk), np.float32)
    assert clean.shape == want.shape == (2, s, heads, hd)
    np.testing.assert_allclose(clean[0, :valid_rows], want[0, :valid_rows], atol=2e-2, rtol=2e-2)
    visits = make_visits(tables, live_pages(tables, positions, 64), walk)
    assert int(visits.count) * 128 == rows_visited(held, 64, walk) == -(-held // 128) * 128

    for page in state.tables[0, -(-held // 64):]:
        state.k[page] = state.v[page] = np.nan
    state.k[TRASH_PAGE] = state.v[TRASH_PAGE] = np.nan
    real = pl.pallas_call

    def leaving_unwritten_blocks_dirty(kernel, **kwargs):
        call = real(kernel, **kwargs)

        def run(seq, group, last, live, table, *operands):
            out = call(seq, group, last, live, table, *operands)
            return jnp.where((live > 0)[:, None, None], out, jnp.nan)

        return run

    monkeypatch.setattr(pl, "pallas_call", leaving_unwritten_blocks_dirty)
    page_walk._jitted_walk.cache_clear()     # (a trace of its own, not the clean one's)
    try:
        dirty = np.asarray(by_kernel(q, *state.arrays(), positions, kvh, walk), np.float32)
    finally:
        page_walk._jitted_walk.cache_clear()
    assert np.all(np.isfinite(dirty))
    assert np.all(dirty[1] == 0.0)
    np.testing.assert_array_equal(clean, dirty)


def test_a_chunks_first_tile_of_a_long_prompt_and_a_whole_chunk():
    """A chunk with every row valid at an offset deep in a 4,096-row view
    (twenty-eight visits, the last two under the predicate and the others
    without) and the prompt's FIRST chunk (two visits): the walk's rows follow
    the live rows, not the view."""
    heads, kvh, hd, s, n_pages, walk = CHUNKS["mistral docs / rerank"]
    for offset in (0, 3328):
        held = offset + s
        cache, tables = Pool([held], n_pages, kvh * hd, page_size=64).arrays()
        positions = jnp.asarray(offset + np.arange(s, dtype=np.int32))[None]
        q = queries(1, s, heads, hd)
        want = np.asarray(paged_attention_ref(q, cache, tables, positions, kvh), np.float32)
        got = np.asarray(by_kernel(q, cache, tables, positions, kvh, walk), np.float32)
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
        visits = make_visits(tables, live_pages(tables, positions, 64), walk)
        assert int(visits.count) == -(-held // 128)


def test_the_walk_at_the_served_shapes():
    """A step's or a verify's query rows are one tile over 1,024 K and V rows
    a visit (sixteen 64-row pages); rows wider than 2,048 values take as many
    as 8 MB hold; plain multi-head attention (OLMoE) is the same walk. From one
    tile of query rows on (a chunk) the walk is a lane block a KV head, 128
    rows a visit against up to 2,048 query rows of whole tokens a block. The int8
    pool, a mesh (whose pool of heads of 128 keeps its head axis:
    ``TransformerConfig.kv_rows_flat``) and a row that is no whole lane tile
    have no walk (``Attention`` keeps the expression)."""
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
    assert paged_read_walk(mistral, 256, 64, 64, bf16) == Plan(2, 1024, blocks=8)  # a chunk
    assert paged_read_walk(mistral, 128, 16, 64, bf16) == Plan(2, 512, blocks=8)   # chat's other one
    assert paged_read_walk(mistral, 16, 64, 64, bf16) == Plan(2, 64, blocks=8)     # 512 query rows
    assert paged_read_walk(olmoe, 256, 16, 64, bf16) == Plan(2, 256, blocks=16)
    assert paged_read_walk(lfm2, 256, 64, 64, bf16) == Plan(2, 2048, blocks=4)     # two heads a block
    qwen = TransformerConfig(dim=2048, n_heads=16, n_kv_heads=2, head_dim=256, dtype=bf16)
    assert paged_read_walk(qwen, 256, 128, 64, bf16) == Plan(2, 2048, blocks=2)
    assert paged_read_walk(mistral, 24, 64, 64, bf16) is None              # 96 rows a block: no whole tiles
    assert paged_read_walk(mistral, 1, 64, 64, jnp.int8) is None           # the int8 pool
    on_mesh = [dataclasses.replace(cfg, mesh=object()) for cfg in (mistral, lfm2)]
    assert [cfg.kv_rows_flat for cfg in on_mesh] == [False, True]          # narrow heads stay flat
    assert all(paged_read_walk(cfg, 1, 64, 64, bf16) is None for cfg in on_mesh)
    assert paged_read_walk(mistral, 1, 64, 8, bf16) is None                # toy pages
    narrow = TransformerConfig(dim=192, n_heads=16, n_kv_heads=4, dtype=bf16)   # 4 x 12 = 48
    assert paged_read_walk(narrow, 1, 64, 64, bf16) is None
    assert gqa_plan(1, 4, 2, 128, 64, 64) is None                          # four query rows
    # 30 heads a token are two bf16 sublane tiles of query rows, two rows of zeros
    olmo_hybrid = TransformerConfig(dim=3840, n_heads=30, n_kv_heads=30, dtype=bf16)
    assert paged_read_walk(olmo_hybrid, 1, 16, 64, bf16) == Plan(8, 32)     # 3,840-wide rows
    assert paged_read_walk(olmo_hybrid, 256, 16, 64, bf16) == Plan(2, 256, blocks=30)


GQA_TOY = dict(vocab_size=96, dim=512, n_layers=2, n_heads=16, n_kv_heads=4, ffn_dim=64,
               max_seq_len=256, dtype="bfloat16")


@pytest.mark.parametrize("more,chunk_walks", [
    ({}, False), (dict(n_kv_heads=8), True), (dict(qk_norm="head"), False),
    (dict(head_dim=128), True), (dict(head_dim=128, n_kv_heads=16, attn_gate=True), True)],
    ids=["rep 4", "rep 2: four KV heads a block", "a norm per head", "heads of 128",
         "heads of their own, gated"])
def test_attention_through_the_kernel_is_attention_through_the_expression(monkeypatch, more,
                                                                          chunk_walks):
    """``Attention`` picks by the lowering platform (the kernel for a TPU, the
    expression elsewhere). Here the TPU's branch is taken by hand, its kernel
    under the interpreter, through a chunk of a prompt (a lane block a KV head
    where its query rows divide into whole tiles; 768 rows against one block do
    not, and keep the expression) and two decode steps of the paged pool,
    beside a slot nobody holds: the same logits as the branch tier-1 otherwise
    runs, and the same pool."""
    import seldon_core_tpu.ops.gqa_attention as module
    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.models.cache import init_paged_kv_caches
    from seldon_core_tpu.models.transformer import paged_live_read, transformer_block

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
    # (the read is a jitted function, and so is the block that calls it: traces of their own)
    paged_live_read.clear_cache()
    transformer_block.clear_cache()
    try:
        got, got_pools = serve()
    finally:
        paged_live_read.clear_cache()
        transformer_block.clear_cache()
    # the chunk where it has a walk, the step: a trace a call shape (the layers
    # and the steps share the jitted read's)
    assert calls == [(2, 48, 16, cfg.head_dim)] * chunk_walks + [(2, 1, 16, cfg.head_dim)]
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    for got_layer, want_layer in zip(got_pools, want_pools):
        assert got_layer[0].shape == (8, 32, cfg.n_kv_heads * cfg.head_dim)
        np.testing.assert_array_equal(np.asarray(got_layer[2]), np.asarray(want_layer[2]))
        # (the second layer's rows come through the first layer's read: a few bf16
        # steps of a value of 2-4 where the chunk's sums were taken in another order)
        for rows, want_rows in zip(got_layer[:2], want_layer[:2]):
            np.testing.assert_allclose(np.asarray(rows[2:], np.float32),
                                       np.asarray(want_rows[2:], np.float32), atol=1e-1, rtol=3e-2)


def test_the_loop_counts_whole_visits_over_live_rows_for_a_gqa_model(monkeypatch):
    """``seldon_llm_attn_rows_read_total`` for a model that runs ``Attention``:
    the whole block-table view of every sequence where the expression serves
    (here on the CPU; the int8 pool; a mesh), whole visits over the live rows
    where the kernel does (a step's of 1,024 rows, a chunk's of 128), by the
    ONE rule the module itself takes. A
    model with conv layers asks it of its first PAGED layer."""
    from types import SimpleNamespace

    from seldon_core_tpu.models.cache import StateEntry
    from seldon_core_tpu.models.transformer import TransformerConfig
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    cfg = TransformerConfig(dim=2048, n_heads=32, n_kv_heads=8, n_layers=2, dtype=jnp.bfloat16,
                            layer_types=("conv", "full_attention"))

    def loop(cfg, pool_dtype=jnp.bfloat16):
        pool = jnp.zeros((1,), pool_dtype)
        return SimpleNamespace(server=SimpleNamespace(_cfg=cfg), n_pages=64, page_size=64,
                               _caches=[StateEntry((jnp.zeros((1,), jnp.float32),)),
                                        (pool, pool, None)],
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
    assert rows_read(loop(cfg), 256, [3000], 1) == 24 * 128              # a chunk: 128-row visits
    assert rows_read(loop(cfg), 256, [256], 1) == 256                    # a prompt's first chunk
    assert rows_read(loop(cfg, jnp.int8), 256, [3000], 1) == view        # the int8 pool: the view
    assert rows_read(loop(cfg, jnp.int8), 1, [3000, 900], 32) == 32 * view
    assert rows_read(loop(dataclasses.replace(cfg, mesh=object())), 1, [3000], 32) == 32 * view


# ---- a sliding-attention layer's read: a FIRST live page as well as a last ------
WINDOW_CASES = {
    # name: (heads, KV heads, head_dim, query tokens, rows each sequence holds,
    #        table entries, window, pages behind the window given back?, walk)
    "decode step, the pages behind the window given back": (
        16, 4, 128, 1, [100, 37, 1, 380, NOBODY, 300], 12, 96, True, Plan(4, 16)),
    "decode step, 7 query heads a KV head (28 / 4), by the rule's walk": (
        28, 4, 128, 1, [100, 37, 380, 300], 12, 100, True, None),
    "decode step, stale rows behind the window still on their pages": (
        16, 4, 128, 1, [100, 380, 300], 12, 70, False, Plan(4, 16)),
    "decode step, a window wider than every sequence": (
        16, 4, 128, 1, [100, 37, 380], 12, 4096, True, Plan(4, 16)),
    "chunk, a lane block a KV head, 7 heads a block": (28, 4, 128, 64, [380], 12, 96, True, None),
    "chunk, stale rows behind the window, a window of two pages": (
        28, 4, 128, 128, [300], 12, 64, False, None),
    "chunk, rep 4, the window's edge inside the chunk's own rows": (
        16, 4, 128, 256, [384], 12, 70, True, None),
}


def give_back(pool, rows, positions, window):
    """What the batcher does before the call: every page whose last position
    lies below the first query's window is freed (NaN: it is another's now) and
    its table entry reads NULL_PAGE."""
    for i, held in enumerate(rows):
        if held == NOBODY:
            continue
        valid = np.asarray(positions[i])
        first = (int(valid[valid < PAD_POS].min()) - window + 1) // PAGE
        for j in range(max(first, 0)):
            page = pool.tables[i, j]
            pool.k[page] = pool.v[page] = np.nan
            pool.pos[page] = PAD_POS
            pool.tables[i, j] = NULL_PAGE


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_the_window_read_is_the_chain_with_the_lower_bound(case):
    from seldon_core_tpu.ops.page_walk import first_live_pages

    heads, kvh, hd, s, rows, n_pages, window, freed, walk = WINDOW_CASES[case]
    walk = walk or gqa_plan(s, heads, kvh, hd, n_pages, PAGE)
    pool = Pool(rows, n_pages, kvh * hd)
    positions = last_positions(rows, s)
    if freed:
        give_back(pool, rows, positions, window)
    cache, tables = pool.arrays()
    q = queries(len(rows), s, heads, hd)
    # the oracle multiplies the whole view: the freed pages' NaN as zeros there
    clean = tuple(jnp.nan_to_num(a) if a.dtype != jnp.int32 else a for a in cache)
    want = np.asarray(paged_attention_ref(q, clean, tables, positions, kvh, window), np.float32)
    got = np.asarray(gqa_page_attention(q, *cache, tables, positions, kvh, walk, interpret=True,
                                        window=window), np.float32)
    assert np.all(np.isfinite(got))     # no page behind the first live one was fetched
    valid = np.asarray((positions < PAD_POS) & (tables[:, :1] != TRASH_PAGE))
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-2, rtol=2e-2)
    if window < max(rows):      # ... and the bound decides something
        unbounded = np.asarray(paged_attention_ref(q, clean, tables, positions, kvh), np.float32)
        assert np.abs(unbounded[valid] - want[valid]).max() > 0.1
    for i, held in enumerate(rows):
        if held == NOBODY:
            assert np.all(got[i] == 0.0)
    # the visits: from the group that holds each sequence's first live page
    first = first_live_pages(tables, positions, PAGE, window)
    visits = make_visits(tables, live_pages(tables, positions, PAGE), walk, first)
    per_visit = walk.pages * PAGE
    expected = 0
    for held in rows:
        if held > 0:
            low = max(max(held - s, 0) - window + 1, 0)
            assert rows_visited(held, PAGE, walk, low // PAGE * PAGE) == (
                -(-held // per_visit) - low // per_visit) * per_visit
            expected += -(-held // per_visit) - low // per_visit
    assert int(visits.count) == max(expected, 1)
    assert int(visits.start.sum()) >= min(expected, 1)


@pytest.mark.parametrize("window", [0, 96], ids=["full layer", "window layer"])
@pytest.mark.parametrize("valid_rows", [64 + 64 + 30, 64 + 9, 256 - 7],
                         ids=["third tile part padding, fourth all", "two tiles all padding",
                              "the last tile's last rows"])
def test_a_padded_wide_chunks_query_tiles_behind_the_prompts_end(valid_rows, window):
    """A prompt's tail in ONE wide chunk (PR 58): a chunk of SEVERAL query tiles
    (here four of 64 tokens x 4 heads a lane block) behind a context, its rows
    behind the prompt's end padding: the tile that holds the prompt's last row is
    part padding and the tiles behind it are ALL padding (their smallest position
    is PAD_POS: every visit of theirs is "whole", computes no predicate, and their
    rows are nobody's). Every valid row is the expression's, every row finite."""
    heads, kvh, hd, s, n_pages, walk = 16, 4, 128, 256, 24, Plan(2, 256, 4)
    held = 300 + valid_rows
    pool = Pool([held, NOBODY], n_pages, kvh * hd, allocated=[n_pages, 0])
    positions = np.full((2, s), PAD_POS, np.int32)
    positions[0, :valid_rows] = 300 + np.arange(valid_rows)
    positions[1] = 0
    positions = jnp.asarray(positions)
    if window:
        give_back(pool, [held, NOBODY], positions, window)
    cache, tables = pool.arrays()
    q = queries(2, s, heads, hd)
    clean = tuple(jnp.nan_to_num(a) if a.dtype != jnp.int32 else a for a in cache)
    want = np.asarray(paged_attention_ref(q, clean, tables, positions, kvh, window), np.float32)
    got = np.asarray(gqa_page_attention(q, *cache, tables, positions, kvh, walk, interpret=True,
                                        window=window), np.float32)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[0, :valid_rows], want[0, :valid_rows], atol=2e-2, rtol=2e-2)
    assert np.all(got[1] == 0.0)


def test_a_chunk_of_seven_heads_a_kv_head_gets_a_tile_that_divides_its_rows():
    """28 query heads over 4 KV heads (SmallThinker): 512 tokens are 3,584 query
    rows a lane block, which 2,048 does not divide; the walk takes the largest
    tile under it that does, in whole sublane tiles of tokens a head."""
    assert gqa_plan(256, 28, 4, 128, 256, 64) == Plan(pages=2, q_tile=1792, blocks=4)
    assert gqa_plan(512, 28, 4, 128, 256, 64) == Plan(pages=2, q_tile=1792, blocks=4)
    assert gqa_plan(1024, 28, 4, 128, 256, 64) == Plan(pages=2, q_tile=1792, blocks=4)
    # ... and a shape whose rows 2,048 divides walks as it did
    assert gqa_plan(512, 32, 8, 128, 64, 64) == Plan(pages=2, q_tile=2048, blocks=8)
