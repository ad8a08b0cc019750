"""Latent attention's live-page read (ops/latent_attention.py) under the Pallas
interpreter, held to ``absorbed_latent_attention`` over the gathered view: the
expression every lowering that is not for a TPU keeps. (That Mosaic takes the
kernel at the served shapes is in tests/test_kernel_lowering.py and
tests/test_tpu_program.py; what it costs on the chip is in PERF.md and
docs/performance.md.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS, TRASH_PAGE
from seldon_core_tpu.models.transformer import absorbed_latent_attention, absorbed_query_rows
from seldon_core_tpu.ops.latent_attention import (ExpandedWalk, expanded_walk,
                                                  latent_expanded_attention, latent_page_attention)
from seldon_core_tpu.ops.page_walk import Plan, live_pages, make_visits, plan, rows_visited

PAGE, DN, DR, DC, DV, WIDTH, SCALE = 32, 32, 16, 128, 32, 256, 0.11
NOBODY = -1   # a slot nobody holds: its table row is all TRASH_PAGE


class Pool:
    """A paged latent pool filled the way the batcher fills one: sequence i
    holds ``rows[i]`` rows on pages in no order, each row's position cached
    beside it; ``shared`` leading pages are the SAME pages in every sequence
    (a radix-trie prefix); ``allocated`` table entries are backed by pages
    (those behind the rows are reset: positions PAD_POS)."""

    def __init__(self, rows, n_pages, allocated=None, shared=0, seed=0):
        rng = np.random.default_rng(seed)
        b = len(rows)
        self.n = 2 + b * n_pages
        self.rows = np.asarray(rng.normal(size=(self.n, PAGE, WIDTH)), np.float32)
        self.rows[..., DC + DR:] = 0.0
        self.pos = np.full((self.n, PAGE), PAD_POS, np.int32)
        self.tables = np.full((b, n_pages), NULL_PAGE, np.int32)
        free = iter(rng.permutation(np.arange(2, self.n)))
        prefix = [next(free) for _ in range(shared)]
        for i, held in enumerate(rows):
            if held == NOBODY:
                self.tables[i] = TRASH_PAGE
                continue
            backed = max(-(-held // PAGE), (allocated or [0] * b)[i])
            for j in range(backed):
                page = prefix[j] if j < shared else next(free)
                self.tables[i, j] = page
                n = int(np.clip(held - j * PAGE, 0, PAGE))
                self.pos[page, :n] = j * PAGE + np.arange(n)

    def arrays(self):
        return (jnp.asarray(self.rows, jnp.bfloat16), jnp.asarray(self.pos),
                jnp.asarray(self.tables))


def queries(b, s, heads, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf16 = jnp.bfloat16
    return (jax.random.normal(keys[0], (b, s, heads, DN), jnp.float32).astype(bf16),
            jax.random.normal(keys[1], (b, s, heads, DR), jnp.float32).astype(bf16),
            (jax.random.normal(keys[2], (heads, DN, DC), jnp.float32) * DN ** -0.5).astype(bf16),
            (jax.random.normal(keys[3], (heads, DC, DV), jnp.float32) * DC ** -0.5).astype(bf16))


def by_expression(q, pool, pos_pool, tables, positions):
    """The whole logical view gathered, then the one expression."""
    b, n_pages = tables.shape
    view = pool[tables].reshape(b, n_pages * PAGE, WIDTH)
    pos_view = pos_pool[tables].reshape(b, n_pages * PAGE)
    mask = pos_view[:, None, :] <= positions[:, :, None]
    return absorbed_latent_attention(*q, view, mask, SCALE)


def by_kernel(q, pool, pos_pool, tables, positions, walk):
    q_nope, q_rope, w_uk, w_uv = q
    if isinstance(walk, ExpandedWalk):   # the projection's rows and W_UK / W_UV as they are
        out = latent_expanded_attention(q_nope, q_rope, w_uk, w_uv, pool, pos_pool, tables,
                                        positions, SCALE, walk, interpret=True)
        return out.reshape(q_nope.shape[:3] + (DV,))
    ctx = latent_page_attention(
        absorbed_query_rows(q_nope, q_rope, w_uk, WIDTH), pool, pos_pool, tables, positions,
        SCALE, DC, walk, interpret=True)
    return jnp.einsum("bshc,hcv->bshv", ctx, w_uv)


def last_positions(rows, s, valid=None):
    """Each sequence's queries are its last ``s`` rows (a step: the row just
    written), PAD_POS where it has fewer, 0 for a slot nobody holds; with
    ``valid`` its last ``valid`` rows, PAD_POS behind them (a prompt's last
    chunk, padded to the program's width)."""
    out = np.full((len(rows), s), PAD_POS, np.int32)
    for i, held in enumerate(rows):
        n = min(valid or s, max(held, 0))
        out[i, :n] = np.arange(held - n, held)
        if held == NOBODY:
            out[i] = 0
    return jnp.asarray(out)


CASES = {
    # name: (heads, query tokens, rows each sequence holds, table entries, pool kwargs, walk)
    "decode step, 16 heads, a visit of four pages": (16, 1, [100, 37, 1, 380], 12, {}, Plan(4, 16)),
    "decode step, 32 heads": (32, 1, [100, 37, 1, 380], 12, {}, Plan(4, 32)),
    "decode step by the rule's walk": (16, 1, [1500, 640, 2040], 64, {}, None),
    "prefill chunk, 16 heads, four query tiles": (16, 16, [300], 12, {}, Plan(4, 64)),
    "prefill chunk, 32 heads": (32, 16, [300], 12, {}, Plan(4, 128)),
    "prefill chunk by the rule's walk": (16, 64, [1100], 40, {}, None),
    "a prompt's last chunk: PAD_POS behind its tokens": (16, 16, [7], 12, {}, Plan(2, 64)),
    "speculative verify, 16 heads": (16, 3, [100, 37, 2, 380], 12, {}, Plan(4, 48)),
    "speculative verify, 32 heads": (32, 5, [100, 37, 380], 12, {}, Plan(4, 160)),
    "a half-filled last page and a full one": (16, 1, [PAGE * 3 + 1, PAGE * 4], 12, {}, Plan(2, 16)),
    "pages allocated ahead of the rows (PAD_POS rows)": (
        16, 1, [50, 200], 12, dict(allocated=[6, 12]), Plan(4, 16)),
    "a slot nobody holds between two that decode": (16, 1, [90, NOBODY, 260], 12, {}, Plan(4, 16)),
    "nobody holds any slot": (16, 1, [NOBODY, NOBODY], 12, {}, Plan(4, 16)),
    "trie-shared leading pages": (16, 1, [200, 170, 330], 12, dict(shared=5), Plan(4, 16)),
    "table entries no visit divides": (16, 1, [100, 210], 7, {}, Plan(4, 16)),
    # the expanded-once kernel (a wide chunk's read): one tile of all the tokens ...
    "expanded: a chunk at offset 0": (16, 128, [128], 12, {}, ExpandedWalk(4, 128)),
    "expanded: a chunk of 1,024 tokens behind a context": (
        4, 1024, [1024 + 300], 44, {}, ExpandedWalk(4, 1024)),
    "expanded: mid-page after a copy-on-write hit": (
        16, 128, [PAGE * 2 + 11 + 100], 12, dict(valid=100), ExpandedWalk(4, 128)),
    "expanded: a context that ends mid-visit": (16, 128, [PAGE * 5 + 7], 12, {}, ExpandedWalk(4, 128)),
    "expanded: NULL pages behind the live ones": (
        16, 128, [200], 12, dict(allocated=[9]), ExpandedWalk(2, 128)),
    # ... or the fallback's two
    "expanded: two token tiles": (16, 256, [700], 24, {}, ExpandedWalk(4, 128)),
    "expanded: 32 heads, a visit of two pages": (32, 128, [333], 12, {}, ExpandedWalk(2, 128)),
    "expanded: a prompt's last chunk, PAD_POS behind its tokens": (
        16, 128, [50], 12, dict(valid=50), ExpandedWalk(4, 128)),
    "expanded: two sequences and a slot nobody holds": (
        4, 128, [400, NOBODY, 150], 14, {}, ExpandedWalk(4, 128)),
    # a prompt's tail in ONE wide chunk behind its context (PR 58): the rows behind
    # the prompt's end are padding in the last token tile, or are the whole of it
    "expanded: a padded wide chunk, its second tile part padding": (
        16, 256, [500 + 200], 24, dict(valid=200), ExpandedWalk(4, 128)),
    "expanded: a padded wide chunk, its second tile all padding": (
        16, 256, [500 + 100], 24, dict(valid=100), ExpandedWalk(4, 128)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_live_page_read_is_the_expression_over_the_gathered_view(case):
    heads, s, rows, n_pages, pool_kwargs, walk = CASES[case]
    walk = walk or plan(s, heads, n_pages, PAGE, WIDTH, DC)
    pool_kwargs = dict(pool_kwargs)
    positions = last_positions(rows, s, pool_kwargs.pop("valid", None))
    pool, pos_pool, tables = Pool(rows, n_pages, **pool_kwargs).arrays()
    q = queries(len(rows), s, heads)
    want = np.asarray(by_expression(q, pool, pos_pool, tables, positions), np.float32)
    got = np.asarray(by_kernel(q, pool, pos_pool, tables, positions, walk), np.float32)
    visits = int(make_visits(tables, live_pages(tables, positions, PAGE), walk).count)
    assert np.all(np.isfinite(got))
    valid = np.asarray((positions < PAD_POS) & (tables[:, :1] != TRASH_PAGE))
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-2, rtol=2e-2)
    # a slot with no valid query makes no visit and comes out zero
    for i, held in enumerate(rows):
        if held == NOBODY:
            assert np.all(got[i] == 0.0)
    per_visit = walk.pages * PAGE
    assert visits == max(sum(-(-max(held, 0) // per_visit) for held in rows), 1)
    assert visits * per_visit == max(sum(rows_visited(max(held, 0), PAGE, walk) for held in rows),
                                     per_visit)


@pytest.mark.parametrize("s,heads", [(1, 16), (16, 16), (3, 32)])
def test_pages_behind_the_live_ones_are_never_read(s, heads):
    """Whatever lies on a sequence's pages behind its queries' largest
    position (pages allocated ahead, the rest of a longer table) changes
    nothing, NaN included: they are not fetched."""
    rows, n_pages, walk = [70, 200], 12, Plan(4, s * heads)
    state = Pool(rows, n_pages, allocated=[9, 12])
    q = queries(len(rows), s, heads)
    positions = last_positions(rows, s)
    clean = by_kernel(q, *state.arrays(), positions, walk)
    for i, held in enumerate(rows):
        for page in state.tables[i, -(-held // PAGE):]:
            if page != NULL_PAGE:
                state.rows[page] = np.nan
    state.rows[TRASH_PAGE] = np.nan
    dirty = by_kernel(q, *state.arrays(), positions, walk)
    assert np.all(np.isfinite(np.asarray(dirty, np.float32)))
    np.testing.assert_array_equal(np.asarray(clean, np.float32), np.asarray(dirty, np.float32))


def test_the_visit_list():
    """Three sequences over tables of ten entries, four a visit: one with
    five live pages visits two groups, one nobody holds visits none, one with
    ten visits all three; entries behind the live pages read as NULL_PAGE.
    Where nobody holds any slot the grid is one visit that finishes nothing."""
    tables = jnp.asarray(np.arange(2, 32).reshape(3, 10), jnp.int32).at[1].set(TRASH_PAGE)
    positions = jnp.asarray([[PAGE * 4 + 3], [0], [PAGE * 10 - 1]], jnp.int32)
    live = live_pages(tables, positions, PAGE)
    assert live.tolist() == [5, 0, 10]
    visits = make_visits(tables, live, Plan(4, 16))
    n = int(visits.count)
    assert n == 5
    assert visits.seq[:n].tolist() == [0, 0, 2, 2, 2]
    assert visits.group[:n].tolist() == [0, 1, 0, 1, 2]
    assert visits.last[:n].tolist() == [0, 1, 0, 0, 1]
    nobody = make_visits(tables.at[:].set(TRASH_PAGE), jnp.zeros((3,), jnp.int32), Plan(4, 16))
    assert int(nobody.count) == 1 and int(nobody.last[0]) == 0 and int(nobody.live[nobody.seq[0]]) == 0
    table = np.asarray(visits.table).reshape(3, 12)
    assert table[0].tolist() == [2, 3, 4, 5, 6] + [NULL_PAGE] * 7
    assert table[1].tolist() == [NULL_PAGE] * 12
    assert table[2].tolist() == list(range(22, 32)) + [NULL_PAGE] * 2
    # padding and a position past the table count for nothing, a negative one neither
    padded = jnp.asarray([[3, PAD_POS], [-1, PAD_POS], [PAGE * 40, 5]], jnp.int32)
    assert live_pages(tables.at[1].set(7), padded, PAGE).tolist() == [1, 0, 10]


def test_the_walk_at_the_served_shapes():
    """A step's or a verify's query rows are one tile over 1,024 rows a visit
    (sixteen 64-row pages); a chunk's are tiles of 512 over 512 rows a visit;
    a shape the kernel does not take has no walk (the caller keeps the
    expression)."""
    for heads, slots_pages in ((16, 256), (32, 64)):
        assert plan(1, heads, slots_pages, 64, 640, 512) == Plan(16, heads)
        assert plan(256, heads, slots_pages, 64, 640, 512) == Plan(8, 512)
        assert plan(128, heads, slots_pages, 64, 640, 512) == Plan(8, 512)
        assert plan(3, heads, slots_pages, 64, 640, 512) == Plan(16, 3 * heads)
    assert plan(1, 16, 5, 64, 640, 512) == Plan(6, 16)    # a short table: whole lane tiles
    assert plan(1, 16, 256, 64, 576, 512) is None          # a row that is no whole lane tile
    assert plan(1, 16, 256, 64, 640, 448) is None
    assert plan(1, 16, 256, 16, 640, 512) is None          # a visit of 64 pages
    assert plan(1, 2, 256, 64, 640, 512) is None           # two query rows
    assert plan(40, 16, 256, 64, 640, 512) is None         # 640 query rows: no whole tiles
    assert rows_visited(0, 64, Plan(16, 16)) == 0 and rows_visited(1024, 64, Plan(16, 16)) == 1024
    assert rows_visited(1025, 64, Plan(16, 16)) == 2048


def test_the_expanded_walk_at_the_served_shapes():
    """The form follows the call's tokens a tile: DeepSeek-V2-Lite's wide chunk
    (1,024 tokens x 16 heads) is ONE tile over 512 rows a visit; Xing4's wide
    program (32 heads) two tiles of 512; a chunk of 256 tokens, a decode step
    and a speculative verify stay absorbed (the expansion a visit would cost
    more than it spares), whatever the heads; widths that are no whole lane
    tiles have no expanded walk."""
    widths = dict(nope=128, rope=64, v_dim=128, latent=512, page_size=64, row_dim=640)
    assert expanded_walk(1024, 16, n_pages=256, **widths) == ExpandedWalk(8, 1024)
    assert expanded_walk(2048, 16, n_pages=256, **widths) == ExpandedWalk(8, 1024)
    assert expanded_walk(1024, 32, n_pages=64, **widths) == ExpandedWalk(8, 512)
    assert expanded_walk(512, 16, n_pages=256, **widths) == ExpandedWalk(8, 512)
    assert expanded_walk(1024, 16, n_pages=5, **widths) == ExpandedWalk(6, 1024)   # a short table
    for s, heads in ((256, 16), (256, 32), (128, 16), (1, 16), (8, 16), (1, 32), (3, 32)):
        assert expanded_walk(s, heads, n_pages=256, **widths) is None
    assert expanded_walk(1024, 16, n_pages=256, **{**widths, "nope": 96}) is None
    assert expanded_walk(1024, 16, n_pages=256, **{**widths, "row_dim": 576}) is None
    assert expanded_walk(1024, 16, n_pages=256, **{**widths, "rope": 192}) is None   # no room behind c
    assert expanded_walk(1024, 16, n_pages=256, **{**widths, "latent": 128}) is None  # absorbed is cheap
    # one tile: whole visits over the live rows, once
    assert rows_visited(6600, 64, ExpandedWalk(8, 1024)) == 13 * 512


LATENT_TOY = dict(vocab_size=96, dim=64, n_layers=2, n_heads=16, n_kv_heads=16, ffn_dim=64,
                  max_seq_len=256, kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
                  v_head_dim=16, dtype="bfloat16")


@pytest.mark.parametrize("form,chunk", [("absorbed", 16), ("absorbed", 128), ("expanded", 128)])
def test_latent_attention_through_the_kernel_is_latent_attention_through_the_expression(
        monkeypatch, form, chunk):
    """``LatentAttention`` picks by the lowering platform (a kernel for a
    TPU, the expression elsewhere). Here the TPU's branch is taken by hand,
    its kernel under the interpreter, through a chunk of a prompt and two
    decode steps of the paged pool: the same logits as the branch tier-1
    otherwise runs, and the same pool. ``absorbed``: every call through the
    walk; ``expanded``: a wide chunk, whose rule (toy widths are no lane
    tiles) is taken by hand too: the chunk through the expanded-once kernel,
    the steps behind it, which read what it left in the pool, absorbed."""
    import seldon_core_tpu.models.transformer as transformer
    import seldon_core_tpu.ops.latent_attention as module
    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.models.cache import init_paged_kv_caches

    model = get_model("transformer", **LATENT_TOY)
    cfg = model.cfg
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, chunk), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :4])
    n_pages = chunk // 32 + 2
    tables = jnp.asarray([[5, 2, 7, 3, 9, 4, 8][:n_pages - 1] + [NULL_PAGE], [TRASH_PAGE] * n_pages],
                         jnp.int32)

    def serve():
        pools = init_paged_kv_caches(cfg, 10, 32)
        out = []
        positions = jnp.stack([jnp.arange(chunk), jnp.full((chunk,), PAD_POS)]).astype(jnp.int32)
        logits, pools = model.apply(params, tokens, positions=positions, caches=pools,
                                    block_tables=tables)
        out.append(logits[0])
        for step in range(2):
            positions = jnp.asarray([[chunk + step], [0]], jnp.int32)
            logits, pools = model.apply(params, tokens[:, step:step + 1], positions=positions,
                                        caches=pools, block_tables=tables)
            out.append(logits[0])
        return np.concatenate([np.asarray(x, np.float32) for x in out]), pools

    # both sides op by op, as they ran before a layer's block was a jitted
    # function (PR 54): a block compiled whole is cut into other fusions around
    # a kernel than around the expression, and a bf16 array that a fusion keeps
    # to itself is not rounded, so the two sides would differ by the compiler's
    # cuts and not by the read (tests/test_shared_block.py holds the jitted
    # block to the plain loop, on one read)
    with jax.disable_jit():
        want, want_pools = serve()
    calls = {"absorbed": [], "expanded": []}

    def interpreted(kernel, form):
        def call(*args, interpret, **kw):
            calls[form].append(args[0].shape)
            return kernel(*args, interpret=True, **kw)
        return call

    monkeypatch.setattr(module, "latent_page_attention",
                        interpreted(module.latent_page_attention, "absorbed"))
    monkeypatch.setattr(module, "latent_expanded_attention",
                        interpreted(module.latent_expanded_attention, "expanded"))
    if form == "expanded":
        rule = transformer.paged_read_walk
        monkeypatch.setattr(
            transformer, "paged_read_walk",
            lambda cfg, s, *rest: ExpandedWalk(4, s) if s == chunk else rule(cfg, s, *rest))
    monkeypatch.setattr(jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))
    with jax.disable_jit():
        got, got_pools = serve()
    wide = cfg.n_layers if form == "expanded" else 0
    assert len(calls["expanded"]) == wide and len(calls["absorbed"]) == 3 * cfg.n_layers - wide
    assert all(shape == (2, chunk, cfg.n_heads, cfg.qk_nope_head_dim) for shape in calls["expanded"])
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    # (the second layer's rows carry the first's bf16 roundings: among 130 rows
    # a few lie two steps of bf16 apart at the largest values, ~4, whichever
    # kernel read, which among 18 none does)
    atol = 3e-2 if chunk == 16 else 8e-2
    for (rows, pos), (want_rows, want_pos) in zip(got_pools, want_pools):
        np.testing.assert_array_equal(np.asarray(pos), np.asarray(want_pos))
        np.testing.assert_allclose(np.asarray(rows[2:], np.float32),
                                   np.asarray(want_rows[2:], np.float32), atol=atol, rtol=3e-2)


def test_the_loop_counts_the_rows_the_read_visited(monkeypatch):
    """``seldon_llm_attn_rows_read_total``: the whole block-table view of every
    sequence of a call where the expression serves (every lowering that is not
    for a TPU, a mesh, a shape the kernel does not take), whole visits over the
    live rows where the kernel does, by the rule the module itself takes."""
    from types import SimpleNamespace

    from seldon_core_tpu.models.transformer import TransformerConfig, paged_read_walk
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher, LoopPhases

    cfg = TransformerConfig(**{**LATENT_TOY, "kv_lora_rank": 512, "qk_rope_head_dim": 64})
    assert cfg.latent_row_dim == 640

    def loop(cfg):
        return SimpleNamespace(server=SimpleNamespace(_cfg=cfg), n_pages=256, page_size=64,
                               _caches=[(jnp.zeros((1,), jnp.bfloat16), None)], _read_walks={})

    def rows_read(loop, *args):
        loop._read_walk = lambda s: ContinuousBatcher._read_walk(loop, s)
        return ContinuousBatcher._rows_read(loop, *args)

    view = 256 * 64
    assert rows_read(loop(cfg), 1, [12000, 9000], 8) == 8 * view        # here: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_read_walk(cfg, 1, 256, 64, jnp.bfloat16) == Plan(16, 16)
    assert rows_read(loop(cfg), 1, [12000, 9000], 8) == 12 * 1024 + 9 * 1024
    assert rows_read(loop(cfg), 256, [6250], 1) == 13 * 512
    # a wide chunk's read is expanded once: one tile, whole visits over the live rows
    cfg = dataclasses.replace(cfg, qk_nope_head_dim=128, v_head_dim=128)
    wide = loop(cfg)
    assert paged_read_walk(cfg, 1024, 256, 64, jnp.bfloat16) == ExpandedWalk(8, 1024)
    assert rows_read(wide, 1024, [6600], 1) == 13 * 512
    form = lambda s: ContinuousBatcher._read_form(wide, s)   # noqa: E731
    assert [form(s) for s in (1024, 256, 1)] == ["expanded", "absorbed", "absorbed"]
    assert rows_read(loop(cfg), 40, [6250], 1) == view                   # no walk for 640 query rows
    for other in (dict(kv_lora_rank=0), dict(mesh=object()), dict(dtype="float32")):
        assert rows_read(loop(dataclasses.replace(cfg, **other)), 1, [12000], 8) == 8 * view
    phases = LoopPhases()
    phases.count_attention("decode", 21000, 21 * 1024)
    phases.count_attention("chunk", 6600, 13 * 512, "expanded")
    phases.count_attention("chunk", 6856, 14 * 512, "absorbed")
    assert phases.stats()["attn_rows_read"] == {"chunk": 27 * 512, "decode": 21 * 1024}
    assert phases.stats()["attn_expanded_rows_read"] == {"chunk": 13 * 512, "decode": 0}
    # ... and leaves with the loop's other tallies: llm_stats -> sync_llm -> /metrics
    from seldon_core_tpu.metrics.registry import MetricsRegistry

    registry = MetricsRegistry()
    registry.sync_llm(SimpleNamespace(llm_stats=phases.stats))
    text = registry.expose().decode()
    assert 'seldon_llm_attn_rows_read_total{' in text
    (line,) = [ln for ln in text.splitlines()
               if ln.startswith("seldon_llm_attn_rows_read_total{") and 'program="decode"' in ln]
    assert float(line.rsplit(" ", 1)[1]) == 21 * 1024 and 'form="absorbed"' in line
    # the chunk's series by form: the readers' sum over ``program="chunk"`` is all of them
    for name, expanded, absorbed in (("calls", 1, 1), ("context_tokens", 6600, 6856),
                                     ("rows_read", 13 * 512, 14 * 512)):
        found = {('form="expanded"' in ln): float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                 if ln.startswith(f"seldon_llm_attn_{name}_total{{") and 'program="chunk"' in ln}
        assert found == {True: expanded, False: absorbed}
    assert not [ln for ln in text.splitlines() if 'form="expanded"' in ln and 'program="decode"' in ln]
