"""A prefill chunk's rows land in the paged pool as whole pages
(models/transformer.py ``paged_write_pages``), and the pool afterwards is the
token scatter's.

The contract (ISSUE 42): after the page-wise write every row of every page the
sequence holds, and its position, is bit for bit what one scatter row a token
(``paged_write_targets``) leaves; only TRASH_PAGE may differ, and NULL_PAGE's
PAD_POS row is never written. Held here at the served widths (a K / V row of
512, 1,024 and 2,048 lanes, a latent row of 640), the served chunks (128 and
256 tokens) and the served page (64 rows), on the CPU: the helper is plain
``jax.numpy``, the same expression on every lowering.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.cache import (
    NULL_PAGE,
    PAD_POS,
    TRASH_PAGE,
    paged_write_by_page,
    paged_write_pages,
    paged_write_targets,
)
from seldon_core_tpu.models.transformer import paged_attention_ref

PAGE, POOL_PAGES, TABLE = 64, 24, 12
WIDTHS, CHUNKS = (512, 640, 1024, 2048), (128, 256)


@jax.jit
def token_scatter(pools, pos_pool, block_tables, positions, rows):
    """What the write was: one scatter row a token."""
    at = paged_write_targets(block_tables, positions, pos_pool.shape[1])
    return (tuple(pool.at[at].set(new[None]) for pool, new in zip(pools, rows)),
            pos_pool.at[at].set(positions))


def fresh_pool(rng, width):
    """A pool in use: every row holds something, NULL_PAGE's positions PAD_POS."""
    k, v = (jnp.asarray(rng.standard_normal((POOL_PAGES, PAGE, width)), jnp.bfloat16)
            for _ in range(2))
    pos = rng.integers(0, 4096, (POOL_PAGES, PAGE)).astype(np.int32)
    pos[NULL_PAGE] = PAD_POS
    return (k, v), jnp.asarray(pos)


def table(rng, live_pages):
    """[1, TABLE]: ``live_pages`` distinct pool pages, NULL_PAGE behind them."""
    row = np.full((1, TABLE), NULL_PAGE, np.int32)
    row[0, :live_pages] = rng.permutation(np.arange(2, POOL_PAGES))[:live_pages]
    return jnp.asarray(row)


def chunk_positions(s, start, n):
    pos = np.full((1, s), PAD_POS, np.int32)
    pos[0, :n] = np.arange(start, start + n)
    return jnp.asarray(pos)


def assert_same_outside_trash(got, want):
    keep = np.arange(POOL_PAGES) != TRASH_PAGE
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32))[keep],
                                      np.asarray(b.astype(jnp.float32))[keep])


# (the chunk's start, its live rows, the pages the table holds) of a chunk of s
CASES = {
    # the benchmark's every chunk: a multiple of the chunk, all rows live
    "aligned": lambda s: (s, s, 2 * s // PAGE),
    # a copy-on-write prefix hit starts mid-page: s / page + 1 pages touched
    "mid_page": lambda s: (PAGE + 23, s, (PAGE + 23 + s) // PAGE + 1),
    # a prompt's last chunk: the last live page part padding ...
    "padded_tail": lambda s: (s, s - 17, 2 * s // PAGE),
    # ... and one whole page of padding behind it
    "padded_page": lambda s: (s, s - PAGE - 17, 2 * s // PAGE),
    # mid-page AND padded
    "mid_page_padded": lambda s: (PAGE + 23, s - 40, (PAGE + 23 + s - 40) // PAGE + 1),
    # NULL_PAGE right behind the live pages: the page after the run goes to TRASH_PAGE
    "null_behind": lambda s: (0, s, s // PAGE),
    # rows the host did not provision (their entry is NULL_PAGE) go to TRASH_PAGE
    "unprovisioned_rows": lambda s: (PAGE, s, s // PAGE),
    # the run ends where the table ends: the page after it lies past the table
    "table_end": lambda s: (TABLE * PAGE - s, s, TABLE),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("width", WIDTHS)
def test_the_pool_after_a_page_wise_write_is_the_token_scatters(width, chunk, case):
    rng = np.random.default_rng([width, chunk, list(CASES).index(case)])
    start, n, live_pages = CASES[case](chunk)
    pools, pos_pool = fresh_pool(rng, width)
    bt, positions = table(rng, live_pages), chunk_positions(chunk, start, n)
    rows = tuple(jnp.asarray(rng.standard_normal((chunk, width)), jnp.bfloat16) for _ in pools)
    want = token_scatter(pools, pos_pool, bt, positions, rows)
    got = paged_write_pages(pools, pos_pool, bt, positions, rows)
    assert_same_outside_trash(got, want)
    # the null page's row is the device-side invariant no write may break
    np.testing.assert_array_equal(np.asarray(got[1])[NULL_PAGE], PAD_POS)
    np.testing.assert_array_equal(np.asarray(got[0][0].astype(jnp.float32))[NULL_PAGE],
                                  np.asarray(pools[0].astype(jnp.float32))[NULL_PAGE])
    # ... and the live rows did land: the written pages hold the run's positions
    held = np.asarray(got[1])[np.asarray(bt)[0, :live_pages]].reshape(-1)
    landed = [p for p in range(start, start + n) if p // PAGE < live_pages]
    np.testing.assert_array_equal(held[landed], landed)


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("width", WIDTHS)
def test_a_prompts_chunks_written_in_turn_read_back_as_the_token_scatters(width, chunk):
    """Three chunks of one prompt (the last padded), each written page-wise
    into the pool the one before left, then the last chunk's queries read the
    whole prompt back through ``paged_attention_ref``: bit-equal to the same
    over the token scatter's pool, and the dense causal attention over the
    prompt's own K and V within bf16's rounding."""
    from seldon_core_tpu.models.transformer import grouped_query_attention

    rng = np.random.default_rng(width + chunk)
    heads = width // 128
    length = 2 * chunk + chunk // 2 + 5
    k, v = (jnp.asarray(rng.standard_normal((length, width)) * 0.3, jnp.bfloat16) for _ in range(2))
    pools, pos_pool = fresh_pool(rng, width)
    # nothing of the pool's earlier tenants is below the prompt's positions
    pos_pool = jnp.where(pos_pool < PAD_POS, pos_pool + 8192, pos_pool)
    bt = table(rng, -(-length // PAGE))
    by_page, by_token = (pools, pos_pool), (pools, pos_pool)
    for start in range(0, length, chunk):
        n = min(chunk, length - start)
        positions = chunk_positions(chunk, start, n)
        rows = tuple(jnp.zeros((chunk, width), jnp.bfloat16).at[:n].set(x[start:start + n])
                     for x in (k, v))
        by_page = paged_write_pages(*by_page, bt, positions, rows)
        by_token = token_scatter(*by_token, bt, positions, rows)
    assert_same_outside_trash(by_page, by_token)
    q = jnp.asarray(rng.standard_normal((1, chunk, heads, 128)), jnp.bfloat16)
    read = functools.partial(jax.jit(paged_attention_ref, static_argnames="n_kv_heads"),
                             q, block_tables=bt, positions=positions, n_kv_heads=heads)
    out = read(cache=(*by_page[0], by_page[1]))
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(read(cache=(*by_token[0], by_token[1])).astype(jnp.float32)))
    dense = grouped_query_attention(
        q[:, :n], k.reshape(1, length, heads, 128), v.reshape(1, length, heads, 128),
        (jnp.arange(length)[None, None, :] <= positions[:, :n, None]))
    np.testing.assert_allclose(np.asarray(out[:, :n].astype(jnp.float32)),
                               np.asarray(dense.astype(jnp.float32)), atol=2e-2)


def _pool(*tail, leaves=3, dtype=jnp.bfloat16):
    values = [jax.ShapeDtypeStruct((POOL_PAGES, PAGE) + tail, dtype)] * (leaves - 1)
    return (*values, jax.ShapeDtypeStruct((POOL_PAGES, PAGE), jnp.int32))


@pytest.mark.parametrize("what,cache,b,s,by_page", [
    ("the batcher's chunk over flat K / V rows", _pool(1024), 1, 256, True),
    ("a chunk of one page", _pool(1024), 1, PAGE, True),
    ("a chunk over latent rows", _pool(640, leaves=2), 1, 256, True),
    ("the decode step: a token a slot", _pool(1024), 32, 1, False),
    ("the decode step of one slot", _pool(1024), 1, 1, False),
    ("the speculative verify: a few tokens a slot", _pool(1024), 4, 5, False),
    ("a run under a page (hlolint's chunks of 8)", _pool(1024), 1, 8, False),
    ("the int8 pool's five leaves", _pool(8, 128, leaves=5, dtype=jnp.int8), 1, 256, False),
    ("a pool with its head axes split out (a mesh)", _pool(8, 128), 1, 256, False),
])
def test_the_path_rests_on_what_the_call_shows(what, cache, b, s, by_page):
    assert paged_write_by_page(cache, b, s) is by_page, what


@pytest.mark.parametrize("kv_cache_dtype,path,pages", [
    # 19 tokens in chunks of 8 over pages of 8: three writes of 8 / 8 + 1 pages
    ("bf16", "page", 3 * 2),
    # the int8 pool keeps the token scatter: the pages the live rows lie in
    ("int8", "token", 3),
])
def test_the_loop_counts_how_the_chunks_rows_reached_the_pool(kv_cache_dtype, path, pages):
    """``seldon_llm_kv_chunk_writes_total`` / ``_kv_pages_written_total``: the
    loop asks the ONE rule the modules take, of its own pool, and the tally
    leaves with the loop's others (llm_stats -> sync_llm -> /metrics). The
    served tokens are ``generate()``'s either way."""
    import asyncio
    from types import SimpleNamespace

    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(model="transformer", init_random=True, max_new_tokens=4, len_buckets=(32,),
                       temperature=0.0, eos_id=-1, seed=3, kv_cache_dtype=kv_cache_dtype,
                       model_kwargs=dict(vocab_size=96, dim=32, n_layers=2, n_heads=2,
                                         n_kv_heads=2, ffn_dim=64, max_seq_len=96))
    server.load()
    prompt = list(range(5, 24))

    async def go():
        batcher = ContinuousBatcher(server, page_size=8, prefill_chunk=8)
        out = await batcher.submit(prompt, max_new_tokens=4)
        stats = batcher._phases.stats()
        await batcher.close()
        return out, stats

    out, stats = asyncio.run(go())
    assert out == server.generate([prompt], max_new_tokens=4)["tokens"][0]
    other = "token" if path == "page" else "page"
    assert stats["kv_chunk_writes"] == {path: 3, other: 0}
    assert stats["kv_pages_written"] == {path: pages, other: 0}
    registry = MetricsRegistry()
    registry.sync_llm(SimpleNamespace(llm_stats=lambda: stats))
    lines = [ln for ln in registry.expose().decode().splitlines()
             if ln.startswith("seldon_llm_kv_pages_written_total{")]
    assert {ln.rsplit(" ", 1)[1] for ln in lines if f'path="{path}"' in ln} == {f"{float(pages)}"}
