"""The prefill chunk runs the head for the one row its caller reads (ISSUE 47).

``_get_prefill_chunk``'s program takes ``head_row``: the head runs for that one
row, under a conditional on ``head_row >= 0``, and the logits are
``[1, 1, vocab]``; a chunk that is not a prompt's last hands in -1 and gets
zeros. Held here, on the CPU at toy widths:

(a) what the callers read is what they read before: the last chunk's one row,
    the first token drawn from it and the probe's float32 row are bit for bit
    those of the all-rows form (the whole chunk through the head, row
    ``n - 1`` taken after), greedy and seeded, for a dense GQA, a
    latent-attention MoE and a state-layer model, chunks of 8 and a prompt
    that ends mid-chunk; the pool a chunk leaves behind is the same too;
(b) the chunk's program multiplies by the head only inside the conditional's
    true branch and returns no ``[1, chunk, vocab]`` array
    (tests/test_tpu_program.py holds the same of the program compiled for a
    v5e, the int8 head's dequant included);
(c) one program a chunk shape;
(d) ``chunk_head`` counts 1 ran / n - 1 not for a prompt of n chunks, by the
    width of the chunk program that ran (ISSUE 58), and reaches ``/metrics`` as
    ``seldon_llm_chunk_head_total{ran,width}``.
"""

from __future__ import annotations

import asyncio
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.cache import (
    NULL_PAGE,
    PAD_POS,
    RESERVED_PAGES,
    init_paged_kv_caches,
    window_slot_pages,
)
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer, _slot_sampler

CHUNK, PAGE, PAGES = 8, 4, 12
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}
# float32 toys, and ``dense_gqa_bf16`` below: the CPU's compiler drops a bfloat16
# round trip (f32 -> bf16 -> f32) where its fusions let it, program by program,
# and a row taken BEFORE the final norm came out of this program one rounding
# away from the all-rows form's (a seeded request drew another first token:
# tests/test_chaos.py); the norm stays ahead of the conditional for that
MODELS = {
    # grouped-query attention, dense FFN
    "dense_gqa": dict(vocab_size=101, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=64, max_seq_len=96, dtype="float32"),
    # DeepSeek-V2-Lite's shape at toy widths (tests/test_reference_mla.py)
    "latent_moe": dict(vocab_size=131, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, ffn_dim=32,
                       max_seq_len=128, n_experts=16, n_experts_per_token=4,
                       router_renormalize=False, first_dense_layers=1, dense_ffn_dim=96,
                       n_shared_experts=2, kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16, rope_scaling=YARN, dtype="float32"),
    # LFM2's shape in small: conv state layers around one GQA layer (tests/test_hybrid_state.py)
    "state_layers": dict(vocab_size=101, dim=32, n_layers=5, n_heads=4, n_kv_heads=2, ffn_dim=16,
                         dense_ffn_dim=48, first_dense_layers=2, n_experts=8,
                         n_experts_per_token=2, router_score="sigmoid", router_bias=True,
                         router_renormalize=True, router_renormalize_eps=1e-6, qk_norm="head",
                         max_seq_len=96, norm_eps=1e-5, rope_theta=1e6, dtype="float32",
                         layer_types=["conv", "conv", "full_attention", "conv", "conv"]),
}
BF16 = {"dense_gqa_bf16": dict(MODELS["dense_gqa"], dtype="bfloat16")}
# three chunks of 8, the last one 5 rows long
PROMPT = np.random.default_rng(11).integers(1, 96, size=21).tolist()
SAMPLING = {"greedy": dict(temperature=0.0), "seeded": dict(temperature=0.8, top_k=20)}


@pytest.fixture(scope="module")
def servers():
    made = {}

    def get(model, sampling="greedy"):
        if (model, sampling) not in made:
            s = LLMServer(model="transformer", model_kwargs={**MODELS, **BF16}[model],
                          init_random=True,
                          max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                          eos_id=-1, seed=3, **SAMPLING[sampling])
            s.load()
            made[model, sampling] = s
        return made[model, sampling]

    return get


def make_batcher(server, **kw) -> ContinuousBatcher:
    base = dict(max_slots=3, max_len=PAGES * PAGE, len_buckets=(CHUNK,), page_size=PAGE,
                prefill_chunk=CHUNK)
    base.update(kw)
    return ContinuousBatcher(server, **base)


def built() -> threading.Thread:
    """What ``_wide_build`` holds once the wide program's build has ended."""
    thread = threading.Thread(target=lambda: None)
    thread.start()
    thread.join()
    return thread


def set_wide_chunk(b: ContinuousBatcher, wide: int) -> ContinuousBatcher:
    """A second chunk width at rehearsal sizes: a batcher made with an explicit
    ``prefill_chunk`` has none (a width somebody gave is every chunk's), so a
    test sets the wide one by hand and says its program is there (its first
    call builds it). The window class is sized when the batcher is made, for
    its widest chunk: a slot may hold a wide chunk's pages here, and the class
    (every slot's worth at the narrow width) holds the one request of a test."""
    assert b.prefill_wide == 0
    b.prefill_wide, b._wide_build = wide, built()
    if b.window and wide:
        b.window_slot_pages = min(b.n_pages, window_slot_pages(b.window, wide, b.page_size))
        assert b.window_slot_pages <= b.window_pool_pages - RESERVED_PAGES
    return b


def chunk_events(timelines) -> list:
    """[(start, tokens, head)] of the chunks of the requests recorded."""
    return sorted((e["start"], e["tokens"], e["head"]) for t in timelines for e in t["events"]
                  if e["kind"] == "prefill_chunk")


def chunks_of(prompt):
    """(tokens [1, CHUNK], positions [1, CHUNK], live rows) a chunk, as
    ``_prefill_step`` builds them."""
    for start in range(0, len(prompt), CHUNK):
        part = prompt[start:start + CHUNK]
        toks = np.zeros((1, CHUNK), np.int32)
        pos = np.full((1, CHUNK), PAD_POS, np.int32)
        toks[0, :len(part)] = part
        pos[0, :len(part)] = np.arange(start, start + len(part))
        yield jnp.asarray(toks), jnp.asarray(pos), len(part)


def fresh_pool(server):
    """(pool, block row, the state-slot operand) of one sequence on pages 2..."""
    pools = init_paged_kv_caches(server._cfg, RESERVED_PAGES + PAGES, PAGE,
                                 server.kv_cache_dtype, state_slots=2)
    row = np.full((1, PAGES), NULL_PAGE, np.int32)
    row[0] = np.arange(RESERVED_PAGES, RESERVED_PAGES + PAGES)
    extra = (jnp.asarray([1], jnp.int32),) if server._cfg.state_layers else ()
    return pools, jnp.asarray(row), extra


def all_rows_chunk(server):
    """The chunk as it was: every row through the final norm and the head,
    logits [1, chunk, vocab]."""
    @jax.jit
    def chunk(params, pools, block_row, tokens, positions, state_slots=None):
        return server._forward_with_aside(
            params, tokens, positions=positions, caches=pools, block_tables=block_row,
            state_slots=state_slots)

    return chunk


# ------------------------------------------- (a) what the callers read
@pytest.mark.parametrize("model", [*MODELS, *BF16])
def test_the_last_chunks_one_row_is_the_all_rows_forms_row(servers, model):
    server = servers(model)
    one_row, all_rows = server._get_prefill_chunk(CHUNK, PAGES), all_rows_chunk(server)
    pools_new, bt_row, extra = fresh_pool(server)
    pools_old = fresh_pool(server)[0]
    parts = list(chunks_of(PROMPT))
    for at, (toks, pos, n) in enumerate(parts):
        last = at == len(parts) - 1
        head_row = jnp.asarray(n - 1 if last else -1, jnp.int32)
        got, pools_new, aside_new = one_row(server._params, pools_new, bt_row, toks, pos,
                                            head_row, *extra)
        want, pools_old, aside_old = all_rows(server._params, pools_old, bt_row, toks, pos, *extra)
        assert got.shape == (1, 1, server._cfg.vocab_size) and got.dtype == jnp.float32
        assert want.shape == (1, CHUNK, server._cfg.vocab_size)
        if last:
            assert n == 5     # the prompt ends mid-chunk
            assert np.array_equal(np.asarray(got[0, 0]), np.asarray(want[0, n - 1]))
        else:
            assert not np.asarray(got).any()
        # everything else a chunk leaves behind is untouched by the argument
        for a, b in zip(jax.tree.leaves((pools_new, aside_new)),
                        jax.tree.leaves((pools_old, aside_old))):
            assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("sampling", SAMPLING)
@pytest.mark.parametrize("model", MODELS)
def test_first_token_and_probe_row_are_the_all_rows_forms(servers, model, sampling):
    """Through the batcher: the request's first token and the probe's first
    float32 row against the all-rows chunk, its row ``n - 1`` and the step
    sampler's draw on it with the request's key."""
    server = servers(model, sampling)
    all_rows = all_rows_chunk(server)
    pools, bt_row, extra = fresh_pool(server)
    for toks, pos, n in chunks_of(PROMPT):
        want, pools, _ = all_rows(server._params, pools, bt_row, toks, pos, *extra)
    want_row = np.asarray(want[0, n - 1].astype(jnp.float32))
    _, want_tok = _slot_sampler(server.top_k)(
        jax.random.PRNGKey(99)[None], jnp.asarray(want_row)[None],
        jnp.asarray(server.temperature, jnp.float32))

    async def go():
        b = make_batcher(server)
        info = {"logits": []}
        out = await b.submit(PROMPT, 3, info=info, seed=99)
        stats = b._phases.stats()
        await b.close()
        return out, info, stats

    out, info, stats = asyncio.run(go())
    row = info["logits"][0]
    assert row.dtype == np.float32 and np.array_equal(row, want_row)
    assert out[0] == int(want_tok[0])
    assert out == server.generate([PROMPT], max_new_tokens=3, seed=99)["tokens"][0]
    # (d) three chunks a prompt: the head ran in one
    assert stats["chunk_head"] == {"1": {"8": 1}, "0": {"8": 2}}


# ------------------------------------------- (b) the program, lowered for the CPU
@pytest.mark.parametrize("model", MODELS)
def test_the_head_is_multiplied_only_inside_the_conditionals_true_branch(servers, model):
    """Of the ops that compute a vocabulary-wide array there are two: the
    product with the head in the true branch, the zeros of the other."""
    from test_tpu_program import conditional_branches, vocabulary_wide

    server = servers(model)
    vocab = server._cfg.vocab_size
    pools, bt_row, extra = fresh_pool(server)
    toks, pos, _ = next(chunks_of(PROMPT))
    compiled = server._get_prefill_chunk(CHUNK, PAGES).lower(
        server._params, pools, bt_row, toks, pos, jnp.asarray(-1, jnp.int32), *extra).compile()
    hlo = compiled.as_text()
    zeros, true = conditional_branches(hlo)
    assert vocabulary_wide(hlo, vocab) == sorted([
        (zeros, (1, 1, vocab), "broadcast"), (true, (vocab,), "dot")])
    # no array of the all-rows form, in a fusion either
    assert f"[1,{CHUNK},{vocab}]" not in hlo and f"[{CHUNK},{vocab}]" not in hlo
    assert (1, 1, vocab) in [o.shape for o in jax.tree.leaves(compiled.out_info)]


# ------------------------------------------- (c) one program a chunk shape
def test_one_program_a_chunk_shape(servers):
    """Chunks that are a prompt's last and chunks that are not, whole and
    part full, run ONE compiled program: no "last chunk" twin."""
    server = LLMServer(model="transformer", model_kwargs=MODELS["dense_gqa"], init_random=True,
                       max_new_tokens=8, len_buckets=(16,), eos_id=-1, seed=3, temperature=0.0)
    server.load()

    async def go():
        b = make_batcher(server)
        await asyncio.gather(b.submit(PROMPT, 2), b.submit(PROMPT[:8], 2), b.submit(PROMPT[:3], 2))
        stats = b._phases.stats()
        await b.close()
        return stats

    stats = asyncio.run(go())
    assert stats["chunk_head"] == {"1": {"8": 3}, "0": {"8": 2}}
    assert [k for k in server._prefill_cache if k[0] == "pchunk"] == [
        ("pchunk", CHUNK, PAGES, False)]
    assert server._get_prefill_chunk(CHUNK, PAGES)._cache_size() == 1


# ------------------------------------------- (d) the counter
def exposed_chunk_heads(stats) -> dict:
    """{(ran, width): value} of ``seldon_llm_chunk_head_total`` on ``/metrics``."""
    from types import SimpleNamespace

    from seldon_core_tpu.metrics.registry import MetricsRegistry

    registry = MetricsRegistry()
    registry.sync_llm(SimpleNamespace(llm_stats=lambda: stats))
    found = {}
    for line in registry.expose().decode().splitlines():
        if line.startswith("seldon_llm_chunk_head_total{"):
            labels = {part.split("=")[0]: part.split('"')[1]
                      for part in line[line.index("{") + 1:line.index("}")].split(",")}
            found[labels["ran"], labels["width"]] = float(line.rsplit(" ", 1)[1])
    return found


def test_chunk_head_counts_one_ran_a_prompt_and_reaches_metrics(servers):
    server = servers("dense_gqa")

    async def go():
        b = make_batcher(server, tracing=True)
        await b.submit(PROMPT, 2)            # 3 chunks
        await b.submit(PROMPT[:16], 2)       # 2 chunks, the last one full
        await b.submit(PROMPT[:5], 2)        # 1 chunk: it IS the last
        stats, timelines = b._phases.stats(), b._flight.timelines()
        await b.close()
        return stats, timelines

    stats, timelines = asyncio.run(go())
    assert stats["chunk_head"] == {"1": {"8": 3}, "0": {"8": 3}}
    # the flight recorder's chunk events say which chunk it was
    heads = sorted([e["head"] for e in t["events"] if e["kind"] == "prefill_chunk"]
                   for t in timelines)
    assert heads == [[0, 0, 1], [0, 1], [1]]
    assert exposed_chunk_heads(stats) == {("0", "8"): 3.0, ("1", "8"): 3.0}


def test_chunk_head_says_which_programs_ran_the_head(servers):
    """Two chunk widths a batcher (the wide one set by hand, as
    tests/test_wide_chunk.py does): ``ran="1"`` at the wide width over ``ran="1"``
    is the share of prompts whose tail went in ONE padded wide chunk. Here two of
    four: 21 rows are a wide chunk and 5 rows, 13 rows one padded wide chunk, 29
    rows a wide chunk and another of 13 rows, 6 rows a narrow one."""
    server = servers("dense_gqa")

    async def go():
        b = set_wide_chunk(make_batcher(server), 16)
        for n in (21, 13, 29, 6):
            await b.submit((PROMPT + PROMPT)[:n], 2)
        stats = b._phases.stats()
        await b.close()
        return stats

    stats = asyncio.run(go())
    assert stats["chunk_head"] == {"1": {"8": 2, "16": 2}, "0": {"16": 2}}
    assert stats["chunk_rows"] == {"16": 16 + 13 + 29, "8": 5 + 6}
    assert exposed_chunk_heads(stats) == {
        ("0", "16"): 2.0, ("1", "8"): 2.0, ("1", "16"): 2.0}
