"""The generic page operations over a LATENT pool: a latent-attention model
(one cached row a token for all heads, models/transformer.py LatentAttention)
through the same parity bars the per-head K/V pool is held to: the paged
batcher against ``generate()``'s dense cache, the radix trie (shared pages,
copy-on-write) against cold prefill, the speculative verify's K-token write
against sequential decode, and the disaggregated handoff (export, transfer and
import of latent pages) against single-slice serving. Token for token: the
allocator, block tables, ``cow_page_copy`` and ``export_pages`` are generic
over a layer's tuple and are not told what a page holds."""

import asyncio

import pytest

from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, ffn_dim=64,
          max_seq_len=96, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
          v_head_dim=8)
TURNS = ([9, 8, 7, 6, 5, 4, 3, 2, 1, 11, 12], [30, 31, 32], [44, 45])
PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13], [7], [60, 61, 62, 63, 64, 65]]


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True, max_new_tokens=8,
                len_buckets=(16,), batch_buckets=(1, 4), temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def server():
    return make_server(prefix_cache_size=8)


def run_batch(server, prompts, *, n=8, seeds=None, **batcher_kw):
    async def go():
        b = ContinuousBatcher(server, **batcher_kw)
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=n, seed=None if seeds is None else seeds[i])
            for i, p in enumerate(prompts)])
        pages = b.page_stats()
        await b.close()
        return outs, pages

    return asyncio.run(go())


def chat_turns(server, disaggregation=None):
    """tests/test_radix.py's multi-turn shape: each prompt extends the last."""
    async def go():
        b = ContinuousBatcher(server, disaggregation=disaggregation, max_slots=2, page_size=4,
                              max_len=64, len_buckets=(16, 32), prefill_chunk=8)
        outs, hits = [], []
        prompt = list(TURNS[0])
        for i, user in enumerate(TURNS):
            if i > 0:
                prompt = prompt + outs[-1] + list(user)
            outs.append(await b.submit(prompt, max_new_tokens=6))
            hits.append(b._radix.stats()["prefix_hit_tokens"])
        await b.close()
        return outs, hits

    return asyncio.run(go())


def cold(server):
    outs, prompt = [], list(TURNS[0])
    for i, user in enumerate(TURNS):
        if i > 0:
            prompt = prompt + outs[-1] + list(user)
        outs.append(server.generate([prompt], max_new_tokens=6)["tokens"][0])
    return outs


def test_paged_latent_pool_matches_generates_dense_cache(server):
    expected = [server.generate([p], max_new_tokens=8)["tokens"][0] for p in PROMPTS]
    outs, pages = run_batch(server, PROMPTS, max_slots=3, max_len=40, len_buckets=(8,),
                            pipeline_depth=3, page_size=8)
    assert outs == expected
    assert pages["kv_page_sheds"] == 0


def test_seeded_sampling_through_the_latent_pool_matches_generate():
    s = make_server(temperature=0.8, top_k=20, seed=5)
    prompts, seeds = [[5, 9, 17, 2], [40, 3, 22], [7, 7, 7, 7, 7]], [42, 1234, 7]
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(prompts, seeds)]
    outs, _ = run_batch(s, prompts, seeds=seeds, max_slots=3, max_len=40, len_buckets=(8,),
                        pipeline_depth=2, page_size=8)
    assert outs == expected


def test_radix_trie_shares_latent_pages(server):
    """Turn 2 and 3 are served mostly from shared latent pages (a partial
    block pays one copy-on-write page copy) and decode what cold prefill does."""
    outs, hits = chat_turns(server)
    assert outs == cold(server)
    assert hits[0] == 0 and hits[1] >= 8 and hits[2] > hits[1]


@pytest.mark.parametrize("k", [2, 4])
def test_speculative_verify_writes_latent_rows_at_their_positions(server, k):
    expected = [server.generate([p], max_new_tokens=8)["tokens"][0] for p in PROMPTS[:3]]
    rep = [3, 7, 11, 3, 7, 11, 3, 7, 11, 3, 7]      # the n-gram proposer's home turf
    expected.append(server.generate([rep], max_new_tokens=8)["tokens"][0])
    outs, _ = run_batch(server, PROMPTS[:3] + [rep], max_slots=2, max_len=32, len_buckets=(8,),
                        pipeline_depth=2, page_size=8, spec_mode="ngram", spec_k=k)
    assert outs == expected


def test_disaggregated_handoff_moves_latent_pages(server):
    """Remote prefill stages latent pages on a prefill-slice worker, ships the
    written ones and imports them into the decode pool: the same tokens."""
    outs, hits = chat_turns(server, disaggregation="remote_prefill")
    assert outs == cold(server)
    assert hits[1] > 0
