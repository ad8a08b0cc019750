"""The generic page operations under a STREAMS-ON model (four residual streams
mixed by hyper-connections, compressed-query latent attention, the sigmoid
router with a selection bias; tests/test_reference_xing4.py holds its logits
to the reference): tests/test_mla_serving_paths.py's parity bars once more,
token for token. The streams live inside the model's forward, so the paged
batcher against ``generate()``'s dense cache, seeded sampling, the radix trie
over latent pages and the speculative verify's K-token write see the same
step programs' signatures as for any model; a dead slot or a padded row runs
through the mixing (its Sinkhorn chain included) and must touch no live row."""

import asyncio

import pytest

from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2, ffn_dim=16,
          max_seq_len=96, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
          v_head_dim=8, q_lora_rank=12, n_experts=8, n_experts_per_token=2,
          router_renormalize=True, routed_scaling_factor=2.0, router_score="sigmoid",
          router_bias=True, n_shared_experts=1, first_dense_layers=1, dense_ffn_dim=48,
          hc_mult=4)
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


def chat_turns(server):
    """tests/test_radix.py's multi-turn shape: each prompt extends the last."""
    async def go():
        b = ContinuousBatcher(server, max_slots=2, page_size=4,
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


def test_paged_pool_matches_generates_dense_cache_with_streams(server):
    expected = [server.generate([p], max_new_tokens=8)["tokens"][0] for p in PROMPTS]
    outs, pages = run_batch(server, PROMPTS, max_slots=3, max_len=40, len_buckets=(8,),
                            pipeline_depth=3, page_size=8)
    assert outs == expected
    assert pages["kv_page_sheds"] == 0


def test_seeded_sampling_with_streams_matches_generate():
    s = make_server(temperature=0.8, top_k=20, seed=5)
    prompts, seeds = [[5, 9, 17, 2], [40, 3, 22], [7, 7, 7, 7, 7]], [42, 1234, 7]
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(prompts, seeds)]
    outs, _ = run_batch(s, prompts, seeds=seeds, max_slots=3, max_len=40, len_buckets=(8,),
                        pipeline_depth=2, page_size=8)
    assert outs == expected


def test_radix_trie_shares_latent_pages_under_streams(server):
    """Turn 2 and 3 are served mostly from shared latent pages (a partial
    block pays one copy-on-write page copy) and decode what cold prefill does."""
    outs, hits = chat_turns(server)
    assert outs == cold(server)
    assert hits[0] == 0 and hits[1] >= 8 and hits[2] > hits[1]


@pytest.mark.parametrize("k", [2, 4])
def test_speculative_verify_with_streams(server, k):
    expected = [server.generate([p], max_new_tokens=8)["tokens"][0] for p in PROMPTS[:3]]
    rep = [3, 7, 11, 3, 7, 11, 3, 7, 11, 3, 7]      # the n-gram proposer's home turf
    expected.append(server.generate([rep], max_new_tokens=8)["tokens"][0])
    outs, _ = run_batch(server, PROMPTS[:3] + [rep], max_slots=2, max_len=32, len_buckets=(8,),
                        pipeline_depth=2, page_size=8, spec_mode="ngram", spec_k=k)
    assert outs == expected


def test_rest_probe_carries_logits_and_routing():
    """POST /v1/generate {"logits": true} on a mixture-of-experts model: the
    reply has the float32 logits of every sampled token and the experts every
    PROCESSED token took (all but the last sampled), from the prompt's first
    token on, as base64 arrays; a dense model's reply has no "routing"."""
    import base64
    import json
    import socket
    import threading
    import urllib.request

    import numpy as np
    from aiohttp import web

    from seldon_core_tpu.transport.rest import make_component_app

    comp = make_server(temperature=0.7, continuous_batching=2, kv_page_size=4,
                       prefill_chunk=8, len_buckets=(16, 32))
    loop = asyncio.new_event_loop()
    runner = web.AppRunner(make_component_app(comp))
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(runner.setup())
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        run.port = s.getsockname()[1]
        loop.run_until_complete(web.SockSite(runner, s).start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    try:
        body = {"prompt": PROMPTS[1], "max_new_tokens": 5, "seed": 4, "logits": True}
        req = urllib.request.Request(
            f"http://127.0.0.1:{run.port}/v1/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=120).read())
    finally:
        loop.call_soon_threadsafe(loop.stop)
    assert len(out["tokens"]) == 5 and out["logits"]["shape"] == [5, KW["vocab_size"]]
    took = out["routing"]
    assert took["first_token"] == 0 and took["dtype"] == "int32"
    # 12 prompt tokens in two chunks + 4 decode steps, one MoE layer, top-2
    assert took["shape"] == [len(PROMPTS[1]) + 5 - 1, 1, 2]
    experts = np.frombuffer(base64.b64decode(took["base64"]), "<i4").reshape(took["shape"])
    assert experts.min() >= 0 and experts.max() < KW["n_experts"]
    assert all(a != b for a, b in experts[:, 0])       # two distinct experts a token
