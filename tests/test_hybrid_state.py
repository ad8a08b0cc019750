"""A second kind of state in the cache tree: a model whose ``layer_types`` name
"conv" layers (LFM2's gated short convolution, models/state_mixers.py
``ShortConv``) keeps a fixed [taps - 1, dim] block a sequence beside the paged
K/V of its attention layers, and the batcher carries it across every chunk
boundary and decode step, between other slots' programs on the same arrays.

Held here, on LOGITS against the plain reference's full forward
(models/reference.py: no cache, no state, the taps as a shifted sum over the
whole sequence): chunked prefill + decode for every way a chunk boundary can
fall against the three taps; a request among others; a slot reused; a request
shed and sent again; and the generic bars (tokens == ``generate()``, the page
operations hand a state entry on, what is not built is refused by name)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.cache import (
    PAD_POS,
    init_kv_caches,
    init_paged_kv_caches,
    is_state_entry,
)
from seldon_core_tpu.models.state_mixers import short_conv
from seldon_core_tpu.runtime.batcher import ContinuousBatcher, _page_table_ops
from seldon_core_tpu.runtime.resilience import ShedError
from seldon_core_tpu.servers.llmserver import LLMServer

# LFM2-8B-A1B's shape in small: dense conv layers first, then sigmoid-routed
# experts behind conv and attention layers (GQA, a norm per head)
KW = dict(vocab_size=96, dim=32, n_layers=5, n_heads=4, n_kv_heads=2, ffn_dim=16,
          dense_ffn_dim=48, first_dense_layers=2, n_experts=8, n_experts_per_token=2,
          router_score="sigmoid", router_bias=True, router_renormalize=True,
          router_renormalize_eps=1e-6, qk_norm="head", max_seq_len=96, norm_eps=1e-5,
          rope_theta=1e6, dtype="float32",
          layer_types=["conv", "conv", "full_attention", "conv", "conv"])
CHUNK = 8
RNG = np.random.default_rng(7)
LONG = RNG.integers(1, 96, size=40).tolist()


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True, max_new_tokens=8,
                len_buckets=(16,), batch_buckets=(1, 4), temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def server():
    return make_server()


def batcher(server, **kw):
    base = dict(max_slots=3, max_len=48, len_buckets=(CHUNK,), pipeline_depth=2,
                page_size=4, prefill_chunk=CHUNK)
    base.update(kw)
    return ContinuousBatcher(server, **base)


async def ask(b, prompt, n=5, **kw):
    """(tokens, logits [n, vocab], routing [prompt + n - 1, moe layers, k])."""
    info = {"logits": []}
    out = await b.submit(prompt, max_new_tokens=n, info=info, **kw)
    return out, np.stack(info["logits"]), np.stack(info["routing"])


def reference_logits(server, prompt, out, routing):
    """The plain reference's rows for the positions the served logits came
    from, following the served experts (a near-tie is not the program's)."""
    first = len(prompt) - 1
    ref, took = reference.forward(server._params, server._cfg, prompt + out[:-1],
                                  rows=slice(first, first + len(out)), follow=routing)
    assert max(float(layer["behind"].max()) for layer in took) < 1e-4
    return np.asarray(ref)


# every way a chunk boundary can fall against a 3-tap window
@pytest.mark.parametrize("length", [1, 2, CHUNK - 1, CHUNK, CHUNK + 1, CHUNK + 2, 2 * CHUNK + 3])
def test_chunked_prefill_and_decode_equal_the_full_forward(server, length):
    prompt = LONG[:length]

    async def go():
        b = batcher(server)
        got = await ask(b, prompt)
        stats = b._phases.stats()
        await b.close()
        return got, stats

    (out, logits, routing), stats = asyncio.run(go())
    assert logits.shape == (5, KW["vocab_size"])
    np.testing.assert_allclose(logits, reference_logits(server, prompt, out, routing),
                               atol=2e-5, rtol=0)
    # the live rows through the conv layers, as the loop counted them
    assert stats["conv_rows"] == {"chunk": length, "decode": 4}
    assert stats["conv_layer_calls"] == {"chunk": 4 * -(-length // CHUNK), "decode": 4 * 4}


# the bf16 pool holds a token's heads as ONE row whatever a head's width (64 in a
# row of 128 values, LFM2's kind; 128 in a row of 256); the expression splits
# them out of the gathered view, in a chunk and in a step
FLAT_KW = dict(KW, dim=256, n_heads=4, n_kv_heads=2, layer_types=["conv", "full_attention", "conv"],
               n_layers=3, first_dense_layers=1)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("length", [1, CHUNK + 2, 2 * CHUNK + 3])
def test_heads_are_held_as_flat_rows_whatever_their_width(length, head_dim):
    flat_server = make_server(model_kwargs=dict(FLAT_KW, dim=4 * head_dim))
    cfg = flat_server._cfg
    assert cfg.head_dim == head_dim
    pool = init_paged_kv_caches(cfg, 10, 4, state_slots=3)[1]
    assert pool[0].shape == pool[1].shape == (10, 4, 2 * head_dim)
    assert init_paged_kv_caches(cfg, 10, 4, "int8", state_slots=3)[1][0].shape == (10, 4, 2, head_dim)
    assert init_kv_caches(cfg, 2, 16)[1][0].shape == (2, 16, 2, head_dim)
    prompt = LONG[:length]

    async def go():
        b = batcher(flat_server)
        got = await ask(b, prompt)
        await b.close()
        return got

    out, logits, routing = asyncio.run(go())
    np.testing.assert_allclose(logits, reference_logits(flat_server, prompt, out, routing),
                               atol=5e-5, rtol=0)
    assert out == flat_server.generate([prompt], max_new_tokens=5)["tokens"][0]


def test_a_request_among_others_gives_the_logits_it_gives_alone(server):
    """B is prefilled (three chunks) while A decodes, and decodes while C is
    prefilled: steps of the other slots run between B's chunks on the same
    state arrays, and chunks of C between B's steps."""
    a, b_, c = LONG[:5], LONG[10:10 + 2 * CHUNK + 3], LONG[3:3 + 2 * CHUNK + 1]

    async def alone(prompt, n):
        bt = batcher(server)
        got = await ask(bt, prompt, n)
        await bt.close()
        return got

    async def together():
        bt = batcher(server)
        ta = asyncio.ensure_future(ask(bt, a, 14))
        await asyncio.sleep(0.05)
        tb = asyncio.ensure_future(ask(bt, b_, 10))
        await asyncio.sleep(0.05)
        tc = asyncio.ensure_future(ask(bt, c, 6))
        got = await asyncio.gather(ta, tb, tc)
        turns = bt._phases.stats()
        await bt.close()
        return got, turns

    got, turns = asyncio.run(together())
    for (out, logits, routing), prompt, n in zip(got, (a, b_, c), (14, 10, 6)):
        solo_out, solo_logits, _ = asyncio.run(alone(prompt, n))
        assert out == solo_out
        np.testing.assert_allclose(logits, solo_logits, atol=1e-5, rtol=0)
        np.testing.assert_allclose(logits, reference_logits(server, prompt, out, routing),
                                   atol=2e-5, rtol=0)
    # steps did run with fewer than all live rows' worth of chunks before them
    assert turns["conv_layer_calls"]["decode"] > 0 and turns["conv_layer_calls"]["chunk"] >= 4 * 7


def test_a_reused_slot_starts_from_zeros(server):
    """One slot: a long request, then a short one in the same slot. The short
    one reads no state the long one left (the taps mask by position)."""
    long_, short = LONG[:2 * CHUNK + 5], LONG[20:23]

    async def go(first):
        b = batcher(server, max_slots=1)
        if first:
            await b.submit(first, max_new_tokens=9)
        got = await ask(b, short)
        await b.close()
        return got

    fresh, reused = asyncio.run(go(None)), asyncio.run(go(long_))
    assert fresh[0] == reused[0]
    np.testing.assert_array_equal(fresh[1], reused[1])


@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_batcher_tokens_equal_generate(kv_cache_dtype):
    """The existing parity bar, with the int8 cache for the attention layers
    too (the conv state is in the serving dtype either way)."""
    s = make_server(kv_cache_dtype=kv_cache_dtype, temperature=0.8, top_k=20, seed=5)
    prompts = [LONG[:3], LONG[5:5 + CHUNK + 2], [7], LONG[1:1 + 2 * CHUNK + 3]]
    seeds = [42, 1234, 7, 99]
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(prompts, seeds)]

    async def go():
        b = batcher(s)
        outs = await asyncio.gather(*[b.submit(p, max_new_tokens=8, seed=sd)
                                      for p, sd in zip(prompts, seeds)])
        await b.close()
        return outs

    assert asyncio.run(go()) == expected


def test_a_shed_request_sent_again_repeats_its_tokens(server):
    """An oversubscribed pool sheds the newest request mid-decode (503); sent
    again it prefills from token 0 and gives what it gives alone."""
    p1, p2 = LONG[:4], LONG[8:12]
    want = server.generate([p2], max_new_tokens=24)["tokens"][0]

    async def go():
        b = batcher(server, max_slots=2, max_len=32, pool_pages=10)
        t1 = asyncio.ensure_future(b.submit(p1, max_new_tokens=24))
        await asyncio.sleep(0)
        t2 = asyncio.ensure_future(b.submit(p2, max_new_tokens=24))
        first = await asyncio.gather(t1, t2, return_exceptions=True)
        again = await b.submit(p2, max_new_tokens=24)
        await b.close()
        return first, again

    (r1, r2), again = asyncio.run(go())
    assert isinstance(r2, ShedError) and r2.status_code == 503
    assert r1 == server.generate([p1], max_new_tokens=24)["tokens"][0]
    assert again == want


REFUSALS = {
    "prefix_cache": (dict(prefix_cache_size=4), "prefix_cache_size"),
    "speculation": (dict(spec_mode="ngram"), "spec_mode"),
    "remote_prefill": (dict(disaggregation="remote_prefill"), "remote_prefill"),
    "tensor_parallel": (dict(tensor_parallel=2), "parallelism"),
    "lora": (dict(lora_rank=4), "lora_rank"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_built_over_a_stateful_layer_is_refused_at_load(what):
    kwargs, names = REFUSALS[what]
    if what == "lora":   # adapters are refused for MoE first; a dense hybrid names the state
        kwargs = dict(kwargs, model_kwargs=dict(KW, n_experts=0, first_dense_layers=0, dense_ffn_dim=0))
    s = LLMServer(**{**dict(model="transformer", model_kwargs=KW, init_random=True), **kwargs})
    with pytest.raises(ValueError, match="conv layers.*" + names):
        s.load()


@pytest.mark.parametrize("bad, match", [
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8), "latent attention"),
    (dict(hc_mult=4), "hyper-connections"),
    (dict(layer_types=["conv", "window"] + ["conv"] * 3), "layer_types"),
    (dict(layer_types=["conv"]), "layer_types"),
    (dict(qk_norm="heads"), "qk_norm"),
])
def test_the_config_refuses_what_no_model_pairs(bad, match):
    from seldon_core_tpu.models import get_model

    with pytest.raises(ValueError, match=match):
        get_model("transformer", **{**KW, **bad})


@pytest.mark.parametrize("cuts", [(3,), (1, 2), (5, 6, 7), (2, 9), (11,)])
def test_the_taps_in_pieces_equal_the_taps_at_once(cuts):
    """``short_conv`` over a sequence cut anywhere (each piece padded behind
    its valid rows, the state handed on) is the same function as over the whole."""
    key = jax.random.PRNGKey(0)
    z = jax.random.normal(key, (2, 12, 16))
    taps = jax.random.normal(jax.random.fold_in(key, 1), (16, 3))
    whole, end = short_conv(z, taps, None, jnp.broadcast_to(jnp.arange(12), (2, 12)),
                            jnp.ones((2, 12), bool))
    state, pieces, start = None, [], 0
    for stop in cuts + (12,):
        n, pad = stop - start, 3
        piece = jnp.concatenate([z[:, start:stop], 9.0 * jnp.ones((2, pad, 16))], axis=1)
        pos = jnp.concatenate([jnp.broadcast_to(jnp.arange(start, stop), (2, n)),
                               jnp.full((2, pad), PAD_POS)], axis=1)
        v, state = short_conv(piece, taps, state, pos, pos < PAD_POS)
        pieces.append(v[:, :n])
        start = stop
    np.testing.assert_allclose(jnp.concatenate(pieces, axis=1), whole, atol=1e-6)
    np.testing.assert_array_equal(state, end)
    np.testing.assert_array_equal(end, z[:, -2:])
    # a sequence with no valid row keeps its state as it came
    _, kept = short_conv(z[:, :1], taps, state, jnp.full((2, 1), PAD_POS), jnp.zeros((2, 1), bool))
    np.testing.assert_array_equal(kept, state)


def test_the_cache_trees_hold_two_kinds_of_entry(server):
    cfg = server._cfg
    dense = init_kv_caches(cfg, 2, 16)
    paged = init_paged_kv_caches(cfg, 10, 4, state_slots=3)
    assert [is_state_entry(layer) for layer in paged] == [True, True, False, True, True]
    assert dense[0][0].shape == (2, 2, 32) and paged[0][0].shape == (3, 2, 32)
    assert len(paged[2]) == 3 and paged[2][0].shape == (10, 4, 2 * 8)
    with pytest.raises(ValueError, match="state_slots"):
        init_paged_kv_caches(cfg, 10, 4)
    from seldon_core_tpu.models.cache import state_bytes, kv_cache_bytes_per_token

    assert state_bytes(cfg) == 4 * 2 * 32 * 4          # float32 here
    assert kv_cache_bytes_per_token(cfg) == 1 * (2 * 2 * 8 * 4 + 4)


def test_the_page_operations_hand_a_state_entry_on(server):
    _, _, reset_pages, _, _, cow_page_copy, export_pages, _ = _page_table_ops()
    tree = init_paged_kv_caches(server._cfg, 10, 4, state_slots=3)
    tree = [type(layer)((layer[0] + 1.5,)) if is_state_entry(layer) else layer for layer in tree]
    state = [np.asarray(layer[0]) for layer in tree if is_state_entry(layer)]
    tree = reset_pages(tree, jnp.asarray([2, 3, 1, 1]))
    tree = cow_page_copy(tree, jnp.asarray(2), jnp.asarray(3), jnp.asarray(2))
    assert [np.asarray(layer[0]) for layer in tree if is_state_entry(layer)][0].tolist() == state[0].tolist()
    exported = export_pages(tree, jnp.asarray([2, 3]))
    assert len(exported) == 1 and exported[0][0].shape[0] == 2      # the attention layer's pages alone


def test_the_state_gauge_and_the_conv_counters_reach_the_registry():
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    comp = make_server(continuous_batching=2, kv_page_size=4, prefill_chunk=CHUNK,
                       len_buckets=(CHUNK, 16, 32))
    svc = get_batcher_service(comp)

    async def go():
        return await svc.submit(LONG[:CHUNK + 2], max_new_tokens=4)

    try:
        assert len(asyncio.run(go())) == 4
        stats = comp.llm_stats()
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(comp)
        text = reg.expose().decode()
    finally:
        svc.close()
    assert stats["state_bytes"] == 4 * 2 * 2 * 32 * 4        # 4 conv layers x 2 slots x [2, 32] float32
    assert stats["conv_rows"] == {"chunk": CHUNK + 2, "decode": 3}
    assert stats["conv_layer_calls"] == {"chunk": 8, "decode": 12}
    lines = [line for line in text.splitlines() if not line.startswith("#")]
    assert any(line.startswith("seldon_llm_state_bytes") and line.endswith(" 2048.0") for line in lines)
    assert any(line.startswith("seldon_llm_conv_rows_total") and 'program="chunk"' in line
               and line.endswith(f" {CHUNK + 2}.0") for line in lines)
    assert any(line.startswith("seldon_llm_conv_layer_calls_total") and 'program="decode"' in line
               and line.endswith(" 12.0") for line in lines)


# ---- a third kind of state: "mamba" layers (granite-4.0-h's Mamba-2 mixer) ----
# conv rows of [x ; B ; C] and a float32 h a head (held transposed) a slot; the
# logits against the reference are tests/test_reference_granite_hybrid.py's
MAMBA_KW = dict(vocab_size=96, dim=32, n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=48,
                max_seq_len=96, norm_eps=1e-5, rope_theta=None, dtype="float32",
                tie_embeddings=True, layer_types=["mamba", "mamba", "full_attention", "mamba"],
                mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, embedding_multiplier=12.0,
                attention_multiplier=0.0625, residual_multiplier=0.22, logits_scaling=8.0)


@pytest.fixture(scope="module")
def mamba_server():
    return make_server(model_kwargs=MAMBA_KW)


async def ask_logits(b, prompt, n=5):
    info = {"logits": []}
    out = await b.submit(prompt, max_new_tokens=n, info=info)
    return out, np.stack(info["logits"])


def test_a_reused_slot_of_a_mamba_model_reads_h_as_zeros(mamba_server):
    """One slot: a long request, then a short one in the same slot. The short
    one reads neither the h nor the conv rows the long one left, and nothing
    was reset at admission."""
    long_, short = LONG[:2 * CHUNK + 5], LONG[20:23]

    async def go(first):
        b = batcher(mamba_server, max_slots=1)
        if first:
            await b.submit(first, max_new_tokens=9)
        got = await ask_logits(b, short)
        await b.close()
        return got

    fresh, reused = asyncio.run(go(None)), asyncio.run(go(long_))
    assert fresh[0] == reused[0]
    np.testing.assert_array_equal(fresh[1], reused[1])


@pytest.mark.parametrize("kv_cache_dtype", ["bf16", "int8"])
def test_batcher_tokens_equal_generate_over_mamba_layers(kv_cache_dtype):
    s = make_server(model_kwargs=MAMBA_KW, kv_cache_dtype=kv_cache_dtype, temperature=0.8,
                    top_k=20, seed=5)
    prompts = [LONG[:3], LONG[5:5 + CHUNK + 2], [7], LONG[1:1 + 2 * CHUNK + 3]]
    seeds = [42, 1234, 7, 99]
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(prompts, seeds)]

    async def go():
        b = batcher(s)
        outs = await asyncio.gather(*[b.submit(p, max_new_tokens=8, seed=sd)
                                      for p, sd in zip(prompts, seeds)])
        await b.close()
        return outs

    assert asyncio.run(go()) == expected


def test_a_shed_request_over_mamba_layers_sent_again_repeats_its_tokens(mamba_server):
    p1, p2 = LONG[:4], LONG[8:12]
    want = mamba_server.generate([p2], max_new_tokens=24)["tokens"][0]

    async def go():
        b = batcher(mamba_server, max_slots=2, max_len=32, pool_pages=10)
        t1 = asyncio.ensure_future(b.submit(p1, max_new_tokens=24))
        await asyncio.sleep(0)
        t2 = asyncio.ensure_future(b.submit(p2, max_new_tokens=24))
        first = await asyncio.gather(t1, t2, return_exceptions=True)
        again = await b.submit(p2, max_new_tokens=24)
        await b.close()
        return first, again

    (r1, r2), again = asyncio.run(go())
    assert isinstance(r2, ShedError) and r2.status_code == 503
    assert r1 == mamba_server.generate([p1], max_new_tokens=24)["tokens"][0]
    assert again == want


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_built_over_a_mamba_layer_is_refused_at_load(what):
    kwargs, names = REFUSALS[what]
    s = LLMServer(**{**dict(model="transformer", model_kwargs=MAMBA_KW, init_random=True), **kwargs})
    # (a mesh is refused where the config is made, before load() looks: the same "no")
    match = "'mamba' layer.*a mesh" if what == "tensor_parallel" else "mamba layers.*" + names
    with pytest.raises(ValueError, match=match):
        s.load()


def test_the_cache_trees_hold_a_mamba_layers_two_arrays_and_the_page_operations_hand_them_on(
        mamba_server):
    cfg = mamba_server._cfg
    _, _, reset_pages, _, _, cow_page_copy, export_pages, _ = _page_table_ops()
    tree = init_paged_kv_caches(cfg, 10, 4, state_slots=3)
    assert [is_state_entry(layer) for layer in tree] == [True, True, False, True]
    assert [a.shape for a in tree[0]] == [(3, 3, 96), (3, 8, 16, 8)]     # h a head transposed
    assert [a.dtype for a in tree[0]] == [jnp.float32, jnp.float32]      # (the serving dtype here)
    tree = [type(layer)(a + 1.5 for a in layer) if is_state_entry(layer) else layer for layer in tree]
    state = [np.asarray(a) for layer in tree if is_state_entry(layer) for a in layer]
    tree = reset_pages(tree, jnp.asarray([2, 3, 1, 1]))
    tree = cow_page_copy(tree, jnp.asarray(2), jnp.asarray(3), jnp.asarray(2))
    after = [np.asarray(a) for layer in tree if is_state_entry(layer) for a in layer]
    assert all((a == b).all() for a, b in zip(after, state))
    exported = export_pages(tree, jnp.asarray([2, 3]))
    assert len(exported) == 1 and exported[0][0].shape[0] == 2      # the attention layer's pages alone


# ---- a fourth kind: "s6" layers (Mamba-1's selective scan) beside layers that keep
# NOTHING of their own (a gated memory unit, a cross-attention layer that reads the
# one full layer's pages) and window layers of the window page class: state slots,
# the window book and an empty entry in ONE tree and ONE batcher; the logits
# against the reference are tests/test_reference_phi4flash.py's
SAMBAY_KW = dict(vocab_size=96, dim=32, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=8,
                 ffn_dim=48, max_seq_len=96, norm_eps=1e-5, rope_theta=None, dtype="float32",
                 tie_embeddings=True, sliding_window=8,
                 layer_types=["s6", "sliding_attention", "s6", "sliding_attention", "s6",
                              "full_attention", "gmu", "cross_attention"],
                 mamba_d_inner=64, mamba_d_state=8, mamba_dt_rank=2, memory_source=4,
                 kv_source=5, differential=True, attention_bias=True, norm="layer")


@pytest.fixture(scope="module")
def sambay_server():
    return make_server(model_kwargs=SAMBAY_KW)


def test_a_reused_slot_of_a_model_with_s6_layers_reads_h_as_zeros(sambay_server):
    """The second request takes the slot (its h, its conv rows and its window
    pages) the first one left: its logits are those it gives in a fresh batcher."""
    p1, p2 = LONG[:11], LONG[20:26]

    async def go(prompts):
        b = batcher(sambay_server, max_slots=1)
        got = [await ask_logits(b, p) for p in prompts]
        await b.close()
        return got

    (_, reused), (_, fresh) = asyncio.run(go([p1, p2]))[1], asyncio.run(go([p2]))[0]
    np.testing.assert_allclose(reused, fresh, rtol=1e-5, atol=1e-6)


def test_batcher_tokens_equal_generate_over_s6_and_cross_layers(sambay_server):
    prompt = LONG[:19]
    want = sambay_server.generate([prompt], max_new_tokens=9)["tokens"][0]

    async def go():
        b = batcher(sambay_server)
        out = await b.submit(prompt, max_new_tokens=9)
        await b.close()
        return out

    assert asyncio.run(go()) == want


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_built_over_an_s6_layer_is_refused_at_load(what):
    kwargs, names = REFUSALS[what]
    s = LLMServer(**{**dict(model="transformer", model_kwargs=SAMBAY_KW, init_random=True),
                     **kwargs})
    # (a mesh is refused where the config is made, before load() looks: the same "no")
    match = "'s6', 'gmu' or 'cross_attention' layer.*a mesh" if what == "tensor_parallel" else (
        "cross_attention and gmu and s6 layers.*" + names)
    with pytest.raises(ValueError, match=match):
        s.load()


def test_the_cache_tree_holds_three_kinds_of_entry_and_the_page_operations_keep_each(
        sambay_server):
    """ONE full page class entry (layer 5), two window-class entries, three state
    blocks (conv rows, h [N, E] float32) and two EMPTY entries; a token costs its
    K and V in ONE full layer for the two layers that read it."""
    from seldon_core_tpu.models.cache import (
        is_window_entry, kv_cache_bytes_per_token, matrix_state_nbytes, state_bytes)

    cfg = sambay_server._cfg
    _, _, reset_pages, *_ = _page_table_ops()
    tree = init_paged_kv_caches(cfg, 10, 4, state_slots=3, window_pages=7)
    assert [is_state_entry(layer) for layer in tree] == [True, False, True, False, True, False,
                                                         True, True]
    assert [is_window_entry(layer) for layer in tree] == [False, True, False, True] + [False] * 4
    assert [a.shape for a in tree[0]] == [(3, 3, 64), (3, 8, 64)]
    assert tree[0][1].dtype == jnp.float32 and tree[6] == () and tree[7] == ()
    assert [a.shape for a in tree[5]] == [(10, 4, 16), (10, 4, 16), (10, 4)]
    assert tree[1][0].shape == (7, 4, 16)
    assert state_bytes(cfg) == 3 * (3 * 64 * 4 + 8 * 64 * 4)
    assert matrix_state_nbytes(tree)[0] == 3 * 3 * 8 * 64 * 4
    row = 2 * 2 * 8 * 4 + 4            # K and V of 2 heads of 8 in float32, and a position
    assert kv_cache_bytes_per_token(cfg, page_class="full") == row
    assert kv_cache_bytes_per_token(cfg, page_class="window") == 2 * row
    assert kv_cache_bytes_per_token(cfg) == 3 * row
    tree = [type(layer)(a + 1.5 for a in layer) if is_state_entry(layer) else layer
            for layer in tree]
    state = [np.asarray(a) for layer in tree if is_state_entry(layer) for a in layer]
    after = reset_pages(tree, jnp.asarray([2, 3, 1, 1]), jnp.asarray([2, 1, 1, 1]))
    assert [type(layer) for layer in after] == [type(layer) for layer in tree]
    assert all((a == b).all() for a, b in zip(
        [np.asarray(a) for layer in after if is_state_entry(layer) for a in layer], state))
