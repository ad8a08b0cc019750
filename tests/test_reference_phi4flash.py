"""Phi-4-mini-flash-reasoning's stack (SambaY: models/state_mixers.py ``Mamba1Mixer``
over ops/selective_scan.py and ``GatedMemoryUnit``; models/transformer.py's differential
``Attention`` over window / full / cross reads of plain GQA, ``LayerNorm``, biases, a prompt's chunk
that stops its rows behind the layer whose K/V the cross layers read) and its plain
float32 reference (models/reference.py), what holds them, and what they hold. The
``phi4flash`` modeling file is NOT installed, so the model as a whole is held to the
reading in the reference's docstring; two of its layers have an installed
implementation:

- the reference's Mamba-1 mixer to ``transformers`` ``MambaMixer.slow_forward`` and
  its differential attention to ``DiffLlamaAttention`` (rotary made the identity,
  its norm given ``w``, a fixed permutation of the q / k / v rows taking stripes to
  halves), at 1e-5;
- the served forward, and chunked prefill then decode through the batcher (three
  slots, prompts across two chunk boundaries and the window, one that ends ON a
  boundary, a slot reused), to the reference's full forward, on LOGITS;
- each WRONG reference is another model in float32; the combinations nobody built
  are refused where the config is made.

n = 8 layers (s6, window, s6, window | s6 handing m up, full | gmu, cross), uneven
sizes: 8 query / 4 KV heads of 6 (4 pairs over 2 groups), 96 channels with a state
of 8 behind a step bottleneck of 3, a window of 12 over chunks of 8.
"""

import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.cache import PAD_POS, init_paged_kv_caches, state_bytes
from seldon_core_tpu.models.convert import (
    config_kwargs_from_hf, convert_phi4flash_state_dict, sambay_layer_types)
from seldon_core_tpu.models.transformer import SAMBAY_LAYERS_COMPOSE_REFUSAL
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

N = 8
KW = dict(vocab_size=97, dim=48, n_layers=N, n_heads=8, n_kv_heads=4, head_dim=6, ffn_dim=80,
          max_seq_len=96, norm_eps=1e-5, rope_theta=None, dtype="float32", tie_embeddings=True,
          layer_types=sambay_layer_types(N), sliding_window=12, mamba_d_inner=96,
          mamba_d_state=8, mamba_dt_rank=3, mamba_d_conv=4, memory_source=N // 2,
          kv_source=N // 2 + 1, differential=True, attention_bias=True, norm="layer")
CHUNK = 8
RNG = np.random.default_rng(55)
TOKENS = RNG.integers(0, 97, size=41)
LONG = RNG.integers(1, 97, size=60).tolist()


def assert_close(got, want, rel):
    """Within ``rel`` of the logits' SCALE (max |want|)."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= rel * np.abs(want).max(), (
        np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def served():
    module = get_model("transformer", **KW)
    params = module.init(jax.random.PRNGKey(7), jnp.asarray(TOKENS[None]))
    return module, params


def test_the_plan_is_the_published_one():
    assert sambay_layer_types(8) == (
        "s6", "sliding_attention", "s6", "sliding_attention", "s6", "full_attention", "gmu",
        "cross_attention")
    kinds = sambay_layer_types(32)
    assert [kinds.count(k) for k in ("s6", "sliding_attention", "full_attention", "gmu",
                                     "cross_attention")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "s6" and kinds[17] == "full_attention" and set(kinds[18:]) == {
        "gmu", "cross_attention"}
    with pytest.raises(ValueError, match="multiple of 4"):
        sambay_layer_types(10)


# ---- the two layers that have an installed implementation ----------------------
def test_the_references_mixer_is_the_installed_mamba_mixers_slow_forward(served):
    torch = pytest.importorskip("torch")
    from transformers import MambaConfig
    from transformers.models.mamba.modeling_mamba import MambaMixer

    module, params = served
    cfg, p = module.cfg, params["params"]["layer_0"]["s6"]
    config = MambaConfig(hidden_size=cfg.dim, state_size=cfg.mamba_d_state, conv_kernel=4,
                         intermediate_size=cfg.mamba_d_inner, time_step_rank=cfg.mamba_dt_rank,
                         use_bias=False, use_conv_bias=True, hidden_act="silu", expand=2)
    theirs = MambaMixer(config, layer_idx=0).eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    with torch.no_grad():
        theirs.in_proj.weight.copy_(t(p["in_proj"]).T)
        theirs.conv1d.weight.copy_(t(p["conv1d"])[:, None, :])
        theirs.conv1d.bias.copy_(t(p["conv_bias"]))
        theirs.x_proj.weight.copy_(t(p["x_proj"]).T)
        theirs.dt_proj.weight.copy_(t(p["dt_proj"]).T)
        theirs.dt_proj.bias.copy_(t(p["b_dt"]))
        theirs.A_log.copy_(t(p["A_log_t"]).T)
        theirs.D.copy_(t(p["D"]))
        theirs.out_proj.weight.copy_(t(p["out_proj"]).T)
        u = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (23, cfg.dim)), np.float32)
        want = theirs.slow_forward(t(u)[None])[0].numpy()
    with jax.default_matmul_precision("highest"):
        got = reference._mamba1(p, jnp.asarray(u), cfg, reference.WRONG)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_references_attention_is_the_installed_diffllama_attention(served):
    """DiffLlama pairs head h with h + H/2 (and KV head g with g + G/2): our
    stripes (2j, 2j+1) are its halves under a fixed permutation of the rows of
    the q, k and v projections; the pairs' outputs come out in the same order."""
    torch = pytest.importorskip("torch")
    from transformers import DiffLlamaConfig
    from transformers.models.diffllama.modeling_diffllama import DiffLlamaAttention

    module, params = served
    cfg, layer = module.cfg, 3
    p = params["params"][f"layer_{layer}"]["attention"]
    H, G, d, s = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, 19
    config = DiffLlamaConfig(hidden_size=cfg.dim, num_attention_heads=H, num_key_value_heads=G,
                             head_dim=d, attention_bias=True, rms_norm_eps=cfg.norm_eps,
                             attention_dropout=0.0)
    config._attn_implementation = "eager"
    theirs = DiffLlamaAttention(config, layer_idx=layer).eval()
    theirs.groupnorm = torch.nn.RMSNorm(2 * d, eps=cfg.norm_eps, elementwise_affine=True)

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    def halves(w, heads):     # our head 2j -> their j, our 2j+1 -> their j + heads/2
        order = list(range(0, heads, 2)) + list(range(1, heads, 2))
        return t(np.asarray(w).reshape(-1, heads, d)[:, order].reshape(len(w), heads * d))

    with torch.no_grad():
        for name, w, b, heads in (("q_proj", "wq", "bq", H), ("k_proj", "wk", "bk", G),
                                  ("v_proj", "wv", "bv", G)):
            getattr(theirs, name).weight.copy_(halves(p[w], heads).T)
            getattr(theirs, name).bias.copy_(halves(np.asarray(p[b])[None], heads)[0])
        theirs.o_proj.weight.copy_(t(p["wo"]).T)
        theirs.o_proj.bias.copy_(t(p["bo"]))
        for i, name in enumerate(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")):
            getattr(theirs, name).copy_(t(p["lambdas"][i]))
        theirs.groupnorm.weight.copy_(t(p["subln"]["weight"]))
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (s, cfg.dim)), np.float32)
        causal = torch.full((s, s), float("-inf")).triu(1)[None, None]
        identity = (torch.ones(1, s, d), torch.zeros(1, s, d))
        want = theirs(t(x)[None], identity, attention_mask=causal)[0][0].numpy()
    assert theirs.lambda_init == pytest.approx(0.8 - 0.6 * math.exp(-0.3 * layer))
    with jax.default_matmul_precision("highest"):
        got = reference._diff_attention(p, jnp.asarray(x), cfg, layer, reference.WRONG)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_padded_query_form_is_the_literal_equations(served):
    """``Attention`` reads both softmaxes of a pair as ONE plain GQA read over KV
    heads 2 d wide with zero-padded queries; the reference computes the two
    softmaxes as written. One window layer, alone."""
    from seldon_core_tpu.models.transformer import Attention

    module, params = served
    cfg, layer = module.cfg, 1
    p = params["params"][f"layer_{layer}"]["attention"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 31, cfg.dim))
    positions = jnp.arange(31)[None]
    got, (k, v) = Attention(cfg, window=cfg.sliding_window, rotary=False).apply(
        {"params": p}, x, positions, lambda_init=jnp.float32(cfg.lambda_init(layer)))
    assert cfg.read_heads == (8, 2, 12) and k.shape == (1, 31, 4, 6)
    with jax.default_matmul_precision("highest"):
        want = reference._diff_attention(p, x[0], cfg, layer, reference.WRONG,
                                         window=cfg.sliding_window)
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-5)


# ---- the whole stack ------------------------------------------------------------
def test_served_forward_matches_the_reference(served):
    module, params = served
    got, caches = module.apply(params, jnp.asarray(TOKENS[None]))
    want, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    assert routing == [] and float(jnp.abs(want).max()) > 0.1
    assert_close(got[0], want, 2e-5)
    # without a cache: an s6 layer's (rows of x, h [N, E]), an attention layer's
    # (k, v), NOTHING of a gated memory unit and of a cross layer
    assert [tuple(a.shape for a in c) for c in caches] == (
        [((1, 3, 96), (1, 8, 96)), ((1, 41, 4, 6),) * 2] * 2
        + [((1, 3, 96), (1, 8, 96)), ((1, 41, 4, 6),) * 2, (), ()])
    assert "lm_head" not in params["params"]
    tree = params["params"]
    assert sorted(tree["layer_7"]["attention"]) == ["bo", "bq", "lambdas", "subln", "wo", "wq"]
    assert sorted(tree["layer_6"]["gmu"]) == ["in_proj", "out_proj"]
    assert sorted(tree["norm"]) == ["bias", "weight"]


def test_the_published_state_dict_converts_to_the_tree(served):
    """``convert_phi4flash_state_dict`` from the published names, on a state dict
    laid out as torch holds it, gives the tree back leaf for leaf; a weight it
    does not map is refused."""
    module, params = served
    tree = jax.tree.map(np.asarray, params["params"])

    class Config:
        model_type, hidden_size, num_hidden_layers = "phi4flash", 48, N
        num_attention_heads, num_key_value_heads, intermediate_size = 8, 4, 80
        vocab_size, max_position_embeddings, sliding_window = 97, 96, 12
        layer_norm_eps, tie_word_embeddings, mb_per_layer = 1e-5, True, 2
        mamba_d_state, mamba_dt_rank = 8, 3

    kwargs = config_kwargs_from_hf(Config)
    assert kwargs == {**{k: v for k, v in KW.items() if k not in ("dtype", "head_dim")},
                      "mamba_conv_bias": True}
    sd = {"model.embed_tokens.weight": tree["tok_embeddings"],
          "model.final_layernorm.weight": tree["norm"]["weight"],
          "model.final_layernorm.bias": tree["norm"]["bias"]}
    for i, kind in enumerate(KW["layer_types"]):
        layer, hf = tree[f"layer_{i}"], f"model.layers.{i}"
        first = layer["operator_norm" if kind in ("s6", "gmu") else "attention_norm"]
        for name, norm in (("input_layernorm", first), ("post_attention_layernorm",
                                                        layer["ffn_norm"])):
            sd[f"{hf}.{name}.weight"], sd[f"{hf}.{name}.bias"] = norm["weight"], norm["bias"]
        sd[f"{hf}.mlp.fc1.weight"] = np.concatenate([layer["ffn"]["w1"].T, layer["ffn"]["w3"].T])
        sd[f"{hf}.mlp.fc2.weight"] = layer["ffn"]["w2"].T
        if kind == "s6":
            p = layer["s6"]
            sd.update({f"{hf}.attn.in_proj.weight": p["in_proj"].T,
                       f"{hf}.attn.conv1d.weight": p["conv1d"][:, None, :],
                       f"{hf}.attn.conv1d.bias": p["conv_bias"],
                       f"{hf}.attn.x_proj.weight": p["x_proj"].T,
                       f"{hf}.attn.dt_proj.weight": p["dt_proj"].T,
                       f"{hf}.attn.dt_proj.bias": p["b_dt"], f"{hf}.attn.A_log": p["A_log_t"].T,
                       f"{hf}.attn.D": p["D"], f"{hf}.attn.out_proj.weight": p["out_proj"].T})
        elif kind == "gmu":
            sd.update({f"{hf}.attn.in_proj.weight": layer["gmu"]["in_proj"].T,
                       f"{hf}.attn.out_proj.weight": layer["gmu"]["out_proj"].T})
        else:
            p = layer["attention"]
            parts = ("q",) if kind == "cross_attention" else ("q", "k", "v")
            sd.update({f"{hf}.attn.Wqkv.weight": np.concatenate([p["w" + n].T for n in parts]),
                       f"{hf}.attn.Wqkv.bias": np.concatenate([p["b" + n] for n in parts]),
                       f"{hf}.attn.out_proj.weight": p["wo"].T,
                       f"{hf}.attn.out_proj.bias": p["bo"],
                       f"{hf}.attn.subln.weight": p["subln"]["weight"]})
            for j, name in enumerate(("q1", "k1", "q2", "k2")):
                sd[f"{hf}.attn.lambda_{name}"] = p["lambdas"][j]
    back = convert_phi4flash_state_dict(sd, kwargs)["params"]
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    jax.tree.map(np.testing.assert_array_equal, back, tree)
    with pytest.raises(ValueError, match="unmapped weights"):
        convert_phi4flash_state_dict({**sd, "model.layers.0.attn.extra": np.zeros(1)}, kwargs)


def test_a_chunk_that_reads_no_row_leaves_the_pool_and_every_state_as_the_all_rows_forward(
        served):
    """``head_row`` < 0: the chunk runs the layers up to the shared pool's and no
    more, and what it leaves in the cache (the full layer's pool, the window
    layers', every s6 block) is bit for bit what the all-rows forward leaves;
    ``head_row`` >= 0 gives that row's logits of the all-rows forward."""
    module, params = served
    cfg, width, n = module.cfg, 16, 13
    pools = init_paged_kv_caches(cfg, 10, 4, state_slots=2, window_pages=12)
    tables = (jnp.asarray([[2, 3, 4, 5, 6, 0]]), jnp.asarray([[2, 3, 4, 5, 6, 7]]))
    toks = jnp.asarray(np.concatenate([TOKENS[:n], [0] * (width - n)])[None])
    pos = jnp.where(jnp.arange(width) < n, jnp.arange(width), PAD_POS)[None]
    call = dict(positions=pos, caches=pools, block_tables=tables, state_slots=jnp.asarray([1]))
    all_rows, want = module.apply(params, toks, **call)
    nothing, skipped = module.apply(params, toks, head_row=jnp.int32(-1), **call)
    one, read = module.apply(params, toks, head_row=jnp.int32(n - 1), **call)
    assert nothing.shape == one.shape == (1, 1, 97) and not np.asarray(nothing).any()
    np.testing.assert_allclose(one[0, 0], all_rows[0, n - 1], rtol=1e-5, atol=1e-6)
    for got in (skipped, read):
        assert [type(e).__name__ for e in got] == [type(e).__name__ for e in want]
        jax.tree.map(np.testing.assert_array_equal, got, want)
    assert want[6] == () and want[7] == ()
    # slot 1 holds the state, slot 0 nothing; the shared pool's rows are layer 5's
    assert np.asarray(want[0][1][1]).any() and not np.asarray(want[0][1][0]).any()
    assert state_bytes(cfg) == 3 * (3 * 96 * 4 + 8 * 96 * 4)


# ---- through the batcher -------------------------------------------------------
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
    base = dict(max_slots=3, max_len=64, len_buckets=(CHUNK,), pipeline_depth=2,
                page_size=4, prefill_chunk=CHUNK)
    base.update(kw)
    return ContinuousBatcher(server, **base)


async def ask(b, prompt, n=5, **kw):
    info = {"logits": []}
    out = await b.submit(prompt, max_new_tokens=n, info=info, **kw)
    return out, np.stack(info["logits"])


def reference_logits(server, prompt, out):
    first = len(prompt) - 1
    return np.asarray(reference.forward(server._params, server._cfg, prompt + out[:-1],
                                        rows=slice(first, first + len(out)))[0])


# every way a chunk boundary can fall against the four taps, the carried h and the
# window of 12 (a prompt past 12 gives pages back behind it): 3e-5 of the logits'
# scale is float32's own noise through eight layers
@pytest.mark.parametrize("length", [1, 3, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 2 * CHUNK + 3,
                                    3 * CHUNK + 5])
def test_chunked_prefill_and_decode_equal_the_full_forward(server, length):
    prompt = LONG[:length]

    async def go():
        b = batcher(server)
        got = await ask(b, prompt)
        stats = {**b._phases.stats(), **b.page_stats()}
        await b.close()
        return got, stats

    (out, logits), stats = asyncio.run(go())
    assert logits.shape == (5, KW["vocab_size"])
    assert_close(logits, reference_logits(server, prompt, out), 3e-5)
    chunks = -(-length // CHUNK)
    assert stats["s6_rows"] == {"chunk": length, "decode": 4}
    assert stats["s6_layer_calls"] == {"chunk": 3 * chunks, "decode": 3 * 4}
    # the skip, counted: every row ran the layers up to the shared pool's, ONE row
    # a prompt (its last chunk's) and every decoded row ran the rest
    assert stats["self_decoder_rows"] == {"chunk": length, "decode": 4}
    assert stats["cross_decoder_rows"] == {"chunk": 1, "decode": 4}
    assert stats["chunk_head"] == {"1": {"8": 1}, "0": {"8": chunks - 1} if chunks > 1 else {}}
    assert stats["attn_shared_calls"] == {"chunk": 1, "decode": 4}
    assert stats["attn_shared_context_tokens"] == {
        "chunk": length, "decode": sum(length + 1 + j for j in range(4))}
    assert stats["attn_window_calls"] == {"chunk": chunks, "decode": 4}
    assert "ssd_rows" not in stats and "ssd_step_path" not in stats


# (rows of the prompt, its last chunk without a seed, its last chunks with one)
@pytest.mark.parametrize("length,last,seeded_last", [
    (13, (0, 13, 1), [(0, 8, 0), (8, 5, 1)]),
    (29, (16, 13, 1), [(0, 16, 0), (16, 8, 0), (24, 5, 1)]),
    (41, (32, 9, 1), [(16, 16, 0), (32, 8, 0), (40, 1, 1)]),
])
def test_a_padded_wide_last_chunk_narrows_to_the_head_row_and_a_seeded_one_stays_narrow(
        server, length, last, seeded_last):
    """Two chunk widths (8 and 16: the wide one set by hand, as
    tests/test_wide_chunk.py does). WITHOUT a seed a prompt whose tail the narrow
    program would pad to a wide chunk's rows takes it in ONE wide chunk, rows of
    padding behind the prompt's end: the layers past cfg.kv_source, the norm and
    the head run on ``head_row`` = its last VALID row (not the chunk's last), the
    h and the conv rows are those at that row, the window class books the rows
    the chunk holds, and the logits are the reference's. WITH a seed the same
    prompt takes the chunks it took before PR 58 (its tail narrow) and gives the
    same tokens."""
    from test_chunk_head import chunk_events, set_wide_chunk

    prompt, wide = LONG[:length], 2 * CHUNK

    async def go(**kw):
        b = set_wide_chunk(batcher(server, tracing=True), wide)
        got = await ask(b, prompt, **kw)
        stats = {**b._phases.stats(), **b.page_stats()}
        chunks = chunk_events(b._flight.timelines())
        await b.close()
        return got, stats, chunks

    (out, logits), stats, chunks = asyncio.run(go())
    assert chunks[-1] == last and last[1] < wide
    assert stats["chunk_head"]["1"] == {str(wide): 1}
    assert stats["chunk_rows"] == {str(wide): length}
    assert_close(logits, reference_logits(server, prompt, out), 3e-5)
    # ONE row a prompt ran the cross-decoder, whatever the width of its last chunk
    assert stats["self_decoder_rows"] == {"chunk": length, "decode": 4}
    assert stats["cross_decoder_rows"] == {"chunk": 1, "decode": 4}
    assert stats["attn_shared_context_tokens"]["chunk"] == length

    (seeded_out, seeded_logits), seeded_stats, seeded_chunks = asyncio.run(go(seed=1234))
    assert seeded_chunks[-len(seeded_last):] == seeded_last
    assert seeded_stats["chunk_head"]["1"] == {str(CHUNK): 1}
    assert seeded_out == out          # (greedy: the seed draws nothing)
    assert_close(seeded_logits, logits, 3e-5)


@pytest.mark.parametrize("length,new", [(CHUNK + 2, 1), (3, 9), (2 * CHUNK, 6)])
def test_a_probe_reads_back_the_h_its_sequence_leaves(server, length, new):
    """What a probe that asks for "state" is sent: the first s6 layer's h after the
    prompt and every sampled token but the last, as blocks of channels standing
    where heads do (here ONE block: 96 channels are no whole 128), against the
    reference's scan; the reference that rounds h to bf16 after every token lies
    two orders further off than float32's own noise."""
    prompt = LONG[:length]

    async def go():
        b = batcher(server)
        info = {"state": {}}
        out = await b.submit(prompt, max_new_tokens=new, info=info)
        await b.close()
        return out, info["state"]

    out, state = asyncio.run(go())
    assert state["layer"] == 0 and state["tokens"] == length + new - 1
    fed = prompt + out[:new - 1]
    cfg = server._cfg
    assert state["array"].shape == (1, cfg.mamba_d_state, cfg.mamba_d_inner)
    got = state["array"][0].T                       # [E, N], the reference's layout

    def off(want):
        want = np.asarray(want)
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    assert off(reference.s6_state(server._params, cfg, fed, 0)) < 1e-5
    assert off(reference.s6_state(server._params, cfg, fed, 0, s6_state_bf16=True)) > 1e-3
    deep = reference.s6_state(server._params, cfg, fed, 4)
    assert deep.shape == got.shape and np.isfinite(np.asarray(deep)).all()


def test_three_slots_and_a_slot_reused_give_the_logits_each_request_gives_alone(server):
    """B crosses two chunk boundaries and the window while A decodes, C ends ON a
    boundary and is prefilled while B decodes; D takes the slot A leaves (its h and
    conv rows read as a sequence that starts, nothing reset; its window pages
    A's) while B and C still decode through the shared pool."""
    a, b_, c, d = LONG[:5], LONG[10:10 + 2 * CHUNK + 3], LONG[3:3 + 2 * CHUNK], LONG[20:20 + CHUNK + 2]

    async def together():
        bt = batcher(server)
        ta = asyncio.ensure_future(ask(bt, a, 6))
        await asyncio.sleep(0.05)
        tb = asyncio.ensure_future(ask(bt, b_, 16))
        await asyncio.sleep(0.05)
        tc = asyncio.ensure_future(ask(bt, c, 14))
        got_a = await ta
        td = asyncio.ensure_future(ask(bt, d, 8))
        got = [got_a, await tb, await tc, await td]
        await bt.close()
        return got

    for prompt, (out, logits) in zip((a, b_, c, d), asyncio.run(together())):
        assert_close(logits, reference_logits(server, prompt, out), 3e-5)


def test_batcher_tokens_equal_generate(server):
    """The cache-less / dense path (``generate()``: every layer on every row, the
    cross layers over layer 5's DENSE entry) and the batcher's (pages, the chunk
    that narrows, the shared pool) sample the same tokens."""
    prompt = LONG[5:5 + 2 * CHUNK + 1]
    want = server.generate([prompt], max_new_tokens=7)["tokens"][0]

    async def go():
        b = batcher(server)
        out = await b.submit(prompt, max_new_tokens=7)
        await b.close()
        return out

    assert asyncio.run(go()) == want


def test_the_counters_reach_the_registry():
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    comp = make_server(continuous_batching=2, kv_page_size=4, prefill_chunk=CHUNK,
                       len_buckets=(CHUNK, 16, 32))
    svc = get_batcher_service(comp)

    async def go():
        return await svc.submit(LONG[:CHUNK + 3], max_new_tokens=4)

    try:
        assert len(asyncio.run(go())) == 4
        stats = comp.llm_stats()
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(comp)
        text = reg.expose().decode()
    finally:
        svc.close()
    matrix = 3 * 2 * 8 * 96 * 4
    assert stats["state_bytes"] == 3 * 2 * 3 * 96 * 4 + matrix
    assert stats["state_matrix_bytes"] == matrix
    lines = [line for line in text.splitlines() if not line.startswith("#")]

    def value(name, *labels):
        found = [float(line.rsplit(" ", 1)[1]) for line in lines
                 if line.startswith(name + "{") and all(label in line for label in labels)]
        assert len(found) == 1, (name, labels, found)
        return found[0]

    assert value("seldon_llm_self_decoder_rows_total", 'program="chunk"') == CHUNK + 3
    assert value("seldon_llm_cross_decoder_rows_total", 'program="chunk"') == 1
    assert value("seldon_llm_cross_decoder_rows_total", 'program="decode"') == 3
    assert value("seldon_llm_s6_rows_total", 'program="chunk"') == CHUNK + 3
    assert value("seldon_llm_s6_layer_calls_total", 'program="decode"') == 3 * 3
    assert value("seldon_llm_attn_context_tokens_total", 'kind="shared"',
                 'program="chunk"') == CHUNK + 3
    assert value("seldon_llm_attn_calls_total", 'kind="shared"', 'program="decode"') == 3
    assert value("seldon_llm_attn_calls_total", 'kind="window"', 'program="chunk"') == 2
    assert value("seldon_llm_attn_calls_total", 'kind="full"', 'program="chunk"') == 2
    assert "seldon_llm_ssd_step_path_total{" not in text


# ---- what is refused, and the seeded leaves ---------------------------------------
@pytest.mark.parametrize("more", [
    dict(n_experts=4), dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8),
    dict(hc_mult=2), dict(mtp_layers=1), dict(rope_theta=10000.0),
    dict(kv_cache_dtype="int8"), dict(qk_norm="head"), dict(mesh=object())])
def test_the_combinations_nobody_built_are_refused_where_the_config_is_made(more):
    with pytest.raises(ValueError) as refusal:
        get_model("transformer", **{**KW, **more})
    assert str(refusal.value) == SAMBAY_LAYERS_COMPOSE_REFUSAL


@pytest.mark.parametrize("bad,match", [
    (dict(memory_source=None), "memory_source"), (dict(kv_source=1), "kv_source"),
    (dict(kv_source=None), "kv_source"), (dict(memory_source=5), "memory_source"),
    (dict(mamba_dt_rank=0), "'s6' layer needs"), (dict(n_heads=7), "pairs the heads"),
    (dict(norm="batch"), "unknown norm"),
    (dict(layer_types=sambay_layer_types(N)[:6] + ("s6", "cross_attention")), "every layer past")])
def test_a_plan_that_is_not_the_familys_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        get_model("transformer", **{**KW, **bad})


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache_size=4), "prefix_cache_size"), (dict(spec_mode="ngram"), "spec_mode"),
    (dict(lora_rank=4), "lora_rank"), (dict(kv_cache_dtype="int8"), "int8")])
def test_load_refuses_by_name_what_is_not_built_over_these_layers(option, match):
    with pytest.raises(ValueError, match=match):
        make_server(**option)


def test_the_seeded_small_leaves_are_the_layers_published_initialisation(served, server):
    """A_log = log(1 .. N) a channel, b_dt = softplus^-1 of a step log-uniform over
    (0.001, 0.1), D ones, W_dt within +- rank^-1/2 (the module's own init), the taps
    and the conv bias drawn; the four lambda vectors normal(0, 0.1); every
    projection and LayerNorm BIAS normal(0, 0.02), not zeros; the norms' weights
    ones: by the module's init and by the server's alike."""
    for tree in (served[1]["params"], server._params["params"]):
        s6 = tree["layer_0"]["s6"]
        np.testing.assert_allclose(np.exp(np.asarray(s6["A_log_t"], np.float32)),
                                   np.broadcast_to(np.arange(1, 9)[:, None], (8, 96)), rtol=1e-6)
        dt = np.asarray(jax.nn.softplus(jnp.asarray(s6["b_dt"], jnp.float32)))
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
        np.testing.assert_array_equal(np.asarray(s6["D"], np.float32), np.ones(96))
        assert np.abs(np.asarray(s6["dt_proj"], np.float32)).max() <= 3 ** -0.5 + 1e-6
        assert float(jnp.std(jnp.asarray(s6["conv_bias"], jnp.float32))) > 0.2
        attention = tree["layer_1"]["attention"]
        assert 0.05 < float(jnp.std(jnp.asarray(attention["lambdas"], jnp.float32))) < 0.2
        for bias in (attention["bq"], attention["bk"], attention["bv"], attention["bo"],
                     tree["layer_1"]["attention_norm"]["bias"], tree["norm"]["bias"]):
            assert 0.008 < float(jnp.std(jnp.asarray(bias, jnp.float32))) < 0.04
        np.testing.assert_array_equal(np.asarray(attention["subln"]["weight"], np.float32),
                                      np.ones(12))
        np.testing.assert_array_equal(np.asarray(tree["layer_0"]["ffn_norm"]["weight"],
                                                 np.float32), np.ones(48))
    cfg = served[0].cfg
    assert [round(cfg.lambda_init(i), 4) for i in (0, 1, 17)] == [0.2, 0.3555, 0.7963]


# ---- the wrong references of the chip check ------------------------------------
# ... and by how much of the logits' scale each must differ from the right one in
# float32 at this size (the served path lies within 3e-5 of the right one)
WRONG = {
    "state_held_in_bf16": (dict(s6_state_bf16=True), 1e-3),
    "state_zeroed_at_a_chunk_start": (dict(s6_reset_every=8), 0.05),
    "memory_taken_after_the_gate": (dict(gmu_memory_gated=True), 0.05),
    "memory_from_an_earlier_layer": (dict(gmu_memory_layer=2), 0.05),
    "skip_left_out_of_the_memory": (dict(gmu_memory_skip=False), 0.05),
    "cross_layers_read_their_own_input": (dict(cross_kv_own=True), 0.02),
    "pairs_by_halves": (dict(diff_pairs="halves"), 0.1),
    "lambda_init_of_layer_0": (dict(lambda_init_layer0=True), 0.1),
    "sub_norm_left_out": (dict(diff_subln=False), 0.1),
    "one_minus_lambda_init_left_out": (dict(diff_scale=False), 0.1),
    "window_4_rows_off": (dict(window_wrong=8), 0.05),
    "layer_norm_without_the_mean": (dict(layer_norm_mean=False), 0.1),
    "projection_biases_left_out": (dict(bias_off="attention"), 0.02),
    "norm_biases_left_out": (dict(bias_off="norm"), 0.02),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_reference_is_another_model_in_float32(served, name):
    module, params = served
    keywords, margin = WRONG[name]
    right, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    wrong, _ = reference.forward(params, module.cfg, TOKENS.tolist(), **keywords)
    differ = float(jnp.abs(wrong - right).max() / jnp.abs(right).max())
    assert differ > margin, differ
    assert np.isfinite(np.asarray(wrong)).all()


def test_the_check_tool_knows_these_wrong_references():
    """benchmarks/xing4_reference_check.py picks this model's wrong references by
    the configuration's ``work``; each is a keyword of the reference."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "xing4_reference_check.py")
    spec = importlib.util.spec_from_file_location("reference_check", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    wrongs = tool.sambay_wrongs(770, 256)
    assert set(wrongs) >= set(WRONG) - {"window_4_rows_off"} and "state_held_in_bf16" in wrongs
    assert wrongs["window_4_rows_off"] == {"window_wrong": 508}
    for keywords in wrongs.values():
        assert set(keywords) <= set(reference.WRONG)
