"""Qwen3-Next's block (models/state_mixers.py ``GatedDeltaNet``, the gated
attention, the expert share of ``MoEFFN``) and its plain float32 reference
(models/reference.py: the delta rule as a ``lax.scan`` over tokens, no chunking,
no cache), what holds them, and what they hold:

- both to the installed ``transformers`` ``Qwen3NextForCausalLM`` on converted
  weights (models/convert.py: the ``qkvz`` / ``ba`` interleave by key head,
  ``q_proj``'s query | gate a head, ``1 + w``), for every layer pattern;
- the chunked form of the rule (``gated_delta_rule``: sub-chunks of 64 rows, a
  triangular system inside, S in float32 between) to the recurrence, for every
  way a sequence can end against a sub-chunk; padded rows leave both state
  arrays as they came;
- chunked prefill then decode through the batcher (the conv rows and S carried
  across every chunk boundary and step, among other slots' programs on the same
  arrays) to the reference's full forward, on LOGITS;
- the shares add up: the four partial MoE outputs of four shares of 4 of 16
  experts, the shared expert counted once, are the uncut layer's;
- each WRONG reference of the chip check
  (perf/configs/qwen3-next-80b-a3b-int8.json ``reference_tolerance``) is another
  model in float32; what is not built over a layer with state is refused at load().
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.convert import (
    config_kwargs_from_hf, convert_hf_model, convert_qwen3_next_state_dict)
from seldon_core_tpu.models.cache import (
    PAD_POS,
    init_kv_caches,
    init_paged_kv_caches,
    is_state_entry,
)
from seldon_core_tpu.models.state_mixers import GDN_CHUNK, gated_delta_rule, l2_normalize
from seldon_core_tpu.runtime.batcher import ContinuousBatcher, _page_table_ops
from seldon_core_tpu.servers.llmserver import LLMServer

PATTERNS = {
    "published_period": ["linear_attention"] * 3 + ["full_attention"],
    "two_periods": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "all_linear": ["linear_attention"] * 2,
    "all_attention": ["full_attention"] * 2,
}
GDN = dict(linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
           linear_value_head_dim=8, linear_conv_kernel_dim=4)
# the served model in small: Qwen3-Next's kinds of layer, and a SHARE of its
# experts (8 of 16, from expert 4 on) behind a router that is 16 wide
KW = dict(vocab_size=96, dim=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=16,
          n_experts=16, n_experts_per_token=4, experts_first=4, experts_held=8,
          router_renormalize=True, n_shared_experts=1, shared_expert_gate=True, qk_norm="head",
          attn_gate=True, partial_rotary_factor=0.25, max_seq_len=96, norm_eps=1e-6,
          rope_theta=1e7, dtype="float32",
          layer_types=["linear_attention"] * 3 + ["full_attention"], **GDN)
CHUNK = 8
RNG = np.random.default_rng(11)
TOKENS = RNG.integers(0, 96, size=21)
LONG = RNG.integers(1, 96, size=40).tolist()


def hf_model(layer_types, **extra):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(0)
    config = transformers.Qwen3NextConfig(
        vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=len(layer_types),
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, partial_rotary_factor=0.25,
        moe_intermediate_size=16, shared_expert_intermediate_size=16, num_experts=8,
        num_experts_per_tok=3, norm_topk_prob=True, max_position_embeddings=128,
        rope_theta=1e7, rms_norm_eps=1e-6, tie_word_embeddings=False,
        layer_types=list(layer_types), **{**GDN, **extra})
    model = transformers.Qwen3NextForCausalLM(config).eval()
    with torch.no_grad():   # weights that a swapped order or a missing 1 + w would show in
        for name, p in model.named_parameters():
            if "norm" in name or "A_log" in name or "dt_bias" in name:
                p.add_(0.3 * torch.randn_like(p))
            elif name != "model.embed_tokens.weight":
                p.mul_(6.0 if "conv1d" in name else 3.0)
    return model, torch


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_reference_and_served_forward_match_transformers_qwen3_next(pattern):
    model, torch = hf_model(PATTERNS[pattern])
    # three sub-chunks, the last partial
    tokens = np.random.default_rng(len(pattern)).integers(0, 96, size=2 * GDN_CHUNK + 9)
    with torch.no_grad():
        want = model(torch.tensor(tokens[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    cfg = module.cfg
    assert cfg.layer_types == tuple(PATTERNS[pattern]) and cfg.head_dim == 16
    assert cfg.attn_gate and cfg.shared_expert_gate and cfg.rotary_dim == 4
    ref, _ = reference.forward(variables, cfg, tokens.tolist())
    served, _ = module.apply(variables, jnp.asarray(tokens[None]))
    scale = np.abs(want).max()
    assert scale > 0.5                      # not a model of zeros
    # float32 rounding adds up with depth: 1e-5 of the scale a period of four layers
    tol = 1e-5 * max(scale, 1.0) * max(1, len(PATTERNS[pattern]) // 4)
    assert np.abs(np.asarray(ref) - want).max() <= tol
    assert np.abs(np.asarray(served[0]) - want).max() <= tol


def test_a_converted_qwen3_next_serves_transformers_logits_through_the_dense_cache():
    """The published layout -> this tree -> a padded prefill into the dense
    cache and decoded rows give ``Qwen3NextForCausalLM``'s logits: the first
    decoded row reads conv rows and S that a PADDED prefill left."""
    model, torch = hf_model(PATTERNS["published_period"])
    with torch.no_grad():
        want = model(torch.tensor(TOKENS[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    caches = init_kv_caches(module.cfg, 1, 32)
    pos = jnp.where(jnp.arange(16) < 13, jnp.arange(16), PAD_POS)[None]
    toks = jnp.asarray(np.concatenate([TOKENS[:13], [0, 0, 0]])[None])
    logits, caches = module.apply(variables, toks, positions=pos, caches=caches, cache_index=0)
    np.testing.assert_allclose(logits[0, :13], want[:13], atol=2e-5)
    for t in range(13, 16):
        logits, caches = module.apply(variables, jnp.asarray(TOKENS[None, t:t + 1]),
                                      positions=jnp.full((1, 1), t), caches=caches,
                                      cache_index=jnp.full((1,), t))
        np.testing.assert_allclose(logits[0, 0], want[t], atol=2e-5)


def test_conversion_refuses_what_it_cannot_represent():
    model, _ = hf_model(PATTERNS["all_linear"])
    model.config.mlp_only_layers = [0]
    with pytest.raises(ValueError, match="mlp_only_layers"):
        config_kwargs_from_hf(model.config)
    model.config.mlp_only_layers = []
    model.config.shared_expert_intermediate_size = 24
    with pytest.raises(ValueError, match="shared_expert_intermediate_size"):
        config_kwargs_from_hf(model.config)
    model.config.shared_expert_intermediate_size = 16
    state = dict(model.state_dict())
    state["model.layers.0.linear_attn.conv1d.bias"] = state["model.norm.weight"]
    with pytest.raises(ValueError, match="unmapped"):
        convert_qwen3_next_state_dict(state, config_kwargs_from_hf(model.config))


def test_a_share_of_a_checkpoint_converts_to_the_stacks_it_holds():
    model, _ = hf_model(PATTERNS["all_linear"])
    kwargs = config_kwargs_from_hf(model.config)
    whole = convert_qwen3_next_state_dict(model.state_dict(), kwargs)["params"]
    part = convert_qwen3_next_state_dict(
        model.state_dict(), dict(kwargs, experts_first=2, experts_held=4))["params"]
    for name in ("w1", "w2", "w3"):
        np.testing.assert_array_equal(part["layer_0"]["moe"][name],
                                      whole["layer_0"]["moe"][name][2:6])
    assert part["layer_0"]["moe"]["router"].shape == (32, 8)       # the router stays whole


# ---- the rule ---------------------------------------------------------------
def rule_inputs(b, s, heads=3, dk=16, dv=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2_normalize(jax.random.normal(keys[0], (b, s, heads, dk))) * dk ** -0.5
    k = l2_normalize(jax.random.normal(keys[1], (b, s, heads, dk)))
    v = jax.random.normal(keys[2], (b, s, heads, dv))
    g = -2.0 * jax.nn.softplus(jax.random.normal(keys[3], (b, s, heads)))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (b, s, heads)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], (b, heads, dk, dv))


def recurrence(q, k, v, g, beta, state):
    """The rule a token at a time, written out (not the function's s = 1 case)."""
    outs = []
    for t in range(q.shape[1]):
        state = state * jnp.exp(g[:, t])[..., None, None]
        d = beta[:, t][..., None] * (v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., :, None] * d[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    return jnp.stack(outs, axis=1), state


# a step, under / at / over one sub-chunk, a prompt that ends mid-chunk, a whole
# prefill chunk of 256 rows
@pytest.mark.parametrize("rows", [1, 2, GDN_CHUNK - 1, GDN_CHUNK, GDN_CHUNK + 1, 100, 4 * GDN_CHUNK])
def test_the_chunked_form_is_the_recurrence(rows):
    inputs, state = rule_inputs(2, rows, seed=rows)
    got, got_state = gated_delta_rule(*inputs, state)
    want, want_state = recurrence(*inputs, state)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5)


@pytest.mark.parametrize("cuts", [(1,), (GDN_CHUNK,), (3, 70), (64, 65, 66), (100,)])
def test_the_rule_in_pieces_is_the_rule_at_once(cuts):
    """A sequence cut anywhere, each piece padded behind its rows with rows
    of beta = 0 and g = 0, S handed on: the same outputs and the same S."""
    (q, k, v, g, beta), state = rule_inputs(1, 130, seed=5)
    whole, end = gated_delta_rule(q, k, v, g, beta, state)
    pieces, start = [], 0
    for stop in cuts + (130,):
        pad = ((0, 0), (0, 7), (0, 0))
        piece = [jnp.pad(x[:, start:stop], pad + ((0, 0),), constant_values=3.0)
                 for x in (q, k, v)]
        piece += [jnp.pad(x[:, start:stop], pad) for x in (g, beta)]      # zeros: no token
        out, state = gated_delta_rule(*piece, state)
        pieces.append(out[:, :stop - start])
        start = stop
    np.testing.assert_allclose(jnp.concatenate(pieces, axis=1), whole, atol=2e-5)
    np.testing.assert_allclose(state, end, atol=2e-5)


# ---- the module over a cache ------------------------------------------------
@pytest.fixture(scope="module")
def served():
    module = get_model("transformer", **KW)
    params = module.init(jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))
    return module, params


@pytest.mark.parametrize("shape", ["chunk", "step"])
def test_rows_that_are_no_tokens_leave_both_state_arrays_untouched(served, shape):
    """A chunk whose rows are ALL padding, and a step over slots nobody holds
    (positions that would read as a start among them), hand the conv rows and
    S of every linear-attention layer back bit for bit."""
    module, params = served
    cfg = module.cfg
    caches = init_paged_kv_caches(cfg, 12, 4, state_slots=3)
    caches = [type(layer)(x + 0.25 * (1 + i) for x in layer) if is_state_entry(layer) else layer
              for i, layer in enumerate(caches)]
    if shape == "chunk":
        kwargs = dict(positions=jnp.full((1, CHUNK), PAD_POS),
                      block_tables=jnp.asarray([[2, 3, 4]]), state_slots=jnp.asarray([1]))
        tokens = jnp.zeros((1, CHUNK), jnp.int32)
    else:       # trash rows: slots that are free, or prefilling
        kwargs = dict(positions=jnp.asarray([[0], [5], [0]]),
                      block_tables=jnp.ones((3, 3), jnp.int32))
        tokens = jnp.asarray([[3], [4], [5]])
    _, after = module.apply(params, tokens, caches=caches, **kwargs)
    for before, now in zip(caches, after):
        if is_state_entry(before):
            assert len(now) == 2 and now[1].dtype == jnp.float32
            for a, b in zip(before, now):
                np.testing.assert_array_equal(a, b)


def test_the_cache_trees_hold_a_state_entry_of_two_arrays(served):
    from seldon_core_tpu.models.cache import kv_cache_bytes_per_token, state_bytes

    cfg = served[0].cfg
    dense = init_kv_caches(cfg, 2, 16)
    paged = init_paged_kv_caches(cfg, 10, 4, state_slots=3)
    assert [is_state_entry(layer) for layer in paged] == [True, True, True, False]
    assert [is_state_entry(layer) for layer in dense] == [True, True, True, False]
    channels = 2 * 2 * 16 + 4 * 8
    assert [x.shape for x in paged[0]] == [(3, 3, channels), (3, 4, 16, 8)]
    assert [x.shape for x in dense[0]] == [(2, 3, channels), (2, 4, 16, 8)]
    assert paged[0][1].dtype == jnp.float32
    assert paged[3][0].shape == (10, 4, 2 * 16)         # 2 KV heads of head_dim 16, one row
    assert state_bytes(cfg) == 3 * (3 * channels * 4 + 4 * 16 * 8 * 4)
    assert kv_cache_bytes_per_token(cfg) == 1 * (2 * 2 * 16 * 4 + 4)
    # a latent-attention entry is a 2-tuple too, and is no state entry
    latent = get_model("transformer", vocab_size=32, dim=32, n_layers=1, n_heads=2, n_kv_heads=2,
                       ffn_dim=16, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                       v_head_dim=8, dtype="float32").cfg
    assert [len(e) for e in init_paged_kv_caches(latent, 6, 4)] == [2]
    assert not is_state_entry(init_paged_kv_caches(latent, 6, 4)[0])


def test_the_page_operations_hand_both_arrays_on(served):
    _, _, reset_pages, _, _, cow_page_copy, export_pages, _ = _page_table_ops()
    tree = init_paged_kv_caches(served[0].cfg, 10, 4, state_slots=3)
    tree = [type(layer)(x + 1.5 for x in layer) if is_state_entry(layer) else layer for layer in tree]
    before = [[np.asarray(x) for x in layer] for layer in tree if is_state_entry(layer)]
    tree = reset_pages(tree, jnp.asarray([2, 3, 1, 1]))
    tree = cow_page_copy(tree, jnp.asarray(2), jnp.asarray(3), jnp.asarray(2))
    after = [[np.asarray(x) for x in layer] for layer in tree if is_state_entry(layer)]
    for a, b in zip(before, after):
        assert len(a) == len(b) == 2
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    exported = export_pages(tree, jnp.asarray([2, 3]))
    assert len(exported) == 1 and exported[0][0].shape[0] == 2      # the attention layer's pages alone


def test_served_forward_matches_the_reference_on_the_share(served):
    module, params = served
    got, _ = module.apply(params, jnp.asarray(TOKENS[None]))
    want, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    assert len(routing) == 4 and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # the router chose among all 16; some of what it chose lies elsewhere
    chosen = np.concatenate([np.asarray(layer["experts"]).ravel() for layer in routing])
    assert chosen.min() < 4 and chosen.max() >= 12 and ((chosen >= 4) & (chosen < 12)).any()


# ---- the shares add up ------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in four shares of 4: each share's MoE layer gives its own
    experts' part of the result and (every chip alike) the shared expert; the
    four parts, the shared expert counted ONCE, are the uncut layer's output.
    In the module and in the reference."""
    from seldon_core_tpu.models.transformer import MoEFFN

    whole_kw = {**KW, "experts_first": 0, "experts_held": 0}
    whole_cfg = get_model("transformer", **whole_kw).cfg
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 32))
    whole = MoEFFN(whole_cfg)
    p = whole.init(jax.random.PRNGKey(3), x)["params"]
    # a shared expert and a gate that matter
    p = {**p, "shared_gate": p["shared_gate"] * 4.0}
    full = whole.apply({"params": p}, x)
    no_shared = dict(whole_kw, n_shared_experts=0, shared_expert_gate=False)
    shared_alone = full - MoEFFN(get_model("transformer", **no_shared).cfg).apply(
        {"params": {k: v for k, v in p.items() if k not in ("shared", "shared_gate")}}, x)
    assert float(jnp.abs(shared_alone).max()) > 1e-2
    parts, ref_parts = [], []
    for first in range(0, 16, 4):
        cfg = get_model("transformer", **{**KW, "experts_first": first, "experts_held": 4}).cfg
        mine = {**p, **{w: p[w][first:first + 4] for w in ("w1", "w2", "w3")}}
        (part, sown) = MoEFFN(cfg).apply({"params": mine}, x, mutable=["moe"])
        held, routed = np.asarray(sown["moe"]["pairs"][0])
        assert routed == 2 * 9 * 4 and 0 < held < routed
        parts.append(part)
        ref_parts.append(reference._experts(mine, x[0], cfg, None)[0])
    np.testing.assert_allclose(sum(parts) - 3 * shared_alone, full, atol=1e-5)
    uncut = reference._experts(p, x[0], whole_cfg, None)[0]
    np.testing.assert_allclose(uncut, full[0], atol=1e-5)
    np.testing.assert_allclose(sum(ref_parts) - 3 * shared_alone[0], uncut, atol=1e-5)


def test_the_share_is_validated_where_the_config_is_made():
    with pytest.raises(ValueError, match="expert share"):
        get_model("transformer", **{**KW, "experts_first": 12, "experts_held": 8})
    with pytest.raises(ValueError, match="linear_attention"):
        get_model("transformer", **{**KW, "linear_num_value_heads": 3})
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        get_model("transformer", **{**KW, "partial_rotary_factor": 0.3})


# ---- through the batcher ----------------------------------------------------
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
    info = {"logits": []}
    out = await b.submit(prompt, max_new_tokens=n, info=info, **kw)
    return out, np.stack(info["logits"]), np.stack(info["routing"])


def reference_logits(server, prompt, out, routing):
    first = len(prompt) - 1
    ref, took = reference.forward(server._params, server._cfg, prompt + out[:-1],
                                  rows=slice(first, first + len(out)), follow=routing)
    assert max(float(layer["behind"].max()) for layer in took) < 1e-4
    return np.asarray(ref)


# every way a chunk boundary can fall against the four taps and the carried S
@pytest.mark.parametrize("length", [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1, CHUNK + 3, 2 * CHUNK + 3])
def test_chunked_prefill_and_decode_equal_the_full_forward(server, length):
    prompt = LONG[:length]

    async def go():
        b = batcher(server)
        got = await ask(b, prompt)
        stats = {**b._phases.stats(), **b._moe.stats()}
        await b.close()
        return got, stats

    (out, logits, routing), stats = asyncio.run(go())
    assert logits.shape == (5, KW["vocab_size"])
    np.testing.assert_allclose(logits, reference_logits(server, prompt, out, routing),
                               atol=3e-5, rtol=0)
    # the live rows through the linear-attention layers, as the loop counted them
    assert stats["gdn_rows"] == {"chunk": length, "decode": 4}
    assert stats["gdn_layer_calls"] == {"chunk": 3 * -(-length // CHUNK), "decode": 3 * 4}
    assert "conv_rows" not in stats
    # held + elsewhere = every pair the router made: 4 layers x 4 experts a live row
    for program, rows in (("chunk", length), ("decode", 4)):
        tally = stats["moe_by_program"][program]
        assert tally["routed_pairs"] + tally["pairs_elsewhere"] == 4 * 4 * rows
        assert 0 < tally["routed_pairs"] and tally["experts_touched"] <= 8 * tally["calls"] * 4


def test_a_request_among_others_gives_the_logits_it_gives_alone(server):
    """B is prefilled (three chunks) while A decodes, and decodes while C is
    prefilled: steps of the other slots run between B's chunks on the same S
    and conv arrays, and chunks of C between B's steps."""
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
        await bt.close()
        return got

    for (out, logits, routing), prompt, n in zip(asyncio.run(together()), (a, b_, c), (14, 10, 6)):
        solo_out, solo_logits, _ = asyncio.run(alone(prompt, n))
        assert out == solo_out
        np.testing.assert_allclose(logits, solo_logits, atol=1e-5, rtol=0)
        np.testing.assert_allclose(logits, reference_logits(server, prompt, out, routing),
                                   atol=3e-5, rtol=0)


def test_a_reused_slot_starts_from_zeros(server):
    """One slot: a long request, then a short one in the same slot. The short
    one reads no S and no conv rows the long one left: the first chunk of a
    sequence (position 0) zeroes S, the taps mask by position."""
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


def test_batcher_tokens_equal_generate():
    s = make_server(temperature=0.8, top_k=20, seed=5)
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


REFUSALS = {
    "prefix_cache": (dict(prefix_cache_size=4), "prefix_cache_size"),
    "speculation": (dict(spec_mode="ngram"), "spec_mode"),
    "remote_prefill": (dict(disaggregation="remote_prefill"), "remote_prefill"),
    "tensor_parallel": (dict(tensor_parallel=2), "parallelism"),
    "lora": (dict(lora_rank=4), "lora_rank"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_is_not_built_over_linear_attention_is_refused_at_load(what):
    kwargs, names = REFUSALS[what]
    kw = {**KW, "experts_first": 0, "experts_held": 0}
    if what == "lora":   # adapters are refused for MoE first; a dense hybrid names the state
        kw = dict(kw, n_experts=0, n_shared_experts=0, shared_expert_gate=False)
    s = LLMServer(**{**dict(model="transformer", model_kwargs=kw, init_random=True), **kwargs})
    with pytest.raises(ValueError, match="linear_attention layers.*" + names):
        s.load()


def test_the_gauges_and_counters_reach_the_registry():
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
    channels = 2 * 2 * 16 + 4 * 8
    # 3 layers x 2 slots x (3 conv rows + a [4, 16, 8] matrix state), float32 here
    assert stats["state_bytes"] == 3 * 2 * (3 * channels + 4 * 16 * 8) * 4
    assert stats["gdn_rows"] == {"chunk": CHUNK + 2, "decode": 3}
    assert stats["gdn_layer_calls"] == {"chunk": 6, "decode": 9}
    lines = [line for line in text.splitlines() if not line.startswith("#")]

    def value(name, label):
        found = [float(line.rsplit(" ", 1)[1]) for line in lines
                 if line.startswith(name) and label in line]
        assert len(found) == 1, (name, label, found)
        return found[0]

    assert value("seldon_llm_state_bytes", "") == stats["state_bytes"]
    assert value("seldon_llm_gdn_rows_total", 'program="chunk"') == CHUNK + 2
    assert value("seldon_llm_gdn_layer_calls_total", 'program="decode"') == 9
    assert not any(line.startswith("seldon_llm_conv_rows_total") for line in lines)
    held = value("seldon_llm_moe_routed_pairs_total", 'program="decode"')
    elsewhere = value("seldon_llm_moe_pairs_elsewhere_total", 'program="decode"')
    assert held > 0 and held + elsewhere == 3 * 4 * 4
    # delivered tokens by expert: the HELD experts, under their own ids
    experts = sorted(int(line.split('expert="')[1].split('"')[0]) for line in lines
                     if line.startswith("seldon_llm_moe_expert_tokens_total"))
    assert experts == list(range(4, 12))


def test_the_small_leaves_are_drawn_by_the_published_rule():
    from seldon_core_tpu.models.leaves import FLOAT32_AXES, draw_small_leaf

    key = jax.random.PRNGKey(0)
    a_log = np.asarray(draw_small_leaf("A_log", key, (4096,)))
    assert np.exp(a_log).min() >= 0 and 15.5 < np.exp(a_log).max() <= 16.0
    assert abs(float(np.exp(a_log).mean()) - 8.0) < 0.3
    assert np.asarray(draw_small_leaf("dt_bias", key, (32,))).tolist() == [1.0] * 32
    taps = np.asarray(draw_small_leaf("conv1d", key, (8192, 4)))
    assert abs(float(taps.std()) - 0.5) < 0.01
    gate = np.asarray(draw_small_leaf("shared_gate", key, (2048, 1)))
    assert abs(float(gate.std()) - 2048 ** -0.5) < 2e-3
    assert {"gdn_scalar", "expert_gate", "conv_taps", "head_norm"} <= set(FLOAT32_AXES)


# ---- the wrong references of the chip check ----------------------------------
# ... and by how much of the logits' scale each must differ from the right one
# in float32 at this size
WRONG = {
    "decay_left_out": (dict(gdn_decay=False), 0.02),
    "beta_one": (dict(gdn_beta=False), 0.02),
    "no_l2_norm_on_q_and_k": (dict(gdn_l2norm=False), 0.02),
    "state_zeroed_at_a_chunk_start": (dict(gdn_reset_every=8), 0.02),
    "state_from_the_chunks_last_row": (dict(conv_state_pad=(10, 16)), 0.01),
    "taps_reversed": (dict(taps_reversed=True), 0.02),
    "no_silu_after_the_taps": (dict(gdn_silu=False), 0.02),
    "z_gate_left_out": (dict(gdn_z_gate=False), 0.02),
    "attention_gate_left_out": (dict(attn_gate=False), 0.01),
    "rotary_over_the_whole_head": (dict(rotary_all=True), 0.002),
    "shared_expert_left_out": (dict(shared=False), 0.02),
    "shared_gate_left_out": (dict(shared_gate=False), 0.02),
    "largest_held_expert_left_out": (dict(leave_out_held=True), 0.02),
    "state_held_in_bf16": (dict(gdn_state_bf16=True), 1e-4),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_reference_is_another_model_in_float32(served, name):
    module, params = served
    keywords, margin = WRONG[name]
    right, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    wrong, _ = reference.forward(params, module.cfg, TOKENS.tolist(), **keywords)
    rows = slice(10, None) if "state_" in name and "bf16" not in name else slice(None)
    differ = float(jnp.abs(wrong - right)[rows].max() / jnp.abs(right).max())
    assert differ > margin, differ
    assert np.isfinite(np.asarray(wrong)).all()


@pytest.mark.parametrize("shape", [(4, 6, 8), (16, 64, 32), (3, 257, 130)])
def test_a_stack_drawn_as_one_matrix_is_the_stack_drawn_whole(shape):
    """The streamed init draws an expert stack as ONE matrix of its rows and
    reshapes it (servers/llmserver.py ``make_quantized``: the TPU compiler
    takes 3 s over that and 10-30 s over the 3-D draw): the generator counts
    elements row-major whatever the shape, so every configuration's seeded
    weights are what they were."""
    key = jax.random.PRNGKey(22)
    whole = jax.random.normal(key, shape, jnp.float32)
    rows = jax.random.normal(key, (shape[0] * shape[1], shape[2]), jnp.float32).reshape(shape)
    np.testing.assert_array_equal(whole, rows)
