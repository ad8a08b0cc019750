"""The plain float32 reference of LFM2 (models/reference.py: gated short
convolutions as an explicit shifted sum, a norm per q / k head before RoPE, a
sigmoid router with a selection bias and a renormalised top-k over sum + 1e-6),
what holds it, and what it holds:

- to the installed ``transformers`` ``Lfm2ForCausalLM`` (the dense sibling,
  whose ``Lfm2ShortConv``, ``Lfm2Attention`` and block order are
  LFM2-8B-A1B's too) on converted weights, for every layer pattern: the B, C, X
  split order, the tap order, QK-norm per head before RoPE, the block's two
  norms, ``embedding_norm`` last;
- to the published equations by property for the router (no ``lfm2_moe`` is
  installed): sigmoid scores, the bias chooses and weighs nothing, the k
  weights sum to sum / (sum + 1e-6);
- the served cache-less forward (models/transformer.py) to it;
- and each WRONG reference of the chip check (perf/configs/lfm2-8b-a1b-int8.json
  ``reference_tolerance``) differs from the right one in float32 by a margin,
  so that a limit between the two readings can exist.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.convert import (
    config_kwargs_from_hf, convert_hf_model, convert_lfm2_state_dict)

PATTERNS = {
    "published_head": ["conv", "conv", "full_attention", "conv"],
    "published_tail": ["full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "all_conv": ["conv", "conv", "conv"],
    "all_attention": ["full_attention", "full_attention"],
}
# the served model in small: LFM2-8B-A1B's kinds of layer
KW = dict(vocab_size=96, dim=32, n_layers=6, n_heads=4, n_kv_heads=2, ffn_dim=16,
          dense_ffn_dim=48, first_dense_layers=2, n_experts=8, n_experts_per_token=4,
          router_score="sigmoid", router_bias=True, router_renormalize=True,
          router_renormalize_eps=1e-6, qk_norm="head", max_seq_len=64, norm_eps=1e-5,
          rope_theta=1e6, dtype="float32",
          layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv"])
TOKENS = np.random.default_rng(3).integers(0, 96, size=21)


def hf_model(layer_types, **extra):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(0)
    config = transformers.Lfm2Config(
        vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=len(layer_types),
        num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
        layer_types=list(layer_types), block_auto_adjust_ff_dim=False,
        tie_word_embeddings=False, **extra)
    model = transformers.Lfm2ForCausalLM(config).eval()
    with torch.no_grad():   # weights that a swapped order or a missing norm would show in
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.3 * torch.randn_like(p))
            elif name != "model.embed_tokens.weight":
                p.mul_(8.0 if "conv.conv" in name else 4.0)
    return model, torch


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_reference_and_served_forward_match_transformers_lfm2(pattern):
    model, torch = hf_model(PATTERNS[pattern])
    with torch.no_grad():
        want = model(torch.tensor(TOKENS[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    assert module.cfg.layer_types == tuple(PATTERNS[pattern]) and module.cfg.qk_norm == "head"
    ref, _ = reference.forward(variables, module.cfg, TOKENS.tolist())
    served, _ = module.apply(variables, jnp.asarray(TOKENS[None]))
    scale = np.abs(want).max()
    assert scale > 0.5                      # not a model of zeros
    assert np.abs(np.asarray(ref) - want).max() <= 1e-5 * max(scale, 1.0)
    assert np.abs(np.asarray(served[0]) - want).max() <= 1e-5 * max(scale, 1.0)


@pytest.mark.parametrize("tied", [False, True])
def test_a_converted_lfm2_serves_transformers_logits_through_the_batcher(tied):
    """The published layout -> this tree -> LLMServer's dense path: prefill
    into the cache and a decoded row give ``Lfm2ForCausalLM``'s logits."""
    from seldon_core_tpu.models.cache import PAD_POS, init_kv_caches

    model, torch = hf_model(PATTERNS["published_head"])
    if tied:
        model.lm_head.weight = model.model.embed_tokens.weight
        model.config.tie_word_embeddings = True
    with torch.no_grad():
        want = model(torch.tensor(TOKENS[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    assert ("lm_head" in variables["params"]) == (not tied)
    caches = init_kv_caches(module.cfg, 1, 32)
    pos = jnp.where(jnp.arange(16) < 13, jnp.arange(16), PAD_POS)[None]
    toks = jnp.asarray(np.concatenate([TOKENS[:13], [0, 0, 0]])[None])
    logits, caches = module.apply(variables, toks, positions=pos, caches=caches, cache_index=0)
    np.testing.assert_allclose(logits[0, :13], want[:13], atol=2e-5)
    for t in range(13, 16):     # the first decoded row reads state a PADDED prefill left
        logits, caches = module.apply(variables, jnp.asarray(TOKENS[None, t:t + 1]),
                                      positions=jnp.full((1, 1), t), caches=caches,
                                      cache_index=jnp.full((1,), t))
        np.testing.assert_allclose(logits[0, 0], want[t], atol=2e-5)


def test_conversion_refuses_what_it_cannot_represent():
    model, _ = hf_model(PATTERNS["all_conv"])
    model.config.block_auto_adjust_ff_dim = True
    with pytest.raises(ValueError, match="block_auto_adjust_ff_dim"):
        config_kwargs_from_hf(model.config)
    model.config.block_auto_adjust_ff_dim = False
    model.config.conv_bias = True
    with pytest.raises(ValueError, match="conv_bias"):
        config_kwargs_from_hf(model.config)
    model.config.conv_bias = False
    state = dict(model.state_dict())
    state["model.layers.0.conv.conv.bias"] = state["model.embedding_norm.weight"]
    with pytest.raises(ValueError, match="unmapped"):
        convert_lfm2_state_dict(state, config_kwargs_from_hf(model.config))


@pytest.fixture(scope="module")
def served():
    module = get_model("transformer", **KW)
    params = module.init(jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))
    return module, params


def test_the_moe_names_convert_as_assumed(served):
    """No ``lfm2_moe`` is installed: a state dict under the ASSUMED published
    names (feed_forward.gate / .expert_bias / .experts.N.w1-3) converts to the
    tree the module initialises, leaf for leaf, and its config keys to KW."""
    module, params = served
    p = params["params"]
    state = {"model.embed_tokens.weight": p["tok_embeddings"], "lm_head.weight": p["lm_head"].T,
             "model.embedding_norm.weight": p["norm"]["weight"]}
    for i, kind in enumerate(KW["layer_types"]):
        layer, hf = p[f"layer_{i}"], f"model.layers.{i}"
        state[f"{hf}.ffn_norm.weight"] = layer["ffn_norm"]["weight"]
        if kind == "conv":
            state[f"{hf}.operator_norm.weight"] = layer["operator_norm"]["weight"]
            state[f"{hf}.conv.in_proj.weight"] = layer["conv"]["in_proj"].T
            state[f"{hf}.conv.conv.weight"] = layer["conv"]["taps"][:, None, :]
            state[f"{hf}.conv.out_proj.weight"] = layer["conv"]["out_proj"].T
        else:
            a = layer["attention"]
            state[f"{hf}.operator_norm.weight"] = layer["attention_norm"]["weight"]
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "out_proj")):
                state[f"{hf}.self_attn.{theirs}.weight"] = a[ours].T
            state[f"{hf}.self_attn.q_layernorm.weight"] = a["q_norm"]["weight"]
            state[f"{hf}.self_attn.k_layernorm.weight"] = a["k_norm"]["weight"]
        if i < KW["first_dense_layers"]:
            for w in ("w1", "w2", "w3"):
                state[f"{hf}.feed_forward.{w}.weight"] = layer["ffn"][w].T
        else:
            state[f"{hf}.feed_forward.gate.weight"] = layer["moe"]["router"].T
            state[f"{hf}.feed_forward.expert_bias"] = layer["moe"]["router_bias"]
            for e in range(KW["n_experts"]):
                for w in ("w1", "w2", "w3"):
                    state[f"{hf}.feed_forward.experts.{e}.{w}.weight"] = layer["moe"][w][e].T

    class Published:      # the catalog's keys of LFM2-8B-A1B's config.json, in small
        model_type, vocab_size, hidden_size, intermediate_size = "lfm2_moe", 96, 32, 48
        moe_intermediate_size, num_hidden_layers, num_attention_heads = 16, 6, 4
        num_key_value_heads, max_position_embeddings, norm_eps, rope_theta = 2, 64, 1e-5, 1e6
        layer_types, conv_L_cache, conv_bias = KW["layer_types"], 3, False
        num_experts, num_experts_per_tok, num_dense_layers = 8, 4, 2
        norm_topk_prob, use_expert_bias, routed_scaling_factor = True, True, 1

    kwargs = config_kwargs_from_hf(Published)
    assert {k: kwargs[k] for k in KW if k not in ("dtype", "layer_types")} == {
        k: v for k, v in KW.items() if k not in ("dtype", "layer_types")}
    assert kwargs["layer_types"] == tuple(KW["layer_types"])
    tree = convert_lfm2_state_dict(state, kwargs)
    got = jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                       tree["params"], jax.tree.map(np.asarray, dict(p)))
    assert jax.tree.structure(tree["params"]) == jax.tree.structure(dict(p)) and got is not None


def test_served_forward_matches_the_reference_on_the_moe_model(served):
    module, params = served
    got, _ = module.apply(params, jnp.asarray(TOKENS[None]))
    want, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    assert len(routing) == 4 and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def test_the_router_is_the_published_one(served):
    """s = sigmoid(W_g y); chosen = top-4(s + b); g = s[chosen] / (sum + 1e-6)."""
    module, params = served
    _, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    _, unbiased = reference.forward(params, module.cfg, TOKENS.tolist(), select_bias=False)
    for layer, plain in zip(routing, unbiased):
        weights, experts = np.asarray(layer["weights"]), np.asarray(layer["experts"])
        assert experts.shape == (21, 4) and all(len(set(row)) == 4 for row in experts.tolist())
        assert ((weights > 0) & (weights < 1)).all()
        # sum s / (sum s + 1e-6): under 1 by about 1e-6 / sum, never 1 + anything
        total = weights.sum(axis=-1)
        assert (total < 1.0).all() and (total > 1.0 - 2e-5).all()
    # the bias chooses: with b = 0 some token of the first MoE layer takes other experts
    first, plain = np.asarray(routing[0]["experts"]), np.asarray(unbiased[0]["experts"])
    assert any(set(a) != set(b) for a, b in zip(first.tolist(), plain.tolist()))
    # ... and weighs nothing: a token whose four experts are the same either way has the same weights
    same = [i for i, (a, b) in enumerate(zip(first.tolist(), plain.tolist())) if a == b]
    assert same
    np.testing.assert_allclose(np.asarray(routing[0]["weights"])[same],
                               np.asarray(unbiased[0]["weights"])[same], atol=1e-7)


def test_the_renormalisation_adds_the_configured_epsilon(served):
    """router_renormalize_eps reaches the served router and the reference:
    with an epsilon of 0.5 both shrink the routed output alike."""
    import dataclasses

    module, params = served
    loud = get_model("transformer", **{**KW, "router_renormalize_eps": 0.5})
    got, _ = loud.apply(params, jnp.asarray(TOKENS[None]))
    want, _ = reference.forward(params, loud.cfg, TOKENS.tolist())
    plain, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert float(jnp.abs(want - plain).max()) > 1e-2
    assert dataclasses.replace(module.cfg, router_renormalize_eps=None).router_renormalize_eps is None


# the wrong references of the chip check, and by how much of the logits' scale
# each must differ from the right one in float32 at this size (measured: 2-10x these)
WRONG = {
    "state_zeroed_at_a_chunk_start": (dict(conv_reset_every=8), 0.02),
    "state_from_the_chunks_last_row": (dict(conv_state_pad=(10, 16)), 0.02),
    "taps_reversed": (dict(taps_reversed=True), 0.05),
    "gate_b_left_out": (dict(gate_b=False), 0.05),
    "gate_c_left_out": (dict(gate_c=False), 0.05),
    "qk_norm_over_the_whole_projection": (dict(qk_norm="whole"), 0.005),
    "qk_norm_left_out": (dict(qk_norm=False), 0.01),
    "softmax_scores": (dict(router_score="softmax"), 0.05),
    "largest_expert_left_out": (dict(leave_out_rank=0), 0.05),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_reference_is_another_model_in_float32(served, name):
    module, params = served
    keywords, margin = WRONG[name]
    right, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    wrong, _ = reference.forward(params, module.cfg, TOKENS.tolist(), **keywords)
    rows = slice(10, None) if "state" in name else slice(None)   # behind the boundary it breaks
    differ = float(jnp.abs(wrong - right)[rows].max() / jnp.abs(right).max())
    assert differ > margin, differ


def test_the_selection_bias_left_out_shows_in_the_choices_not_the_logits(served):
    """Followed, a reference without the bias computes the same logits (the
    bias weighs nothing) and finds the served choices behind its own: what
    ``choice_behind`` catches and ``atol_over_scale`` cannot."""
    module, params = served
    _, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    follow = np.stack([np.asarray(layer["experts"]) for layer in routing], axis=1)
    right, took = reference.forward(params, module.cfg, TOKENS.tolist(), follow=follow)
    wrong, blind = reference.forward(params, module.cfg, TOKENS.tolist(), follow=follow,
                                     select_bias=False)
    np.testing.assert_allclose(wrong, right, atol=1e-6)
    assert max(float(layer["behind"].max()) for layer in took) == 0.0
    assert max(float(layer["behind"].max()) for layer in blind) > 0.02
