"""Xing4.0's block (four residual streams mixed by Sinkhorn-projected
hyper-connections, compressed-query latent attention, sigmoid scores with a
selection bias, one shared expert, a leading dense layer, an MTP module)
against the plain reference, and the reference against what it claims to
describe: tests/test_reference_mla.py's chain for the third block it knows.

``transformers.DeepseekV3ForCausalLM`` == reference == ``Transformer`` on
converted weights with the streams off (compressed queries, the sigmoid /
biased-selection router with renormalised weights x routed_scaling_factor, the
shared expert, the leading dense layer, YaRN with the m^2 both sides carry);
the stream mixing, which has no installed port, is held to its equations by
property (a doubly stochastic H_res; the degenerate parameters under which every
stream stays the plain residual); reference == ``Transformer`` full forward ==
chunked prefill then decode through the paged latent pool == generate()'s dense
cache shapes == ``LLMServer`` + ``ContinuousBatcher`` with int8 weights and
bf16 activations, with the streams on and off; the MTP module == the
reference's. Six wrong models fail the served tolerance; the seventh (one
Sinkhorn iteration) fails the float32 one.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.cache import PAD_POS, TRASH_PAGE, init_kv_caches, init_paged_kv_caches
from seldon_core_tpu.models.transformer import HyperConnection

YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
# Xing4.0-29B-A4B's shape at toy widths: layer 0 dense, then 16 experts top-4
# (sigmoid, selection bias, renormalised, x 2) + 1 shared; 4 streams
XING4 = dict(vocab_size=128, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, ffn_dim=32,
             max_seq_len=128, n_experts=16, n_experts_per_token=4, router_renormalize=True,
             routed_scaling_factor=2.0, first_dense_layers=1, dense_ffn_dim=96,
             n_shared_experts=1, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, q_lora_rank=24, router_score="sigmoid", router_bias=True,
             hc_mult=4, rope_scaling=YARN, tie_embeddings=False)
HC = [4, 0]


def xing4(hc_mult: int = 4, **over) -> dict:
    return {**XING4, "hc_mult": hc_mult, **over}


def xing4_params(module, seed: int):
    """The module's seeded init with the embedding at unit scale, so that the
    logits are of order 1 (tests/test_reference_mla.py ``dsv2_params``)."""
    variables = module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda x: x, variables["params"])
    params["tok_embeddings"] = params["tok_embeddings"] * 50.0
    return {"params": params}


def tokens_of(seed: int, n: int, vocab: int = 128) -> np.ndarray:
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab))


# ---------------------------------------------------------------- HF == reference
def v3_config(transformers, **over):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
        first_k_dense_replace=1, kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, norm_topk_prob=True, routed_scaling_factor=2.0,
        n_group=1, topk_group=1, max_position_embeddings=64, rope_scaling=None,
        rms_norm_eps=1e-6)
    return transformers.DeepseekV3Config(**{**base, **over})


def v3_model(transformers, torch, **over):
    """A seeded DeepseekV3ForCausalLM whose every part is visible in the
    logits: norms off ones, experts and router at unit scale, a selection bias
    the size of the scores' spread."""
    torch.manual_seed(0)
    hf = transformers.DeepseekV3ForCausalLM(v3_config(transformers, **over)).eval()
    with torch.no_grad():
        for name, w in hf.named_parameters():
            if "norm" in name:
                w.copy_(1.0 + 0.3 * torch.randn_like(w))
            elif "experts" in name:
                w.mul_(15.0)
            elif "gate.weight" in name:
                w.copy_(torch.randn_like(w))
        for name, buf in hf.named_buffers():
            if name.endswith("e_score_correction_bias"):
                buf.copy_(0.2 * torch.randn_like(buf))
    return hf


@pytest.mark.parametrize("rope", ["plain-rope", "yarn"])
def test_reference_matches_hf_deepseek_v3(rope):
    """Compressed queries (q_a_proj, q_a_layernorm, q_b_proj; the rope columns
    of q_b_proj from interleaved pairs to halves), sigmoid scores chosen by
    score + e_score_correction_bias and weighed without it, renormalised and
    times 2, the shared expert and the leading dense layer are transformers'
    on converted weights, for the reference AND for the module. The V3 port
    carries YaRN's m^2 on the softmax scale as this repo does, so YaRN is
    compared too. float32 both sides: 2e-4 is summation order."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from seldon_core_tpu.models.convert import convert_hf_model

    scaling = None if rope == "plain-rope" else {**YARN, "rope_type": "yarn"}
    hf = v3_model(transformers, torch, rope_scaling=scaling)
    module, variables = convert_hf_model(hf)
    cfg = module.cfg
    assert cfg.q_lora_rank == 24 and cfg.router_score == "sigmoid" and cfg.router_bias
    assert cfg.router_renormalize and cfg.routed_scaling_factor == 2.0 and cfg.hc_mult == 0
    assert cfg.mtp_layers == 0 and (cfg.rope_scaling is None) == (rope == "plain-rope")
    bias = variables["params"]["layer_1"]["moe"]["router_bias"]
    assert bias.shape == (16,) and float(np.abs(bias).max()) > 0.1
    tokens = tokens_of(1, 24)
    with torch.no_grad():
        theirs = hf(torch.from_numpy(tokens[None].astype(np.int64))).logits[0].numpy()
    logits, routing = reference.forward(variables, cfg, tokens)
    assert len(routing) == 2 and float(np.abs(theirs).max()) > 0.3
    np.testing.assert_allclose(np.asarray(logits), theirs, atol=2e-4, rtol=2e-4)
    ours, _ = module.apply(variables, jnp.asarray(tokens[None], jnp.int32))
    np.testing.assert_allclose(np.asarray(ours[0]), theirs, atol=2e-4, rtol=2e-4)
    # the bias decides choices here: without it the reference is another model
    unbiased, _ = reference.forward(variables, cfg, tokens, select_bias=False)
    assert float(np.abs(np.asarray(unbiased) - theirs).max()) > 0.05


def test_converter_maps_the_mtp_layer_and_the_module_matches_the_reference():
    """The published layout of the MTP module (the layer behind the last:
    eh_proj over [enorm(emb) ; hnorm(h)], shared_head.norm, a copy of the
    embedding and the head) converts to ours: eh_proj's halves swap to the
    paper's [h ; emb], the copies go. The installed port drops the layer when
    it loads, so the state dict is the port's plus that layer, and the check of
    the values is the module's MTP logits against ``reference.forward_mtp``."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from seldon_core_tpu.models.convert import (
        config_kwargs_from_hf, convert_deepseek_v2_state_dict, has_mtp_weights)

    hf = v3_model(transformers, torch, num_nextn_predict_layers=1)
    state = dict(hf.state_dict())
    kwargs = config_kwargs_from_hf(hf.config)
    assert kwargs["mtp_layers"] == 1 and not has_mtp_weights(state, kwargs)
    gen = torch.Generator().manual_seed(5)
    for key in [k for k in state if k.startswith("model.layers.2.")]:
        state[key.replace("layers.2.", "layers.3.")] = state[key] + 0.05 * torch.randn(
            state[key].shape, generator=gen)
    eh = torch.randn(64, 128, generator=gen) / 8.0
    state.update({
        "model.layers.3.eh_proj.weight": eh,
        "model.layers.3.enorm.weight": 1.0 + 0.3 * torch.randn(64, generator=gen),
        "model.layers.3.hnorm.weight": 1.0 + 0.3 * torch.randn(64, generator=gen),
        "model.layers.3.shared_head.norm.weight": 1.0 + 0.3 * torch.randn(64, generator=gen),
        "model.layers.3.embed_tokens.weight": state["model.embed_tokens.weight"],
        "model.layers.3.shared_head.head.weight": state["lm_head.weight"]})
    assert has_mtp_weights(state, kwargs)
    variables = convert_deepseek_v2_state_dict(state, kwargs)
    mtp = variables["params"]["mtp"]
    assert sorted(mtp) == ["block", "eh_proj", "enorm", "hnorm", "norm"]
    np.testing.assert_array_equal(mtp["eh_proj"][:64], eh.numpy().T[64:])   # h's rows first
    np.testing.assert_array_equal(mtp["eh_proj"][64:], eh.numpy().T[:64])
    assert "router_bias" in mtp["block"]["moe"] and "wq_b" in mtp["block"]["attention"]
    state["model.layers.3.surprise.weight"] = eh
    with pytest.raises(ValueError, match="unmapped weights"):
        convert_deepseek_v2_state_dict(state, kwargs)

    module = get_model("transformer", dtype="float32", **kwargs)
    tokens = tokens_of(6, 20)
    with jax.default_matmul_precision("highest"):
        logits, _, served = module.apply(
            variables, jnp.asarray(tokens[None, :-1]), next_tokens=jnp.asarray(tokens[None, 1:]))
    want = np.asarray(reference.forward_mtp(variables, module.cfg, tokens))
    assert want.shape == (19, 128) and float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(np.asarray(served[0]), want, atol=1e-4, rtol=1e-4)
    main = np.asarray(reference.forward(variables, module.cfg, tokens[:-1])[0])
    np.testing.assert_allclose(np.asarray(logits[0]), main, atol=1e-4, rtol=1e-4)
    assert float(np.abs(want - main).max()) > 0.1      # another prediction, not a copy


# --------------------------------- the stream mixing, held to its equations
def mixing_of(side: str, params: dict, X: np.ndarray, cfg):
    """(u, H_post [t, n], H_res [t, n, n]) of one sub-layer from either side."""
    if side == "reference":
        u, h_post, h_res = reference._mix(params, jnp.asarray(X), cfg, cfg.hc_sinkhorn_iters)
        return np.asarray(u), np.asarray(h_post), np.asarray(h_res)
    u, h_post, h_res = HyperConnection(cfg).apply({"params": params}, jnp.asarray(X[None]))
    # maps-major [n, 1, t] / [n, n, 1, t] -> tokens first
    return (np.asarray(u[0]), np.asarray(h_post[:, 0]).T,
            np.moveaxis(np.asarray(h_res[:, :, 0]), -1, 0))


@pytest.mark.parametrize("side", ["reference", "served"])
def test_sinkhorn_makes_the_residual_matrix_doubly_stochastic(side):
    """After the configured 20 iterations on inputs of the size the stated
    init gives, H_res's rows and columns sum to 1 within 1e-4 and its entries
    are positive; H_pre / H_post lie in (0, 1) / (0, 2); and with the maps
    driven to the clamp (+-30 before the exponential) everything stays finite.
    Both sides, which share no code, agree to float32 rounding."""
    cfg = get_model("transformer", dtype="float32", **xing4()).cfg
    params = HyperConnection(cfg).init(jax.random.PRNGKey(3), jnp.zeros((1, 2, 4, 64)))["params"]
    X = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (40, 4, 64))) * 3.0
    u, h_post, h_res = mixing_of(side, params, X, cfg)
    assert u.shape == (40, 64) and h_post.shape == (40, 4) and h_res.shape == (40, 4, 4)
    assert np.all(h_res > 0) and np.all((h_post > 0) & (h_post < 2))
    np.testing.assert_allclose(h_res.sum(axis=2), 1.0, atol=1e-4)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=1e-4)
    assert np.abs(h_res - 0.25).max() > 0.2 and np.abs(h_res - np.eye(4)).max() > 0.2
    other = mixing_of("served" if side == "reference" else "reference", params, X, cfg)
    for mine, theirs in zip((u, h_post, h_res), other):
        np.testing.assert_allclose(mine, theirs, atol=2e-5, rtol=2e-5)
    wild = {**params, "alpha": jnp.asarray([1.0, 1.0, 1e4]), "b_res": params["b_res"] * 100.0}
    u, h_post, h_res = mixing_of(side, wild, X, cfg)
    assert np.all(np.isfinite(u)) and np.all(np.isfinite(h_res)) and np.all(h_res >= 0)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=1e-4)


@pytest.mark.parametrize("form,tokens", [("loop", (2, 5)), ("kernel", (32, 1)), ("kernel", (1, 256)),
                                         ("kernel", (3, 700))],
                         ids=["looped", "kernel: a decode step's rows", "kernel: a chunk's rows",
                              "kernel: rows that no tile divides, three tiles"])
def test_sinkhorn_is_the_plain_iteration_in_both_forms(form, tokens):
    """The served Sinkhorn chain is ONE entry-by-entry iteration
    (ops/sinkhorn.py ``entrywise_iteration``): a program lowered for a TPU
    runs it inside the repo's Pallas kernel, every other lowering as a loop
    (``sinkhorn_entrywise``). Both forms, evaluated here (the kernel under the
    Pallas interpreter, padded to whole [8, 128] tiles of tokens), are the
    plain rows-then-columns iteration on a [4, 4, t] array to float32
    rounding, and each other bit for bit."""
    from seldon_core_tpu.models.transformer import sinkhorn_entrywise
    from seldon_core_tpu.ops.sinkhorn import sinkhorn as sinkhorn_kernel

    iters = 20
    m = np.exp(np.asarray(jax.random.normal(jax.random.PRNGKey(9), (4, 4) + tokens)) * 0.9)
    want = m.copy()
    for _ in range(iters):
        want = want / (want.sum(axis=1, keepdims=True) + 1e-6)
        want = want / (want.sum(axis=0, keepdims=True) + 1e-6)
    looped = np.asarray(sinkhorn_entrywise(jnp.asarray(m), iters, 1e-6))
    got = looped if form == "loop" else np.asarray(
        sinkhorn_kernel(jnp.asarray(m), iters, 1e-6, interpret=True))
    assert got.shape == m.shape
    np.testing.assert_array_equal(got, looped)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(got.sum(axis=0), 1.0, atol=1e-5)   # columns last: exact


def plain_residual_mixing(n: int, dim: int) -> dict:
    """Phi = 0; b_pre = +30 on stream 0 and -30 on the others (H_pre = e_0);
    b_post = 0 (H_post = 2 sigmoid(0) = 1); B_res = 0 on the diagonal and -30
    off it (H_res = I: 1e-13 off the diagonal, 1 / (1 + hc_eps) on it): every
    stream stays the plain residual."""
    return {"phi": jnp.zeros((n * dim, 2 * n + n * n)), "alpha": jnp.ones((3,)),
            "b_pre": jnp.full((n,), -30.0).at[0].set(30.0), "b_post": jnp.zeros((n,)),
            "b_res": jnp.full((n, n), -30.0) * (1.0 - jnp.eye(n))}


@pytest.mark.parametrize("side", ["reference", "served"])
def test_degenerate_mixing_is_the_plain_residual(side):
    """With those parameters in every sub-layer the four-stream model's logits
    are the hc_mult 0 model's on the same weights (the exit sums four equal
    streams, which the final norm undoes up to its eps)."""
    plain = get_model("transformer", dtype="float32", **xing4(0))
    mixed = get_model("transformer", dtype="float32", **xing4(4))
    variables = xing4_params(plain, seed=0)
    with_maps = jax.tree.map(lambda x: x, variables)
    for i in range(3):
        for name in ("attention_hc", "ffn_hc"):
            with_maps["params"][f"layer_{i}"][name] = plain_residual_mixing(4, 64)
    tokens = tokens_of(2, 24)
    want = np.asarray(reference.forward(variables, plain.cfg, tokens)[0])
    if side == "reference":
        got = np.asarray(reference.forward(with_maps, mixed.cfg, tokens)[0])
        h_res = np.asarray(reference._mix(plain_residual_mixing(4, 64), jnp.ones((3, 4, 64)),
                                          mixed.cfg, 20)[2])
        # off the diagonal e^-30 = 9.4e-14; on it 1 / (1 + hc_eps)
        assert np.abs(h_res * (1 - np.eye(4))).max() < 1e-12
        assert np.abs(h_res - np.eye(4)).max() < 2e-6
    else:
        with jax.default_matmul_precision("highest"):
            got = np.asarray(mixed.apply(with_maps, jnp.asarray(tokens[None]))[0][0])
    assert float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ------------------------------------------------ full forward == reference
@pytest.mark.parametrize("hc_mult", HC)
def test_full_forward_matches_reference(hc_mult):
    """The module (absorbed read, the Sinkhorn chain entry by entry, the maps
    as one [nC, 24] product) against the reference (expanded keys and values,
    a loop over a [t, 4, 4] array) on the same weights, float32: 1e-4."""
    module = get_model("transformer", dtype="float32", **xing4(hc_mult))
    variables = xing4_params(module, seed=0)
    tokens = tokens_of(2, 24)
    with jax.default_matmul_precision("highest"):
        logits, _ = module.apply(variables, jnp.asarray(tokens[None]))
    ref, routing = reference.forward(variables, module.cfg, tokens)
    assert len(routing) == module.cfg.n_moe_layers == 2
    assert float(jnp.max(jnp.abs(ref))) > 0.3
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("hc_mult", HC)
def test_paged_prefill_then_decode_matches_reference(hc_mult):
    """Prompt of 21 tokens in chunks of 8 (the later chunks read the earlier
    ones' latents from the pool, the last is padded with PAD_POS), then 6
    decode steps of two slots of which one is DEAD: every position's LOGITS
    equal the reference's full forward (float32: 1e-4). Padding and the dead
    slot go through the stream mixing as any row does and touch nothing."""
    module = get_model("transformer", dtype="float32", **xing4(hc_mult))
    cfg = module.cfg
    variables = xing4_params(module, seed=0)
    tokens = tokens_of(3, 27)
    ref = np.asarray(reference.forward(variables, cfg, tokens)[0])
    page, n_pages, chunk, plen = 8, 8, 8, 21
    pools = init_paged_kv_caches(cfg, 2 + n_pages, page)
    row = np.arange(2, 2 + n_pages, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        for start in range(0, plen, chunk):
            n = min(chunk, plen - start)
            toks = np.zeros((1, chunk), np.int32)
            pos = np.full((1, chunk), PAD_POS, np.int32)
            toks[0, :n], pos[0, :n] = tokens[start:start + n], np.arange(start, start + n)
            logits, pools = module.apply(
                variables, jnp.asarray(toks), positions=jnp.asarray(pos), caches=pools,
                block_tables=jnp.asarray(row))
            np.testing.assert_allclose(np.asarray(logits[0, :n]), ref[start:start + n],
                                       atol=1e-4, rtol=1e-4)
        tables = np.concatenate([row, np.full((1, n_pages), TRASH_PAGE, np.int32)])
        for p in range(plen, 27):
            logits, pools = module.apply(
                variables, jnp.asarray([[tokens[p]], [tokens[p]]], jnp.int32),
                positions=jnp.asarray([[p], [p]], jnp.int32), caches=pools,
                block_tables=jnp.asarray(tables))
            np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[p], atol=1e-4, rtol=1e-4)
    assert int(jnp.min(pools[0][1][0])) == PAD_POS


@pytest.mark.parametrize("hc_mult", HC)
def test_dense_cache_prefill_decode_and_verify_shapes_match_reference(hc_mult):
    """generate()'s dense cache: a prefill at a scalar offset, a single-token
    step at a per-sequence offset, and the speculative verify's K tokens at
    their own positions (a PAD_POS column dropped)."""
    module = get_model("transformer", dtype="float32", **xing4(hc_mult))
    cfg = module.cfg
    variables = xing4_params(module, seed=0)
    tokens = tokens_of(4, 20)
    ref = np.asarray(reference.forward(variables, cfg, tokens)[0])
    caches = init_kv_caches(cfg, 1, 32)
    with jax.default_matmul_precision("highest"):
        logits, caches = module.apply(
            variables, jnp.asarray(tokens[None, :12]), positions=jnp.arange(12)[None],
            caches=caches, cache_index=0)
        np.testing.assert_allclose(np.asarray(logits[0]), ref[:12], atol=1e-4, rtol=1e-4)
        logits, caches = module.apply(
            variables, jnp.asarray(tokens[None, 12:13]), positions=jnp.asarray([[12]]),
            caches=caches, cache_index=jnp.asarray([12]))
        np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[12], atol=1e-4, rtol=1e-4)
        toks = np.concatenate([tokens[13:16], [0]])[None]
        pos = np.asarray([[13, 14, 15, PAD_POS]], np.int32)
        logits, caches = module.apply(
            variables, jnp.asarray(toks, jnp.int32), positions=jnp.asarray(pos),
            caches=caches, cache_index=jnp.asarray([13]))
        np.testing.assert_allclose(np.asarray(logits[0, :3]), ref[13:16], atol=1e-4, rtol=1e-4)
    assert int(jnp.sum(caches[0][1] < PAD_POS)) == 16


def test_mtp_module_matches_reference_with_streams():
    """The MTP module with its own four streams (entered from h', left before
    its own norm), seeded weights: position t's logits, from the main model's
    hidden state of token t and the embedding of token t + 1, are the
    reference's; the main logits beside them are unchanged by asking."""
    module = get_model("transformer", dtype="float32", **xing4(4, mtp_layers=1))
    variables = xing4_params(module, seed=0)
    assert "mtp" in variables["params"] and "attention_hc" in variables["params"]["mtp"]["block"]
    tokens = tokens_of(5, 22)
    with jax.default_matmul_precision("highest"):
        logits, _, served = module.apply(
            variables, jnp.asarray(tokens[None, :-1]), next_tokens=jnp.asarray(tokens[None, 1:]))
        alone, _ = module.apply(variables, jnp.asarray(tokens[None, :-1]))
    want = np.asarray(reference.forward_mtp(variables, module.cfg, tokens))
    assert float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(np.asarray(served[0]), want, atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(alone))


# -------------------- LLMServer + ContinuousBatcher, int8, bf16 activations
# bf16 activations against the reference's float32 on the same int8-rounded
# weights, 3 layers, logits of scale 0.45-0.65. Every seed has a fourth expert
# within 1e-3 of the fifth somewhere, and this router weighs its four experts
# about 0.5 each, so where bf16 breaks such a tie the other way the two sides
# compute different functions: with the reference choosing for itself the
# served path is 0.016-0.18 from it over SERVED_SEED 1..14 (median 0.07). So
# the reference FOLLOWS the experts the served path took (the probe's
# ``routing``, out of the same step programs as its logits), weighs them by its
# own scores and computes everything else itself: what is left is the
# activations' rounding, and every part of the model shows in it again. What
# following would hide, a served router that chooses by another rule, shows in
# ``behind``: how far the worst followed expert lies behind the reference's own
# last choice (the served noise at a near-tie; the spread of the scores under
# another rule). One Sinkhorn iteration in place of twenty is the one wrong
# model a bf16 bound does not separate at this depth (the init's residual
# matrix is chosen so that 20 iterations converge, models/transformer.py
# SMALL_LEAF_INIT, and then one is already within a few percent): it is held
# to the float32 module, below.
BF16_ATOL = 0.03
SERVED_SEED = {4: 8, 0: 2}
REQUEST_SEED = 17


def serve(hc_mult: int):
    """Two requests of different lengths in 4 slots (two stay dead), the
    longer one's prompt in three chunks, logits asked."""
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(
        model="transformer", model_kwargs=xing4(hc_mult), quantize="int8", init_random=True,
        eos_id=-1, temperature=0.7, tokenizer="bytes", len_buckets=[32, 64, 128],
        max_new_tokens=8, kv_page_size=8, prefill_chunk=8, seed=SERVED_SEED[hc_mult])
    server.load()
    batcher = ContinuousBatcher(server, max_slots=4, max_len=64)
    prompts = [tokens_of(11, 19).tolist(), tokens_of(12, 6).tolist()]
    budgets = [5, 3]

    async def run():
        infos = [{"logits": []} for _ in prompts]
        outs = await asyncio.gather(*(
            batcher.submit(p, n, info=i, seed=REQUEST_SEED + j)
            for j, (p, n, i) in enumerate(zip(prompts, budgets, infos))))
        await batcher.close()
        return outs, infos

    outs, infos = asyncio.run(run())
    return server, batcher, prompts, outs, infos


@pytest.fixture(scope="module")
def served():
    return serve(4)


def served_against(served, follow: bool = True, **wrong) -> tuple:
    """(max |served logits - reference logits|, the furthest a followed
    choice lies behind the reference's own) over both requests."""
    server, _, prompts, outs, infos = served
    worst, behind = 0.0, 0.0
    for prompt, out, info in zip(prompts, outs, infos):
        got = np.stack(info["logits"])
        assert got.shape == (len(out), 128) and got.dtype == np.float32
        took = np.stack(info["routing"]) if follow else None
        ref, routing = reference.forward(server._params, server._cfg, prompt + out,
                                         follow=took, **wrong)
        want = np.asarray(ref)[len(prompt) - 1:len(prompt) - 1 + len(out)]
        assert np.abs(want).max() > 0.3
        worst = max(worst, float(np.abs(got - want).max()))
        behind = max([behind] + [float(np.max(layer["behind"])) for layer in routing])
    return worst, behind


CHOICE_BEHIND = 0.02   # a sigmoid score + its bias; the scores' spread is 0.2


def test_served_logits_match_reference(served):
    worst, behind = served_against(served)
    assert worst <= BF16_ATOL and behind <= CHOICE_BEHIND


def test_served_logits_match_reference_with_the_streams_off():
    worst, behind = served_against(serve(0))
    assert worst <= BF16_ATOL and behind <= CHOICE_BEHIND


def test_probe_reports_the_experts_every_processed_token_took(served):
    """``routing`` [prompt + new - 1, MoE layers, k] from the prompt's first
    token on, out of the chunk programs and the decode steps: the reference's
    own choices wherever its margin is not a near-tie. A reference that does
    not follow is the same function only where no near-tie fell the other
    way."""
    server, _, prompts, outs, infos = served
    for prompt, out, info in zip(prompts, outs, infos):
        took = np.stack(info["routing"])
        assert info["routing_start"] == 0 and took.dtype == np.int32
        assert took.shape == (len(prompt) + len(out) - 1, 2, 4)
        assert took.min() >= 0 and took.max() < 16
        _, routing = reference.forward(server._params, server._cfg, prompt + out, follow=took)
        for layer, chose in enumerate(routing):
            # followed: what the reference took is what was served
            np.testing.assert_array_equal(
                np.sort(np.asarray(chose["experts"])[:len(took)], axis=-1),
                np.sort(took[:, layer], axis=-1))
            # another set of four is at least the margin behind, so under the
            # bound the served choice IS the reference's own wherever the
            # margin is clear of it
            margin, behind = np.asarray(chose["margin"]), np.asarray(chose["behind"])
            assert np.all(behind[behind > 0] >= margin[behind > 0] - 1e-6)
            assert np.all(behind[margin > CHOICE_BEHIND] == 0)
    free, _ = served_against(served, follow=False)
    assert free >= served_against(served)[0] - 1e-3


@pytest.mark.parametrize("wrong", [
    {"streams": False}, {"router_score": "softmax"},
    {"q_norm": False}, {"shared": False}, {"leave_out_rank": 0}],
    ids=["plain-residual", "softmax-scores", "no-q-norm",
         "no-shared-expert", "largest-expert-left-out"])
def test_wrong_models_fail_the_served_tolerance(served, wrong):
    """The tolerance is tight: each wrong reference is over twice the bound
    away from what is served."""
    assert served_against(served, **wrong)[0] > 2 * BF16_ATOL


def test_a_router_that_chooses_by_another_rule_is_behind(served):
    """Followed, a reference WITHOUT the selection bias computes the same
    logits (the bias weighs nothing); that the served choices are not its own
    shows in ``behind``, at several times the bound."""
    worst, behind = served_against(served, select_bias=False)
    assert worst <= BF16_ATOL and behind > 3 * CHOICE_BEHIND


def test_one_sinkhorn_iteration_is_another_model_in_float32():
    """What the served tolerance cannot separate (above) the float32 forward
    can: against the reference stopped after one iteration the module is a
    hundred float32 tolerances away; the twentieth iteration moves nothing a
    float32 tolerance sees (the chain has converged)."""
    module = get_model("transformer", dtype="float32", **xing4(4))
    variables = xing4_params(module, seed=0)
    tokens = tokens_of(2, 24)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(module.apply(variables, jnp.asarray(tokens[None]))[0][0])
    one = np.asarray(reference.forward(variables, module.cfg, tokens, sinkhorn_iters=1)[0])
    assert float(np.abs(logits - one).max()) > 1e-2
    nineteen = np.asarray(reference.forward(variables, module.cfg, tokens, sinkhorn_iters=19)[0])
    twenty = np.asarray(reference.forward(variables, module.cfg, tokens)[0])
    assert float(np.abs(nineteen - twenty).max()) < 1e-4


def test_served_tree_holds_the_new_leaves_in_int8_or_float32(served):
    """wq_a is a plain int8 matrix and wq_b, which feeds the head split, an
    output-major one; the mixing maps, their scalars and biases and the
    selection bias are float32 as drawn (never int8, never the serving
    dtype), while the norm weights beside them took the serving dtype."""
    from seldon_core_tpu.ops.quantize import QuantizedTensor

    server = served[0]
    layer = server._params["params"]["layer_1"]
    att = layer["attention"]
    assert not att["wq_a"].out_major and att["wq_a"].q.shape == (64, 24)
    assert att["wq_b"].out_major and att["wq_b"].q.shape == (4 * 24, 24)
    assert att["wq_b"].q.dtype == att["wq_a"].q.dtype == jnp.int8 and "wq" not in att
    assert att["q_norm"]["weight"].shape == (24,)
    for name in ("attention_hc", "ffn_hc"):
        hc = layer[name]
        assert {k: (v.dtype, v.shape) for k, v in hc.items()} == {
            "phi": (jnp.float32, (256, 24)), "alpha": (jnp.float32, (3,)),
            "b_pre": (jnp.float32, (4,)), "b_post": (jnp.float32, (4,)),
            "b_res": (jnp.float32, (4, 4))}
        assert 0.5 < float(jnp.std(hc["phi"])) * 16.0 < 2.0      # normal(0, 1 / sqrt(nC))
    bias = layer["moe"]["router_bias"]
    assert bias.dtype == jnp.float32 and bias.shape == (16,) and float(jnp.std(bias)) > 0.03
    assert isinstance(layer["moe"]["router"], QuantizedTensor)
    assert "attention_hc" in server._params["params"]["layer_0"]      # the dense layer mixes too
    kept = server._dequant(server._params)["params"]["layer_1"]
    assert kept["attention_hc"]["phi"].dtype == jnp.float32
    assert kept["moe"]["w1"].q.dtype == jnp.int8 and kept["attention"]["wq_b"].dtype != jnp.int8


def test_streamed_init_draws_the_float32_leaves_by_the_modules_rule(monkeypatch):
    """The leaf-by-leaf init of a model too large to draw whole (what the
    benchmark's configuration takes) holds the same leaves float32, drawn by
    ``SMALL_LEAF_INIT``: alpha about 0.7, a visible b_res, Phi at 1 / sqrt(nC)."""
    from seldon_core_tpu.servers import llmserver

    monkeypatch.setattr(llmserver, "STREAM_INIT_THRESHOLD_BYTES", 0)
    server = llmserver.LLMServer(model="transformer", model_kwargs=xing4(4), quantize="int8",
                                 init_random=True, tokenizer="bytes", seed=22)
    server.load()
    layer = server._params["params"]["layer_2"]
    hc = layer["ffn_hc"]
    assert all(v.dtype == jnp.float32 for v in hc.values())
    assert np.all(np.abs(np.asarray(hc["alpha"]) - 1.0) < 0.5) and float(jnp.std(hc["b_res"])) > 0.3
    assert 0.5 < float(jnp.std(hc["phi"])) * 16.0 < 2.0
    assert layer["moe"]["router_bias"].dtype == jnp.float32
    assert float(jnp.std(layer["moe"]["router_bias"])) > 0.03
    assert layer["attention"]["wq_b"].out_major and layer["attention"]["wq_a"].q.dtype == jnp.int8
    out = server.generate([[5, 6, 7]], max_new_tokens=3)["tokens"][0]
    assert len(out) == 3


def test_loop_counts_this_models_routing_and_context_as_deepseeks(served):
    """``seldon_llm_moe_*`` and ``seldon_llm_attn_context_tokens_total`` are fed
    by the same tallies: 2 MoE layers of 3, four routed pairs a live row a
    layer, chunks of 8 tokens at their offsets."""
    _, batcher, prompts, outs, _ = served
    moe = batcher._moe.stats()
    assert moe["moe_layers"] == 2
    chunk = moe["moe_by_program"]["chunk"]
    assert chunk["routed_pairs"] == chunk["live_rows"] * 4 * 2
    loop = batcher._phases.stats()
    assert loop["attn_calls"]["chunk"] == 4                       # 19 = 8 + 8 + 3, and 6
    assert loop["attn_context_tokens"]["chunk"] == 8 + 16 + 19 + 6
    assert loop["attn_calls"]["decode"] >= max(len(o) for o in outs) - 1


# -------------------------------------------- what is not built is refused by name
def test_lora_is_refused_for_this_model_at_load():
    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(model="transformer", model_kwargs=xing4(4), init_random=True,
                       tokenizer="bytes", lora_rank=4)
    with pytest.raises(ValueError, match="MoE|latent"):
        server.load()


@pytest.mark.parametrize("over,match", [
    (dict(n_group=2, topk_group=1), "n_group=2"),
    (dict(hc_mult=4), "hc_mult=4"),
    (dict(topk_method="group_limited_greedy"), "topk_method='group_limited_greedy'"),
])
def test_converter_refuses_what_is_not_built_by_name(over, match):
    transformers = pytest.importorskip("transformers")
    from seldon_core_tpu.models.convert import config_kwargs_from_hf

    with pytest.raises(ValueError, match=match):
        config_kwargs_from_hf(v3_config(transformers, **over))


@pytest.mark.parametrize("over,match", [
    (dict(mtp_layers=2), "mtp_layers=2 is not built"),
    (dict(router_score="tanh"), "unknown router_score"),
])
def test_config_refuses_what_is_not_built_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        get_model("transformer", **xing4(4, **over))


def test_mtp_module_is_refused_a_cache():
    module = get_model("transformer", dtype="float32", **xing4(4, mtp_layers=1))
    variables = xing4_params(module, seed=0)
    toks = jnp.asarray(tokens_of(7, 8)[None])
    with pytest.raises(ValueError, match="MTP module runs cache-less"):
        module.apply(variables, toks, positions=jnp.arange(8)[None],
                     caches=init_kv_caches(module.cfg, 1, 16), cache_index=0, next_tokens=toks)
