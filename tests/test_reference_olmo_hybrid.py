"""Olmo-Hybrid's block (models/transformer.py: the branch-norm ``TransformerBlock``,
``Attention`` without a rotary embedding, ``GatedDeltaNet`` with beta in (0, 2)
over a state [dk, dv] that is not square, held two heads side by side along the
lanes) and its plain float32 reference (models/reference.py), what holds them,
and what they hold. No ``olmo_hybrid`` modeling file is installed, so the two
holds to ``transformers`` are of its parts:

- the attention block, the FFN, the branch norms and the whole-projection
  QK-norm to ``Olmo3ForCausalLM`` on converted weights (its own RoPE on, every
  layer ``full_attention``), at 1e-5;
- the rule to ``modeling_qwen3_next``'s ``torch_chunk_gated_delta_rule`` /
  ``torch_recurrent_gated_delta_rule`` with beta in (0, 2), dk 96, dv 192;
- served forward = reference at toy widths with dk != dv, neither a multiple of
  (8, 128), six heads (no divisor that is a multiple of 8);
- chunked prefill then decode through the batcher (the conv rows and the packed
  S carried across every chunk boundary and step) to the reference's full
  forward, on LOGITS;
- each WRONG reference of the chip check
  (perf/configs/olmo-hybrid-7b-int8.json ``reference_tolerance``) is another
  model in float32; the combinations nobody built are refused where the config
  is made.
"""

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
    matrix_state_nbytes,
    pack_state,
    tiled_nbytes,
    unpack_state,
)
from seldon_core_tpu.models.convert import config_kwargs_from_hf, convert_hf_model
from seldon_core_tpu.models.leaves import draw_small_leaf
from seldon_core_tpu.models.state_mixers import (
    GDN_CHUNK,
    _unit_lower_inverse,
    gated_delta_rule,
    l2_normalize,
)
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the served model in small: dk != dv, [24, 64] is no whole (8, 128) tile a head
# (two heads side by side are [24, 128]), six heads
KW = dict(vocab_size=96, dim=48, n_layers=4, n_heads=6, n_kv_heads=6, head_dim=8, ffn_dim=64,
          qk_norm=True, max_seq_len=96, norm_eps=1e-6, rope_theta=None, dtype="float32",
          norm_placement="branch", linear_allow_neg_eigval=True, linear_dt_bias="range",
          layer_types=PERIOD, linear_num_key_heads=6, linear_num_value_heads=6,
          linear_key_head_dim=24, linear_value_head_dim=64, linear_conv_kernel_dim=4)
CHUNK = 8
RNG = np.random.default_rng(45)
TOKENS = RNG.integers(0, 96, size=2 * GDN_CHUNK + 9)
LONG = RNG.integers(1, 96, size=40).tolist()


@pytest.fixture(scope="module")
def served():
    module = get_model("transformer", **KW)
    params = module.init(jax.random.PRNGKey(7), jnp.asarray(TOKENS[None]))
    return module, params


def test_served_forward_matches_the_reference(served):
    module, params = served
    got, caches = module.apply(params, jnp.asarray(TOKENS[None]))
    want, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    assert routing == [] and float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)
    # without a cache the state a layer leaves is in the cache's layout
    assert [tuple(a.shape for a in c) for c in caches[:3]] == [((1, 3, 672), (1, 3, 24, 128))] * 3


def test_the_state_lies_two_heads_side_by_side_and_round_trips(served):
    cfg = served[0].cfg
    S = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 24, 64))
    packed = pack_state(S, 2)
    assert packed.shape == (2, 3, 24, 128)
    np.testing.assert_array_equal(packed[:, 1, :, 64:], S[:, 3])
    np.testing.assert_array_equal(unpack_state(packed, 2), S)
    assert pack_state(S, 1) is S and unpack_state(S, 1) is S
    dense = init_kv_caches(cfg, 2, 16)
    paged = init_paged_kv_caches(cfg, 6, 4, state_slots=5)
    assert dense[0][1].shape == (2, 3, 24, 128) and paged[2][1].shape == (5, 3, 24, 128)
    assert dense[0][1].dtype == jnp.float32
    own, tiled = matrix_state_nbytes(paged)
    assert own == tiled == 3 * 5 * 6 * 24 * 64 * 4
    # held a head a row, [.., 96, 192] float32 would be three quarters full
    assert tiled_nbytes((32, 30, 96, 192), jnp.float32) * 3 == 4 * 32 * 30 * 96 * 192 * 4
    assert tiled_nbytes((32, 15, 96, 384), jnp.float32) == 32 * 30 * 96 * 192 * 4
    assert tiled_nbytes((4, 3, 100), jnp.bfloat16) == 4 * 16 * 128 * 2


def test_prefill_into_the_dense_cache_then_decode_equals_the_full_forward(served):
    """The first decoded row reads conv rows and a packed S that a PADDED
    prefill left; the attention layer reads positions it never rotated."""
    module, params = served
    want, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    n = len(TOKENS) - 6
    caches = init_kv_caches(module.cfg, 1, 160)
    width = n + 3
    pos = jnp.where(jnp.arange(width) < n, jnp.arange(width), PAD_POS)[None]
    toks = jnp.asarray(np.concatenate([TOKENS[:n], [0, 0, 0]])[None])
    logits, caches = module.apply(params, toks, positions=pos, caches=caches, cache_index=0)
    np.testing.assert_allclose(logits[0, :n], want[:n], atol=2e-5, rtol=0)
    for t in range(n, len(TOKENS)):
        logits, caches = module.apply(
            params, jnp.asarray(TOKENS[t:t + 1][None]), positions=jnp.asarray([[t]]),
            caches=caches, cache_index=jnp.asarray([t]))
        np.testing.assert_allclose(logits[0, 0], want[t], atol=2e-5, rtol=0)


# ---- the two holds to transformers --------------------------------------------
def test_the_branch_norm_attention_block_is_olmo3s():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(0)
    config = transformers.Olmo3Config(
        vocab_size=96, hidden_size=48, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=6, num_key_value_heads=6, max_position_embeddings=128,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False, pad_token_id=None,
        layer_types=["full_attention"] * 3)
    model = transformers.Olmo3ForCausalLM(config).eval()
    with torch.no_grad():   # weights that a norm in the wrong place would show in
        for name, p in model.named_parameters():
            if "norm" in name:
                p.add_(0.3 * torch.randn_like(p))
            elif name != "model.embed_tokens.weight":
                p.mul_(30.0)
    tokens = RNG.integers(0, 96, size=33)
    with torch.no_grad():
        want = model(torch.tensor(tokens[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    cfg = module.cfg
    assert cfg.norm_placement == "branch" and cfg.qk_norm is True and cfg.rope_theta == 10000.0
    ref, _ = reference.forward(variables, cfg, tokens.tolist())
    got, _ = module.apply(variables, jnp.asarray(tokens[None]))
    scale = np.abs(want).max()
    assert scale > 0.5
    assert np.abs(np.asarray(ref) - want).max() <= 1e-5 * max(scale, 1.0)
    assert np.abs(np.asarray(got[0]) - want).max() <= 1e-5 * max(scale, 1.0)
    # pre-norm on the same weights is another model
    pre, _ = reference.forward(variables, cfg, tokens.tolist(), norm_placement="pre")
    assert np.abs(np.asarray(pre) - want).max() > 0.05 * scale
    # (a window layer converts since PR 49: tests/test_reference_smallthinker.py holds it)
    config.layer_types = ["full_attention", "sliding_attention", "full_attention"]
    assert config_kwargs_from_hf(config)["layer_types"] == tuple(config.layer_types)
    config.rope_scaling = {"rope_type": "linear", "factor": 2.0}
    with pytest.raises(ValueError, match="rope_scaling"):
        config_kwargs_from_hf(config)


@pytest.mark.parametrize("rows", [1, GDN_CHUNK, 2 * GDN_CHUNK + 9])
def test_the_rule_is_transformers_rule_with_beta_up_to_two(rows):
    """dk 96, dv 192, beta in (0, 2), a decay near 1 and keys that resemble each
    other (what made the old triangular inverse lose every digit)."""
    torch = pytest.importorskip("torch")
    qwen = pytest.importorskip("transformers.models.qwen3_next.modeling_qwen3_next")
    keys = jax.random.split(jax.random.PRNGKey(rows), 6)
    b, H, dk, dv = 2, 4, 96, 192
    q = jax.random.normal(keys[0], (b, rows, H, dk))
    k = jax.random.normal(keys[5], (b, 1, H, dk)) + 0.3 * jax.random.normal(keys[1], (b, rows, H, dk))
    v = jax.random.normal(keys[2], (b, rows, H, dv))
    g = -0.02 * jax.nn.softplus(jax.random.normal(keys[3], (b, rows, H)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, rows, H)))
    S0 = pack_state(jnp.zeros((b, H, dk, dv)), 2)
    got, state = gated_delta_rule(l2_normalize(q) * dk ** -0.5, l2_normalize(k), v, g, beta, S0)
    tt = [torch.tensor(np.asarray(x)) for x in (q, k, v, g, beta)]
    rule = qwen.torch_recurrent_gated_delta_rule if rows == 1 else qwen.torch_chunk_gated_delta_rule
    want, want_state = rule(*tt, initial_state=None, output_final_state=True,
                            use_qk_l2norm_in_kernel=True)
    scale = float(want.abs().max())
    np.testing.assert_allclose(got, want.numpy(), atol=2e-5 * max(scale, 1.0), rtol=0)
    np.testing.assert_allclose(unpack_state(state, 2), want_state.numpy(),
                               atol=2e-5 * float(want_state.abs().max()), rtol=0)


def test_the_triangular_inverse_keeps_its_digits_where_the_keys_resemble_each_other():
    """(I + A)^-1 by halves against numpy's solve in float64: A = 2 (k_i . k_j)
    below the diagonal with keys within 0.1 of each other (entries near 2: the
    powers of A reach 1e30 before they cancel)."""
    rng = np.random.default_rng(0)
    k = rng.normal(size=(1, 24)) + 0.1 * rng.normal(size=(GDN_CHUNK, 24))
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    a = np.tril(2.0 * k @ k.T, -1)
    want = np.linalg.inv(np.eye(GDN_CHUNK) + a)
    got = np.asarray(_unit_lower_inverse(jnp.asarray(a, jnp.float32)))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()
    odd = np.asarray(_unit_lower_inverse(jnp.asarray(a[:21, :21], jnp.float32)))   # no power of two
    assert np.abs(odd - np.linalg.inv(np.eye(21) + a[:21, :21])).max() < 1e-4 * np.abs(want).max()


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
    base = dict(max_slots=3, max_len=48, len_buckets=(CHUNK,), pipeline_depth=2,
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


# every way a chunk boundary can fall against the four taps and the carried S
@pytest.mark.parametrize("length", [1, 2, 3, CHUNK - 1, CHUNK, CHUNK + 1, CHUNK + 3, 2 * CHUNK + 3])
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
    np.testing.assert_allclose(logits, reference_logits(server, prompt, out), atol=3e-5, rtol=0)
    assert stats["gdn_rows"] == {"chunk": length, "decode": 4}
    assert stats["gdn_layer_calls"] == {"chunk": 3 * -(-length // CHUNK), "decode": 3 * 4}
    # here, on the CPU, the step's rule is the expression: one program, counted once
    assert stats["gdn_step_path"] == {"kernel": 0, "expression": 1}
    # 3 layers x 3 slots x 6 heads of [24, 64] float32, no padded lane: two heads a row
    assert stats["state_matrix_bytes"] == stats["state_matrix_tiled_bytes"] == 3 * 3 * 6 * 24 * 64 * 4


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

    mixed = asyncio.run(together())
    for (out, logits), (prompt, n) in zip(mixed, ((a, 14), (b_, 10), (c, 6))):
        out_alone, logits_alone = asyncio.run(alone(prompt, n))
        assert out == out_alone
        np.testing.assert_allclose(logits, logits_alone, atol=3e-5, rtol=0)
        np.testing.assert_allclose(logits, reference_logits(server, prompt, out), atol=5e-5, rtol=0)


def test_the_gauges_and_the_path_counter_reach_the_registry():
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
    channels = 2 * 6 * 24 + 6 * 64
    matrix = 3 * 2 * 6 * 24 * 64 * 4
    assert stats["state_bytes"] == 3 * 2 * 3 * channels * 4 + matrix
    assert stats["state_matrix_bytes"] == stats["state_matrix_tiled_bytes"] == matrix
    lines = [line for line in text.splitlines() if not line.startswith("#")]

    def value(name, label):
        found = [float(line.rsplit(" ", 1)[1]) for line in lines
                 if line.startswith(name + "{") and label in line]
        assert len(found) == 1, (name, label, found)
        return found[0]

    assert value("seldon_llm_state_bytes", "") == stats["state_bytes"]
    assert value("seldon_llm_state_matrix_bytes", "") == matrix
    assert value("seldon_llm_state_matrix_tiled_bytes", "") == matrix
    assert value("seldon_llm_gdn_step_path_total", 'path="expression"') == 1
    assert value("seldon_llm_gdn_step_path_total", 'path="kernel"') == 0


def test_a_model_without_linear_attention_exports_no_path_and_zero_matrix_bytes():
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    kw = {k: v for k, v in KW.items() if not k.startswith("linear_") and k != "layer_types"}
    comp = make_server(model_kwargs=kw, continuous_batching=2, kv_page_size=4,
                       prefill_chunk=CHUNK, len_buckets=(CHUNK, 16, 32))
    svc = get_batcher_service(comp)
    try:
        assert len(asyncio.run(svc.submit(LONG[:5], max_new_tokens=3))) == 3
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(comp)
        text = reg.expose().decode()
    finally:
        svc.close()
    assert "seldon_llm_gdn_step_path_total{" not in text
    assert [line.rsplit(" ", 1)[1] for line in text.splitlines()
            if line.startswith("seldon_llm_state_matrix_bytes{")] == ["0.0"]


# ---- what is refused, and how the seeded leaves are drawn ----------------------
@pytest.mark.parametrize("more,match", [
    (dict(hc_mult=4, layer_types=None), "norm_placement"),
    (dict(norm_placement="post"), "norm_placement"),
    (dict(partial_rotary_factor=0.5), "rope_theta"),
    (dict(rope_scaling={"factor": 8.0, "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                        "original_max_position_embeddings": 64}), "rope_theta"),
    (dict(linear_dt_bias="zeros"), "linear_dt_bias"),
])
def test_the_combinations_nobody_built_are_refused_where_the_config_is_made(more, match):
    with pytest.raises(ValueError, match=match):
        get_model("transformer", **{**KW, **more})


def test_the_layers_own_dt_bias_makes_a_decay_that_carries_hundreds_of_tokens(served):
    key = jax.random.PRNGKey(0)
    dt = np.asarray(jax.nn.softplus(draw_small_leaf("dt_bias_range", key, (4096,))))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert abs(float(np.log(dt).mean()) - np.log(1e-2)) < 0.1      # log-uniform
    # the worst decay a token, at A = 16 and dt = 0.1 with a = 0: e^-1.6
    assert np.exp(-16.0 * dt.max()) > 0.19
    cfg = served[0].cfg
    assert cfg.small_leaf("dt_bias") == "dt_bias_range" and cfg.small_leaf("A_log") == "A_log"
    assert get_model("transformer", **{**KW, "linear_dt_bias": "ones"}).cfg.small_leaf(
        "dt_bias") == "dt_bias"
    drawn = np.asarray(jax.nn.softplus(served[1]["params"]["layer_0"]["linear_attn"]["dt_bias"]))
    assert 1e-3 * 0.999 <= drawn.min() and drawn.max() <= 1e-1 * 1.001


# ---- the wrong references of the chip check ------------------------------------
# ... and by how much of the logits' scale each must differ from the right one
# in float32 at this size
WRONG = {
    "beta_not_doubled": (dict(gdn_beta_doubled=False), 0.02),
    "decay_left_out": (dict(gdn_decay=False), 0.01),
    "no_l2_norm_on_q_and_k": (dict(gdn_l2norm=False), 0.02),
    "state_zeroed_at_a_chunk_start": (dict(gdn_reset_every=8), 0.02),
    "state_from_the_chunks_last_row": (dict(conv_state_pad=(10, 16)), 0.01),
    "taps_reversed": (dict(taps_reversed=True), 0.02),
    "output_gate_left_out": (dict(gdn_z_gate=False), 0.02),
    "q_not_scaled": (dict(gdn_q_scale=False), 0.02),
    "pre_norm_in_place_of_branch_norm": (dict(norm_placement="pre"), 0.02),
    "qk_norm_a_head": (dict(qk_norm="head_tiled"), 0.002),
    "rope_at_theta_500000": (dict(rope_theta_wrong=500000.0), 0.002),
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
    if name == "no_l2_norm_on_q_and_k":
        # with beta up to 2 and a decay near 1 an unnormalised key's I - beta k k^T
        # has an eigenvalue far below -1: S overflows float32 within the sequence
        # (a nan is under no limit: read as not correct)
        assert not differ <= margin, differ
        return
    assert differ > margin, differ
    assert np.isfinite(np.asarray(wrong)).all()
