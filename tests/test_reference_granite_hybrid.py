"""granite-4.0-h's block (models/state_mixers.py: ``Mamba2Mixer`` over ops/ssd.py,
``Attention`` with no position at the config's own softmax scale, the four
scalar multipliers, the tied table) and its plain float32 reference
(models/reference.py), what holds them, and what they hold. The published
modeling file IS installed (``transformers`` ``granitemoehybrid``), so the
reference is held to an implementation, not to a reading:

- the reference AND the served forward to ``GraniteMoeHybridForCausalLM``
  (``torch_forward``) on converted weights at 1e-5, two periods of uneven length
  of the layer pattern, the small leaves seeded (not the file's placeholders);
- chunked prefill then decode through the batcher (three slots; the conv rows
  and h carried across every chunk boundary and step; a slot reused) to the
  reference's full forward, on LOGITS;
- each WRONG reference of the chip check
  (perf/configs/granite-4.0-h-micro-int8.json ``reference_tolerance``) is
  another model in float32; the combinations nobody built are refused where the
  config is made.
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
    state_bytes,
)
from seldon_core_tpu.models.convert import config_kwargs_from_hf, convert_hf_model
from seldon_core_tpu.models.leaves import draw_small_leaf
from seldon_core_tpu.models.transformer import (
    MAMBA_LAYERS_COMPOSE_REFUSAL,
    STATE_LAYERS_COMPOSE_REFUSAL,
)
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

# two periods of the published m m m m m A m m m m, in small and of uneven length
PERIODS = ["mamba", "mamba", "full_attention"] + ["mamba", "mamba", "mamba", "full_attention"]
# the served model in small: 8 heads of [8, 16] float32 (held transposed, [16, 8]
# a head: no whole tile, so the step is the expression wherever it is lowered), one group, GQA 4 / 2 heads of 8 at a
# softmax scale that is NOT 8^-1/2, the four scalars, the tied table
KW = dict(vocab_size=96, dim=32, n_layers=7, n_heads=4, n_kv_heads=2, ffn_dim=48,
          max_seq_len=96, norm_eps=1e-5, rope_theta=None, dtype="float32", tie_embeddings=True,
          layer_types=PERIODS, mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
          mamba_n_groups=1, mamba_d_conv=4, mamba_conv_bias=True, embedding_multiplier=12.0,
          attention_multiplier=0.0625, residual_multiplier=0.22, logits_scaling=8.0)
CHUNK = 8
RNG = np.random.default_rng(53)
TOKENS = RNG.integers(0, 96, size=41)
LONG = RNG.integers(1, 96, size=40).tolist()


def assert_close(got, want, rel):
    """Within ``rel`` of the logits' SCALE (max |want|: the tied table's rows are
    drawn at 0.02 and the logits divided by 8, so they are hundredths)."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= rel * np.abs(want).max(), (
        np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def served():
    module = get_model("transformer", **KW)
    params = module.init(jax.random.PRNGKey(7), jnp.asarray(TOKENS[None]))
    return module, params


def test_served_forward_matches_the_reference(served):
    module, params = served
    got, caches = module.apply(params, jnp.asarray(TOKENS[None]))
    want, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    assert routing == [] and float(jnp.abs(want).max()) > 0.03
    assert_close(got[0], want, 2e-5)
    # without a cache the state a layer leaves is in the cache's layout: the rows
    # of [x ; B ; C] and a head's h transposed
    assert [tuple(a.shape for a in c) for c in caches[:2]] == [((1, 3, 96), (1, 8, 16, 8))] * 2
    assert "lm_head" not in params["params"]


def test_the_cache_holds_three_rows_and_a_float32_state_a_head(served):
    cfg = served[0].cfg
    dense = init_kv_caches(cfg, 2, 16)
    paged = init_paged_kv_caches(cfg, 6, 4, state_slots=5)
    assert dense[0][0].shape == (2, 3, 96) and dense[0][1].shape == (2, 8, 16, 8)
    assert paged[3][1].shape == (5, 8, 16, 8) and paged[3][1].dtype == jnp.float32
    own, tiled = matrix_state_nbytes(paged)
    assert own == 5 * 5 * 8 * 8 * 16 * 4 and tiled == 5 * 5 * 8 * 16 * 128 * 4
    assert state_bytes(cfg) == 5 * (3 * 96 * 4 + 8 * 8 * 16 * 4)
    # at the published sizes two heads' transposed h side by side are [128, 128],
    # whole (8, 128) tiles: 2,097,152 B a slot a layer, no padded lane, and three
    # bf16 rows of 4,352 channels, 76.4 MB a slot over 36 layers
    full = get_model("transformer", **{
        **KW, "dim": 2048, "n_layers": 40, "dtype": "bfloat16", "mamba_n_heads": 64,
        "mamba_d_head": 64, "mamba_d_state": 128,
        "layer_types": (["mamba"] * 5 + ["full_attention"] + ["mamba"] * 4) * 4}).cfg
    assert len(full.layers_of("mamba")) == 36
    assert state_bytes(full) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504
    whole = init_paged_kv_caches(full, 4, 64, state_slots=2)
    assert whole[0][1].shape == (2, 32, 128, 128)
    own, tiled = matrix_state_nbytes(whole)
    assert own == tiled == 36 * 2 * 2_097_152


def test_prefill_into_the_dense_cache_then_decode_equals_the_full_forward(served):
    """The first decoded row reads conv rows and an h that a PADDED prefill left."""
    module, params = served
    want, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    n = len(TOKENS) - 6
    caches = init_kv_caches(module.cfg, 1, 64)
    width = n + 3
    pos = jnp.where(jnp.arange(width) < n, jnp.arange(width), PAD_POS)[None]
    toks = jnp.asarray(np.concatenate([TOKENS[:n], [0, 0, 0]])[None])
    logits, caches = module.apply(params, toks, positions=pos, caches=caches, cache_index=0)
    assert_close(logits[0, :n], want[:n], 2e-5)
    for t in range(n, len(TOKENS)):
        logits, caches = module.apply(
            params, jnp.asarray(TOKENS[t:t + 1][None]), positions=jnp.asarray([[t]]),
            caches=caches, cache_index=jnp.asarray([t]))
        assert_close(logits[0, 0], want[t], 2e-5)


def test_a_padded_row_leaves_h_and_the_conv_rows_untouched(served):
    """A call whose rows are all padding (a slot nobody holds in a step, a chunk
    past a prompt's end) hands the state on bit for bit."""
    module, params = served
    _, caches = module.apply(params, jnp.asarray(TOKENS[None, :9]))
    dense = init_kv_caches(module.cfg, 1, 32)
    dense = [type(entry)(caches[i]) if i in module.cfg.state_layers else entry
             for i, entry in enumerate(dense)]
    for width in (1, 5):
        _, after = module.apply(params, jnp.zeros((1, width), jnp.int32),
                                positions=jnp.full((1, width), PAD_POS), caches=dense,
                                cache_index=9)
        for i in module.cfg.state_layers:
            for got, want in zip(after[i], dense[i]):
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---- the hold to transformers ---------------------------------------------------
@pytest.fixture(scope="module")
def published():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(0)
    config = transformers.GraniteMoeHybridConfig(
        vocab_size=96, hidden_size=32, intermediate_size=48, shared_intermediate_size=48,
        num_hidden_layers=7, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, tie_word_embeddings=True,
        layer_types=["mamba" if kind == "mamba" else "attention" for kind in PERIODS],
        position_embedding_type="nope", num_local_experts=0, num_experts_per_tok=0,
        mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
        mamba_expand=2, mamba_conv_bias=True, mamba_proj_bias=False, mamba_chunk_size=16,
        embedding_multiplier=12.0, attention_multiplier=0.0625, residual_multiplier=0.22,
        logits_scaling=8.0, attention_bias=False, pad_token_id=None, initializer_range=0.2)
    model = transformers.GraniteMoeHybridForCausalLM(config).eval()
    key = jax.random.PRNGKey(3)
    with torch.no_grad():   # the small leaves seeded, not the file's placeholders
        for name, p in model.named_parameters():
            key, sub = jax.random.split(key)
            leaf = name.rsplit(".", 1)[-1]
            if "norm" in name:
                p.add_(0.3 * torch.randn_like(p))
            elif leaf == "dt_bias":
                p.copy_(torch.tensor(np.asarray(draw_small_leaf("dt_bias_range", sub, p.shape))))
            elif leaf == "D":
                p.add_(0.3 * torch.randn_like(p))
            elif name.endswith("conv1d.bias"):
                p.copy_(0.5 * torch.randn_like(p))
    return model, config


def test_the_reference_and_the_served_forward_are_the_published_models(published):
    torch = pytest.importorskip("torch")
    model, config = published
    tokens = RNG.integers(0, 96, size=37)      # two of the file's chunks of 16 and a part
    with torch.no_grad():
        want = model(torch.tensor(tokens[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    cfg = module.cfg
    assert cfg.layer_types == tuple(PERIODS) and cfg.rope_theta is None and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.attention_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.0625, 0.22, 8.0)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state) == (8, 8, 16)
    ref, _ = reference.forward(variables, cfg, tokens.tolist())
    got, _ = module.apply(variables, jnp.asarray(tokens[None]))
    scale = np.abs(want).max()
    assert scale > 0.5
    assert np.abs(np.asarray(ref) - want).max() <= 1e-5 * max(scale, 1.0)
    assert np.abs(np.asarray(got[0]) - want).max() <= 1e-5 * max(scale, 1.0)
    # every wrong model is another model than the PUBLISHED one too
    for keywords in (dict(ssd_gate_after_norm=True), dict(attention_multiplier_off=True),
                     dict(residual_multiplier_off=True), dict(ssd_skip=False),
                     dict(ssd_conv_bias=False), dict(ssd_conv_bc=False)):
        wrong, _ = reference.forward(variables, cfg, tokens.tolist(), **keywords)
        assert np.abs(np.asarray(wrong) - want).max() > 0.003 * scale, keywords


def test_what_the_converter_does_not_hold_is_refused_by_name(published):
    _, config = published
    for key, value, match in (("num_local_experts", 4, "experts"),
                              ("mamba_proj_bias", True, "mamba_proj_bias"),
                              ("attention_bias", True, "bias")):
        wrong = type(config)(**{**config.to_dict(), key: value})
        with pytest.raises(ValueError, match=match):
            config_kwargs_from_hf(wrong)
    rotary = type(config)(**{**config.to_dict(), "position_embedding_type": "rope"})
    assert config_kwargs_from_hf(rotary)["rope_theta"] == 10000.0


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


# every way a chunk boundary can fall against the four taps and the carried h:
# 3e-5 of the logits' scale is float32's own noise through seven layers
@pytest.mark.parametrize("length", [1, 3, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 2 * CHUNK + 3])
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
    assert stats["ssd_rows"] == {"chunk": length, "decode": 4}
    assert stats["ssd_layer_calls"] == {"chunk": 5 * -(-length // CHUNK), "decode": 5 * 4}
    # here, on the CPU, the step's recurrence is the expression: one program, counted once
    assert stats["ssd_step_path"] == {"kernel": 0, "expression": 1}
    assert "gdn_step_path" not in stats and "gdn_rows" not in stats
    # 5 layers x 3 slots x 8 heads of [16, 8] float32; the chip would tile 8 lanes to 128
    assert stats["state_matrix_bytes"] == 5 * 3 * 8 * 8 * 16 * 4
    assert stats["state_matrix_tiled_bytes"] == 16 * stats["state_matrix_bytes"]


@pytest.mark.parametrize("length,new", [(CHUNK + 2, 1), (3, 9), (2 * CHUNK, 6)])
def test_a_probe_reads_back_the_h_its_sequence_leaves(server, length, new):
    """What a probe that asks for "state" is sent: the first mamba layer's h
    after the prompt and every sampled token but the last (which no step has
    been fed), against the reference's scan; and the reference that rounds h to
    bf16 after every token, which is what a cache holding it in bf16 would do,
    lies two orders further off than float32's own noise."""
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
    assert state["array"].shape == (cfg.mamba_n_heads, cfg.mamba_d_state, cfg.mamba_d_head)
    got = np.swapaxes(state["array"], 1, 2)

    def off(want):     # the furthest head, as a share of that head's own size
        want = np.asarray(want)
        return float(np.max(np.linalg.norm((got - want).reshape(len(want), -1), axis=1)
                            / np.linalg.norm(want.reshape(len(want), -1), axis=1)))

    assert off(reference.ssd_state(server._params, cfg, fed, 0)) < 1e-5
    assert off(reference.ssd_state(server._params, cfg, fed, 0, ssd_state_bf16=True)) > 1e-3
    # and a later mamba layer's, behind an attention layer, is the reference's too
    deep = reference.ssd_state(server._params, cfg, fed, 3)
    assert deep.shape == got.shape and np.isfinite(np.asarray(deep)).all()


def test_three_slots_and_a_slot_reused_give_the_logits_each_request_gives_alone(server):
    """B crosses two chunk boundaries while A decodes, C ends ON a boundary and
    is prefilled while B decodes; D takes the slot A leaves (its h and conv rows
    read as a sequence that starts, nothing reset) while B and C still decode."""
    a, b_, c, d = LONG[:5], LONG[10:10 + 2 * CHUNK + 3], LONG[3:3 + 2 * CHUNK], LONG[20:20 + CHUNK + 2]

    async def alone(prompt, n):
        bt = batcher(server)
        got = await ask(bt, prompt, n)
        await bt.close()
        return got

    async def together():
        bt = batcher(server)
        ta = asyncio.ensure_future(ask(bt, a, 6))
        await asyncio.sleep(0.05)
        tb = asyncio.ensure_future(ask(bt, b_, 16))
        await asyncio.sleep(0.05)
        tc = asyncio.ensure_future(ask(bt, c, 14))
        first = await ta
        td = asyncio.ensure_future(ask(bt, d, 6))     # three slots: A's is the free one
        got = [first] + list(await asyncio.gather(tb, tc, td))
        await bt.close()
        return got

    mixed = asyncio.run(together())
    for (out, logits), (prompt, n) in zip(mixed, ((a, 6), (b_, 16), (c, 14), (d, 6))):
        out_alone, logits_alone = asyncio.run(alone(prompt, n))
        assert out == out_alone
        assert_close(logits, logits_alone, 3e-5)
        assert_close(logits, reference_logits(server, prompt, out), 5e-5)


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
    matrix = 5 * 2 * 8 * 8 * 16 * 4
    assert stats["state_bytes"] == 5 * 2 * 3 * 96 * 4 + matrix
    assert stats["state_matrix_bytes"] == matrix
    lines = [line for line in text.splitlines() if not line.startswith("#")]

    def value(name, label):
        found = [float(line.rsplit(" ", 1)[1]) for line in lines
                 if line.startswith(name + "{") and label in line]
        assert len(found) == 1, (name, label, found)
        return found[0]

    assert value("seldon_llm_state_matrix_bytes", "") == matrix
    assert value("seldon_llm_state_matrix_tiled_bytes", "") == 16 * matrix
    assert value("seldon_llm_ssd_rows_total", 'program="chunk"') == CHUNK + 2
    assert value("seldon_llm_ssd_layer_calls_total", 'program="decode"') == 5 * 3
    assert value("seldon_llm_ssd_step_path_total", 'path="expression"') == 1
    assert value("seldon_llm_ssd_step_path_total", 'path="kernel"') == 0
    assert "seldon_llm_gdn_step_path_total{" not in text


# ---- what is refused, and how the seeded leaves are drawn ----------------------
@pytest.mark.parametrize("more,message", [
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e4,
          attention_multiplier=None), STATE_LAYERS_COMPOSE_REFUSAL),
    (dict(hc_mult=4), STATE_LAYERS_COMPOSE_REFUSAL),
    (dict(mtp_layers=1), STATE_LAYERS_COMPOSE_REFUSAL),
    (dict(n_experts=4), MAMBA_LAYERS_COMPOSE_REFUSAL),
    (dict(mesh=object()), MAMBA_LAYERS_COMPOSE_REFUSAL),
    (dict(layer_types=["mamba"] * 6 + ["linear_attention"], linear_num_key_heads=2,
          linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8),
     MAMBA_LAYERS_COMPOSE_REFUSAL),
    (dict(mamba_n_heads=0), "a 'mamba' layer needs"),
    (dict(mamba_n_groups=3), "a 'mamba' layer needs"),
])
def test_the_combinations_nobody_built_are_refused_where_the_config_is_made(more, message):
    assert "'mamba'" in STATE_LAYERS_COMPOSE_REFUSAL
    with pytest.raises(ValueError) as refused:
        get_model("transformer", **{**KW, **more})
    assert message in str(refused.value)


def test_a_scale_of_the_configs_own_is_not_latent_attentions():
    with pytest.raises(ValueError, match="attention_multiplier"):
        get_model("transformer", vocab_size=96, dim=32, n_layers=1, n_heads=4, n_kv_heads=4,
                  ffn_dim=48, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                  v_head_dim=8, attention_multiplier=0.1)


@pytest.mark.parametrize("option,match", [
    (dict(prefix_cache_size=2), "prefix_cache_size"),
    (dict(spec_mode="ngram"), "spec_mode"),
    (dict(lora_rank=4), "lora_rank"),
])
def test_load_refuses_by_name_what_is_not_built_over_a_mamba_layer(option, match):
    with pytest.raises(ValueError) as refused:
        make_server(**option)
    assert "mamba" in str(refused.value) and match in str(refused.value)


def test_the_seeded_small_leaves_are_the_layers_published_initialisation(served, server):
    """The one leaf ``heads`` [3, heads]: A_log = log(1 .. heads), dt_bias =
    softplus^-1 of a step log-uniform over (0.001, 0.1), D ones; the taps and the
    conv bias drawn (not zeros): by the module's init and by the server's
    streamed one alike."""
    assert served[0].cfg.small_leaf("heads") == "heads"
    for tree in (served[1]["params"], server._params["params"]):
        leaves = tree["layer_0"]["mamba"]
        a_log, dt_bias, skip = np.asarray(leaves["heads"], np.float32)
        np.testing.assert_allclose(np.exp(a_log), np.arange(1, 9), rtol=1e-6)
        dt = np.asarray(jax.nn.softplus(jnp.asarray(dt_bias)))
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
        np.testing.assert_array_equal(skip, np.ones(8))
        assert float(jnp.std(jnp.asarray(leaves["conv_bias"], jnp.float32))) > 0.2
        assert float(jnp.std(jnp.asarray(leaves["conv1d"], jnp.float32))) > 0.2
        assert all(jnp.asarray(leaves[name]).dtype == jnp.float32
                   for name in ("heads", "conv_bias", "conv1d"))
    # the slowest head at the smallest step forgets e^-0.001 a token: h carries
    # thousands of tokens, which is what lets a check see its precision
    assert np.exp(-1.0 * 1e-3) > 0.998


# ---- the wrong references of the chip check ------------------------------------
# ... and by how much of the logits' scale each must differ from the right one
# in float32 at this size
WRONG = {
    "state_held_in_bf16": (dict(ssd_state_bf16=True), 1e-4),
    "gate_after_the_norm": (dict(ssd_gate_after_norm=True), 0.01),
    "attention_scaled_by_head_dim": (dict(attention_multiplier_off=True), 0.002),
    "residual_multiplier_one": (dict(residual_multiplier_off=True), 0.02),
    "skip_left_out": (dict(ssd_skip=False), 0.01),
    "conv_bias_left_out": (dict(ssd_conv_bias=False), 0.01),
    "b_and_c_not_convolved": (dict(ssd_conv_bc=False), 0.01),
    "state_zeroed_at_a_chunk_start": (dict(ssd_reset_every=8), 0.01),
    "state_from_the_chunks_last_row": (dict(conv_state_pad=(10, 16)), 0.005),
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
