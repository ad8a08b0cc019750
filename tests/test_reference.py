"""The served decoder against the plain reference (models/reference.py), and
the reference against the model it claims to describe.

The chain: ``transformers.OlmoeForCausalLM`` == reference (so the equations
are OLMoE's, not this repo's reading of them); reference == ``Transformer``
full forward; == chunked prefill then decode through the PAGED pool;
== ``LLMServer`` + ``ContinuousBatcher`` with int8 weights and bf16
activations, dead slots and padding present, logits as /v1/generate's probe
returns them; and the routing tallies the programs report == the reference's.

Weights: the module's seeded init, with the experts' output projection and
the embedding scaled up (``olmoe_params``) so that the expert layer is a large
part of the residual stream: a wrong expert layer moves the logits by far more
than any tolerance here.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.cache import PAD_POS, TRASH_PAGE, init_paged_kv_caches
from seldon_core_tpu.models.transformer import moe_routing_stats

OLMOE = dict(vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
             ffn_dim=32, max_seq_len=128, n_experts=16, n_experts_per_token=4,
             router_renormalize=False, qk_norm=True, tie_embeddings=False)
CASES = {
    # OLMoE's block: QK-norm, softmax over all experts then top-k, weights as they are
    "olmoe": OLMOE,
    # today's semantics (Mixtral's router: the top-k weights renormalised), GQA
    "renormalised-top2-of-4": dict(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
        max_seq_len=128, tie_embeddings=True, n_experts=4, n_experts_per_token=2),
    # no experts at all: the reference's dense branch, and GQA's groups
    "dense-gqa": dict(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
        max_seq_len=128, tie_embeddings=True),
}


def olmoe_params(module, seed: int):
    variables = module.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(lambda x: x, variables["params"])
    params["tok_embeddings"] = params["tok_embeddings"] * 50.0   # 0.02 -> unit scale
    for i in range(module.cfg.n_layers):
        if "moe" in params[f"layer_{i}"]:
            # unit-variance expert outputs AFTER the router's weights (which sum to < 1)
            params[f"layer_{i}"]["moe"]["w2"] = params[f"layer_{i}"]["moe"]["w2"] * 4.0
    return {"params": params}


def tokens_of(seed: int, n: int, vocab: int) -> np.ndarray:
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1, vocab))


# ---------------------------------------------------------------- HF == reference
def test_reference_matches_hf_olmoe():
    """QK-norm over the whole projection, softmax over all experts then top-k,
    no renormalisation: the reference's logits and its choice of experts are
    transformers' on converted weights. float32 both sides: 2e-4 is
    summation order."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from seldon_core_tpu.models.convert import convert_hf_model

    config = transformers.OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, num_experts=16,
        num_experts_per_tok=4, max_position_embeddings=64, norm_topk_prob=False)
    torch.manual_seed(0)
    hf = transformers.OlmoeForCausalLM(config).eval()
    with torch.no_grad():
        for name, w in hf.named_parameters():
            if "norm" in name:          # ones would hide a misplaced norm weight
                w.copy_(1.0 + 0.3 * torch.randn_like(w))
            elif "experts" in name:     # the default 0.02 makes the experts a rumour
                w.mul_(15.0)
    module, variables = convert_hf_model(hf)
    assert module.cfg.qk_norm and not module.cfg.router_renormalize
    tokens = tokens_of(1, 24, 128)
    with torch.no_grad():
        out = hf(torch.from_numpy(tokens[None].astype(np.int64)), output_router_logits=True)
    logits, routing = reference.forward(variables, module.cfg, tokens)
    np.testing.assert_allclose(np.asarray(logits), out.logits[0].numpy(), atol=2e-4, rtol=2e-4)
    for layer, router_logits in zip(routing, out.router_logits):
        theirs = torch.topk(torch.softmax(router_logits.float(), dim=-1), 4).indices.numpy()
        assert np.array_equal(np.sort(layer["experts"], -1), np.sort(theirs, -1))
    # and the system itself, for the same weights
    ours, _ = module.apply(variables, jnp.asarray(tokens[None], jnp.int32))
    np.testing.assert_allclose(np.asarray(ours[0]), out.logits[0].numpy(), atol=2e-4, rtol=2e-4)


# ------------------------------------------------ (a) full forward == reference
def check_full_forward(case: str) -> None:
    """float32 on both sides, so no choice of expert may differ and 1e-4 (of
    logits of scale ~1) is summation order alone."""
    module = get_model("transformer", dtype="float32", **CASES[case])
    variables = olmoe_params(module, seed=0)
    tokens = tokens_of(2, 24, module.cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        if module.cfg.n_experts:
            (logits, _), sown = module.apply(
                variables, jnp.asarray(tokens[None]), mutable=["moe"])
        else:
            logits, _ = module.apply(variables, jnp.asarray(tokens[None]))
    ref, routing = reference.forward(variables, module.cfg, tokens)
    assert logits.shape == (1, 24, module.cfg.vocab_size)
    assert float(jnp.max(jnp.abs(ref))) > 0.3     # a real signal to compare
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref), atol=1e-4, rtol=1e-4)
    if module.cfg.n_experts:
        counted, stats = moe_routing_stats(sown["moe"], module.cfg)
        want = reference.expert_token_counts(routing, module.cfg.n_experts)
        assert np.array_equal(np.asarray(counted[0]), np.asarray(want))
        k, layers = module.cfg.n_experts_per_token, module.cfg.n_layers
        assert stats.tolist()[:2] == [24, 24 * k * layers]


@pytest.mark.parametrize("case", sorted(CASES))
def test_full_forward_matches_reference(case):
    check_full_forward(case)


def test_leaving_an_expert_out_is_seen():
    """What the tolerances guard against: every token losing ONE of its four
    experts (the largest, or the smallest) moves the logits by a thousand
    times the float32 tolerance and four times the bf16 one (test (c))."""
    module = get_model("transformer", dtype="float32", **OLMOE)
    variables = olmoe_params(module, seed=0)
    tokens = tokens_of(2, 24, 128)
    ref, _ = reference.forward(variables, module.cfg, tokens)
    for rank in (0, 3):
        wrong, _ = reference.forward(variables, module.cfg, tokens, leave_out_rank=rank)
        assert float(jnp.max(jnp.abs(wrong - ref))) > 4 * BF16_ATOL


# ------------------------- (b) chunked prefill + decode through the paged pool
def test_paged_prefill_then_decode_matches_reference():
    """Prompt of 21 tokens in chunks of 8 (the last one padded with PAD_POS),
    then 6 decode steps of a batch of two slots of which one is DEAD (its
    block-table row all TRASH_PAGE): every position's logits equal the
    reference's full forward over the 27 tokens (float32: 1e-4, no choice of
    expert may differ), and the dead slot and the padding are counted nowhere."""
    module = get_model("transformer", dtype="float32", **OLMOE)
    cfg = module.cfg
    variables = olmoe_params(module, seed=0)
    tokens = tokens_of(3, 27, cfg.vocab_size)
    ref, routing = reference.forward(variables, cfg, tokens)
    ref = np.asarray(ref)
    page, n_pages, chunk, plen = 8, 8, 8, 21
    pools = init_paged_kv_caches(cfg, 2 + n_pages, page)
    row = np.arange(2, 2 + n_pages, dtype=np.int32)[None]
    counted = np.zeros((cfg.n_experts,), np.int64)
    with jax.default_matmul_precision("highest"):
        for start in range(0, plen, chunk):
            n = min(chunk, plen - start)
            toks = np.zeros((1, chunk), np.int32)
            pos = np.full((1, chunk), PAD_POS, np.int32)
            toks[0, :n], pos[0, :n] = tokens[start:start + n], np.arange(start, start + n)
            (logits, pools), sown = module.apply(
                variables, jnp.asarray(toks), positions=jnp.asarray(pos), caches=pools,
                block_tables=jnp.asarray(row), mutable=["moe"])
            np.testing.assert_allclose(np.asarray(logits[0, :n]), ref[start:start + n],
                                       atol=1e-4, rtol=1e-4)
            per_seq, stats = moe_routing_stats(sown["moe"], cfg)
            assert int(stats[0]) == n                      # the padding is no row
            counted += np.asarray(per_seq[0])
        tables = np.concatenate([row, np.full((1, n_pages), TRASH_PAGE, np.int32)])
        for p in range(plen, 27):
            (logits, pools), sown = module.apply(
                variables, jnp.asarray([[tokens[p]], [tokens[p]]], jnp.int32),
                positions=jnp.asarray([[p], [p]], jnp.int32), caches=pools,
                block_tables=jnp.asarray(tables), mutable=["moe"])
            np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[p], atol=1e-4, rtol=1e-4)
            per_seq, stats = moe_routing_stats(sown["moe"], cfg)
            assert int(stats[0]) == 1 and not np.asarray(per_seq[1]).any()   # the dead slot
            counted += np.asarray(per_seq[0])
    assert np.array_equal(counted, np.asarray(reference.expert_token_counts(routing, cfg.n_experts)))


# -------------------- (c) + (d) LLMServer + ContinuousBatcher, int8, bf16 activations
# bf16 activations against the reference's float32, same int8-rounded weights:
# 8 bits of mantissa (2^-9 = 0.2 % a rounding) through 2 layers of residual
# adds, norms and three matmuls each reach 0.003-0.008 on logits of scale
# 0.4-0.7 (67 seeds tried when this was written); the bound is twice the worst.
# A choice of expert flipped put 0.018-0.029 there, and the smallest of a
# token's four experts left out 0.05-0.18 (test_leaving_an_expert_out_is_seen).
BF16_ATOL = 0.015
# top-k is discrete: a probability nearer to the next one than bf16's noise
# on it may be chosen the other way, after which the two sides compute
# different functions. The reference reports every choice's margin. Flips were
# seen at margins up to 9e-4 and none above; the seeds below (the weights', and
# the requests': the tokens they draw are positions too) are chosen so that
# no margin of either request is under twice that (asserted: 2.2e-3 and
# 4.8e-3), so NO disagreement is admitted, the logits tolerance covers every
# position and the counts are exact. (On the chip, at 16 layers x 64 experts,
# near-ties are certain: the benchmark's tolerance is measured with them in.)
MIN_MARGIN = 2e-3
SERVED_SEED = 23
REQUEST_SEED = 17


@pytest.fixture(scope="module")
def served():
    """Two requests of different lengths in flight in 4 slots (two stay dead),
    prompts that end mid-chunk (padding in the last chunk), logits asked."""
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher
    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(
        model="transformer", model_kwargs=OLMOE, quantize="int8", init_random=True,
        eos_id=-1, temperature=0.7, tokenizer="bytes", len_buckets=[32, 64, 128],
        max_new_tokens=8, kv_page_size=8, prefill_chunk=8, seed=SERVED_SEED)
    server.load()
    batcher = ContinuousBatcher(server, max_slots=4, max_len=64)
    prompts = [tokens_of(11, 13, 128).tolist(), tokens_of(12, 6, 128).tolist()]
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


def test_served_logits_match_reference(served):
    server, _, prompts, outs, infos = served
    for prompt, out, info in zip(prompts, outs, infos):
        got = np.stack(info["logits"])
        assert got.shape == (len(out), 128) and got.dtype == np.float32
        ref, routing = reference.forward(server._params, server._cfg, prompt + out)
        margin = min(float(jnp.min(layer["margin"])) for layer in routing)
        assert margin > MIN_MARGIN, (
            f"a choice of expert is within {margin:.2g} of the next: bf16 may flip it. "
            "Pick another SERVED_SEED or REQUEST_SEED (the weights or the draws moved), do not widen the tolerance")
        # row j is what token j was sampled from: positions len(prompt)-1 ..
        want = np.asarray(ref)[len(prompt) - 1:len(prompt) - 1 + len(out)]
        assert np.abs(want).max() > 0.3
        np.testing.assert_allclose(got, want, atol=BF16_ATOL, rtol=0)


def test_served_routing_counts_equal_reference(served):
    """Exactly: per expert, prompt tokens and credited decode rows over both
    layers; dead slots and padding excluded. (No choice can differ: the
    margins above.) The device's own tallies may hold more decode rows than
    were delivered (a spent slot rides along until its drain), never fewer."""
    server, batcher, prompts, outs, _ = served
    cfg = server._cfg
    want = np.zeros((cfg.n_experts,), np.int64)
    fed = 0
    for prompt, out in zip(prompts, outs):
        _, routing = reference.forward(server._params, cfg, prompt + out)
        rows = len(prompt) + len(out) - 1      # the last token is never fed back
        want += np.asarray(reference.expert_token_counts(routing, cfg.n_experts, slice(0, rows)))
        fed += rows
    stats = batcher._moe.stats()
    assert stats["moe_expert_tokens"] == want.tolist()
    k, layers = cfg.n_experts_per_token, cfg.n_layers
    assert sum(stats["moe_expert_tokens"]) == fed * k * layers
    chunk, decode = stats["moe_by_program"]["chunk"], stats["moe_by_program"]["decode"]
    assert chunk["live_rows"] == sum(len(p) for p in prompts)      # not 16 + 8: the padding
    assert chunk["calls"] == 3 and chunk["routed_pairs"] == chunk["live_rows"] * k * layers
    delivered = fed - chunk["live_rows"]
    assert delivered <= decode["live_rows"] <= delivered + 2 * decode["calls"]
    assert decode["live_rows"] < 4 * decode["calls"]               # dead slots are no rows
    assert decode["routed_pairs"] == decode["live_rows"] * k * layers
    assert 0 < decode["max_group"] <= decode["live_rows"] * layers
    assert decode["experts_touched"] <= min(decode["routed_pairs"], 16 * layers * decode["calls"])


def test_int8_expert_stacks_never_become_floats(served):
    """The programs take the int8 stacks as they are (per-expert scales)."""
    server = served[0]
    moe = server._params["params"]["layer_0"]["moe"]
    assert moe["w1"].q.dtype == jnp.int8 and moe["w1"].scale.shape == (16, 32)
    kept = server._dequant(server._params)["params"]["layer_0"]
    assert kept["moe"]["w2"].q.dtype == jnp.int8
    assert kept["attention"]["wq"].dtype != jnp.int8      # 2-D leaves dequantize as before


def test_reference_reads_the_served_tree_in_either_orientation(served):
    """The served tree holds wq / wk / wv output-major ([out, in] int8 bytes,
    ops/quantize.py); the reference reads the container, so it answers for the
    SAME matrices: bit for bit what it gives for that tree's own dequantized
    float32 leaves (the form test_reference_matches_hf_olmoe holds to
    transformers' OLMoE)."""
    from seldon_core_tpu.ops.quantize import dequantize_params

    server, _, prompts = served[:3]
    attention = server._params["params"]["layer_0"]["attention"]
    assert attention["wq"].out_major and attention["wk"].out_major and attention["wv"].out_major
    assert attention["wq"].q.shape == (64, 64) and not attention["wo"].out_major
    floats = dequantize_params(server._params, jnp.float32)
    assert floats["params"]["layer_0"]["attention"]["wk"].dtype == jnp.float32
    tokens = prompts[0]
    got, _ = reference.forward(server._params, server._cfg, tokens)
    want, _ = reference.forward(floats, server._cfg, tokens)
    assert np.array_equal(np.asarray(got), np.asarray(want))
