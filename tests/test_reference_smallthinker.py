"""SmallThinker's block (models/transformer.py: sliding-attention layers beside
full ones, RoPE a layer, the router fed the block's input, ReGLU experts), the
two page classes it is served from (models/cache.py ``WindowEntry``,
runtime/batcher.py: a window layer's pages behind the window are given back
while the request lives) and its plain float32 reference (models/reference.py).
No ``smallthinker`` modeling file is installed, so the hold to ``transformers``
is of the part that has one:

- the window's bound (a query sees ``sliding_window`` keys, itself included) to
  ``Olmo3ForCausalLM`` on converted weights, windows shorter than the sequence,
  at 1e-5 (models/convert.py's refusal of ``sliding_attention`` is lifted);
- served forward = reference at toy widths, THROUGH a window shorter than the
  sequence, and the dense cache of ``generate()``;
- chunked prefill then decode through the batcher and BOTH pools to the
  reference's full forward, on LOGITS, float32 and int8 weights, two sequences
  interleaved so that a page one gives back is the other's next page;
- each WRONG reference of the chip check
  (perf/configs/smallthinker-21b-a3b-int8.json ``reference_tolerance``) is
  another model in float32;
- a window page is given back exactly once, both classes are empty after
  finish and shed, and what is not built over window layers is refused by
  name, where the config is made or at ``load()``.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model, reference
from seldon_core_tpu.models.cache import PAD_POS, init_kv_caches
from seldon_core_tpu.models.convert import convert_hf_model
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

WINDOW = 12
# the served model in small: one period of [global, window, window, window],
# the window shorter than every prompt below
KW = dict(vocab_size=96, dim=48, n_layers=4, n_heads=6, n_kv_heads=2, head_dim=8, ffn_dim=32,
          max_seq_len=128, norm_eps=1e-6, rope_theta=1.5e6, dtype="float32",
          layer_types=["full_attention"] + ["sliding_attention"] * 3, rope_layout=[0, 1, 1, 1],
          sliding_window=WINDOW, n_experts=8, n_experts_per_token=3, router_renormalize=True,
          ffn_act="relu", router_input="layer_input")
CHUNK, PAGE = 8, 4
RNG = np.random.default_rng(49)
TOKENS = RNG.integers(1, 96, size=50)
LONG = RNG.integers(1, 96, size=80).tolist()

# the wrong references of ISSUE 49, point 4 (the weights at 4 bits are the chip check's)
WRONG = {
    "no window": dict(window_off=True),
    "the window a page wide of the mark": dict(window_wrong=WINDOW + PAGE),
    "rope on the global layers too": dict(rope_on_global=True),
    "no rope on the window layers": dict(rope_on_window=False),
    "the router fed the ffn input": dict(router_input="ffn_input"),
    "silu for relu": dict(ffn_act="silu"),
    "top-k weights not renormalised": dict(renormalize=False),
    "one expert a token left out": dict(leave_out_rank=0),
}


@pytest.fixture(scope="module")
def served():
    module = get_model("transformer", **KW)
    params = module.init(jax.random.PRNGKey(7), jnp.asarray(TOKENS[None]))
    return module, params


def test_served_forward_matches_the_reference_through_the_window(served):
    module, params = served
    got, _ = module.apply(params, jnp.asarray(TOKENS[None]))
    want, routing = reference.forward(params, module.cfg, TOKENS.tolist())
    assert len(routing) == 4 and float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("name", sorted(WRONG))
def test_each_wrong_reference_is_another_model_in_float32(served, name):
    module, params = served
    want, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    bad, _ = reference.forward(params, module.cfg, TOKENS.tolist(), **WRONG[name])
    scale = float(jnp.abs(want).max())
    # rows behind the window differ; those inside it (the first WINDOW) need not
    assert float(jnp.abs(bad - want)[WINDOW:].max()) > 0.05 * scale, name


def test_prefill_into_the_dense_cache_then_decode_equals_the_full_forward(served):
    """``generate()``'s cache: every row stays, and the window's lower bound is
    the mask's alone."""
    module, params = served
    want, _ = reference.forward(params, module.cfg, TOKENS.tolist())
    n = len(TOKENS) - 8
    caches = init_kv_caches(module.cfg, 1, 64)
    pos = jnp.where(jnp.arange(n + 3) < n, jnp.arange(n + 3), PAD_POS)[None]
    toks = jnp.asarray(np.concatenate([TOKENS[:n], [0, 0, 0]])[None])
    logits, caches = module.apply(params, toks, positions=pos, caches=caches, cache_index=0)
    np.testing.assert_allclose(logits[0, :n], want[:n], atol=2e-5, rtol=0)
    for t in range(n, len(TOKENS)):
        logits, caches = module.apply(
            params, jnp.asarray(TOKENS[t:t + 1][None]), positions=jnp.asarray([[t]]),
            caches=caches, cache_index=jnp.asarray([t]))
        np.testing.assert_allclose(logits[0, 0], want[t], atol=2e-5, rtol=0)


# ---- the outside oracle for the window's bound ---------------------------------
def test_the_windows_bound_is_olmo3s_sliding_attention():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(0)
    kinds = ["sliding_attention", "full_attention", "sliding_attention"]
    config = transformers.Olmo3Config(
        vocab_size=96, hidden_size=48, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=6, num_key_value_heads=2, max_position_embeddings=128,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False, pad_token_id=None,
        layer_types=kinds, sliding_window=9)
    config._attn_implementation = "eager"
    model = transformers.Olmo3ForCausalLM(config).eval()
    with torch.no_grad():   # weights that a window off the mark would show in
        for name, p in model.named_parameters():
            if "norm" not in name and name != "model.embed_tokens.weight":
                p.mul_(30.0)
    tokens = RNG.integers(0, 96, size=40)
    with torch.no_grad():
        want = model(torch.tensor(tokens[None]), use_cache=False).logits.numpy()[0]
    module, variables = convert_hf_model(model)
    cfg = module.cfg
    assert cfg.layer_types == tuple(kinds) and cfg.sliding_window == 9
    ref, _ = reference.forward(variables, cfg, tokens.tolist())
    got, _ = module.apply(variables, jnp.asarray(tokens[None]))
    scale = np.abs(want).max()
    assert scale > 0.5
    assert np.abs(np.asarray(ref) - want).max() <= 1e-5 * max(scale, 1.0)
    assert np.abs(np.asarray(got[0]) - want).max() <= 1e-5 * max(scale, 1.0)
    # a key more, a key fewer, or no window at all is another model
    for wrong in (dict(window_wrong=10), dict(window_wrong=8), dict(window_off=True)):
        off, _ = reference.forward(variables, cfg, tokens.tolist(), **wrong)
        assert np.abs(np.asarray(off) - want).max() > 1e-3 * scale, wrong


# ---- through the batcher and both pools ----------------------------------------
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


@pytest.fixture(scope="module")
def int8_server():
    return make_server(quantize="int8")


def batcher(server, **kw):
    base = dict(max_slots=3, max_len=96, len_buckets=(CHUNK,), pipeline_depth=2,
                page_size=PAGE, prefill_chunk=CHUNK)
    base.update(kw)
    return ContinuousBatcher(server, **base)


async def ask(b, prompt, n=5, **kw):
    info = {"logits": []}
    out = await b.submit(prompt, max_new_tokens=n, info=info, **kw)
    return out, np.stack(info["logits"]), info.get("routing")


def reference_logits(server, prompt, out, routing=None):
    first = len(prompt) - 1
    follow = None if routing is None else np.stack(routing)[:first + len(out)]
    return np.asarray(reference.forward(server._params, server._cfg, prompt + out[:-1],
                                        rows=slice(first, first + len(out)), follow=follow)[0])


# a prompt under the window, one a chunk past it, and chunk boundaries against the pages
@pytest.mark.parametrize("length", [5, WINDOW, WINDOW + CHUNK + 1, 3 * CHUNK, 45])
@pytest.mark.parametrize("weights", ["float32", "int8"])
def test_chunks_then_steps_through_both_pools_equal_the_reference(
        server, int8_server, weights, length):
    srv = server if weights == "float32" else int8_server
    prompt, n = LONG[:length], 20

    async def go():
        b = batcher(srv)
        got = await ask(b, prompt, n)
        stats = {**b._phases.stats(), **b.page_stats()}
        await b.close()
        return got, stats

    (out, logits, routing), stats = asyncio.run(go())
    # the served experts are followed (int8 rounds the router too, and a renormalised
    # top-3 moves everything behind a flipped near-tie): the comparison is the arithmetic's
    np.testing.assert_allclose(logits, reference_logits(srv, prompt, out, routing), atol=1e-4, rtol=0)
    # pages were given back while the request lived, each once, and none is held now
    last = length + n - 2                       # the last position a step wrote
    assert stats["kv_pages_released"]["window"] == max(last - WINDOW + 1, 0) // PAGE
    assert stats["kv_pages_by_class"]["window"]["in_use"] == 0
    assert stats["kv_pages_by_class"]["full"]["in_use"] == 0 == stats["kv_pages_in_use"]
    # what the loop counted: the rows inside the window, and what a full layer reads
    steps = range(length, length + n - 1)      # the query position of each decode step
    assert stats["attn_window_context_tokens"]["decode"] == sum(min(p + 1, WINDOW) for p in steps)
    assert stats["attn_window_context_tokens_unwindowed"]["decode"] == sum(p + 1 for p in steps)
    assert stats["attn_context_tokens"]["decode"] == sum(p + 1 for p in steps)


def test_a_page_one_sequence_gives_back_is_the_others_next_page(server):
    """B is prefilled (chunks) while A decodes past its window, and decodes
    while C is prefilled: the window class's free list is one, so a page A gives
    back is handed to B mid-prompt, its stale positions reset."""
    a, b_, c = LONG[:30], LONG[10:10 + 5 * CHUNK + 3], LONG[3:3 + 3 * CHUNK + 1]
    moves = []

    async def alone(prompt, n):
        bt = batcher(server)
        got = await ask(bt, prompt, n)
        await bt.close()
        return got

    async def together():
        bt = batcher(server)
        alloc, free = bt._window_allocator.alloc, bt._window_allocator.free

        def holder():   # which slot is booking: the one whose call is being built
            job = bt._prefill
            return "job" if job is not None and bt._phases._open_parts and \
                bt._phases._open_parts[-1].name.startswith("prefill") else "step"

        def spy_alloc(n):
            got = alloc(n)
            moves.append(("alloc", holder(), tuple(got or ())))
            return got

        def spy_free(pages):
            moves.append(("free", holder(), tuple(pages)))
            return free(pages)

        bt._window_allocator.alloc, bt._window_allocator.free = spy_alloc, spy_free
        ta = asyncio.ensure_future(ask(bt, a, 30))
        await asyncio.sleep(0.05)
        tb = asyncio.ensure_future(ask(bt, b_, 12))
        await asyncio.sleep(0.05)
        tc = asyncio.ensure_future(ask(bt, c, 6))
        got = await asyncio.gather(ta, tb, tc)
        stats = bt.page_stats()
        await bt.close()
        return got, stats

    mixed, stats = asyncio.run(together())
    for (out, logits, _), (prompt, n) in zip(mixed, ((a, 30), (b_, 12), (c, 6))):
        out_alone, logits_alone, routing = asyncio.run(alone(prompt, n))
        assert out == out_alone
        np.testing.assert_allclose(logits, logits_alone, atol=3e-5, rtol=0)
        np.testing.assert_allclose(logits, reference_logits(server, prompt, out, routing),
                                   atol=1e-4, rtol=0)
    # a page a decode step gave back was booked by a prefill chunk afterwards
    freed_by_steps, handed_on = set(), 0
    for what, who, pages in moves:
        if what == "free" and who == "step":
            freed_by_steps.update(pages)
        elif what == "alloc" and who == "job":
            handed_on += len(freed_by_steps.intersection(pages))
            freed_by_steps.difference_update(pages)
    assert handed_on > 0
    # every page freed as often as it was booked: exactly once a booking
    booked = [p for what, _, pages in moves if what == "alloc" for p in pages]
    freed = [p for what, _, pages in moves if what == "free" for p in pages]
    assert sorted(booked) == sorted(freed)
    assert stats["kv_pages_in_use"] == 0


def test_both_classes_are_empty_after_sheds_on_exhaustion(server):
    """Finish is every test's above; here the FULL class is oversubscribed
    (``pool_pages``: room for one long sequence and a bit), so the loop itself
    sheds the newest tenant when a decode step cannot grow: whichever it sheds
    (a staged prefill job mid-prompt, an active slot mid-decode), its pages of
    BOTH classes go back, and the window class, always fully provisioned, never
    runs out."""
    from seldon_core_tpu.runtime.resilience import ShedError

    async def go():
        bt = batcher(server, pool_pages=2 + 24 + 6)
        asks = [asyncio.ensure_future(bt.submit(LONG[:30], max_new_tokens=60))]
        await asyncio.sleep(0.05)
        asks += [asyncio.ensure_future(bt.submit(LONG[5:45], max_new_tokens=50)),
                 asyncio.ensure_future(bt.submit(LONG[9:59], max_new_tokens=30))]
        got = await asyncio.gather(*asks, return_exceptions=True)
        out = await bt.submit(LONG[:40], max_new_tokens=6)     # and the pool serves again
        stats, released = bt.page_stats(), bt.page_stats()["kv_pages_released"]["window"]
        await bt.close()
        return got, out, stats, released

    got, out, stats, released = asyncio.run(go())
    shed = [g for g in got if isinstance(g, ShedError)]
    assert shed and len(shed) < 3, got                 # somebody was shed, somebody finished
    assert all(isinstance(g, (list, ShedError)) for g in got), got
    assert len(out) == 6 and released > 0
    assert stats["kv_page_sheds"] >= len(shed)
    assert stats["kv_pages_by_class"] == {
        "full": {"total": 32, "in_use": 0},
        "window": {"total": 3 * (-(-(WINDOW + CHUNK) // PAGE) + 1) + 2, "in_use": 0}}


def test_the_page_classes_and_the_released_counter_reach_the_registry():
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    comp = make_server(continuous_batching=2, kv_page_size=PAGE, prefill_chunk=CHUNK,
                       len_buckets=(CHUNK, 16, 32, 64), continuous_batching_max_len=96)
    svc = get_batcher_service(comp)
    try:
        assert len(asyncio.run(svc.submit(LONG[:30], max_new_tokens=8))) == 8
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(comp)
        text = reg.expose().decode()
        pages = svc.batcher.page_stats()
    finally:
        svc.close()
    lines = [line for line in text.splitlines() if not line.startswith("#")]

    def value(name, *labels):
        found = [float(line.rsplit(" ", 1)[1]) for line in lines
                 if line.startswith(name + "{") and all(label in line for label in labels)]
        assert len(found) == 1, (name, labels, found)
        return found[0]

    for page_class, counted in pages["kv_pages_by_class"].items():
        assert value("seldon_llm_kv_pages_total", f'class="{page_class}"') == counted["total"]
    assert value("seldon_llm_kv_pages_released_total", 'reason="window"') == (30 + 8 - 2 - WINDOW + 1) // PAGE
    window = value("seldon_llm_attn_context_tokens_total", 'program="decode"', 'kind="window"')
    full = value("seldon_llm_attn_context_tokens_total", 'program="decode"', 'kind="full"')
    assert window == 7 * WINDOW and full == sum(range(31, 38))
    assert value("seldon_llm_attn_context_tokens_unwindowed_total",
                 'program="decode"', 'kind="window"') == full


def test_a_model_without_window_layers_has_the_full_class_alone():
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import get_batcher_service

    kw = {k: v for k, v in KW.items() if k not in ("layer_types", "sliding_window")}
    comp = make_server(model_kwargs=kw, continuous_batching=2, kv_page_size=PAGE,
                       prefill_chunk=CHUNK, len_buckets=(CHUNK, 16, 32))
    svc = get_batcher_service(comp)
    try:
        assert len(asyncio.run(svc.submit(LONG[:5], max_new_tokens=3))) == 3
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(comp)
        text = reg.expose().decode()
    finally:
        svc.close()
    assert 'class="window"' not in text and 'kind="window"' not in text
    assert "seldon_llm_kv_pages_in_use{" in text and 'class="full"' in text


# ---- what is refused, by name ---------------------------------------------------
@pytest.mark.parametrize("more,match", [
    (dict(sliding_window=0), "sliding_window > 0"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
          rope_layout=None), "sliding_attention"),
    (dict(hc_mult=4, router_input="ffn_input"), "sliding_attention"),
    (dict(mtp_layers=1, router_input="ffn_input"), "sliding_attention"),
    (dict(attention_impl="ring"), "sliding_attention"),
    (dict(kv_cache_dtype="int8"), "sliding_attention"),
    (dict(rope_layout=[0, 1, 1]), "rope_layout"),
    (dict(rope_layout=[0, 1, 2, 1]), "rope_layout"),
    (dict(rope_theta=None), "rope_layout"),
    (dict(ffn_act="gelu"), "ffn_act"),
    (dict(router_input="attention_output"), "router_input"),
    (dict(n_experts=0), "router_input"),
    (dict(layer_types=None, sliding_window=0, hc_mult=4), "router_input"),
])
def test_the_combinations_nobody_built_are_refused_where_the_config_is_made(more, match):
    with pytest.raises(ValueError, match=match):
        get_model("transformer", **{**KW, **more})


@pytest.mark.parametrize("more,match", [
    (dict(prefix_cache_size=4), "prefix_cache_size"),
    (dict(spec_mode="ngram"), "spec_mode"),
    (dict(disaggregation="remote_prefill"), "disaggregation"),
    (dict(tensor_parallel=2), "mesh"),
    (dict(kv_cache_dtype="int8"), "int8"),
    # (an MoE model refuses adapters on its own account: a dense one with window layers)
    (dict(lora_rank=4, model_kwargs={**KW, "n_experts": 0, "router_input": "ffn_input"}),
     "lora_rank"),
])
def test_what_restarts_a_sequence_over_window_layers_is_refused_at_load(more, match):
    with pytest.raises(ValueError, match=match) as refused:
        make_server(**more)
    assert "sliding_attention" in str(refused.value)


def test_the_tree_operations_that_restart_a_sequence_refuse_a_window_entry(server):
    from seldon_core_tpu.models import cache as kvcache

    tree = kvcache.init_paged_kv_caches(server._cfg, 8, PAGE, window_pages=6)
    ids = jnp.asarray([2, 3])
    for what, call in (
            ("cow_page_copy", lambda: kvcache.cow_page_copy(tree, 2, 3, 1)),
            ("export_pages", lambda: kvcache.export_pages(tree, ids)),
            ("import_pages", lambda: kvcache.import_pages(tree, [], ids, 1, 2)),
            ("forget_positions", lambda: kvcache.forget_positions(tree, ids[None]))):
        with pytest.raises(ValueError, match="sliding-attention layers") as refused:
            call()
        assert what in str(refused.value)
