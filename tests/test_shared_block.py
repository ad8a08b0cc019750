"""A layer class is traced once a program (ISSUE 54).

``Transformer`` calls its layers through ``transformer_block``
(models/transformer.py), a jitted function of a layer's parameter subtree, so
the layers that ``TransformerBlock`` builds alike (``cfg.layer_class``: the
mixer's kind, its window, its rotary embedding, the FFN's kind) share ONE trace
of it in whatever program calls them, and that program lowers to calls of one
function which the compiler inlines. Held here, on the CPU at toy widths:

(a) a program's count of block traces is its count of classes: a dense model of
    four layers traces the block once, a hybrid twice, and what has to come
    through the call does: the ``moe`` collection's sown counters, a layer's
    slice of the LoRA pool, ``state_slots``, the pair of block tables;
(b) what a server serves (tokens, the float32 logits a probe reads, the cache
    tree a request leaves) is the plain per-layer loop's to the bit, for one
    model of every layer kind the tree has;
(c) the class itself, from the four things a block reads of its number.
"""

from __future__ import annotations

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_chunk_head import MODELS as CHUNK_HEAD_MODELS
from test_hybrid_state import MAMBA_KW, SAMBAY_KW
from test_reference_olmo_hybrid import KW as OLMO_HYBRID_KW
from test_reference_smallthinker import KW as SMALLTHINKER_KW
from test_reference_xing4 import XING4
from test_wide_chunk import MODELS as WIDE_CHUNK_MODELS

from seldon_core_tpu.models import get_model
from seldon_core_tpu.models import transformer as T
from seldon_core_tpu.models.cache import init_paged_kv_caches
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

S = jax.ShapeDtypeStruct
# a layer's call of its class's block (a program's second class is ``transformer_block_0``)
BLOCK_CALL = re.compile(r"call @transformer_block(_\d+)?\(")
DENSE = dict(vocab_size=96, dim=32, n_layers=4, n_heads=4, n_kv_heads=2, ffn_dim=64,
             max_seq_len=64, dtype="float32")


def plain_block(cfg, layer, name):
    """The per-layer loop this PR replaced: a ``TransformerBlock`` a layer,
    traced where it stands."""
    return T.TransformerBlock(cfg, layer, name=name)


@pytest.fixture
def block_traces(monkeypatch):
    """The layer numbers ``TransformerBlock`` was traced for, in order."""
    traces, real = [], T.TransformerBlock.__call__

    def counted(self, *args, **kwargs):
        traces.append(self.layer)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(T.TransformerBlock, "__call__", counted)
    T.transformer_block.clear_cache()
    yield traces
    T.transformer_block.clear_cache()


# ------------------------------------------- (a) a trace a class
def paged_call(model, sequences: int, tokens: int, page: int = 4, pages: int = 4):
    """(a step or chunk of the model over a paged pool, its abstract arguments)"""
    cfg = model.cfg
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    windowed = {"window_pages": 2 + sequences * pages} if cfg.window_layers else {}
    pools = jax.eval_shape(lambda: init_paged_kv_caches(
        cfg, 2 + sequences * pages, page, "bf16", state_slots=sequences, **windowed))
    table = S((sequences, pages), jnp.int32)
    more = {"state_slots": S((sequences,), jnp.int32)} if cfg.state_layers else {}

    def call(params, pools, tokens, positions, block_tables, **more):
        return model.apply(params, tokens, positions=positions, caches=pools,
                           block_tables=block_tables, **more)

    shape = S((sequences, tokens), jnp.int32)
    return call, (params, pools, shape, shape, (table, table) if windowed else table), more


TRACES = {
    # model kwargs -> the classes' first layers, in the order they are traced
    "dense, four layers: one class": (DENSE, [0]),
    "conv layers around an attention layer: two": (
        dict(DENSE, n_layers=5, layer_types=["conv", "conv", "full_attention", "conv", "conv"]),
        [0, 2]),
    "a dense layer ahead of the experts': two": (
        dict(DENSE, n_experts=8, n_experts_per_token=2, first_dense_layers=1, dense_ffn_dim=48),
        [0, 1]),
    "full attention without position, windows with: two": (SMALLTHINKER_KW, [0, 1]),
    "mamba layers around an attention layer: two": (MAMBA_KW, [0, 2]),
    # s6, window, s6, window | s6 that hands m up, full | gmu, cross: the layer that
    # hands its scan output up is a class of its own; lambda_init is an ARGUMENT
    # (a traced scalar), so the two window layers are one class
    "a decoder-hybrid-decoder of eight layers: six": (SAMBAY_KW, [0, 1, 4, 5, 6, 7]),
    "dense and routed conv layers, a routed attention layer: three": (
        CHUNK_HEAD_MODELS["state_layers"], [0, 2, 3]),
}


@pytest.mark.parametrize("case", TRACES)
@pytest.mark.parametrize("program", ["step", "chunk"])
def test_a_program_traces_the_block_once_a_class(block_traces, case, program):
    kwargs, classes = TRACES[case]
    model = get_model("transformer", **kwargs)
    cfg = model.cfg
    assert sorted({cfg.layer_class(i) for i in range(cfg.n_layers)}) == classes
    call, args, more = paged_call(model, *{"step": (3, 1), "chunk": (1, 8)}[program])
    _, other_args, other_more = paged_call(model, 2, 2)
    del block_traces[:]     # (the shapes came from an initialisation: the plain loop)
    text = jax.jit(call).lower(*args, **more).as_text()
    assert block_traces == classes
    assert len(BLOCK_CALL.findall(text)) == cfg.n_layers
    assert len(re.findall(r"func.func private @transformer_block(_\d+)?\(", text)) == len(classes)
    # a second program of other shapes traces each class once more, and no layer twice
    del block_traces[:]
    jax.jit(call).lower(*other_args, **other_more)
    assert block_traces == classes


def test_the_plain_loop_traced_a_block_a_layer(block_traces, monkeypatch):
    """What the count above was before: the fixture counts what it says."""
    monkeypatch.setattr(T, "SharedBlock", plain_block)
    model = get_model("transformer", **DENSE)
    call, args, more = paged_call(model, 3, 1)
    del block_traces[:]
    text = jax.jit(call).lower(*args, **more).as_text()
    # (each under its class's number, which is all a block reads of its own)
    assert block_traces == [0, 0, 0, 0] and not BLOCK_CALL.search(text)


def test_initialisation_keeps_the_plain_loop_and_the_tree_is_what_it_was(block_traces):
    model = get_model("transformer", **DENSE)
    tokens = jnp.zeros((1, 4), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    assert block_traces == [0, 1, 2, 3]
    assert sorted(variables["params"]) == [
        "layer_0", "layer_1", "layer_2", "layer_3", "lm_head", "norm", "tok_embeddings"]
    assert sorted(variables["params"]["layer_3"]) == [
        "attention", "attention_norm", "ffn", "ffn_norm"]


def both_ways(monkeypatch, fn):
    """``fn()`` through the shared block and through the plain loop."""
    T.transformer_block.clear_cache()
    shared = fn()
    with monkeypatch.context() as patch:
        patch.setattr(T, "SharedBlock", plain_block)
        plain = fn()
    return shared, plain


def assert_trees_equal(got, want):
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_sown_counters_come_through_the_call(block_traces, monkeypatch):
    """``mutable=["moe"]``: each MoE layer's ``choice``, ``tokens``, ``pairs``
    and ``tile_rows`` lie where ``TransformerBlock`` of that name sows them,
    with the plain loop's values, and ``moe_routing_stats`` reads them."""
    kwargs = dict(DENSE, n_experts=8, n_experts_per_token=2, first_dense_layers=1,
                  dense_ffn_dim=48)
    model = get_model("transformer", **kwargs)
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 96, size=(2, 6)))
    variables = model.init(jax.random.PRNGKey(2), tokens)
    del block_traces[:]

    def forward():
        return jax.jit(lambda v, t: model.apply(v, t, mutable=["moe"]))(variables, tokens)

    ((logits, _), sown), ((plain_logits, _), plain_sown) = both_ways(monkeypatch, forward)
    assert block_traces == [0, 1] + [0, 1, 1, 1]    # a trace a class, then the plain loop's four
    assert sorted(sown["moe"]) == ["layer_1", "layer_2", "layer_3"]
    assert sorted(sown["moe"]["layer_2"]["moe"]) == ["choice", "pairs", "tile_rows", "tokens"]
    assert_trees_equal(sown, plain_sown)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain_logits))
    moe_tokens, stats = T.moe_routing_stats(sown["moe"], model.cfg)
    assert int(moe_tokens.sum()) == 2 * 6 * 2 * 3 and int(stats[0]) == 12
    # a caller that does not ask for them gets none, and the same logits
    quiet, _ = jax.jit(lambda v, t: model.apply(v, t))(variables, tokens)
    np.testing.assert_array_equal(np.asarray(quiet), np.asarray(logits))


def test_a_layers_slice_of_the_lora_pool_comes_through_the_call(block_traces, monkeypatch):
    """Each layer is handed ITS factors ``[N, ...]`` out of the pool ``[N, L,
    ...]``: one trace, and the adapted logits are the plain loop's (layer 2's
    factors alone are not zero, so a slice of the wrong layer would show)."""
    model = get_model("transformer", **DENSE)
    cfg = model.cfg
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(1, 96, size=(3, 5)))
    variables = model.init(jax.random.PRNGKey(4), tokens)
    rank, n, d, hd = 4, 3, cfg.dim, cfg.n_heads * cfg.head_dim

    def factors(d_in, d_out):
        a = np.zeros((n, cfg.n_layers, d_in, rank), np.float32)
        b = np.zeros((n, cfg.n_layers, rank, d_out), np.float32)
        a[1:, 2], b[1:, 2] = rng.normal(size=a[1:, 2].shape), rng.normal(size=b[1:, 2].shape)
        return jnp.asarray(a), jnp.asarray(b)

    pool = {"wq": factors(d, hd), "wo": factors(hd, d), "w1": factors(d, cfg.ffn_dim),
            "w2": factors(cfg.ffn_dim, d), "w3": factors(d, cfg.ffn_dim),
            "scale": jnp.asarray([0.0, 0.5, 0.25], jnp.float32)}
    ids = jnp.asarray([0, 1, 2], jnp.int32)
    del block_traces[:]

    def forward():
        return jax.jit(lambda v, t, pool, ids: model.apply(
            v, t, adapters=pool, adapter_ids=ids)[0])(variables, tokens, pool, ids)

    adapted, plain = both_ways(monkeypatch, forward)
    assert block_traces == [0] + [0, 0, 0, 0]       # one trace, then the plain loop's four
    np.testing.assert_array_equal(np.asarray(adapted), np.asarray(plain))
    base = jax.jit(lambda v, t: model.apply(v, t)[0])(variables, tokens)
    np.testing.assert_array_equal(np.asarray(adapted[0]), np.asarray(base[0]))   # id 0: identity
    assert float(jnp.abs(adapted[1:] - base[1:]).max()) > 1e-3


# ------------------------------------------- (b) what a server serves, to the bit
KINDS = {
    "full_attention": CHUNK_HEAD_MODELS["dense_gqa"],
    "sliding_attention": SMALLTHINKER_KW,
    "conv": CHUNK_HEAD_MODELS["state_layers"],
    "linear_attention": WIDE_CHUNK_MODELS["delta_rule_state"],
    "linear_attention, norms on the branches": OLMO_HYBRID_KW,
    "mamba": MAMBA_KW,
    "s6, gmu, cross_attention": SAMBAY_KW,
    "latent attention": CHUNK_HEAD_MODELS["latent_moe"],
    "latent attention in residual streams": XING4,
}
# three chunks of 8, the last one 5 rows long: state and pages cross a chunk's
# edge, then five decode steps
PROMPT = np.random.default_rng(54).integers(1, 96, size=21).tolist()


@pytest.mark.parametrize("kind", KINDS)
def test_served_tokens_logits_and_caches_are_the_plain_loops_to_the_bit(monkeypatch, kind):
    def serve():
        server = LLMServer(model="transformer", model_kwargs=KINDS[kind], init_random=True,
                           max_new_tokens=8, len_buckets=(16,), eos_id=-1, seed=3,
                           temperature=0.0)
        server.load()

        async def go():
            b = ContinuousBatcher(server, max_slots=2, max_len=48, len_buckets=(8,),
                                  page_size=4, prefill_chunk=8)
            info = {"logits": []}
            out = await b.submit(PROMPT, 5, info=info)
            caches = jax.tree.map(np.asarray, b._caches)
            await b.close()
            return out, np.stack(info["logits"]), caches

        return asyncio.run(go())

    (out, logits, caches), (plain_out, plain_logits, plain_caches) = both_ways(monkeypatch, serve)
    assert out == plain_out and len(out) == 5
    np.testing.assert_array_equal(logits, plain_logits)
    assert_trees_equal(caches, plain_caches)


def test_in_bfloat16_the_cpu_compiler_rounds_where_its_fusions_fall(monkeypatch):
    """Not to the bit there: the CPU's compiler drops a bfloat16 round trip
    (f32 -> bf16 -> f32) where its fusions let it, program by program
    (tests/test_chunk_head.py), and the inlined calls reach its fusion pass in
    another order. The tokens are the plain loop's and the logits within a few
    bfloat16 roundings of values of 0.1-0.2; what the TPU's compiler makes of
    both is tests/test_tpu_program.py's and tools/hlolint's."""
    def forward():
        model = get_model("transformer", **dict(CHUNK_HEAD_MODELS["dense_gqa"], dtype="bfloat16"))
        tokens = jnp.asarray([PROMPT])
        variables = model.init(jax.random.PRNGKey(5), tokens)
        return jax.jit(lambda v, t: model.apply(v, t)[0])(variables, tokens)

    logits, plain = both_ways(monkeypatch, forward)
    np.testing.assert_array_equal(np.argmax(logits, -1), np.argmax(plain, -1))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(plain), atol=1e-2, rtol=0)


# ------------------------------------------- (c) the class
def test_a_layers_class_is_what_the_block_reads_of_its_number():
    def classes(**kwargs):
        cfg = get_model("transformer", **{**DENSE, **kwargs}).cfg
        return [cfg.layer_class(i) for i in range(cfg.n_layers)]

    assert classes(n_layers=6) == [0] * 6
    assert classes(layer_types=["conv", "full_attention", "conv", "full_attention"]) == [0, 1, 0, 1]
    # the window is the kind's; the rotary embedding is a layer's own
    assert classes(layer_types=["full_attention"] + ["sliding_attention"] * 3,
                   sliding_window=8) == [0, 1, 1, 1]
    assert classes(rope_layout=[0, 1, 1, 0]) == [0, 1, 1, 0]
    # an s6 layer that hands its scan output up is another block than one that does not
    sambay = get_model("transformer", **SAMBAY_KW).cfg
    assert [sambay.layer_class(i) for i in range(8)] == [0, 1, 0, 1, 4, 5, 6, 7]
    assert sambay.layer_reads(4).hands_up and not sambay.layer_reads(2).hands_up
    assert classes(layer_types=["full_attention"] * 2 + ["sliding_attention"] * 2,
                   sliding_window=8, rope_layout=[0, 1, 1, 0]) == [0, 1, 2, 3]
    # the FFN's kind: dense under first_dense_layers where the model routes experts
    moe = dict(n_experts=8, n_experts_per_token=2, dense_ffn_dim=48)
    assert classes(first_dense_layers=2, **moe) == [0, 0, 2, 2]
    assert classes(first_dense_layers=0, **moe) == [0, 0, 0, 0]
    # the MTP module's block stands behind the last layer and is its own caller's
    cfg = get_model("transformer", **XING4).cfg
    assert cfg.layer_kind(cfg.n_layers) == "full_attention"
