"""The sampler's candidates in two exact stages (ISSUE 56 tentpole).

``_top_k_candidates`` (servers/llmserver.py) is what every emitted token is
chosen among: the batcher's decode step, the speculative verify, the first
token and generate() all call it. Over a served vocabulary it names the ``k``
blocks of 128 columns with the largest maxima and runs ``lax.top_k`` over
those blocks' columns alone. The contract held here: values, indices and
order are ``jax.lax.top_k(lg, k)``'s BIT FOR BIT, ties included, and ``greedy``
is ``jnp.argmax``'s answer, on both sides of the rule's threshold, at
vocabularies that are and are not whole blocks. CPU; what the form costs is a
chip's to say (benchmarks/sampler_topk_bench.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.servers import llmserver
from seldon_core_tpu.servers.llmserver import (
    TOPK_BLOCK, LLMServer, _batch_sampler, _slot_sampler, _top_k_candidates,
    sampler_topk_columns)

K = 40
# 256: the test models' (direct); 10,240 / 10,368: the threshold's two sides;
# 37,984: Qwen3-Next's held rows, no whole number of blocks
VOCABS = (256, 10240, 10368, 32000, 37984, 50304, 131072)
ROWS = (1, 3, 32)

candidates = jax.jit(lambda lg: _top_k_candidates(lg, K))
reference = jax.jit(lambda lg: (jnp.argmax(lg, axis=-1), *jax.lax.top_k(lg, K)))


def spread(rng, vocab: int, n: int) -> np.ndarray:
    """``n`` distinct columns, one a stride of the row: over more than 40
    blocks wherever the row has that many."""
    stride = vocab // n
    return np.arange(n) * stride + rng.integers(0, stride, n)


def logits(kind: str, rows: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(rows * 1_000_003 + vocab)
    lg = rng.standard_normal((rows, vocab)).astype(np.float32)
    if kind == "maximum repeated in several blocks":
        for row in lg:
            row[spread(rng, vocab, 12)] = 9.0
    elif kind == "more than 40 columns equal to the 40th value":
        for row in lg:                    # 10 above, 100 AT the 40th value
            at = spread(rng, vocab, 110)
            row[at[::11]] = 9.0
            row[np.delete(at, np.s_[::11])] = 7.0
    elif kind == "constant":
        lg[:] = 0.25
    elif kind == "-inf but for fewer than 40 columns":
        finite = spread(rng, vocab, 7)
        kept = lg[:, finite]
        lg[:] = -np.inf
        lg[:, finite] = kept
    elif kind == "rounded to bf16":
        lg = np.asarray(jnp.asarray(lg).astype(jnp.bfloat16).astype(jnp.float32))
    elif kind == "zeros of both signs":
        # lax.top_k's order is the bit patterns' total order: +0.0 above -0.0
        zero = rng.random((rows, vocab)) < 0.01
        lg = np.where(zero, np.where(rng.random((rows, vocab)) < 0.3, 0.0, -0.0),
                      -1.0 - np.abs(lg)).astype(np.float32)
        lg[:, 1], lg[:, 200] = -0.0, 0.0    # (every row has both, -0.0 first)
    else:
        assert kind == "random"
    return lg


KINDS = ("random", "maximum repeated in several blocks",
         "more than 40 columns equal to the 40th value", "constant",
         "-inf but for fewer than 40 columns", "rounded to bf16")


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("vocab", VOCABS)
@pytest.mark.parametrize("kind", KINDS)
def test_the_candidates_are_lax_top_ks_bit_for_bit(kind, vocab, rows):
    lg = logits(kind, rows, vocab)
    want_greedy, want_values, want_indices = reference(lg)
    greedy, values, indices = candidates(lg)
    assert indices.dtype == want_indices.dtype and values.dtype == want_values.dtype
    np.testing.assert_array_equal(np.asarray(indices), np.asarray(want_indices))
    # bit for bit: equal as bit patterns, not as numbers
    np.testing.assert_array_equal(np.asarray(values).view(np.int32),
                                  np.asarray(want_values).view(np.int32))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(want_greedy))
    assert greedy.dtype == want_greedy.dtype


@pytest.mark.parametrize("vocab", (256, 10368, 37984))
def test_zeros_of_both_signs_order_as_lax_top_k_orders_them(vocab):
    """The block maxima are taken on ``lax.top_k``'s own order, in which +0.0
    lies above -0.0 (a float maximum may answer either): a block whose one
    +0.0 hides behind a -0.0 is still named. ``greedy`` is the first
    candidate, the first +0.0, where ``jnp.argmax`` takes the first zero of
    either sign."""
    lg = logits("zeros of both signs", 3, vocab)
    _, want_values, want_indices = reference(lg)
    greedy, values, indices = candidates(lg)
    np.testing.assert_array_equal(np.asarray(indices), np.asarray(want_indices))
    np.testing.assert_array_equal(np.asarray(values).view(np.int32),
                                  np.asarray(want_values).view(np.int32))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(want_indices)[:, 0])
    assert not np.signbit(np.asarray(values)[:, 0]).any()


def top_k_operands(fn, *args) -> list:
    """The operand shapes of every ``top_k`` in ``fn``'s jaxpr, in order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "top_k":
                found.append(tuple(eqn.invars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("vocab,top_k,columns", [
    (256, 40, 256),                 # the test models': direct
    (10240, 40, 10240),             # the threshold itself: direct
    (10368, 40, 5120),              # one block past it: blocked
    (32000, 40, 5120), (131072, 40, 5120), (200064, 40, 5120),
    (37984, 40, 5216),              # 296 whole blocks + 96 columns behind them
    (32000, 200, 32000),            # a top_k near vocab / 128: direct
    (32000, 124, 124 * 128), (96, 200, 96)])
def test_the_rule_reads_the_calls_static_shape_alone(vocab, top_k, columns):
    """Blocked where ``vocab > 2 x k x TOPK_BLOCK``, and the LAST ``top_k`` of
    the trace runs over what `sampler_topk_columns` says (the gauge's value)."""
    assert TOPK_BLOCK == 128
    assert sampler_topk_columns(vocab, top_k) == columns
    lg = jax.ShapeDtypeStruct((3, vocab), jnp.float32)
    operands = top_k_operands(lambda x: _top_k_candidates(x, top_k), lg)
    assert operands[-1] == (3, columns)
    if columns == vocab:
        assert operands == [(3, vocab)]
    else:       # the block maxima, then the chosen blocks' columns
        assert operands == [(3, vocab // TOPK_BLOCK), (3, columns)]


@pytest.mark.parametrize("sampler", ["slot", "batch"])
def test_both_samplers_draw_from_the_one_definition_under_the_two_scopes(sampler, monkeypatch):
    """`_slot_sampler` and `_batch_sampler` call `_top_k_candidates` (one
    definition for every emitted token), the candidates under
    ``sample.topk`` and the split / categorical / choice under
    ``sample.draw``: what the trace's HLO carries and ``sampler_step_ms``
    reads."""
    calls = []
    real = llmserver._top_k_candidates
    monkeypatch.setattr(llmserver, "_top_k_candidates",
                        lambda lg, top_k: calls.append(lg.shape) or real(lg, top_k))
    lg = jnp.asarray(logits("random", 4, 10368))
    temperature = jnp.float32(0.7)
    if sampler == "slot":
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4))
        lowered = jax.jit(_slot_sampler(K)).lower(keys, lg, temperature)
    else:
        lowered = jax.jit(_batch_sampler(K)).lower(lg, jax.random.PRNGKey(0), temperature)
    assert calls == [(4, 10368)]
    text = lowered.as_text(debug_info=True)
    ops = {scope: [line for line in text.splitlines() if f"{scope}/" in line]
           for scope in ("sample.topk", "sample.draw")}
    assert any("top_k" in line for line in ops["sample.topk"])
    assert not any("top_k" in line for line in ops["sample.draw"])
    assert ops["sample.draw"]


def test_the_gauge_says_which_form_a_built_step_program_holds():
    """``seldon_llm_sampler_topk_columns{program}``: nothing before a program
    is built, then the columns its last TopK runs over (here the direct
    form's vocabulary: the rule's other side is
    `test_the_rule_reads_the_calls_static_shape_alone`'s)."""
    from seldon_core_tpu.metrics.registry import MetricsRegistry

    server = LLMServer(
        model="transformer", init_random=True, len_buckets=(8,), batch_buckets=(1,),
        model_kwargs=dict(vocab_size=96, dim=32, n_layers=1, n_heads=2, n_kv_heads=2,
                          ffn_dim=64, max_seq_len=32))
    server.load()
    assert server.llm_stats()["sampler_topk_columns"] == {}
    server._get_first_token()
    server._get_decode_step_paged(2, 4, 1)
    assert server.llm_stats()["sampler_topk_columns"] == {"first_token": 96, "decode_step": 96}
    registry = MetricsRegistry(deployment="d", predictor="p")
    registry.sync_llm(server)
    lines = [line for line in registry.expose().decode().splitlines()
             if line.startswith("seldon_llm_sampler_topk_columns{")]
    assert sorted((line.split('program="')[1].split('"')[0], line.rsplit(" ", 1)[1])
                  for line in lines) == [("decode_step", "96.0"), ("first_token", "96.0")]
