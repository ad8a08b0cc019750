"""hlolint self-tests: every contract kind proven to go RED on a mutated
fixture (drop a donation, insert a host callback, widen a KV dtype,
inflate a budget, add a collective), plus the waiver/baseline mechanics
and the CLI the CI gate relies on.

Fixtures are tiny synthetic jits — no model load — so everything here is
tier-1 except the full-registry run (marked slow; CI runs the real gate
as its own step anyway)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from functools import partial

import numpy as np
import pytest

from tools.hlolint.core import (
    Contract,
    apply_baseline,
    collective_counts_from_text,
    load_baseline,
    opcode_counts_from_text,
    run_contracts,
    save_budgets,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS = os.path.join(REPO, "tools", "hlolint", "budgets.json")


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def run_one(contract, **kw):
    reported, absorbed, waived, diff, measured = run_contracts([contract], **kw)
    return reported, absorbed, waived, diff, measured


def checks_of(findings):
    return [f.check for f in findings]


# ---------------------------------------------------------------------------
# alias: donation must survive into input_output_alias
# ---------------------------------------------------------------------------

def _build_donating(donate: bool):
    def build():
        import jax

        @partial(jax.jit, donate_argnums=(0,) if donate else ())
        def step(cache, tok):
            return cache.at[0].set(tok), tok + 1

        return step, (_sds((4, 8), "float32"), _sds((8,), "float32"))

    return build


def test_alias_dropped_donation_fires():
    """Mutation: remove donate_argnums from the decode step — the alias
    contract must go red."""
    c = Contract("fix.alias", "t", _build_donating(donate=False), donated=(0,))
    reported, *_ = run_one(c)
    assert checks_of(reported) == ["alias"]
    assert "input_output_alias" in reported[0].message


def test_alias_live_donation_is_clean():
    c = Contract("fix.alias", "t", _build_donating(donate=True), donated=(0,))
    reported, *_ = run_one(c)
    assert reported == []


def test_alias_degraded_donation_fires():
    """The reason this check reads COMPILED HLO instead of the source: the
    jit below DOES declare donate_argnums=(0, 1), but arg 0's buffer can
    alias no output (shape mismatch), so XLA silently drops it — an AST
    walk sees a donation, the compiled module shows a copy."""

    def build():
        import jax

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(small, big):
            return big.at[0].set(small)

        return step, (_sds((8,), "float32"), _sds((4, 8), "float32"))

    c = Contract("fix.alias2", "t", build, donated=(0, 1))
    reported, *_ = run_one(c)
    assert checks_of(reported) == ["alias"]
    assert reported[0].detail == "arg0"  # the big buffer's donation held


# ---------------------------------------------------------------------------
# transfer: no host round-trips inside the compiled hot function
# ---------------------------------------------------------------------------

def test_transfer_host_callback_fires():
    """Mutation: a jax.debug.print-style host callback inside the step."""

    def build():
        import jax

        @jax.jit
        def step(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        return step, (_sds((4,), "float32"),)

    c = Contract("fix.transfer", "t", build)
    reported, *_ = run_one(c)
    assert "transfer" in checks_of(reported)
    assert any("callback" in f.message for f in reported)


def test_opcode_parsing_sees_tuple_typed_instructions():
    """send/recv/infeed are ALWAYS tuple-typed in HLO text, and the
    all-reduce combiner can merge same-shape collectives into one
    tuple-shaped op — the instruction parser must not be blind to either
    (review regression: a single-shape-only regex silently passed every
    send/recv)."""
    hlo = "\n".join([
        "  %s = (f32[], u32[], token[]) send(f32[] %x, token[] %t), channel_id=1",
        "  %r = (f32[4]{0}, token[]) recv(token[] %t), channel_id=2",
        "  %i = (f32[2]{0}, token[]) infeed(token[] %t)",
        "  %ar = (f32[4]{0}, f32[4]{0}) all-reduce(f32[4]{0} %a, f32[4]{0} %b), to_apply=%add",
        "  ROOT %d = f32[4]{0} dot(f32[4]{0} %a, f32[4]{0} %b)",
    ])
    counts = opcode_counts_from_text(hlo)
    assert counts == {"send": 1, "recv": 1, "infeed": 1, "all-reduce": 1,
                      "dot": 1}
    assert collective_counts_from_text(hlo) == {"all-reduce": 1}


def test_transfer_pure_step_is_clean():
    def build():
        import jax

        return jax.jit(lambda x: x * 2), (_sds((4,), "float32"),)

    reported, *_ = run_one(Contract("fix.transfer", "t", build))
    assert reported == []


# ---------------------------------------------------------------------------
# dtype: forbidden signatures + output dtypes
# ---------------------------------------------------------------------------

def _build_kv_read(widen: bool):
    def build():
        import jax
        import jax.numpy as jnp

        @jax.jit
        def read(cache, q):
            kv = cache.astype(jnp.float32) if widen else cache
            return jnp.einsum("ld,d->l", kv, q.astype(kv.dtype))

        return read, (_sds((64, 16), "bfloat16"), _sds((16,), "bfloat16"))

    return build


KV_F32 = (r"tensor<64x16xf32>", "full-cache f32 materialization")


def test_dtype_widened_kv_fires():
    """Mutation: upcast the whole KV buffer to f32 before the read."""
    c = Contract("fix.dtype", "t", _build_kv_read(widen=True),
                 forbid_dtypes=(KV_F32,))
    reported, *_ = run_one(c)
    assert checks_of(reported) == ["dtype"]
    assert "forbidden dtype" in reported[0].message


def test_dtype_native_kv_read_is_clean():
    c = Contract("fix.dtype", "t", _build_kv_read(widen=False),
                 forbid_dtypes=(KV_F32,))
    reported, *_ = run_one(c)
    assert reported == []


def test_dtype_widened_output_fires():
    def build():
        import jax
        import jax.numpy as jnp

        # mutation: the final cast back to the model dtype was dropped
        return jax.jit(lambda x: (x.astype(jnp.float32) * 2.0)), (
            _sds((4, 8), "bfloat16"),)

    c = Contract("fix.outdtype", "t", build, out_dtypes=((0, "bf16"),))
    reported, *_ = run_one(c)
    assert checks_of(reported) == ["dtype"]
    assert "output 0 is f32" in reported[0].message


# ---------------------------------------------------------------------------
# shape: grouped-query attention never materializes K/V n_heads wide
# ---------------------------------------------------------------------------

def _result_sizes(text):
    """Element counts of every array type in StableHLO (``tensor<3x40x8xbf16>``)
    or HLO (``bf16[3,40,8]``) text."""
    import re

    sizes = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)\w+>", text):
        sizes.add(int(np.prod([int(d) for d in dims.split("x") if d])))
    for dims in re.findall(r"\b[a-z]+\d+\[([\d,]+)\]", text):
        sizes.add(int(np.prod([int(d) for d in dims.split(",")])))
    return sizes


def _lowered_and_compiled(fn, args):
    lowered = fn.lower(*args)
    return lowered.as_text(), lowered.compile().as_text()


# slots x cache length x n_heads x head_dim of the rep = 4 config below: a
# size no weight, pool, view or logits tensor of that config shares
GQA_SLOTS, GQA_PAGES, GQA_PAGE = 3, 5, 8
GQA_HEADS, GQA_KV_HEADS, GQA_HEAD_DIM = 8, 2, 8
GQA_EXPANDED = GQA_SLOTS * GQA_PAGES * GQA_PAGE * GQA_HEADS * GQA_HEAD_DIM


def test_expanded_kv_scan_sees_a_repeat():
    """Mutation: the pre-PR-24 chain (``jnp.repeat`` up to n_heads, then the
    n_heads einsum) — the scan the next test relies on must see its
    [slots, L, n_heads, hd] tensor in both texts."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def expanded(q, k):
        k = jnp.repeat(k, GQA_HEADS // GQA_KV_HEADS, axis=2)
        return jnp.einsum("bqhd,bkhd->bhqk", q, k)

    L = GQA_PAGES * GQA_PAGE
    texts = _lowered_and_compiled(expanded, (
        _sds((GQA_SLOTS, 1, GQA_HEADS, GQA_HEAD_DIM), "bfloat16"),
        _sds((GQA_SLOTS, L, GQA_KV_HEADS, GQA_HEAD_DIM), "bfloat16")))
    assert GQA_EXPANDED in _result_sizes(texts[0])
    assert GQA_EXPANDED in _result_sizes(texts[1])


def test_paged_decode_step_holds_no_expanded_kv():
    """The paged decode step of a rep = 4 config (Mistral-7B's ratio): no
    instruction, lowered or compiled, has slots x L x n_heads x hd elements
    — K/V stay n_kv_heads wide from the gather through both contractions
    (models/transformer.py ``grouped_query_attention``). On the chip the
    expanded copy was 54 ms of a 71 ms step (PERF.md, PR 24)."""
    import jax

    from seldon_core_tpu.models.cache import RESERVED_PAGES, init_paged_kv_caches
    from seldon_core_tpu.servers.llmserver import LLMServer

    s = LLMServer(
        model="transformer",
        model_kwargs=dict(vocab_size=96, dim=GQA_HEADS * GQA_HEAD_DIM,
                          n_layers=2, n_heads=GQA_HEADS,
                          n_kv_heads=GQA_KV_HEADS, ffn_dim=160,
                          max_seq_len=GQA_PAGES * GQA_PAGE, dtype="bfloat16"),
        init_random=True, len_buckets=(16,), seed=3)
    s.load()
    pools = jax.eval_shape(lambda: init_paged_kv_caches(
        s._cfg, RESERVED_PAGES + GQA_SLOTS * GQA_PAGES, GQA_PAGE, "bf16"))
    fn = s._get_decode_step_paged(GQA_SLOTS, GQA_PAGES, 1)
    lowered, compiled = _lowered_and_compiled(fn, (
        s._params, pools, _sds((GQA_SLOTS,), "int32"),
        _sds((GQA_SLOTS,), "int32"), _sds((GQA_SLOTS, 2), "uint32"),
        _sds((), "float32"), _sds((GQA_SLOTS, GQA_PAGES), "int32")))
    view = GQA_EXPANDED // (GQA_HEADS // GQA_KV_HEADS)
    for text in (lowered, compiled):
        sizes = _result_sizes(text)
        assert view in sizes          # the gathered view is there, unexpanded
        assert GQA_EXPANDED not in sizes


# ---------------------------------------------------------------------------
# sparse MoE: no dense form, no floating expert stack (ISSUE 25)
# ---------------------------------------------------------------------------

def _moe_contracts():
    from tools.hlolint.contracts import all_contracts

    return [c for c in all_contracts() if c.name.startswith("llm.moe_")]


@pytest.mark.parametrize("name", ["llm.moe_paged_decode_step_s4",
                                  "llm.moe_prefill_chunk_c8",
                                  "llm.moe_prefill_chunk_c16"])
def test_moe_step_programs_hold_no_dense_form_and_no_float_stack(name):
    """The paged decode step and chunk of a small OLMoE shape with int8
    weights, lowered for a TPU: no result shaped [rows, n_experts,
    expert_width], no floating copy of an expert stack; the pool donated, no
    transfer. (Lowered for the CPU, ``ragged_dot`` IS a dense masked
    expansion: that is jax's fallback where the tests run, not the chip's.)"""
    (contract,) = [c for c in _moe_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []


@pytest.mark.parametrize("name", ["llm.mla_paged_decode_step_s4",
                                  "llm.mla_prefill_chunk_c8",
                                  "llm.mla_prefill_chunk_c32"])
def test_latent_step_programs_never_expand_the_cached_view(name):
    """DeepSeek-V2's block at test dims (ISSUE 29): the absorbed read leaves
    no floating [view rows, heads, head width] array, the MoE promises hold
    for its routed experts beside the shared ones, the latent pool is
    donated, no transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []


@pytest.mark.parametrize("name", ["llm.hybrid_paged_decode_step_s4",
                                  "llm.hybrid_prefill_chunk_c8"])
def test_hybrid_step_programs_donate_the_state_and_keep_the_pool_flat(name):
    """LFM2's block at test dims (ISSUE 35): the conv layers' per-slot state
    blocks are donated and aliased with the page pools (a leaf that is not
    shows as an alias finding), never widened whole to float32; the pool of
    64-wide heads stays flat rows; the MoE promises hold; no transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []


@pytest.mark.parametrize("name", ["llm.gdn_paged_decode_step_s4",
                                  "llm.gdn_prefill_chunk_c8"])
def test_linear_attention_step_programs_donate_both_state_arrays(name):
    """Qwen3-Next's block at test dims (ISSUE 38): a linear-attention layer's
    conv rows AND its float32 matrix state are donated and aliased with the
    page pools (a leaf that is not shows as an alias finding); S is never
    narrowed to 16 bits; no floating copy of an expert stack, held or whole;
    the MoE promises hold; no transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []


@pytest.mark.parametrize("name", ["llm.gdn_rect_paged_decode_step_s4",
                                  "llm.gdn_rect_prefill_chunk_c8"])
def test_a_state_that_is_not_square_stays_float32_in_the_caches_layout(name):
    """Olmo-Hybrid's block at test dims (ISSUE 45): 6 heads of [32, 64] held
    two side by side along the lanes, [slots, 3, 32, 128]; both state arrays
    are donated and aliased with the page pool; S is never narrowed to 16 bits
    in either layout, and no program holds every slot's S a head a row (the
    step's kernel reads it as it lies; the chunk unpacks ONE slot's); no
    transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []
    if "decode" in name:    # the step carries the kernel, not the expression
        fn, args = contract.build()
        assert "gated_delta_step" in fn.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("name", ["llm.ssd_paged_decode_step_s4",
                                  "llm.ssd_prefill_chunk_c8"])
def test_mamba_step_programs_donate_the_state_and_the_step_passes_over_it_once(name):
    """granite-4.0-h's block at test dims (ISSUE 53): 8 heads of [64, 128]
    float32 a slot, held transposed two side by side along the lanes,
    [slots, 4, 128, 128]: whole (8, 128) tiles; the conv rows and h are donated and
    aliased with the page pool; h is never narrowed to 16 bits; the step lowered
    for a TPU carries the kernel and NO XLA op over every slot's h (the SAME
    contract read from the module lowered for the CPU, where the expression
    serves, reports its passes: the scan sees them when they are there); no
    transfer."""
    import dataclasses

    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []
    if "decode" in name:
        fn, args = contract.build()
        assert "ssd_step" in fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        here = dataclasses.replace(contract, lowering_platform=None)
        reported, *_ = run_one(here, checks=("dtype",))
        assert len(reported) == 1 and "the expression's further pass" in reported[0].message


@pytest.mark.parametrize("name", ["llm.swa_paged_decode_step_s4",
                                  "llm.swa_prefill_chunk_c64"])
def test_window_layer_step_programs_donate_both_page_classes_and_hold_no_view(name):
    """SmallThinker's block at dims the live-page kernel takes (ISSUE 49): the
    pools of BOTH page classes are donated and aliased (a leaf that is not shows
    as an alias finding); lowered for a TPU neither the full layer's read nor
    the window layer's holds an array of a whole block-table view's shape (both
    walk their live pages with the one kernel); the MoE promises hold with the
    router fed the block's input; no transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []
    fn, args = contract.build()
    assert fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text().count(
        "gqa_page_attention") >= 2


@pytest.mark.parametrize("name", ["llm.sambay_paged_decode_step_s4",
                                  "llm.sambay_prefill_chunk_c64"])
def test_decoder_hybrid_decoder_step_programs_read_the_shared_pool_in_place(name):
    """Phi-4-mini-flash's plan at 8 layers and dims the live-page kernel takes
    (ISSUE 55): every leaf of the tree (ONE full page class entry, two window-class
    entries, three state blocks) is donated and aliased; lowered for a TPU the full
    layer's read, the window layers' and the cross layer's read of the SAME pool
    each walk the live pages (no array of a whole block-table
    view's shape: nothing is copied or gathered); h is float32; the chunk's scan is
    the repo's kernel, once an s6 layer; no transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []
    fn, args = contract.build()
    text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    # (a read's jitted function is traced once a shape: the window layers', the
    # full layer's and the cross layer's are two or three)
    assert text.count("gqa_page_attention") >= 2
    assert ("s6_chunk_scan" in text) == ("chunk" in name)


@pytest.mark.parametrize("name", ["llm.xing4_paged_decode_step_s4",
                                  "llm.xing4_prefill_chunk_c8"])
def test_stream_step_programs_keep_the_streams_in_the_models_dtype(name):
    """Xing4.0's block at test dims (ISSUE 31): no float32 [.., streams, dim]
    array in the lowered module (the mixing widens one stream at a time), and
    the latent and MoE promises hold under the hyper-connections; the pool is
    donated, no transfer."""
    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == name]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype",
                                             "collective"))
    assert reported == []


def test_the_live_page_read_holds_no_gathered_view_for_a_tpu():
    """ISSUE 32: at dims the kernel of ops/latent_attention.py takes, the step
    lowered for a TPU has no floating array of the logical view's shape; the
    SAME contract read from the module lowered for the CPU, where the
    expression over the gathered view serves, reports it: the scan sees the
    view when it is there."""
    import dataclasses

    from tools.hlolint.contracts import all_contracts

    (contract,) = [c for c in all_contracts() if c.name == "llm.mla_live_page_read_s4"]
    reported, *_ = run_one(contract, checks=("alias", "transfer", "dtype", "collective"))
    assert reported == []
    here = dataclasses.replace(contract, lowering_platform=None)
    reported, *_ = run_one(here, checks=("dtype",))
    assert len(reported) == 1 and "gathered into a copy of the whole logical view" in reported[0].message


def test_stream_scan_sees_a_widened_stream_when_it_is_there():
    from tools.hlolint.contracts import HC_FLOAT32_STREAMS, HC_STREAMS, MOE_DIM, SLOTS

    def build():
        import jax
        import jax.numpy as jnp

        def widened(X, h):   # the write-back as one float32 einsum over the whole stream
            return jnp.einsum("bsij,bsjc->bsic", h, X.astype(jnp.float32)).astype(X.dtype)

        return jax.jit(widened), (
            _sds((SLOTS, 1, HC_STREAMS, MOE_DIM), "bfloat16"),
            _sds((SLOTS, 1, HC_STREAMS, HC_STREAMS), "float32"))

    contract = Contract(name="test.hc_widened", description="float32 streams",
                        build=build, forbid_dtypes=(HC_FLOAT32_STREAMS,),
                        lowering_platform="tpu")
    reported, *_ = run_one(contract, checks=("dtype",))
    assert len(reported) == 1 and "float32 [.., streams, dim]" in reported[0].message


def test_latent_scan_sees_expanded_keys_when_they_are_there():
    """The expanded formulation at the contract's dims: per-head keys made
    from the gathered view are named by the scan."""
    from tools.hlolint.contracts import (
        LATENT_ROW, MLA_EXPANDED_KV, MLA_HEADS, MLA_LATENT, MLA_NOPE,
        PAGE_SIZE, PAGES_PER_SLOT, SLOTS)

    def build():
        import jax
        import jax.numpy as jnp

        def expanded(q_nope, rows, w_uk):
            k_nope = jnp.einsum("blc,hnc->blhn", rows[..., :MLA_LATENT], w_uk)
            return jnp.einsum("bshn,blhn->bhsl", q_nope, k_nope)

        view = PAGES_PER_SLOT * PAGE_SIZE
        return jax.jit(expanded), (
            _sds((SLOTS, 1, MLA_HEADS, MLA_NOPE), "bfloat16"),
            _sds((SLOTS, view, LATENT_ROW), "bfloat16"),
            _sds((MLA_HEADS, MLA_NOPE, MLA_LATENT), "bfloat16"))

    contract = Contract(name="test.mla_expanded", description="expanded keys",
                        build=build, forbid_dtypes=(MLA_EXPANDED_KV,),
                        lowering_platform="tpu")
    reported, *_ = run_one(contract, checks=("dtype",))
    assert len(reported) == 1 and "expanded into per-head keys" in reported[0].message


def _build_dense_moe(dequantized_stack: bool):
    """The formulation the sparse FFN replaced, at the MoE contract's dims."""
    def build():
        import jax
        import jax.numpy as jnp

        from tools.hlolint.contracts import (
            MOE_DIM, MOE_EXPERTS, MOE_WIDTH, SLOTS)

        def dense(x, q, scale, gates):
            if dequantized_stack:   # dequantize_params' habit, on a stack
                w1 = q.astype(jnp.bfloat16) * scale[:, None, :].astype(jnp.bfloat16)
                return jax.lax.ragged_dot(
                    x[:, 0], w1, jnp.full((MOE_EXPERTS,), 0, jnp.int32).at[0].set(SLOTS))
            h = jnp.einsum("bsd,edf->bsef", x, q.astype(jnp.bfloat16))
            return jnp.einsum("bsef,bse->bsf", h, gates)

        return jax.jit(dense), (
            _sds((SLOTS, 1, MOE_DIM), "bfloat16"),
            _sds((MOE_EXPERTS, MOE_DIM, MOE_WIDTH), "int8"),
            _sds((MOE_EXPERTS, MOE_WIDTH), "float32"),
            _sds((SLOTS, 1, MOE_EXPERTS), "bfloat16"))

    return build


@pytest.mark.parametrize("dequantized_stack", [False, True])
def test_moe_scan_sees_the_dense_form_when_it_is_there(dequantized_stack):
    """The twin: the same two signatures fire on the dense einsum and on a
    dequantized stack, so their silence above is not blindness."""
    from tools.hlolint.contracts import MOE_DENSE_FORM, MOE_FLOAT_STACK

    c = Contract("fix.moe", "t", _build_dense_moe(dequantized_stack),
                 forbid_dtypes=(MOE_DENSE_FORM, MOE_FLOAT_STACK),
                 lowering_platform="tpu")
    reported, *_ = run_one(c)
    assert set(checks_of(reported)) == {"dtype"}
    wanted = MOE_FLOAT_STACK if dequantized_stack else MOE_DENSE_FORM
    assert wanted[0] in {f.detail for f in reported}


# ---------------------------------------------------------------------------
# collective: exact count-per-kind budget
# ---------------------------------------------------------------------------

def _build_permute():
    def build():
        import jax
        import numpy as _np
        from jax.sharding import Mesh, PartitionSpec as P

        from seldon_core_tpu.parallel.compat import shard_map

        mesh = Mesh(_np.array(jax.devices()[:8]), ("x",))
        perm = [(i, (i + 1) % 8) for i in range(8)]
        fn = shard_map(lambda a: jax.lax.ppermute(a, "x", perm),
                       mesh=mesh, in_specs=(P("x"),), out_specs=P("x"))
        return jax.jit(fn), (_sds((8, 4), "float32"),)

    return build


def test_collective_unbudgeted_fires(eight_devices):
    """Mutation: a permute appears where the contract budgets none — the
    'stray reshard in the decode step' class."""
    c = Contract("fix.coll", "t", _build_permute(), collectives={})
    reported, *_ = run_one(c)
    assert checks_of(reported) == ["collective"]
    assert "collective-permute" in reported[0].detail


def test_collective_exact_budget_is_clean(eight_devices):
    c = Contract("fix.coll", "t", _build_permute(),
                 collectives={"collective-permute": 1})
    reported, *_ = run_one(c)
    assert reported == []


def test_collective_missing_also_fires(eight_devices):
    """The budget is exact in both directions: a vanished collective means
    the compiled program is not the one the contract describes."""

    def build():
        import jax

        return jax.jit(lambda x: x + 1), (_sds((8, 4), "float32"),)

    c = Contract("fix.coll", "t", build,
                 collectives={"collective-permute": 1})
    reported, *_ = run_one(c)
    assert checks_of(reported) == ["collective"]
    assert "missing" in reported[0].message


# ---------------------------------------------------------------------------
# cost: tolerance band around the committed budget
# ---------------------------------------------------------------------------

def _cost_contract():
    def build():
        import jax

        return jax.jit(lambda a, b: a @ b), (
            _sds((32, 32), "float32"), _sds((32, 32), "float32"))

    return Contract("fix.cost", "t", build, cost=True)


def test_cost_missing_budget_fires():
    reported, *_ = run_one(_cost_contract(), budgets={"entries": {}})
    assert checks_of(reported) == ["cost"]
    assert reported[0].detail == "missing-budget"


def test_cost_inflated_budget_fires_then_rebaseline_clears(tmp_path):
    """Mutation: the compiled cost drifts far past the committed budget ->
    red; --update-budgets writes the measured snapshot -> green."""
    budgets = {"tolerance": 0.2,
               "entries": {"fix.cost": {"flops": 1.0, "bytes_accessed": 1.0}}}
    reported, _, _, diff, measured = run_one(_cost_contract(), budgets=budgets)
    assert sorted(f.detail for f in reported) == ["bytes_accessed", "flops"]
    assert "fix.cost" in diff and diff["fix.cost"]["flops"]["budget"] == 1.0

    path = str(tmp_path / "budgets.json")
    save_budgets(path, measured, previous=budgets)
    rebased = json.loads(open(path).read())
    assert rebased["tolerance"] == 0.2  # survives re-baseline
    reported2, *_ = run_one(_cost_contract(), budgets=rebased)
    assert reported2 == []


# ---------------------------------------------------------------------------
# waiver + baseline mechanics
# ---------------------------------------------------------------------------

def test_waiver_with_reason_suppresses_and_empty_reason_fires():
    c = Contract("fix.alias", "t", _build_donating(donate=False), donated=(0,),
                 waivers={"alias:arg0": "known CPU-only fixture"})
    reported, _, waived, *_ = run_one(c)
    assert reported == [] and len(waived) == 1

    c2 = Contract("fix.alias", "t", _build_donating(donate=False), donated=(0,),
                  waivers={"alias:arg0": "   "})
    reported2, *_ = run_one(c2)
    assert "bad-waiver" in checks_of(reported2)
    assert "alias" in checks_of(reported2)  # empty reason does NOT suppress


def test_baseline_absorbs_by_fingerprint_and_dies_with_the_detail():
    c = Contract("fix.alias", "t", _build_donating(donate=False), donated=(0,))
    reported, *_ = run_one(c)
    fp = reported[0].fingerprint()
    baseline = {fp: {"fingerprint": fp, "reason": "grandfathered", "count": 1}}
    reported2, absorbed, *_ = run_one(c, baseline=baseline)
    assert reported2 == [] and len(absorbed) == 1
    # a different detail (another contract name) must NOT be absorbed
    c3 = Contract("fix.alias_v2", "t", _build_donating(donate=False), donated=(0,))
    reported3, absorbed3, *_ = run_one(c3, baseline=baseline)
    assert len(reported3) == 1 and absorbed3 == []


def test_baseline_without_reason_is_rejected(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"entries": [{"fingerprint": "abc", "reason": ""}]}))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(p))


def test_build_error_is_a_finding_not_a_crash():
    def build():
        raise RuntimeError("model too big for this host")

    reported, *_ = run_one(Contract("fix.broken", "t", build))
    assert checks_of(reported) == ["build-error"]
    # meta findings can never be baselined away
    fp = reported[0].fingerprint()
    still, absorbed = apply_baseline(
        reported, {fp: {"fingerprint": fp, "reason": "nope", "count": 1}})
    assert len(still) == 1 and absorbed == []


# ---------------------------------------------------------------------------
# the committed registry artifacts + CLI
# ---------------------------------------------------------------------------

def test_budgets_json_covers_every_cost_contract():
    from tools.hlolint.contracts import all_contracts

    budgets = json.loads(open(BUDGETS).read())
    entries = budgets.get("entries", {})
    for c in all_contracts():
        if c.cost:
            assert c.name in entries, (
                f"{c.name} has cost=True but no committed budget — run "
                "--update-budgets and commit the reviewed snapshot")
            assert entries[c.name].get("flops", 0) > 0


def test_registry_waivers_all_carry_reasons():
    from tools.hlolint.contracts import all_contracts

    for c in all_contracts():
        for key, reason in c.waivers.items():
            assert str(reason).strip(), f"{c.name} waiver {key!r} has no reason"


def cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.hlolint", *args],
        capture_output=True, text=True, cwd=REPO)


def test_cli_list_and_usage_errors():
    res = cli("--list")
    assert res.returncode == 0
    assert "llm.paged_decode_step_s4" in res.stdout
    assert cli("--contracts", "no.such.contract").returncode == 2
    assert cli("--checks", "no-such-check").returncode == 2
    assert cli("no/such/path").returncode == 2


def test_cli_single_cheap_contract_enforcing():
    """The ring-attention contract end-to-end through the CLI (no model load:
    this is the fast smoke of the real gate; CI runs the full registry)."""
    res = cli("--contracts", "ops.ring_attention_seq8", "--format", "json")
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout)
    assert payload["findings"] == []
    assert "ops.ring_attention_seq8" in payload["budget_diff"]


@pytest.mark.slow
def test_full_registry_is_green():
    """The CI gate, in-process: every committed contract holds on the real
    tree with the committed budgets."""
    from tools.hlolint.contracts import all_contracts
    from tools.hlolint.core import load_budgets

    reported, absorbed, waived, diff, _ = run_contracts(
        all_contracts(), budgets=load_budgets(BUDGETS))
    assert reported == [], "\n".join(f.render() for f in reported)
    # the enforcement is real: the registry carries a reasoned waiver
    # (the TP sampling all-gathers) that absorbs an actual finding
    assert waived, "expected the decode_scan_tp2 all-gather waiver to fire"
