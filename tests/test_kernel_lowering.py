"""Which Pallas kernels the compiled serving path can reach on a TPU, and
that each of them lowers for one — checked from the CPU with
``jax.export(platforms=["tpu"])`` at the 7B serving shapes, so a kernel that
Mosaic's front end refuses is caught on every push instead of on the first
chip run. (What only the chip can say — that Mosaic then compiles and runs
the kernel, and how close it lands to the reference — is in PERF.md.)

Also the regression for the old probe gates: the implementation choice is a
host fact, identical inside and outside a ``jax.jit`` trace, and a compile
error on a TPU raises instead of serving an XLA reference.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import export

from seldon_core_tpu import ops
from seldon_core_tpu.models import get_model
from seldon_core_tpu.models.cache import init_paged_kv_caches
from seldon_core_tpu.models.transformer import transformer_block
from seldon_core_tpu.ops.grouped_matmul import (SUB_BLOCK, grouped_matmul, make_visits,
                                                 row_tile)
from seldon_core_tpu.ops.gqa_attention import gqa_page_attention, gqa_plan
from seldon_core_tpu.ops.latent_attention import (ExpandedWalk, expanded_walk,
                                                  latent_expanded_attention,
                                                  latent_page_attention, plan)
from seldon_core_tpu.ops.pallas_int8 import int8_matmul
from seldon_core_tpu.ops.sinkhorn import sinkhorn

S = jax.ShapeDtypeStruct
MOSAIC_CALL = "tpu_custom_call"  # how a lowered Pallas TPU kernel appears
# a layer's call of its class's block (models/transformer.py ``transformer_block``)
BLOCK_CALL = "call @transformer_block("


def tpu_mlir(fn, *specs) -> str:
    return export.export(jax.jit(fn), platforms=["tpu"])(*specs).mlir_module()


@pytest.mark.parametrize("rows,dim,width,experts,tile", [
    (32 * 8, 2048, 1024, 64, 64), (128 * 8, 2048, 1024, 64, 64),     # OLMoE: step, chunks
    (256 * 8, 2048, 1024, 64, 64),
    (8 * 6, 2048, 1408, 64, 16), (128 * 6, 2048, 1408, 64, 64),      # DeepSeek-V2-Lite
    (256 * 6, 2048, 1408, 64, 64),
    # the 1,024-row chunks whose mean group is over 64 rows: the only served calls
    # that walk the 128-row tile, whose visits multiply a run of its 32-row blocks
    # (an aligned dynamic slice of the rows' sublanes)
    (1024 * 6, 2048, 1408, 64, 128),    # DeepSeek-V2-Lite
    (1024 * 4, 2048, 1792, 32, 128),    # LFM2
    (1024 * 6, 2560, 768, 64, 128)])    # SmallThinker
def test_grouped_matmul_lowers_for_tpu(rows, dim, width, experts, tile):
    """the routed experts' two orientations (gate / up, down) at the served
    shapes, int8 stacks and per-expert scales, at the rule's row tile"""
    assert row_tile(rows, experts) == tile

    def swiglu(x, sizes, w1, s1, w2, s2):
        visits = make_visits(sizes, rows, tile)
        h = grouped_matmul(x, w1, visits, s1, interpret=False)
        return grouped_matmul(h.astype(x.dtype), w2, visits, s2, interpret=False)

    text = tpu_mlir(
        swiglu, S((rows, dim), jnp.bfloat16), S((experts,), jnp.int32),
        S((experts, dim, width), jnp.int8), S((experts, width), jnp.float32),
        S((experts, width, dim), jnp.int8), S((experts, dim), jnp.float32))
    assert text.count(MOSAIC_CALL) == 2


@pytest.mark.parametrize("tile", [16, 32, 64, 128])
def test_a_tile_of_64_rows_or_fewer_keeps_the_one_product_over_its_rows(tile):
    """The kernel's traced body: up to 64 rows a visit is ONE ``dot_general``
    over the whole row block, no index of it computed (what every decode step
    and narrow chunk had before the 128-row tile got its blocks: their
    programs did not change); at 128 one product a length of the run, over an
    aligned slice that starts where the visit's first block does."""
    def call(x, sizes, w, s):
        return grouped_matmul(x, w, make_visits(sizes, 512, tile), s, interpret=False)

    jaxpr = jax.make_jaxpr(call)(S((512, 128), jnp.bfloat16), S((4,), jnp.int32),
                                 S((4, 128, 128), jnp.int8), S((4, 128), jnp.float32))
    (kernel,) = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    body = str(kernel.params["jaxpr"])
    if tile <= 64:
        assert body.count("dot_general[") == 1 and "multiple_of" not in body
        assert f"bf16[{tile},128] <-" in body and "+" not in body   # (a dynamic slice prints as a:a+n)
    else:
        assert body.count("dot_general[") == tile // SUB_BLOCK and "multiple_of" in body
        assert all(f"bf16[{n * SUB_BLOCK},128] <-" in body for n in range(1, tile // SUB_BLOCK + 1))


@pytest.mark.parametrize("rows,tile", [(64 * 10, 32), (256 * 10, 64)])
def test_grouped_matmul_lowers_for_tpu_over_a_share_of_the_experts(rows, tile):
    """Qwen3-Next's step and chunk: ``tokens x 10`` sorted rows of which the
    pairs of the 128 HELD experts come first, the tile of the mean group over
    all 512 the router chooses among, the kernel's zeros behind the last held
    group; a [2048, 512] int8 block is 1 MB."""
    assert row_tile(rows, 512) == tile

    def swiglu(x, sizes, w1, s1, w2, s2):
        visits = make_visits(sizes, rows, row_tile(rows, 512))
        h = grouped_matmul(x, w1, visits, s1, interpret=False)
        return grouped_matmul(h.astype(x.dtype), w2, visits, s2, interpret=False)

    text = tpu_mlir(
        swiglu, S((rows, 2048), jnp.bfloat16), S((128,), jnp.int32),
        S((128, 2048, 512), jnp.int8), S((128, 512), jnp.float32),
        S((128, 512, 2048), jnp.int8), S((128, 2048), jnp.float32))
    assert text.count(MOSAIC_CALL) == 2


@pytest.mark.parametrize("tokens", [(32, 1), (1, 256), (1, 4096)])
def test_sinkhorn_lowers_for_tpu(tokens):
    """the hyper-connections' Sinkhorn chain at a decode step's rows, a
    chunk's, and a cache-less forward's over a whole 4,096-token slot"""
    text = tpu_mlir(lambda m: sinkhorn(m, 20, 1e-6, interpret=False),
                    S((4, 4) + tokens, jnp.float32))
    assert text.count(MOSAIC_CALL) == 1


def test_the_sinkhorn_chain_reaches_the_kernel_on_a_tpu_and_the_loop_elsewhere():
    """``HyperConnection`` chooses as ``MoEFFN`` does: one kernel a sub-layer
    in a program lowered for a TPU, none in any other. (The layers of a class
    call ONE lowering of their block, ``transformer_block``: what is counted
    in a program's text is a block's.)"""
    model = get_model("llama-tiny", dtype="bfloat16", hc_mult=4)
    tokens = jnp.zeros((2, 4), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    forward = jax.jit(lambda params, tokens: model.apply(params, tokens)[0])
    on_tpu = tpu_mlir(forward, params, tokens)
    assert on_tpu.count(MOSAIC_CALL) == 2 and on_tpu.count(BLOCK_CALL) == model.cfg.n_layers
    assert MOSAIC_CALL not in forward.lower(params, tokens).as_text()


def test_the_routed_experts_reach_the_kernel_on_a_tpu_and_ragged_dot_elsewhere():
    """``MoEFFN`` chooses by the platform the program is LOWERED for, not the
    process's backend (tools/hlolint lowers for a TPU from a CPU process)."""
    model = get_model("llama-tiny", dtype="bfloat16", n_experts=8, n_experts_per_token=2)
    tokens = jnp.zeros((2, 4), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    forward = jax.jit(lambda params, tokens: model.apply(params, tokens)[0])
    on_tpu = tpu_mlir(forward, params, tokens)
    # (three in the ONE block the layers call)
    assert on_tpu.count(MOSAIC_CALL) == 3 and on_tpu.count(BLOCK_CALL) == model.cfg.n_layers
    assert MOSAIC_CALL not in forward.lower(params, tokens).as_text()


@pytest.mark.parametrize("slots,tokens,heads,pages", [
    (8, 1, 16, 256), (1, 256, 16, 256), (32, 1, 32, 64), (1, 256, 32, 64), (8, 3, 16, 256)])
def test_latent_page_attention_lowers_for_tpu(slots, tokens, heads, pages):
    """The live-page read of latent attention at the two latent cells' step
    and chunk (DeepSeek-V2-Lite: 8 slots x 16,384 rows, 16 heads; Xing4: 32 x
    4,096, 32 heads) and at a speculative verify of two drafts: the 640-wide
    row as the pool holds it, sixteen 64-row pages a visit."""
    walk = plan(tokens, heads, pages, 64, 640, 512)
    pool_pages = 2 + 2048
    text = tpu_mlir(
        lambda q, pool, pos, tables, positions: latent_page_attention(
            q, pool, pos, tables, positions, 0.1, 512, walk, interpret=False),
        S((slots, tokens, heads, 640), jnp.bfloat16), S((pool_pages, 64, 640), jnp.bfloat16),
        S((pool_pages, 64), jnp.int32), S((slots, pages), jnp.int32), S((slots, tokens), jnp.int32))
    assert text.count(MOSAIC_CALL) == 1


@pytest.mark.parametrize("tokens,heads,pages,walk", [
    (1024, 16, 256, ExpandedWalk(8, 1024)), (1024, 32, 64, ExpandedWalk(8, 512))])
def test_latent_expanded_attention_lowers_for_tpu(tokens, heads, pages, walk):
    """The expanded-once read of a wide chunk at the two latent cells' shapes:
    DeepSeek-V2-Lite's 1,024 tokens x 16 heads as ONE tile, Xing4's 32 heads as
    the fallback's two tiles of 512; the projection's rows, W_UK / W_UV and the
    640-wide pool as they are held, eight 64-row pages a visit."""
    assert expanded_walk(tokens, heads, 128, 64, 128, 512, pages, 64, 640) == walk
    pool_pages = 2 + 2048
    text = tpu_mlir(
        lambda q_nope, q_rope, w_uk, w_uv, pool, pos, tables, positions: latent_expanded_attention(
            q_nope, q_rope, w_uk, w_uv, pool, pos, tables, positions, 0.1, walk, interpret=False),
        S((1, tokens, heads, 128), jnp.bfloat16), S((1, tokens, heads, 64), jnp.bfloat16),
        S((heads, 128, 512), jnp.bfloat16), S((heads, 512, 128), jnp.bfloat16),
        S((pool_pages, 64, 640), jnp.bfloat16), S((pool_pages, 64), jnp.int32),
        S((1, pages), jnp.int32), S((1, tokens), jnp.int32))
    assert text.count(MOSAIC_CALL) == 1


@pytest.mark.parametrize("slots,tokens,heads,kv_heads,head_dim,pages", [
    (32, 1, 32, 8, 128, 16), (8, 1, 32, 8, 128, 64), (32, 1, 16, 16, 128, 16),
    (32, 1, 32, 8, 64, 64), (8, 4, 32, 8, 128, 64), (8, 1, 32, 32, 128, 17),
    (64, 1, 16, 2, 256, 128),
    (1, 256, 32, 8, 128, 64), (1, 128, 32, 8, 128, 16), (1, 256, 32, 8, 128, 16),
    (1, 256, 16, 16, 128, 16), (1, 256, 32, 8, 64, 64), (1, 256, 16, 2, 256, 128)])
def test_gqa_page_attention_lowers_for_tpu(slots, tokens, heads, kv_heads, head_dim, pages):
    """The live-page read of grouped-query attention at the GQA cells' steps
    (Mistral 32 slots x 1,024 rows and 8 x 4,096; OLMoE 32 x 1,024, 16 heads of
    their own; LFM2 32 x 4,096, heads of 64), at a speculative verify of three
    drafts, at Llama-2-7B's 32 KV heads (chip_smoke.py: a 4,096-wide row,
    half as many rows a visit), and at Qwen3-Next's step (64 slots x 8,192
    rows of 2 KV heads of 256, eight query heads a group), and at the prefill
    chunks of the same four configurations (Mistral's three: 256 tokens into a
    4,096-row slot, 128 and 256 into a 1,024-row one), a lane block a KV head:
    K and V rows as the pools hold them."""
    walk = gqa_plan(tokens, heads, kv_heads, head_dim, pages, 64)
    row = kv_heads * head_dim
    assert walk.pages * 64 * row * 4 <= 8 << 20
    pool_pages = 2 + slots * pages
    text = tpu_mlir(
        lambda q, k, v, pos, tables, positions: gqa_page_attention(
            q, k, v, pos, tables, positions, kv_heads, walk, interpret=False),
        S((slots, tokens, heads, head_dim), jnp.bfloat16), S((pool_pages, 64, row), jnp.bfloat16),
        S((pool_pages, 64, row), jnp.bfloat16), S((pool_pages, 64), jnp.int32),
        S((slots, pages), jnp.int32), S((slots, tokens), jnp.int32))
    assert text.count(MOSAIC_CALL) == 1


def test_the_gqa_read_reaches_the_kernel_on_a_tpu_and_the_expression_elsewhere():
    """``Attention`` chooses as ``LatentAttention`` does, for the bf16 paged
    pool: one kernel a layer in a step lowered for a TPU, none in any other
    lowering, none over the int8 pool, the dense cache or without a cache, none
    for a row that is no whole lane tile; a chunk's query rows take it too."""
    kwargs = dict(vocab_size=256, dim=256, n_layers=2, ffn_dim=128, max_seq_len=128,
                  dtype="bfloat16", n_heads=16, n_kv_heads=8)

    def lowerings(tokens_a_call=1, kv_cache_dtype="bf16", **more):
        model = get_model("transformer", **{**kwargs, **more})
        tokens = jnp.zeros((2, tokens_a_call), jnp.int32)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        pools = jax.eval_shape(lambda: init_paged_kv_caches(model.cfg, 6, 64, kv_cache_dtype))

        def step(params, pools, tokens, positions, block_tables):
            return model.apply(params, tokens, positions=positions, caches=pools,
                               block_tables=block_tables)

        args = (params, pools, S(tokens.shape, jnp.int32), S(tokens.shape, jnp.int32),
                S((2, 2), jnp.int32))
        plain = jax.jit(lambda params, tokens: model.apply(params, tokens)[0])
        return (model.cfg, tpu_mlir(step, *args), jax.jit(step).lower(*args).as_text(),
                tpu_mlir(plain, params, tokens))

    # (the block is a jitted function of its own, and so are the read that
    # chooses the walk and the walk: the layers call ONE lowering of all three)
    cfg, on_tpu, elsewhere, no_cache = lowerings()
    assert on_tpu.count(MOSAIC_CALL) == 1
    assert on_tpu.count(BLOCK_CALL) == cfg.n_layers
    assert on_tpu.count("call @paged_live_read(") == 1
    assert on_tpu.count("call @_walk_pages(") == 1
    assert MOSAIC_CALL not in elsewhere and MOSAIC_CALL not in no_cache
    for tokens_a_call in (4, 32):    # a verify, a chunk
        text = lowerings(tokens_a_call=tokens_a_call)[1]
        assert text.count(BLOCK_CALL) == cfg.n_layers and text.count("call @paged_live_read(") == 1
    assert MOSAIC_CALL not in lowerings(kv_cache_dtype="int8")[1]
    assert MOSAIC_CALL not in lowerings(n_kv_heads=4, dim=192, n_heads=16)[1]   # 4 x 12 = 48


def test_a_chunk_program_traces_the_walk_once_and_a_page_operand_is_no_head(monkeypatch):
    """What a chunk program costs to START is a count of traces: ``pallas_call``
    traces the kernel and one index map a block operand wherever it is called,
    and no compile cache serves that (PERF.md section 6, PRs 36 and 41: ~1-2 s
    of every warm start a program on the chip's host). So a two-layer model's
    chunk lowered for a TPU traces ``_walk_pages`` ONCE (the walk is a jitted
    function of its own; its layers call one lowering of it), and the kernel's
    page operands are ``plan.pages`` of the K pool and as many of the V pool,
    whatever the number of KV heads: the head blocks are a loop inside a visit
    of whole rows, its body traced once. A change that turns heads into
    operands, or un-jits the walk, fails here and not on
    ``mistral7b-chat-short``'s ``setup_s``."""
    import functools
    import re

    from seldon_core_tpu.models.transformer import paged_read_walk
    from seldon_core_tpu.ops import page_walk

    model = get_model("transformer", vocab_size=256, dim=512, n_layers=2, ffn_dim=128,
                      max_seq_len=1024, dtype="bfloat16", n_heads=16, n_kv_heads=4, head_dim=128)
    cfg = model.cfg
    chunk, n_pages, pool_pages = 64, 16, 34
    walk = paged_read_walk(cfg, chunk, n_pages, 64, jnp.bfloat16)
    assert walk == page_walk.Plan(pages=2, q_tile=256, blocks=4)
    traces = []
    real = page_walk._walk_pages

    @functools.wraps(real)    # (the lowering is named after the function)
    def counted(*args, **kwargs):
        traces.append(kwargs["walk"])
        return real(*args, **kwargs)

    monkeypatch.setattr(page_walk, "_walk_pages", counted)
    page_walk._jitted_walk.cache_clear()
    transformer_block.clear_cache()     # (a block traced before holds its walk's trace)
    try:
        tokens = jnp.zeros((1, chunk), jnp.int32)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        pools = jax.eval_shape(lambda: init_paged_kv_caches(cfg, pool_pages, 64, "bf16"))
        text = tpu_mlir(
            lambda params, pools, tokens, positions, block_tables: model.apply(
                params, tokens, positions=positions, caches=pools, block_tables=block_tables),
            params, pools, S(tokens.shape, jnp.int32), S(tokens.shape, jnp.int32),
            S((1, n_pages), jnp.int32))
    finally:
        page_walk._jitted_walk.cache_clear()
        transformer_block.clear_cache()
    assert traces == [walk]
    assert text.count(BLOCK_CALL) == cfg.n_layers
    assert text.count("call @paged_live_read(") == 1 and text.count("call @_walk_pages(") == 1
    kernels = [line for line in text.splitlines() if MOSAIC_CALL in line and "custom_call" in line]
    assert len(kernels) == 1
    operands = re.search(r"\} : \((.*)\) -> ", kernels[0]).group(1)
    pool = f"tensor<{pool_pages}x64x{cfg.n_kv_heads * cfg.head_dim}xbf16>"
    assert operands.count(pool) == 2 * walk.pages
    # and nothing else of the call grows with the visit: the grid's length, seven
    # scalar-prefetch operands, the queries, their positions and the cached positions
    assert operands.count("tensor<") == 2 * walk.pages + 11


def test_the_latent_read_reaches_the_kernel_on_a_tpu_and_the_expression_elsewhere():
    """``LatentAttention`` chooses as ``MoEFFN`` does, for the paged pool: one
    kernel a layer in a program lowered for a TPU, none in any other lowering,
    none over the dense cache or without one, none for a call shape the
    kernel does not take (a latent part that is no whole lane tile)."""
    kwargs = dict(vocab_size=256, dim=64, n_layers=2, ffn_dim=128, max_seq_len=128,
                  dtype="bfloat16", n_heads=16, n_kv_heads=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=16, v_head_dim=16)
    tokens = jnp.zeros((2, 1), jnp.int32)

    def lowerings(**more):
        model = get_model("transformer", **{**kwargs, "kv_lora_rank": 128, **more})
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
        pools = jax.eval_shape(lambda: init_paged_kv_caches(model.cfg, 6, 64))

        def step(params, pools, tokens, positions, block_tables):
            return model.apply(params, tokens, positions=positions, caches=pools,
                               block_tables=block_tables)

        args = (params, pools, S((2, 1), jnp.int32), S((2, 1), jnp.int32), S((2, 2), jnp.int32))
        plain = jax.jit(lambda params, tokens: model.apply(params, tokens)[0])
        return (model.cfg, tpu_mlir(step, *args), jax.jit(step).lower(*args).as_text(),
                tpu_mlir(plain, params, tokens))

    # (the block is a jitted function of its own: the layers call ONE lowering of it)
    cfg, on_tpu, elsewhere, no_cache = lowerings()
    assert on_tpu.count(MOSAIC_CALL) == 1
    assert on_tpu.count(BLOCK_CALL) == cfg.n_layers and on_tpu.count("call @_walk_pages(") == 1
    assert MOSAIC_CALL not in elsewhere and MOSAIC_CALL not in no_cache
    assert MOSAIC_CALL not in lowerings(kv_lora_rank=96)[1]


def test_a_wide_latent_chunk_traces_the_expanded_read_once_and_the_rest_stay_absorbed(monkeypatch):
    """A chunk of 1,024 tokens over latent attention at DeepSeek-V2's widths
    lowered for a TPU holds the expanded-once kernel, ONE lowering of it that
    its layers call (``_read_expanded`` is a jitted function of its own, as
    the walk is: a trace a layer is start-up time no compile cache serves), fed
    W_UK / W_UV and eight page operands of the pool; the walk is not in it, and
    no W_UV product stands behind the kernel. The decode step and the 256-token
    chunk of the same model hold the absorbed walk and nothing of the other."""
    import functools
    import re

    from seldon_core_tpu.ops import latent_attention

    model = get_model("transformer", vocab_size=256, dim=256, n_layers=2, ffn_dim=128,
                      max_seq_len=2048, dtype="bfloat16", n_heads=16, n_kv_heads=16,
                      kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    cfg = model.cfg
    n_pages, pool_pages = 32, 66
    traces = []
    real = latent_attention._read_expanded

    @functools.wraps(real)    # (the lowering is named after the function)
    def counted(*args, **kwargs):
        traces.append(kwargs["walk"])
        return real(*args, **kwargs)

    monkeypatch.setattr(latent_attention, "_read_expanded", counted)
    latent_attention._jitted_expanded.cache_clear()
    transformer_block.clear_cache()

    def lowered(sequences, tokens):
        shape = (sequences, tokens)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
        pools = jax.eval_shape(lambda: init_paged_kv_caches(cfg, pool_pages, 64, "bf16"))
        return tpu_mlir(
            lambda params, pools, tokens, positions, block_tables: model.apply(
                params, tokens, positions=positions, caches=pools, block_tables=block_tables),
            params, pools, S(shape, jnp.int32), S(shape, jnp.int32), S((sequences, n_pages), jnp.int32))

    try:
        wide, narrow, step = lowered(1, 1024), lowered(1, 256), lowered(8, 1)
    finally:
        latent_attention._jitted_expanded.cache_clear()
        transformer_block.clear_cache()
    assert traces == [ExpandedWalk(8, 1024)]
    assert wide.count(BLOCK_CALL) == cfg.n_layers
    assert wide.count("call @_read_expanded(") == 1 and "call @_walk_pages(" not in wide
    kernels = [line for line in wide.splitlines() if MOSAIC_CALL in line and "custom_call" in line]
    assert len(kernels) == 1
    operands = re.search(r"\} : \((.*)\) -> ", kernels[0]).group(1)
    assert operands.count(f"tensor<{pool_pages}x64x640xbf16>") == 8
    assert operands.count("tensor<16x128x512xbf16>") == 2             # W_UK, and W_UV a head's [dv, dc]
    assert operands.count("tensor<1x1024x4096xbf16>") == 1            # a token's heads, 256 lanes each
    assert re.search(r"-> tensor<1x1024x2048xbf16>", kernels[0])      # ready for wo
    # and nothing else of the call grows with the visit: the grid's length, seven
    # scalar-prefetch operands, the queries' positions and the cached positions
    assert operands.count("tensor<") == 8 + 3 + 10
    for other in (narrow, step):
        assert other.count(BLOCK_CALL) == cfg.n_layers
        assert other.count("call @_walk_pages(") == 1 and other.count(MOSAIC_CALL) == 1
        assert "call @_read_expanded(" not in other


def test_paged_decode_reaches_no_pallas_kernel_by_default():
    """At toy dims (4 heads: query rows under a sublane tile) one paged decode
    step of the tiny transformer, lowered for a TPU, reads through the XLA
    gather."""
    model = get_model("llama-tiny", dtype="bfloat16")
    cfg = model.cfg
    tokens = jnp.zeros((2, 1), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    pools = jax.eval_shape(lambda: init_paged_kv_caches(cfg, 6, 8))

    def step(params, pools, tokens, positions, block_tables):
        return model.apply(params, tokens, positions=positions, caches=pools,
                           block_tables=block_tables)

    assert MOSAIC_CALL not in tpu_mlir(step, params, pools, S((2, 1), jnp.int32),
                                       S((2, 1), jnp.int32), S((2, 2), jnp.int32))


def test_kernel_choice_is_the_same_inside_and_outside_a_trace():
    """The old gate probed with concrete arrays and np.asarray, so its first
    call from inside jit raised TracerArrayConversionError, which it cached
    as "no kernel" for the life of the process."""
    outside = ops.pallas_interpret_default()
    seen = []

    @jax.jit
    def traced(x):
        seen.append(ops.pallas_interpret_default())
        return x

    traced(jnp.zeros(()))
    assert seen == [outside] and outside is True  # CPU here: interpreter


def test_compile_error_on_a_tpu_raises(monkeypatch):
    """On a (monkey-patched) TPU platform the kernel is handed to the
    compiler; this backend cannot compile it, and that error must come
    out — not the XLA expression the deleted gates used to return."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.ones((8, 128), jnp.float32)
    w = jnp.ones((128,), jnp.float32)
    q = jnp.ones((128, 128), jnp.int8)
    with pytest.raises(ValueError, match="interpret mode"):
        int8_matmul(x, q, w)
    with pytest.raises(ValueError, match="interpret mode"):
        jax.jit(int8_matmul)(x, q, w)
