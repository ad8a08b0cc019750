"""What the TPU compiler makes of the batcher's step programs, read without a
chip: libtpu describes a v5e (``jax.experimental.topologies``), the step
program is lowered for ShapeDtypeStructs placed on that device, and the
compiled module's text carries the op names a device trace would show
(PERF.md section 3, "Reading a program's ops without a chip").

Held here, first: no program writes a TRANSPOSED floating copy of a weight. The
q/k/v projections fuse with the head split and the rotary, and that fusion
reads the weight output-major; held ``[in, out]`` the dequantized weight was
copied into that order every layer of every step (``copy bf16[4096,4096]`` and
two ``copy bf16[1024,4096]``: 1.74 ms of an 18.75 ms Mistral step, PERF.md
section 6, PR 28). ops/quantize.py holds those leaves ``out_major``.

Second (PR 30): the routed experts of OLMoE and DeepSeek-V2-Lite run the repo's
own grouped-matmul kernel (ops/grouped_matmul.py), three calls an MoE layer, on
the int8 stacks as they are held; XLA's own ``ragged-dot-none`` kernel (a 256-row
tile, 40.6 ms of a 61.9 ms DeepSeek chunk) is in no step program.

Third (PR 34): no step or chunk program writes a floating copy of the embedding
table. Dequantized ahead of the token lookup, the whole ``[vocab, dim]`` table
was converted and written out in bf16 to read 32 rows of it
(``multiply_convert_fusion bf16[131072,3584]``: 2.4 ms of a 9 ms Xing4.0 step,
booked as "the head's copy" until the operands were read here); the table
reaches the module int8 and the int8 rows are gathered (ops/quantize.py).

Fourth (PRs 32, 36, 41): the paged pool's attention read of a decode step and of
a prefill chunk walks each sequence's live pages with the repo's kernel
(ops/page_walk.py), fed the pool as it is held; no step or chunk program holds a
gathered copy of the logical view.

Fifth (PR 42): a prefill chunk's K / V (latent) rows and their positions reach
the pool as whole pages, a handful of page-sized windows a scatter, in place;
the scatter fed one index pair a token (``fusion bf16[514,64,1024]`` +
``fusion s32[514,64]``: 11.8 % of a rerank chunk in PR 41's traces) is in no
chunk program, and the step's one-row writes are what they were.

Sixth (PR 47): a prefill chunk runs the head for the ONE row
its caller reads, inside a conditional on that row's index: a chunk that is
not a prompt's last reads no byte of the head, and none writes ``[1, chunk,
vocab]`` logits.

Seventh (PR 48): the WIDE chunk program (``WIDE_PREFILL_CHUNK`` rows, which a
long prompt's chunks are while no other slot streams) is held to all of the
above at DeepSeek-V2-Lite's, LFM2's, Qwen3-Next's and SmallThinker's cell shapes
and, since PR 54 (every model runs it), at Mistral's docs cell's, and the head's
conditional at Mistral's vocabulary: the same tests take it as one more case.
1,024 rows since PR 50.

Eighth (PR 54): the layers of a class call ONE lowering of their block
(models/transformer.py ``transformer_block``), which the compiler inlines: a
compiled program holds no ``call``, and a layer's ops carry the scope path of
their own call (``block_scope``).

Ninth (PR 56): the sampler's TopK runs over the columns of the blocks its
first stage names, not over the vocabulary. XLA's ``TopK`` custom call is bound
by neither bytes nor FLOPs and grows with its columns (``custom-call
f32[32,40]`` over ``[32, 32000]``: as long as the head's whole weight stream in
a chat step, ledger PR 55); at Mistral's vocabulary the step's one TopK reads
``[32, 5120]``, and a test model's 256 columns keep the direct form.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from seldon_core_tpu.runtime.batcher import WIDE_PREFILL_CHUNK as WIDE

FLOATS = ("bf16", "f16", "f32")
# one layer at the published widths; the vocabulary is not what is looked at
MISTRAL = dict(vocab_size=256, dim=4096, n_layers=1, n_heads=32, n_kv_heads=8,
               ffn_dim=14336, max_seq_len=1024, dtype="bfloat16")
# OLMoE's attention (16 = 16 heads, QK-norm between projection and split);
# its expert FFN has no part in this
OLMOE_ATTENTION = dict(vocab_size=256, dim=2048, n_layers=1, n_heads=16, n_kv_heads=16,
                       ffn_dim=1024, max_seq_len=1024, dtype="bfloat16", qk_norm=True)
# DeepSeek-V2-Lite's two kinds of layer (the dense leading one, one MoE layer
# with its shared experts) around latent attention, published widths
DEEPSEEK = dict(vocab_size=256, dim=2048, n_layers=2, n_heads=16, n_kv_heads=16, ffn_dim=1408,
                max_seq_len=1024, dtype="bfloat16", n_experts=64, n_experts_per_token=6,
                router_renormalize=False, first_dense_layers=1, dense_ffn_dim=10944,
                n_shared_experts=2, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128, norm_eps=1e-6,
                rope_scaling={"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
                              "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707})
# ... and one whole OLMoE layer: 64 experts of width 1024, 8 a token
OLMOE = dict(OLMOE_ATTENTION, n_experts=64, n_experts_per_token=8, router_renormalize=False)
# Xing4.0-29B-A4B's two kinds of layer at published widths: compressed-query
# latent attention, 64 sigmoid-routed experts top-4 + 1 shared, four residual
# streams mixed around every sub-layer
XING4 = dict(vocab_size=256, dim=3584, n_layers=2, n_heads=32, n_kv_heads=32, ffn_dim=1024,
             max_seq_len=4096, dtype="bfloat16", n_experts=64, n_experts_per_token=4,
             router_renormalize=True, routed_scaling_factor=2.0, router_score="sigmoid",
             router_bias=True, first_dense_layers=1, dense_ffn_dim=9216, n_shared_experts=1,
             kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128, qk_rope_head_dim=64,
             v_head_dim=128, norm_eps=1e-6, hc_mult=4,
             rope_scaling={"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
                           "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})
# ... and Mistral's own vocabulary for what IS looked at there: a table of a few
# thousand rows is prefetched whole into on-chip memory and shows nothing
MISTRAL_VOCAB = dict(MISTRAL, vocab_size=32000)
# LFM2-8B-A1B's kinds of layer at published widths: a dense conv layer, an MoE
# conv layer (32 sigmoid-routed experts top-4), an MoE attention layer (GQA,
# heads of 64, a norm per head)
LFM2 = dict(vocab_size=256, dim=2048, n_layers=4, n_heads=32, n_kv_heads=8, ffn_dim=1792,
            max_seq_len=4096, dtype="bfloat16", n_experts=32, n_experts_per_token=4,
            router_renormalize=True, router_renormalize_eps=1e-6, router_score="sigmoid",
            router_bias=True, first_dense_layers=2, dense_ffn_dim=7168, qk_norm="head",
            norm_eps=1e-5, rope_theta=1e6,
            layer_types=("conv", "conv", "full_attention", "conv"))
# Qwen3-Next-80B-A3B's period at published widths: three Gated DeltaNet layers
# (16 key / 32 value heads of 128, four taps over 8,192 channels) and one gated
# GQA layer (16 query / 2 KV heads of 256, rotary over the first 64), every layer
# 512 softmax-routed experts top-10 of which 128 are HELD here, one gated shared
QWEN3NEXT = dict(vocab_size=256, dim=2048, n_layers=4, n_heads=16, n_kv_heads=2, head_dim=256,
                 ffn_dim=512, max_seq_len=8192, dtype="bfloat16", n_experts=512,
                 n_experts_per_token=10, experts_first=0, experts_held=128,
                 router_renormalize=True, n_shared_experts=1, shared_expert_gate=True,
                 qk_norm="head", attn_gate=True, partial_rotary_factor=0.25, norm_eps=1e-6,
                 rope_theta=1e7, linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128, linear_conv_kernel_dim=4,
                 layer_types=("linear_attention",) * 3 + ("full_attention",))
# Olmo-Hybrid-7B's period at published widths: three Gated DeltaNet layers (30
# heads, a state [96, 192] a head, beta in (0, 2)) and one multi-head attention
# layer (30 heads of 128, no rotary embedding), the norms on the branches, a
# dense FFN of 11,008
OLMOHYBRID = dict(vocab_size=256, dim=3840, n_layers=4, n_heads=30, n_kv_heads=30, head_dim=128,
                  ffn_dim=11008, max_seq_len=1024, dtype="bfloat16", qk_norm=True, norm_eps=1e-6,
                  rope_theta=None, norm_placement="branch", linear_allow_neg_eigval=True,
                  linear_dt_bias="range", linear_num_key_heads=30, linear_num_value_heads=30,
                  linear_key_head_dim=96, linear_value_head_dim=192, linear_conv_kernel_dim=4,
                  layer_types=("linear_attention",) * 3 + ("full_attention",))
# SmallThinker-21BA3B's period at published widths: one full-attention layer
# without position and three sliding-attention layers (window 4,096, rotary), 28
# query / 4 KV heads of 128, 64 ReGLU experts of 768 top-6, the router fed the
# block's input
SMALLTHINKER = dict(vocab_size=256, dim=2560, n_layers=4, n_heads=28, n_kv_heads=4, head_dim=128,
                    ffn_dim=768, max_seq_len=16384, dtype="bfloat16", n_experts=64,
                    n_experts_per_token=6, router_renormalize=True, norm_eps=1e-6,
                    rope_theta=1.5e6, rope_layout=(0, 1, 1, 1), sliding_window=4096,
                    layer_types=("full_attention",) + ("sliding_attention",) * 3,
                    ffn_act="relu", router_input="layer_input")
# granite-4.0-h-micro's kinds of layer at published widths: three Mamba-2 layers
# (64 heads of 64, a float32 state [64, 128] a head, one group, four taps + a
# bias over 4,352 channels) and one GQA layer (32 query / 8 KV heads of 64, no
# position, softmax scale 1/64), the four scalar multipliers, the table tied
GRANITE4H = dict(vocab_size=256, dim=2048, n_layers=4, n_heads=32, n_kv_heads=8, ffn_dim=8192,
                 max_seq_len=2048, dtype="bfloat16", norm_eps=1e-5, rope_theta=None,
                 tie_embeddings=True, mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
                 embedding_multiplier=12.0, attention_multiplier=0.015625,
                 residual_multiplier=0.22, logits_scaling=8.0,
                 layer_types=("mamba", "mamba", "full_attention", "mamba"))
# Phi-4-mini-flash-reasoning's plan at 8 layers and published widths (every kind
# of layer once or twice: s6, window, s6, window | s6 handing m up, full = the
# shared pool | gmu, cross): Mamba-1 over 5,120 channels with a float32 [16,
# 5120] state, differential attention 40 query / 20 KV heads of 64 (20 pairs over
# 10 groups: reads of 40 heads over 10 KV heads of 128), window 512, LayerNorm,
# biases, a gated FFN of 10,240, no position, the table tied
PHI4FLASH = dict(vocab_size=256, dim=2560, n_layers=8, n_heads=40, n_kv_heads=20, head_dim=64,
                 ffn_dim=10240, max_seq_len=16384, dtype="bfloat16", norm_eps=1e-5,
                 rope_theta=None, tie_embeddings=True, sliding_window=512,
                 layer_types=("s6", "sliding_attention", "s6", "sliding_attention", "s6",
                              "full_attention", "gmu", "cross_attention"),
                 mamba_d_inner=5120, mamba_d_state=16, mamba_dt_rank=160, mamba_d_conv=4,
                 memory_source=4, kv_source=5, differential=True, attention_bias=True,
                 norm="layer")
CONFIGS = {"phi4flash": PHI4FLASH, "granite4h": GRANITE4H, "smallthinker": SMALLTHINKER, "olmohybrid": OLMOHYBRID, "mistral": MISTRAL, "olmoe": OLMOE_ATTENTION, "olmoe_moe": OLMOE, "deepseek": DEEPSEEK,
           "xing4": XING4, "mistral_vocab": MISTRAL_VOCAB, "lfm2": LFM2, "qwen3next": QWEN3NEXT}
PAGE, POOL_PAGES = 64, 514


@pytest.fixture(scope="module")
def v5e():
    """A described v5e chip as a sharding: nothing runs on it, programs
    compile for it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1", chip_config_name="default",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as exc:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"libtpu cannot describe a v5e here: {type(exc).__name__}: {exc}")
    return SingleDeviceSharding(topology.devices[0])


def _served(model_kwargs):
    """The int8 tree as the 7B cells hold it (the streamed init), one layer.
    Only its shapes, dtypes and orientation are looked at, so the weights are
    zeros: drawing 218 M normals would hold every core for seconds while the
    other workers' timing-sensitive tests run."""
    import seldon_core_tpu.servers.llmserver as llmserver_mod
    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(model="transformer", model_kwargs=model_kwargs, init_random=True,
                       quantize="int8", kv_cache_dtype="bf16", len_buckets=(16,))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(llmserver_mod, "STREAM_INIT_THRESHOLD_BYTES", 0)
        # (a function of the key, or XLA folds the constant through the quantizer)
        patch.setattr(jax.random, "normal", lambda key, shape, dtype=jnp.float32:
                      jnp.broadcast_to(jnp.ravel(key)[0].astype(dtype) * 0, shape))
        server.load()
    return server


@pytest.fixture(scope="module")
def servers():
    made = {}

    def get(name):
        if name not in made:
            made[name] = _served(CONFIGS[name])
        return made[name]

    return get


def compiled(server, program: str, sharding, slots: int = 32, length: int = 0, chunk: int = 256):
    """The batcher's step program compiled for the described chip. By default
    the chat cell's step (32 slots x 1024 tokens) and the docs cell's chunk
    (256 tokens into a 4096-token slot) over a pool of POOL_PAGES; with
    ``length``, ``slots`` slots of that many tokens over a fully provisioned pool."""
    # (several tests read one program: compiled once a module)
    if program == "wide_chunk":
        program, chunk = "prefill_chunk", WIDE
    key = (id(server), program, slots, length, chunk)
    if key not in _COMPILED:
        _COMPILED[key] = _compile(server, program, sharding, slots, length, chunk)
    return _COMPILED[key]


_COMPILED = {}


def _compile(server, program, sharding, slots, length, chunk):
    from seldon_core_tpu.models.cache import init_paged_kv_caches

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    def abstract(tree):   # (a shape that is placed already keeps its placement)
        return jax.tree.map(lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
                            else sds(x.shape, x.dtype), tree)

    pages = (length or (1024 if program == "decode_step" else 4096)) // PAGE
    params = abstract(server._params)
    # a model with sliding-attention layers is served from two page classes: the
    # window class's pool as the batcher provisions it, and a pair of tables
    windowed = {"window_pages": window_pool_pages(server._cfg, slots)} if server._cfg.window_layers else {}
    pools = abstract(jax.eval_shape(lambda: init_paged_kv_caches(
        server._cfg, slots * pages + 2 if length else POOL_PAGES, PAGE, "bf16",
        state_slots=slots, **windowed)))

    def tables(rows):
        table = sds((rows, pages), "int32")
        return (table, table) if windowed else table

    if program == "decode_step":
        lowered = server._get_decode_step_paged(slots, pages, 1).lower(
            params, pools, sds((slots,), "int32"), sds((slots,), "int32"),
            sds((slots, 2), "uint32"), sds((), "float32"), tables(slots))
    else:
        # a model with state layers is told which slot's state the chunk continues
        state_slot = (sds((1,), "int32"),) if server._cfg.state_layers else ()
        lowered = server._get_prefill_chunk(chunk, pages).lower(
            params, pools, tables(1), sds((1, chunk), "int32"),
            sds((1, chunk), "int32"), sds((), "int32"), *state_slot)
    return lowered.compile()


def window_pool_pages(cfg, slots: int) -> int:
    """The window class's pool as runtime/batcher.py provisions it: a window, the
    widest chunk and a page a slot, and the two reserved pages."""
    from seldon_core_tpu.models.cache import window_slot_pages

    return slots * window_slot_pages(cfg.sliding_window, WIDE, PAGE) + 2


def block_scope(layer: int) -> str:
    """The scope path ``layer``'s ops carry in a compiled program's text: the
    layers of a class call ONE lowering of their block (models/transformer.py
    ``transformer_block``), whose ops are named from the block down, and the
    compiler inlines each call under that call's own path."""
    return f"layer_{layer}/jit(transformer_block)/TransformerBlock/"


def compiled_text(server, program: str, sharding) -> str:
    return compiled(server, program, sharding).as_text()


_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+(\w+)\[([\d,]*)\]\S*\s+([\w\-]+)\((.*)$")


def own_ops(hlo: str) -> list:
    """(name, dtype, shape, opcode, operands and the rest of the line, the
    computation it is in) of the instructions that are executed as ops of their
    own: those of the entry computation and of loop bodies, not the insides of
    a fusion. A fusion's opcode is its root's."""
    computations, name = {}, None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            name = head.group(1)
            computations[name] = []
        elif name is not None and (m := _INSTR.match(line)):
            shape = tuple(int(n) for n in m.group(4).split(",") if n)
            computations[name].append(
                (bool(m.group(1)), m.group(2), m.group(3), shape, m.group(5), m.group(6)))
    roots = {name: next((i[4] for i in instrs if i[0]), None)
             for name, instrs in computations.items()}
    fused = {m.group(1) for instrs in computations.values() for i in instrs
             if i[4] == "fusion" and (m := re.search(r"calls=%?([\w.\-]+)", i[5]))}
    found = []
    for name, instrs in computations.items():
        if name in fused:
            continue
        for _, instr, dtype, shape, opcode, rest in instrs:
            if opcode == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", rest)
                opcode = roots.get(called.group(1)) if called else None
            found.append((instr, dtype, shape, opcode, rest, name))
    return found


# opcodes that hand an array on without computing one
PASSES_ON = {"parameter", "get-tuple-element", "tuple", "bitcast", "reshape", "copy",
             "copy-start", "copy-done"}


def conditional_branches(hlo: str) -> tuple:
    """(false branch, true branch): the computations of the program's ONE
    conditional on a predicate."""
    found = re.findall(r"conditional\(.*(?:branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}|"
                       r"true_computation=%?([\w.\-]+), false_computation=%?([\w.\-]+))", hlo)
    assert len(found) == 1, found
    # (as an index, branch 0 is the predicate's false)
    return found[0][:2] if found[0][0] else found[0][:1:-1]


def vocabulary_wide(hlo: str, vocab: int) -> list:
    """(computation, shape, opcode) of the ops of their own that COMPUTE an
    array whose last axis is the vocabulary."""
    return sorted((name, shape, opcode) for _, _, shape, opcode, _, name in own_ops(hlo)
                  if shape[-1:] == (vocab,) and opcode not in PASSES_ON)


def floating_arrays(hlo: str, shapes) -> list:
    """The ops of their own whose result is a floating array of one of
    ``shapes``, whatever computes it."""
    return [f"{instr} = {dtype}{list(shape)} in {name}"
            for instr, dtype, shape, _, _, name in own_ops(hlo)
            if dtype in FLOATS and shape in shapes]


def weight_copies(hlo: str, weight_shapes) -> list:
    """Of those, the copies and transposes, and the fusions whose root is one."""
    return [f"{instr} = {dtype}{list(shape)} in {name}"
            for instr, dtype, shape, opcode, _, name in own_ops(hlo)
            if dtype in FLOATS and shape in weight_shapes and opcode in ("copy", "transpose")]


def test_the_parser_sees_a_weight_copy():
    """The check itself, on the parent's own lines (PR 27's program): the
    standalone copy and a fusion whose root is one are both found; a dequant
    fusion and a copy of another shape are not."""
    hlo = """
%fused_computation.24 (p0: s8[2048,2048], p1: f32[2048]) -> bf16[2048,2048] {
  %mul = f32[2048,2048]{1,0:T(8,128)} multiply(%c, %b)
  ROOT %convert.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)} convert(%mul)
}
%fused_computation.9 (p0: bf16[2048,2048]) -> bf16[2048,2048] {
  ROOT %copy.3 = bf16[2048,2048]{0,1:T(8,128)(2,1)} copy(%p0)
}
ENTRY %main.1 (a: s8[2048,2048], s: f32[2048]) -> bf16[2048,2048] {
  %fusion.15 = bf16[2048,2048]{0,1:T(8,128)(2,1)S(1)} fusion(%a, %s), kind=kLoop, calls=%fused_computation.24
  %copy.18 = bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} copy(%fusion.15), backend_config={}
  %fusion.16 = bf16[2048,2048]{1,0:T(8,128)(2,1)} fusion(%copy.18), kind=kLoop, calls=%fused_computation.9
  %copy.19 = bf16[32,2048]{1,0:T(8,128)(2,1)} copy(%x)
  ROOT %copy.20 = s8[2048,2048]{1,0} copy(%a)
}
"""
    found = weight_copies(hlo, {(2048, 2048)})
    assert [f.split(" ")[0] for f in found] == ["copy.18", "fusion.16"]


@pytest.mark.parametrize("config,program", [
    ("mistral", "decode_step"), ("mistral", "prefill_chunk"), ("olmoe", "decode_step"),
    ("deepseek", "decode_step"), ("deepseek", "prefill_chunk"),
    ("xing4", "decode_step"), ("xing4", "prefill_chunk")])
def test_no_transposed_copy_of_a_weight(v5e, servers, config, program):
    """(DeepSeek: wq is head-split and held output-major like the others'; the
    latent projection, W_UK / W_UV in the order the absorbed products read them,
    wo, the dense layer, the shared experts and the expert stacks leave no
    floating copy of themselves either, in either order.)"""
    from seldon_core_tpu.ops.quantize import QuantizedTensor

    server = servers(config)
    layer = server._params["params"]["layer_0"]
    latent = config in ("deepseek", "xing4")
    matrices = [leaf for leaf in jax.tree.leaves(
        server._params["params"] if latent else layer,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
        if isinstance(leaf, QuantizedTensor) and 256 not in leaf.q.shape]   # not the toy vocabulary's
    # wq wk wv wo w1 w2 w3; DeepSeek: 5 attention + 3 dense, 5 + router + 3 stacks + 3 shared;
    # Xing4.0: wq_a and wq_b (head-split: output-major) in wq's place, 6 + 3, 6 + 7
    assert len(matrices) == {"deepseek": 20, "xing4": 22}.get(config, 7)
    shapes = {m.q.shape for m in matrices} | {m.q.shape[:-2] + m.q.shape[:-3:-1] for m in matrices}
    hlo = compiled_text(server, program, v5e)
    # the program is the one the chip runs: the q projection's dequant is there
    held = layer["attention"]["wq_b" if config == "xing4" else "wq"].q.shape
    assert re.search(rf"= bf16\[{held[0]},{held[1]}\]\S* fusion\(", hlo), "no dequant of wq?"
    assert weight_copies(hlo, shapes) == []
    if latent:
        # nor a copy of the latent pool: with the row at its bare 576 values the
        # gather wanted the pool in another layout than the scatter, two whole-
        # pool copies a layer a call (17.7 ms of a 118 ms chunk, PERF.md section
        # 6, PR 29); the row is held in whole 128-lane tiles (640)
        assert server._cfg.latent_row_dim == 640
        assert weight_copies(hlo, {(POOL_PAGES, PAGE, 640), (POOL_PAGES, PAGE, 576)}) == []


@pytest.mark.parametrize("config,program", [
    ("olmoe_moe", "decode_step"), ("olmoe_moe", "prefill_chunk"),
    ("deepseek", "decode_step"), ("deepseek", "prefill_chunk"), ("deepseek", "wide_chunk"),
    ("xing4", "decode_step"), ("xing4", "prefill_chunk")])
def test_the_routed_experts_run_the_repos_grouped_matmul(v5e, servers, config, program):
    """Three Mosaic kernels of the repo's own an MoE layer (gate, up, down),
    float32 [rows x k, width] out of the int8 stacks; none of XLA's own
    grouped-matmul kernels, whose name the device trace showed as
    ``ragged-dot-none_*``; and no floating array of a stack's shape anywhere
    (an expert's block is converted in VMEM)."""
    from seldon_core_tpu.ops.grouped_matmul import KERNEL_NAME

    server = servers(config)
    cfg = server._cfg
    hlo = compiled_text(server, program, v5e)
    calls = [line for line in hlo.splitlines()
             if re.match(rf"\s*%{KERNEL_NAME}[\w.]* = ", line)]
    assert len(calls) == 3 * cfg.n_moe_layers
    rows = {"decode_step": 32, "prefill_chunk": 256, "wide_chunk": WIDE}[program]
    rows *= cfg.n_experts_per_token
    shapes = sorted(re.search(r"= (\w+\[[\d,]+\])", line).group(1) for line in calls)
    assert shapes == sorted([f"f32[{rows},{cfg.ffn_dim}]"] * 2 + [f"f32[{rows},{cfg.dim}]"])
    assert all('custom_call_target="tpu_custom_call"' in line for line in calls)
    assert all(f"s8[{cfg.n_experts}," in line for line in calls), "the stacks go in as int8"
    assert not re.search(r"%ragged-dot", hlo)
    e, d, f = cfg.n_experts, cfg.dim, cfg.ffn_dim
    assert not re.search(rf"(bf16|f16|f32)\[{e},({d},{f}|{f},{d})\]", hlo)


def entry_ops(hlo: str) -> list:
    """(name, dtype, shape, opcode, scope path) of the entry computation's
    instructions: the ops a device trace would show as events of their own."""
    entry = hlo[hlo.index("\nENTRY"):]
    out = []
    for line in entry.splitlines():
        m = _INSTR.match(line)
        if m:
            path = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(2), m.group(3), tuple(int(n) for n in m.group(4).split(",") if n),
                        m.group(5), path.group(1) if path else ""))
    return out


@pytest.mark.parametrize("config", ["mistral", "lfm2"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk", "wide_chunk"])
def test_the_layers_calls_of_their_block_are_inlined(v5e, servers, config, program):
    """The layers of a class call one lowering of ``transformer_block``; the
    compiled program holds no ``call`` (a call left standing would keep the
    compiler from fusing and scheduling across a layer's edge, and from
    aliasing a donated pool through it), every layer's ops are there under its
    OWN path (whatever reads a trace by layer still can), and the pool a layer
    writes is still the donated one."""
    server = servers(config)
    cfg = server._cfg
    exe = compiled(server, program, v5e)
    hlo = exe.as_text()
    assert not re.search(r"= \S+ call\(", hlo) and "to_apply=%transformer_block" not in hlo
    for layer in range(cfg.n_layers):
        assert block_scope(layer) in hlo
    # the K, V and position pools (and a state layer's blocks) alias their inputs
    pools = re.findall(r"%pools_[\w.]* = \S+ parameter\((\d+)\)", hlo[hlo.index("\nENTRY"):])
    aliased = set(re.findall(r"\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo))
    assert pools and set(pools) <= aliased


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_streams_stay_narrow_and_the_sinkhorn_chain_is_one_kernel(
        v5e, servers, program):
    """Xing4.0's hyper-connections as the chip's compiler leaves them. The four
    streams [rows, 4, 3584] are bf16 arrays: no op of the program writes them,
    or the [rows, 14336] vector the maps are read from, in float32 (the mixing
    widens inside its fusions). A sub-layer's twenty Sinkhorn iterations are
    ONE op, the repo's kernel (ops/sinkhorn.py), not two reduces an iteration
    nor a loop of fusions; with the norm, the maps' product and the rest,
    ``resid.hc.pre`` is under a dozen ops a sub-layer, and ``resid.hc.post`` a
    fusion and a layout copy."""
    hlo = compiled_text(servers("xing4"), program, v5e)
    ops = entry_ops(hlo)
    rows = 32 if program == "decode_step" else 256
    # (the routed rows [t, top_k = 4, 3584] are float32 by design: not a stream)
    wide = [op for op in ops if op[1] == "f32" and "moe.experts" not in op[4]
            and (op[2][-2:] == (4, 3584) or op[2][-1:] == (4 * 3584,))]
    assert wide == [], wide[:3]
    # (a while's result is a tuple, which entry_ops does not read: the text)
    assert not re.search(r" while\(.*resid\.hc", hlo)
    for layer in (0, 1):
        for sub in ("attention_hc", "ffn_hc"):
            pre = [op for op in ops if f"{block_scope(layer)}{sub}/resid.hc.pre" in op[4]
                   and op[3] in ("fusion", "convolution", "copy", "custom-call")]
            assert 4 <= len(pre) <= 12, (layer, sub, [op[0] for op in pre])
            chain = [op for op in pre if op[3] == "custom-call"]
            assert [op[0].split(".")[0] for op in chain] == ["sinkhorn_hc"], chain
        post = [op for op in ops if f"layer_{layer}/" in op[4] and "resid.hc.post" in op[4]
                and op[3] in ("fusion", "copy")]
        assert 2 <= len(post) <= 8, [op[0] for op in post]
    streams = [op for op in ops if op[2][-2:] == (4, 3584) and rows in op[2]
               and "moe.experts" not in op[4]]
    assert streams and all(op[1] == "bf16" for op in streams)
    # the named scopes the benchmark's readers look for are in the program
    for scope in ("resid.hc.pre", "resid.hc.post", "attn.latent.q", "attn.latent.read", "moe.route"):
        assert any(scope in op[4] for op in ops), scope


# the two latent cells' servers (PERF.md section 4): slots x tokens a slot
LATENT_CELLS = {"deepseek": (8, 16384), "xing4": (32, 4096)}


@pytest.mark.parametrize("config,program", [
    ("deepseek", "decode_step"), ("deepseek", "prefill_chunk"), ("deepseek", "wide_chunk"),
    ("xing4", "decode_step"), ("xing4", "prefill_chunk"), ("xing4", "wide_chunk")])
def test_the_latent_read_walks_the_live_pages_and_holds_no_view(v5e, servers, config, program):
    """At the cells' own shapes the read under ``attn.latent.read`` is ONE
    Mosaic kernel of the repo's a layer (ops/latent_attention.py), fed the pool
    as it is held, sixteen pages a visit (eight under a chunk); the program holds no gathered copy of
    the logical view (``fusion bf16[2048,64,640]``: 168 MB a layer, the largest
    op of both cells' steps in PR 31's traces), in any shape, and its step
    needs less scratch memory than one such view. The step and the 256-token
    chunk read ABSORBED (the walk, ``tokens x heads`` query rows as wide as a
    cached row, the latent context out); the wide chunk reads EXPANDED ONCE
    (PR 52: its own kernel, W_UK / W_UV among its operands, a token's heads out
    ``H x dv`` wide, and no product with W_UV behind it)."""
    from seldon_core_tpu.ops.latent_attention import EXPANDED_KERNEL_NAME, KERNEL_NAME

    server = servers(config)
    cfg = server._cfg
    slots, length = LATENT_CELLS[config]
    pages = length // PAGE
    exe = compiled(server, program, v5e, slots=slots, length=length)
    hlo = exe.as_text()
    expanded = program == "wide_chunk"
    kernel, other = (EXPANDED_KERNEL_NAME, KERNEL_NAME) if expanded else (KERNEL_NAME, EXPANDED_KERNEL_NAME)
    calls = [line for line in hlo.splitlines() if re.match(rf"\s*%{kernel}[\w.]* = ", line)]
    assert len(calls) == cfg.n_layers and f"%{other}" not in hlo
    assert all('custom_call_target="tpu_custom_call"' in line for line in calls)
    assert all("attn.latent.read" in line for line in calls)
    sequences = slots if program == "decode_step" else 1
    rows = {"decode_step": 1, "prefill_chunk": 256, "wide_chunk": WIDE}[program]
    out = (f"= bf16[1,{WIDE},{cfg.n_heads * cfg.v_head_dim}]" if expanded
           else f"= bf16[{sequences},{rows * cfg.n_heads},512]")
    assert all(out in line for line in calls)
    # a visit's pages are operands of the pool itself, not of a copy
    pool = f"bf16[{slots * pages + 2},{PAGE},640]"
    visit = 16 if program == "decode_step" else 8
    assert all(line.count(pool) == visit for line in calls), calls[0][:400]
    if expanded:   # W_UK [H, dn, dc] and W_UV a head's [dv, dc], bf16, and nothing of W_UV outside
        assert all(line.count(f"bf16[{cfg.n_heads},128,512]") == 2 for line in calls), calls[0][:400]
    for view in ((sequences * pages, PAGE, 640), (sequences, pages, PAGE, 640),
                 (sequences, pages * PAGE, 640)):
        text = ",".join(str(n) for n in view)
        assert f"bf16[{text}]" not in hlo and f"f32[{text}]" not in hlo, view
    if program == "decode_step":
        assert exe.memory_analysis().temp_size_in_bytes < sequences * pages * PAGE * 640 * 2


# the GQA cells' servers (PERF.md section 4): the configuration here, slots x
# tokens a slot, and the K / V row (n_kv_heads x head_dim)
GQA_CELLS = {"mistral chat": ("mistral", 32, 1024, 1024), "mistral docs": ("mistral", 8, 4096, 1024),
             "olmoe chat": ("olmoe", 32, 1024, 2048), "lfm2 rag": ("lfm2", 32, 4096, 512)}


@pytest.mark.parametrize("cell", list(GQA_CELLS))
def test_the_gqa_step_walks_the_live_pages_and_holds_no_view(v5e, servers, cell):
    """At the cells' own shapes the decode step's read under ``attn.gqa.read``
    is ONE Mosaic kernel of the repo's an attention layer (ops/gqa_attention.py),
    fed the K and the V pool as they are held (flat rows ``[pages, 64, kvh x
    hd]``, row-major), sixteen pages of each a visit; the program holds no
    gathered copy of the logical view in any of its shapes (``fusion
    bf16[512,64,8,128]`` / ``[512,64,16,128]`` / ``[2048,64,512]``: the largest
    ops of these cells' steps in PR 35's traces), no copy of a pool, and the
    write is under ``attn.gqa.write``."""
    from seldon_core_tpu.ops.gqa_attention import KERNEL_NAME

    config, slots, length, row = GQA_CELLS[cell]
    server = servers(config)
    cfg = server._cfg
    assert cfg.n_kv_heads * cfg.head_dim == row
    pages = length // PAGE
    layers = cfg.n_layers - len(cfg.conv_layers)
    hlo = compiled(server, "decode_step", v5e, slots=slots, length=length).as_text()
    calls = [line for line in hlo.splitlines() if re.match(rf"\s*%{KERNEL_NAME}[\w.]* = ", line)]
    assert len(calls) == layers
    assert all('custom_call_target="tpu_custom_call"' in line for line in calls)
    assert all("attn.gqa.read" in line for line in calls)
    assert all(f"= bf16[{slots},{cfg.n_heads},{row}]" in line for line in calls)
    # a visit's pages are operands of the pools themselves, not of a copy
    pool_pages = slots * pages + 2
    pool = f"bf16[{pool_pages},{PAGE},{row}]"
    assert all(line.count(pool) == 2 * 16 for line in calls), calls[0][:400]
    entry = hlo[hlo.index("\nENTRY"):]
    assert len(re.findall(rf"%pools_\d__[01]_[\w.]* = bf16\[{pool_pages},{PAGE},{row}\]\{{2,1,0:", entry)
               ) == 2 * layers
    views = [(slots * pages, PAGE), (slots, pages, PAGE), (slots, pages * PAGE)]
    rows = [(row,), (cfg.n_kv_heads, cfg.head_dim)]
    for view in (lead + tail for lead in views for tail in rows):
        text = ",".join(str(n) for n in view)
        assert f"bf16[{text}]" not in hlo and f"f32[{text}]" not in hlo, view
    assert weight_copies(hlo, {(pool_pages, PAGE) + tail for tail in rows}) == []
    assert "attn.gqa.write" in hlo


@pytest.mark.parametrize("program,rows", [("decode_step", 1), ("prefill_chunk", 256),
                                          ("wide_chunk", WIDE)])
def test_the_window_layers_walk_from_their_first_live_page_and_hold_no_view(
        v5e, servers, program, rows):
    """SmallThinker's period at the cell's shapes (24 slots x 16,384, two page
    classes): every attention layer's read is ONE Mosaic kernel of the repo's,
    the three sliding-attention layers' under ``attn.window.read`` over the
    WINDOW class's pools (81 pages a slot, not 256) and the full layer's under
    ``attn.gqa.read`` over the full class's; a window layer's kernel takes the
    first-visit flags beside the visit list (its walk starts at the first live
    page: no page behind a window is an operand's block), and the program holds
    no array of a whole block-table view's shape, of either class. The wide
    chunk (1,024 rows: 7,168 query rows a lane block of 7 heads) is walked too,
    in tiles of 1,792."""
    from seldon_core_tpu.ops.gqa_attention import KERNEL_NAME

    server = servers("smallthinker")
    cfg = server._cfg
    slots, length, row = 24, 16384, cfg.n_kv_heads * cfg.head_dim
    pages = length // PAGE
    hlo = compiled(server, program, v5e, slots=slots, length=length).as_text()
    calls = [line for line in hlo.splitlines() if re.match(rf"\s*%{KERNEL_NAME}[\w.]* = ", line)]
    assert len(calls) == 4 and all('custom_call_target="tpu_custom_call"' in c for c in calls)
    window = [c for c in calls if "attn.window.read" in c]
    full = [c for c in calls if "attn.gqa.read" in c]
    assert (len(window), len(full)) == (3, 1)
    full_pool = f"bf16[{slots * pages + 2},{PAGE},{row}]"
    window_pool = f"bf16[{window_pool_pages(cfg, slots)},{PAGE},{row}]"
    per_visit = 16 if program == "decode_step" else 2
    assert all(c.count(window_pool) == 2 * per_visit and full_pool not in c for c in window)
    assert all(c.count(full_pool) == 2 * per_visit and window_pool not in c for c in full)
    # the first-visit flags: one more scalar-prefetch operand than the full layer's
    # (and, in a chunk's block form, the two bounds of the window's fast path)
    more = 1 if program == "decode_step" else 3
    operands = [c[c.index("custom-call(") :].count("s32[") for c in calls]
    assert {operands[calls.index(c)] for c in window} == {operands[calls.index(full[0])] + more}
    sequences = slots if program == "decode_step" else 1
    views = [(sequences * pages, PAGE), (sequences, pages, PAGE), (sequences, pages * PAGE)]
    tails = [(row,), (cfg.n_kv_heads, cfg.head_dim)]
    for view in (lead + tail for lead in views for tail in tails):
        text = ",".join(str(n) for n in view)
        assert f"bf16[{text}]" not in hlo and f"f32[{text}]" not in hlo, view
    assert "attn.window.write" in hlo and "attn.gqa.write" in hlo


def test_where_the_routing_lands_beside_attention_in_the_compiled_step(v5e, servers):
    """The router reads the layer's INPUT, so nothing of ``moe.route`` depends on
    what attention produces and the compiler is free to place it beside the
    read. Nothing is forced: this reads what the compiled step DOES with it
    (PERF.md section 6, PR 49, has the finding) and holds only that the routing
    is there once a layer and that no route op takes the attention kernel's
    output."""
    from seldon_core_tpu.ops.gqa_attention import KERNEL_NAME

    server = servers("smallthinker")
    hlo = compiled(server, "decode_step", v5e, slots=24, length=16384).as_text()
    entry = hlo[hlo.index("\nENTRY"):].splitlines()
    kernels = [i for i, line in enumerate(entry) if re.match(rf"\s*%{KERNEL_NAME}[\w.]* = ", line)]
    routes = [i for i, line in enumerate(entry) if "moe.route" in line and "top_k" in line.lower()]
    assert len(kernels) == 4
    assert routes, "no op of moe.route in the step's entry computation"
    ahead = [sum(1 for r in routes if r < k) for k in kernels]
    print(f"moe.route top-k ops scheduled ahead of each layer's attention kernel: {ahead} "
          f"(of {len(routes)})")
    names = [re.match(r"\s*%([\w.]+) = ", entry[k]).group(1) for k in kernels]
    for line in entry:
        if "moe.route" in line and "moe.experts" not in line:
            assert not any(f"%{name}" in line.split("=", 1)[1] for name in names), line[:300]


# attention of LFM2's widths (32 heads of 64 over 8 KV heads) around a dense
# FFN: what a mesh serves of narrow heads (conv layers refuse one)
NARROW_HEADS = dict(vocab_size=256, dim=2048, n_layers=1, n_heads=32, n_kv_heads=8,
                    ffn_dim=7168, max_seq_len=1024, dtype="bfloat16", qk_norm="head")


def test_on_a_mesh_narrow_heads_keep_flat_rows_and_no_pool_is_copied():
    """Sharded over a described v5e 2x2 ('model' = 4, the weights placed by the
    repo's own rules), the step keeps the expression over the gathered view:
    no kernel (one device's program) under ``attn.gqa.read``. A pool of heads
    of 128 keeps its ``[.., kvh, hd]`` axes for the partitioner; one of heads
    of 64 is flat rows there too (held ``[.., 8, 64]`` it came out pages-minor
    and was copied whole, PR 35). Either way a chip holds its KV heads' share
    of every page, row-major, and neither program copies a pool."""
    import dataclasses

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from seldon_core_tpu.ops.gqa_attention import KERNEL_NAME
    from seldon_core_tpu.parallel import sharding as sharding_mod

    try:
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"libtpu cannot describe a v5e 2x2 here: {type(exc).__name__}: {exc}")
    mesh = Mesh(np.array(topology.devices).reshape(1, 1, 4), ("data", "seq", "model"))
    replicated = NamedSharding(mesh, PartitionSpec())

    def place(x, sharding):   # nothing can be put on a described device
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding), x)

    for kwargs, flat in ((NARROW_HEADS, True), (MISTRAL, False)):
        server = _served(kwargs)
        server.mesh = mesh
        server._cfg = dataclasses.replace(server._cfg, mesh=mesh)
        server._module = server._module.clone(cfg=server._cfg)
        cfg = server._cfg
        assert cfg.kv_rows_flat is flat
        # a chip's share of the pool: the KV heads are what 'model' divides
        tail = ((cfg.n_kv_heads * cfg.head_dim // 4,) if flat
                else (cfg.n_kv_heads // 4, cfg.head_dim))
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "device_put", place)   # the repo's rules place shapes
            server._params = sharding_mod.shard_params(
                server._params, mesh, server._logical_axes())
        placed = {str(leaf.sharding.spec) for leaf in jax.tree.leaves(server._params)}
        assert any("model" in spec for spec in placed), placed
        hlo = compiled(server, "decode_step", replicated).as_text()
        assert not re.search(rf"^\s*%{KERNEL_NAME}[\w.]* = ", hlo, re.M)
        assert "all-reduce" in hlo or "all-gather" in hlo, "the program is partitioned"
        held = (f"bf16[{POOL_PAGES},{PAGE}," + ",".join(str(n) for n in tail) + "]{"
                + ",".join(str(n) for n in reversed(range(2 + len(tail)))) + ":")
        pools = [line for line in hlo.splitlines()
                 if re.search(r'parameter\(\d+\).*op_name="pools\[0\]\[[01]\]"', line)]
        assert len(pools) == 2 and all(held in line and "devices=[" in line for line in pools), pools
        assert weight_copies(hlo, {(POOL_PAGES, PAGE) + tail}) == []
        assert "attn.gqa.read" in hlo and "attn.gqa.write" in hlo


# the GQA configurations' chunk programs: the configuration here, tokens a
# chunk, tokens a slot, the lane block a KV head (or two of LFM2's) and the
# block's query heads; Mistral's chat server runs two (perf/configs)
GQA_CHUNKS = {"mistral docs": ("mistral", 256, 4096, 128, 4), "mistral chat 128": ("mistral", 128, 1024, 128, 4),
              "mistral chat 256": ("mistral", 256, 1024, 128, 4), "olmoe chat": ("olmoe", 256, 1024, 128, 1),
              "lfm2 rag": ("lfm2", 256, 4096, 128, 8), "qwen3next longctx": ("qwen3next", 256, 8192, 256, 8),
              "lfm2 rag wide": ("lfm2", WIDE, 4096, 128, 8),
              "qwen3next longctx wide": ("qwen3next", WIDE, 8192, 256, 8),
              # a dense server's, since PR 54: the docs and rerank cells' prompts take it
              "mistral docs wide": ("mistral", WIDE, 4096, 128, 4)}


@pytest.mark.parametrize("cell", list(GQA_CHUNKS))
def test_the_gqa_chunk_walks_the_live_pages_a_head_block_and_holds_no_view(v5e, servers, cell):
    """A prefill chunk's read under ``attn.gqa.read`` (256 x H query rows: past
    one tile) is ONE Mosaic kernel of the repo's a GQA layer, fed the K and the
    V pool as they are held, two pages of each a visit whatever the number of
    KV heads (the head blocks are a loop inside the visit, not operands), the queries
    and the context ``[1, s, H x block]`` as the projections hold them; the
    program holds no gathered view of the chunk's sequence (``bf16[64,64,1024]``
    twice a layer before PR 41), no array with the heads split out of one, and
    no copy of a pool."""
    from seldon_core_tpu.ops.gqa_attention import KERNEL_NAME

    config, chunk, length, block, slices = GQA_CHUNKS[cell]
    server = servers(config)
    cfg = server._cfg
    pages = length // PAGE
    layers = cfg.n_layers - len(cfg.state_layers)
    hlo = compiled(server, "prefill_chunk", v5e, slots=2, length=length, chunk=chunk).as_text()
    calls = [line for line in hlo.splitlines() if re.match(rf"\s*%{KERNEL_NAME}[\w.]* = ", line)]
    assert len(calls) == layers
    assert all('custom_call_target="tpu_custom_call"' in line for line in calls)
    assert all("attn.gqa.read" in line for line in calls)
    row = cfg.n_kv_heads * cfg.head_dim
    assert cfg.n_heads * block == slices * row
    assert all(f"= bf16[1,{chunk},{slices * row}]" in line for line in calls), calls[0][:300]
    pool_pages = 2 * pages + 2
    pool = f"bf16[{pool_pages},{PAGE},{row}]"
    assert all(line.count(pool) == 2 * 2 for line in calls), calls[0][:400]
    views = [(pages, PAGE), (1, pages, PAGE), (1, pages * PAGE)]
    rows = [(row,), (cfg.n_kv_heads, cfg.head_dim)]
    for view in (lead + tail for lead in views for tail in rows):
        text = ",".join(str(n) for n in view)
        assert f"bf16[{text}]" not in hlo and f"f32[{text}]" not in hlo, view
    assert weight_copies(hlo, {(pool_pages, PAGE) + tail for tail in rows}) == []
    assert "attn.gqa.write" in hlo


# the latent cells' chunk programs beside the GQA ones: the scope of the write,
# and the pool leaves a paged layer writes (K, V, positions / rows, positions)
LATENT_CHUNKS = {"deepseek": ("deepseek", 256), "xing4": ("xing4", 256),
                 "deepseek wide": ("deepseek", WIDE)}
CHUNK_WRITES = {**{cell: ("attn.gqa.write", 3) for cell in GQA_CHUNKS},
                **{cell: ("attn.latent.write", 2) for cell in LATENT_CHUNKS}}


def pool_scatters(hlo: str, scope: str, pool_pages: int) -> list:
    """(result shape, [operand shapes]) of the scatters traced under ``scope``
    whose result is an array of ``pool_pages`` pages of PAGE rows."""
    ops = own_ops(hlo)
    shape_of = {instr: shape for instr, _, shape, _, _, _ in ops}
    found = []
    for _, _, shape, opcode, rest, _ in ops:
        if opcode == "scatter" and shape[:2] == (pool_pages, PAGE) and scope in rest:
            operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
            found.append((shape, [shape_of[name] for name in operands if name in shape_of]))
    return found


@pytest.mark.parametrize("cell", list(CHUNK_WRITES))
def test_the_chunk_writes_whole_pages_in_place(v5e, servers, cell):
    """Under ``attn.gqa.write`` / ``attn.latent.write`` a chunk program's every
    pool leaf (K, V / the latent rows, and the positions) is written by ONE
    scatter a layer of ``chunk / 64 + 1`` page-sized windows
    (models/transformer.py ``paged_write_pages``): no operand of it has an entry
    a token (the 256 ``(page, offset)`` pairs that fed ``fusion
    bf16[514,64,1024]`` / ``[2050,64,640]`` before PR 42). The K / V (latent)
    pools are donated, aliased and held as they arrive: no copy of one. (The
    positions' ``s32[pages, 64]`` lives pages-minor on the device; the program
    turns it rows-minor for the read's kernel as the parent did, and back.) The
    scopes keep their names: the benchmark's ``mla_chunk_attn_*`` readers find
    their ops by them."""
    from seldon_core_tpu.models.cache import pages_a_run_writes

    scope, leaves = CHUNK_WRITES[cell]
    if cell in GQA_CHUNKS:
        config, chunk, length = GQA_CHUNKS[cell][:3]
        slots = 2
    else:
        config, chunk = LATENT_CHUNKS[cell]
        slots, length = LATENT_CELLS[config]
    server = servers(config)
    cfg = server._cfg
    row = cfg.latent_row_dim or cfg.n_kv_heads * cfg.head_dim
    layers = cfg.n_layers - len(cfg.state_layers)
    pool_pages = slots * (length // PAGE) + 2
    exe = compiled(server, "prefill_chunk", v5e, slots=slots, length=length, chunk=chunk)
    hlo = exe.as_text()
    writes = pool_scatters(hlo, scope, pool_pages)
    assert sorted(shape for shape, _ in writes) == sorted(
        [(pool_pages, PAGE, row)] * (leaves - 1) * layers + [(pool_pages, PAGE)] * layers)
    windows = pages_a_run_writes(chunk, PAGE)
    for shape, operands in writes:
        # (an entry a token leads with the chunk's rows; LFM2's K row is 512 wide too)
        assert not any(operand[:1] == (chunk,) for operand in operands), (shape, operands)
        assert any(operand[:1] == (windows,) for operand in operands), (shape, operands)
    assert weight_copies(hlo, {(pool_pages, PAGE, row)}) == []
    memory = exe.memory_analysis()
    pool_bytes = pool_pages * PAGE * row * 2
    assert memory.alias_size_in_bytes >= (leaves - 1) * layers * pool_bytes
    assert scope in hlo and scope.replace("write", "read") in hlo


@pytest.mark.parametrize("cell", ["mistral chat", "deepseek"])
def test_the_steps_write_is_one_row_a_slot_as_it_was(v5e, servers, cell):
    """The decode step (a token a slot) keeps the token scatter: every pool
    leaf's write takes ``slots`` index pairs, under the same scope."""
    if cell in GQA_CELLS:
        (config, slots, length, _), scope, leaves = GQA_CELLS[cell], "attn.gqa.write", 3
    else:
        config, (slots, length), scope, leaves = cell, LATENT_CELLS[cell], "attn.latent.write", 2
    server = servers(config)
    cfg = server._cfg
    hlo = compiled(server, "decode_step", v5e, slots=slots, length=length).as_text()
    writes = pool_scatters(hlo, scope, slots * (length // PAGE) + 2)
    assert len(writes) == leaves * (cfg.n_layers - len(cfg.state_layers))
    for shape, operands in writes:
        assert any(operand[:1] == (slots,) for operand in operands), (shape, operands)


def test_the_parser_sees_a_dequantized_table():
    """The check itself, on the parent's own lines (PR 33's Mistral step at
    ``vocab_size=32000``): the table's dequant, an op of its own whose operands
    are ``tok_embeddings``' values and scales, is found; the head's dequant, a
    fusion INSIDE the matmul's ``kOutput`` fusion that reads ``lm_head``'s int8
    parameter, is no op of its own and is not."""
    hlo = """
%fused_computation (param_0.2: bf16[32000,4096], param_1.34: s32[1024]) -> bf16[32,4096] {
  ROOT %gather.15 = bf16[32,4096]{1,0:T(8,128)(2,1)} gather(%param_0.2, %param_1.34), offset_dims={1}
}
%fused_computation.12 (param_0.32: s8[32000,4096], param_1.50: f32[4096]) -> bf16[32000,4096] {
  ROOT %convert.9 = bf16[32000,4096]{1,0:T(8,128)(2,1)} convert(%mul)
}
%fused_computation.10 (param_0.31: s8[4096,32000], param_1.49: f32[32000]) -> f32[4096,32000] {
  ROOT %multiply.4 = f32[4096,32000]{1,0:T(8,128)} multiply(%c, %b)
}
%fused_computation.9 (param_0.217: s8[4096,32000], param_1.226: f32[32000], param_2.148: bf16[32,4096]) -> f32[32,32000] {
  %fusion.10 = f32[4096,32000]{1,0:T(8,128)} fusion(%param_0.217, %param_1.226), kind=kLoop, calls=%fused_computation.10
  ROOT %convolution.8 = f32[32,32000]{1,0:T(8,128)S(1)} convolution(%fusion.59, %fusion.10), dim_labels=bf_io->bf
}
ENTRY %main.34 (e: s8[32000,4096], s: f32[4096], h: s8[4096,32000]) -> f32[32,32000] {
  %multiply_convert_fusion = bf16[32000,4096]{1,0:T(8,128)(2,1)} fusion(%params__params____tok_embeddings___0_.1, %params__params____tok_embeddings___1_.1), kind=kLoop, calls=%fused_computation.12
  %fusion = bf16[32,4096]{1,0:T(8,128)(2,1)S(1)} fusion(%multiply_convert_fusion, %pad_clamp_fusion.1), kind=kCustom, calls=%fused_computation
  ROOT %fusion.9 = f32[32,32000]{1,0:T(8,128)S(1)} fusion(%params__params____lm_head___0_.1, %copy-done.14), kind=kOutput, calls=%fused_computation.9
}
"""
    found = floating_arrays(hlo, {(32000, 4096), (4096, 32000)})
    assert [f.split(" ")[0] for f in found] == ["multiply_convert_fusion"]
    assert weight_copies(hlo, {(32000, 4096), (4096, 32000)}) == []


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_lookup_gathers_int8_rows_and_no_op_writes_the_table(v5e, servers, program):
    """At Mistral's vocabulary no op of the step or the chunk yields a floating
    array of the table's shape or the head's (``multiply_convert_fusion
    bf16[32000,4096]``, 0.61 ms of every Mistral step and chunk; 262 MB of
    scratch): the lookup is a gather out of ``tok_embeddings``' int8 values into
    int8 rows, which the consumers dequantize, and the step's head matmul is the
    ``kOutput`` fusion that reads ``lm_head``'s int8 parameter, as it was (the
    chunk's head: ``test_the_chunks_head_runs_for_one_row_inside_the_conditional``)."""
    server = servers("mistral_vocab")
    cfg = server._cfg
    vocab, dim = cfg.vocab_size, cfg.dim
    table, head = server._params["params"]["tok_embeddings"], server._params["params"]["lm_head"]
    assert table.lookup and table.q.shape == (vocab, dim) and table.scale.shape == (dim,)
    assert not head.lookup and head.q.shape == (dim, vocab)
    exe = compiled(server, program, v5e)
    hlo = exe.as_text()
    assert floating_arrays(hlo, {(vocab, dim), (dim, vocab)}) == []
    assert exe.memory_analysis().temp_size_in_bytes < vocab * dim   # no copy in scratch either
    rows = 32 if program == "decode_step" else 256
    ops = own_ops(hlo)
    lookups = [op for op in ops if "tok_embeddings___0_" in op[4] and op[3] != "parameter"]
    assert [(op[1], op[2]) for op in lookups] == [("s8", (rows, dim))], lookups
    def body(op):   # the text of the computation a fusion calls
        called = re.search(r"calls=%?([\w.\-]+)", op[4]).group(1)
        text = hlo[hlo.index(f"%{called} ("):]
        return text[:text.index("\n}")]

    gather = body(lookups[0])
    assert f"s8[{vocab},{dim}]" in gather.splitlines()[0] and re.search(r" gather\(", gather)
    if program == "prefill_chunk":
        return
    heads = [op for op in ops if "lm_head___0_" in op[4] and op[3] != "parameter"]
    assert [(op[1], op[2][-2:]) for op in heads] == [("f32", (rows, vocab))], heads
    matmul = body(heads[0])
    assert "kind=kOutput" in heads[0][4] and re.search(r" convolution\(", matmul)
    assert f"s8[{dim},{vocab}]" in matmul.splitlines()[0]


@pytest.mark.parametrize("program,rows", [("prefill_chunk", 256), ("wide_chunk", WIDE)])
def test_the_chunks_head_runs_for_one_row_inside_the_conditional(v5e, servers, program, rows):
    """Sixth (PR 47): the chunk's program yields logits ``[1, 1, vocab]`` out of
    a conditional on the row it is handed. At Mistral's vocabulary the ONE op
    that computes a vocabulary-wide array is in the conditional's true branch,
    a reduce over the one row that reads ``lm_head``'s int8 values as they are
    held (the dequant is inside its fusion: dequantized ahead of the
    conditional the compiler wrote the whole head out in float32, 524 MB of
    scratch a chunk, read off this program before the head was handed in
    int8); the other branch broadcasts zeros; no op yields ``[256, vocab]``
    (``fusion f32[1,256,32000]``, 1.5 % of a rerank chunk's device time and
    33 MB of output a program in flight, ledger PR 45). The wide program
    (PR 48) is never a prompt's last chunk as the batcher runs it, and is the
    same program but for its rows: no ``[1024, vocab]`` either."""
    server = servers("mistral_vocab")
    vocab, dim = server._cfg.vocab_size, server._cfg.dim
    exe = compiled(server, program, v5e)
    hlo = exe.as_text()
    zeros, true = conditional_branches(hlo)
    assert vocabulary_wide(hlo, vocab) == sorted([
        (zeros, (1, 1, vocab), "broadcast"), (true, (vocab,), "reduce")])
    product = next(op for op in own_ops(hlo) if op[2:4] == ((vocab,), "reduce"))
    called = re.search(r"calls=%?([\w.\-]+)", product[4]).group(1)
    assert f"s8[{dim},{vocab}]" in hlo[hlo.index(f"%{called} ("):].splitlines()[0]
    assert floating_arrays(hlo, {(rows, vocab), (1, rows, vocab), (dim, vocab)}) == []
    shapes = [out.shape for out in jax.tree.leaves(exe.out_info)]
    assert (1, 1, vocab) in shapes and (1, rows, vocab) not in shapes
    assert exe.memory_analysis().temp_size_in_bytes < 256 * vocab * 4


def top_k_operands(hlo: str) -> list:
    """The shape of the array each ``top_k`` of the traced program reads as the
    compiler left it: a ``TopK`` custom call's operand, or the first operand of
    the stable descending ``sort`` it makes of a ``top_k`` over a few hundred
    columns."""
    shapes = {m.group(2): tuple(int(n) for n in m.group(4).split(",") if n)
              for line in hlo.splitlines() if (m := _INSTR.match(line))}
    return sorted(
        (kind, shapes[m.group(1)]) for kind, pattern in (
            ("TopK", r"custom-call\(%?([\w.\-]+)\), custom_call_target=\"TopK\""),
            ("sort", r" sort\(%?([\w.\-]+), [^)]*\)[^\n]*sample\.topk/top_k"))
        for m in re.finditer(pattern, hlo))


@pytest.mark.parametrize("config,reads", [
    ("mistral_vocab", [("TopK", (32, 5120)), ("sort", (32, 250))]),
    ("mistral", [("sort", (32, 256))])])
def test_the_samplers_top_k_reads_the_chosen_blocks_not_the_vocabulary(v5e, servers, config, reads):
    """Ninth (PR 56): in the decode step compiled for the described v5e at a
    served vocabulary (32,000) no ``TopK`` custom call's operand has the
    vocabulary as a dimension: the one there is reads the 40 chosen blocks'
    5,120 columns, under ``sample.topk`` (the block maxima's top 40 of 250 is
    a sort of 250 columns). A vocabulary under the rule's threshold keeps the
    direct ``top_k`` over its 256 columns (which the compiler makes a sort)."""
    server = servers(config)
    hlo = compiled_text(server, "decode_step", v5e)
    assert top_k_operands(hlo) == reads
    for line in hlo.splitlines():
        if 'custom_call_target="TopK"' in line:
            assert "sample.topk/" in line


LFM2_CELL = (32, 4096)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_hybrid_programs_donate_state_and_pool_and_copy_neither(v5e, servers, program):
    """LFM2-8B-A1B's three kinds of layer at published widths and the cell's
    own shapes (32 slots x 4,096 tokens): every leaf of the cache tree, the
    conv layers' ``[slots, 2, 2048]`` state blocks as well as the attention
    layer's K, V and position pools, is an aliased output of its parameter;
    the K / V pool is held as FLAT rows ``[pages, 64, 512]`` row-major (held
    ``[pages, 64, 8, 64]``, with a minor dimension of half a lane tile, the
    compiler laid it out pages-minor and copied all 134 MB five times a step,
    PR 35); no op of its own copies or transposes a K / V pool or a state
    block; the bytes donated are the tree's as computed; nothing is sent to or
    fetched from the host; and the conv operator's three scopes are there."""
    from seldon_core_tpu.models.cache import (
        state_bytes,
        init_paged_kv_caches,
        is_state_entry,
        kv_cache_bytes_per_token,
    )

    server = servers("lfm2")
    cfg = server._cfg
    slots, length = LFM2_CELL
    pages = slots * length // PAGE + 2
    assert cfg.conv_layers == (0, 1, 3)
    # the int8 tree: both projections quantized, W_in ONE [dim, 3 dim] matrix; the
    # taps, the two head norms and the selection bias float32 leaves no tree quantizes
    from seldon_core_tpu.ops.quantize import QuantizedTensor

    layers = server._params["params"]
    conv, attention = layers["layer_0"]["conv"], layers["layer_2"]["attention"]
    assert isinstance(conv["in_proj"], QuantizedTensor) and conv["in_proj"].q.shape == (2048, 6144)
    assert isinstance(conv["out_proj"], QuantizedTensor) and conv["out_proj"].q.shape == (2048, 2048)
    small = {"taps": conv["taps"], "q_norm": attention["q_norm"]["weight"],
             "k_norm": attention["k_norm"]["weight"], "bias": layers["layer_2"]["moe"]["router_bias"]}
    assert {k: (v.dtype.name, v.shape) for k, v in small.items()} == {
        "taps": ("float32", (2048, 3)), "q_norm": ("float32", (64,)), "k_norm": ("float32", (64,)),
        "bias": ("float32", (32,))}
    tree = jax.eval_shape(lambda: init_paged_kv_caches(cfg, pages, PAGE, "bf16", state_slots=slots))
    assert [is_state_entry(layer) for layer in tree] == [True, True, False, True]
    row = cfg.n_kv_heads * cfg.head_dim
    assert tree[2][0].shape == tree[2][1].shape == (pages, PAGE, row) and row == 512
    tree_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree))
    assert tree_bytes == (pages * PAGE * kv_cache_bytes_per_token(cfg, "bf16")
                          + slots * state_bytes(cfg))
    exe = compiled(server, program, v5e, slots=slots, length=length)
    hlo = exe.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    # the six leaves, each a parameter that an output aliases
    leaves = {m.group(2): int(m.group(3)) for m in re.finditer(
        r"%(pools_(\d__\d)_)[\w.]* = \S+ parameter\((\d+)\)", entry)}
    assert sorted(leaves) == ["0__0", "1__0", "2__0", "2__1", "2__2", "3__0"]
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo)}
    assert set(leaves.values()) <= aliased, (leaves, aliased)
    stats = exe.memory_analysis()
    assert tree_bytes <= stats.alias_size_in_bytes < tree_bytes + (1 << 20)
    # row-major flat rows, as they are held
    assert re.search(rf"%pools_2__0_[\w.]* = bf16\[{pages},{PAGE},{row}\]\{{2,1,0:", entry)
    state = (slots, cfg.conv_L_cache - 1, cfg.dim)
    # (a copy INSIDE a several-output fusion is no op of its own, and the parser
    # does not follow those: the step's state READ is one such fusion, which
    # takes the three blocks into the taps' layout beside the start mask)
    copies = weight_copies(hlo, {(pages, PAGE, row), (pages, PAGE, cfg.n_kv_heads, cfg.head_dim),
                                 state})
    assert [c for c in copies if " in fused_computation" not in c] == []
    assert f"[{pages},{PAGE},{cfg.n_kv_heads},{cfg.head_dim}]" not in hlo
    assert not re.search(r"\b(infeed|outfeed|send|recv|send-done|recv-done)\(", hlo)
    assert "HostCompute" not in hlo and "host_compute" not in hlo
    for scope in ("mix.conv.in", "mix.conv.taps", "mix.conv.out"):
        assert all(f"{block_scope(i)}conv/{scope}/" in hlo for i in cfg.conv_layers), scope
    assert f"{block_scope(2)}attn/attention" in hlo and "/conv/attn" not in hlo and "attn/mix.conv" not in hlo
    if program == "decode_step":
        # no gathered view of K or of V: the step walks the live pages
        assert stats.temp_size_in_bytes < slots * length * row * 2


QWEN3NEXT_CELL = (64, 8192)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_linear_attention_programs_donate_both_state_arrays_and_hold_no_floating_stack(
        v5e, servers, program):
    """Qwen3-Next's period at published widths (three Gated DeltaNet layers, one
    gated GQA layer, 128 of 512 experts held) and the cell's own shapes (64
    slots x 8,192 tokens): every leaf of the cache tree, each linear-attention
    layer's ``[slots, 3, 8192]`` conv rows AND its float32 ``[slots, 32, 128,
    128]`` matrix state as well as the attention layer's K, V and position
    pools, is an aliased output of its parameter; no op of its own copies or
    transposes a state array or a pool; no op yields a floating copy of an
    expert stack (``[128, 2048, 512]`` / ``[128, 512, 2048]``: the grouped
    matmul takes the int8 stacks as held, 128 experts, not 512); the live-page
    kernel reads the K / V pool of flat ``[pages, 64, 512]`` rows (2 heads of
    256) in the step, and every slot's S goes through the rule's kernel alone
    (ops/gated_delta.py); the temporaries stay a small part of one S; nothing is
    sent to or fetched from the host; and the operator's four scopes are there,
    outside ``attn``."""
    from seldon_core_tpu.models.cache import (
        init_paged_kv_caches,
        is_state_entry,
        kv_cache_bytes_per_token,
        state_bytes,
    )
    from seldon_core_tpu.ops.quantize import QuantizedTensor

    server = servers("qwen3next")
    cfg = server._cfg
    slots, length = QWEN3NEXT_CELL
    pages = slots * length // PAGE + 2
    assert cfg.kv_rows_flat and cfg.state_layers == (0, 1, 2) and cfg.head_dim == 256
    layers = server._params["params"]
    gdn, attention, moe = (layers["layer_0"]["linear_attn"], layers["layer_3"]["attention"],
                           layers["layer_0"]["moe"])
    # the int8 tree: the projections quantized, the stacks [128, ...] of a router 512 wide
    for name, shape in (("in_proj_qkvz", (2048, 12288)), ("in_proj_ba", (2048, 64)),
                        ("out_proj", (4096, 2048))):
        assert isinstance(gdn[name], QuantizedTensor) and gdn[name].q.shape == shape, name
    assert moe["w1"].q.shape == (128, 2048, 512) and moe["w2"].q.shape == (128, 512, 2048)
    assert moe["router"].q.shape[-2:] in ((2048, 512), (512, 2048))
    assert attention["wq_gate"].q.size == attention["wq"].q.size == 2048 * 4096
    # ... and the float32 leaves no tree quantizes or casts
    small = {"conv1d": gdn["conv1d"], "A_log": gdn["A_log"], "dt_bias": gdn["dt_bias"],
             "norm": gdn["norm"]["weight"], "q_norm": attention["q_norm"]["weight"],
             "shared_gate": moe["shared_gate"]}
    assert {k: (v.dtype.name, v.shape) for k, v in small.items()} == {
        "conv1d": ("float32", (8192, 4)), "A_log": ("float32", (32,)),
        "dt_bias": ("float32", (32,)), "norm": ("float32", (128,)), "q_norm": ("float32", (256,)),
        "shared_gate": ("float32", (2048, 1))}
    tree = jax.eval_shape(lambda: init_paged_kv_caches(cfg, pages, PAGE, "bf16", state_slots=slots))
    assert [is_state_entry(layer) for layer in tree] == [True, True, True, False]
    row = cfg.n_kv_heads * cfg.head_dim
    assert tree[3][0].shape == tree[3][1].shape == (pages, PAGE, row) and row == 512
    conv_rows, matrix = (slots, 3, 8192), (slots, 32, 128, 128)
    assert [leaf.shape for leaf in tree[0]] == [conv_rows, matrix]
    assert tree[0][1].dtype == jnp.float32 and tree[0][0].dtype == jnp.bfloat16
    tree_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree))
    assert tree_bytes == (pages * PAGE * kv_cache_bytes_per_token(cfg, "bf16")
                          + slots * state_bytes(cfg))
    exe = compiled(server, program, v5e, slots=slots, length=length)
    hlo = exe.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    # the nine leaves, each a parameter that an output aliases
    leaves = {m.group(2): int(m.group(3)) for m in re.finditer(
        r"%(pools_(\d__\d)_)[\w.]* = \S+ parameter\((\d+)\)", entry)}
    assert sorted(leaves) == ["0__0", "0__1", "1__0", "1__1", "2__0", "2__1", "3__0", "3__1", "3__2"]
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo)}
    assert set(leaves.values()) <= aliased, (leaves, aliased)
    stats = exe.memory_analysis()
    assert tree_bytes <= stats.alias_size_in_bytes < tree_bytes + (1 << 20)
    assert re.search(rf"%pools_3__0_[\w.]* = bf16\[{pages},{PAGE},{row}\]\{{2,1,0:", entry)
    assert re.search(r"%pools_0__1_[\w.]* = f32\[64,32,128,128\]\{3,2,1,0:", entry)
    # (the 3 MB of conv rows a layer ARE re-laid at the step's loop boundary:
    # the compiler holds [64, 3, 8192] slots-minor-but-one, whole sublane tiles
    # of slots and not three rows padded to a tile; 134 MB of S never is)
    copies = weight_copies(hlo, {(pages, PAGE, row), matrix, (128, 2048, 512), (128, 512, 2048)})
    assert [c for c in copies if " in fused_computation" not in c] == []
    # no floating expert stack, held or whole, as an op's output
    stacks = {(e, 2048, 512) for e in (128, 512)} | {(e, 512, 2048) for e in (128, 512)}
    assert [op for op in own_ops(hlo) if op[1] in FLOATS and op[2] in stacks] == []
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3 * 4     # three projections a layer
    assert "ragged_dot_int8" in hlo and "ragged-dot-none" not in hlo
    # one S is 134 MB: the program's scratch is a fraction of it (no second copy of a state array).
    # The step's reads 24.7 MB. The chunk's reads 69.8 MB (62.6 MB before PR 47 put a conditional
    # behind its final norm), which half of one S, 67.1 MB, no longer bounds: 72 MB does
    bound = 72_000_000 if program == "prefill_chunk" else 64 * 32 * 128 * 128 * 4 // 2
    assert stats.temp_size_in_bytes < bound
    assert not re.search(r"\b(infeed|outfeed|send|recv|send-done|recv-done)\(", hlo)
    assert "HostCompute" not in hlo and "host_compute" not in hlo
    for scope in ("mix.gdn.in", "mix.gdn.conv", "mix.gdn.rule", "mix.gdn.out"):
        assert all(f"{block_scope(i)}linear_attn/{scope}/" in hlo for i in cfg.state_layers), scope
    assert f"{block_scope(3)}attn/attention" in hlo and "/linear_attn/attn/" not in hlo
    assert "/attn/mix.gdn" not in hlo and "/attn/linear_attn" not in hlo
    if program == "decode_step":
        assert "gqa_page_attention" in hlo
        # every slot's S goes through the repo's kernel (ops/gated_delta.py) and
        # through nothing else: no fusion of the step reads or writes a whole
        # [64, 32, 128, 128] (as XLA ops the rule was two passes over it)
        assert len(re.findall(r"= \([^=]*\) custom-call\([^\n]*gated_delta_step", hlo)) == 3
        through = [op for op in own_ops(hlo) if op[1] == "f32" and op[2] in (
            matrix, (slots, 2, 16, 128, 128)) and op[3] not in ("parameter", "bitcast",
                                                               "get-tuple-element")]
        assert through == [], through


OLMOHYBRID_CELL = (32, 1024)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_a_state_that_is_not_square_lies_unpadded_and_goes_through_the_kernel(
        v5e, servers, program):
    """Olmo-Hybrid's period at published widths and the cell's own shapes (32
    slots x 1,024 tokens): each linear-attention layer's float32 matrix state is
    ``[32, 15, 96, 384]`` (two heads of [96, 192] side by side along the lanes:
    whole (8, 128) tiles, where ``[32, 30, 96, 192]`` would be tiled to 256
    lanes), a parameter that an output aliases; the STEP carries the rule's
    kernel (ops/gated_delta.py), once a linear layer, and no op of its own
    yields a whole S in either layout (the expression was two passes over it);
    the step's attention read of 30 heads walks the live pages (32 query rows,
    two of zeros); the chunk unpacks ONE slot's S; S is float32 everywhere;
    the float32 leaves stay float32."""
    from seldon_core_tpu.models.cache import init_paged_kv_caches, matrix_state_nbytes
    from seldon_core_tpu.ops.quantize import QuantizedTensor

    server = servers("olmohybrid")
    cfg = server._cfg
    slots, length = OLMOHYBRID_CELL
    pages = slots * length // PAGE + 2
    assert cfg.state_layers == (0, 1, 2) and cfg.rope_theta is None
    assert cfg.norm_placement == "branch" and cfg.linear_allow_neg_eigval
    gdn = server._params["params"]["layer_0"]["linear_attn"]
    for name, shape in (("in_proj_qkvz", (3840, 17280)), ("in_proj_ba", (3840, 60)),
                        ("out_proj", (5760, 3840))):
        assert isinstance(gdn[name], QuantizedTensor) and gdn[name].q.shape == shape, name
    small = {"conv1d": gdn["conv1d"], "A_log": gdn["A_log"], "dt_bias": gdn["dt_bias"],
             "norm": gdn["norm"]["weight"]}
    assert {k: (v.dtype.name, v.shape) for k, v in small.items()} == {
        "conv1d": ("float32", (11520, 4)), "A_log": ("float32", (30,)),
        "dt_bias": ("float32", (30,)), "norm": ("float32", (192,))}
    tree = jax.eval_shape(lambda: init_paged_kv_caches(cfg, pages, PAGE, "bf16", state_slots=slots))
    packed, a_head = (slots, 15, 96, 384), (slots, 30, 96, 192)
    assert [leaf.shape for leaf in tree[0]] == [(slots, 3, 11520), packed]
    assert tree[0][1].dtype == jnp.float32
    own, tiled = matrix_state_nbytes(tree)
    assert own == tiled == 3 * slots * 30 * 96 * 192 * 4
    exe = compiled(server, program, v5e, slots=slots, length=length)
    hlo = exe.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    assert re.search(r"%pools_0__1_[\w.]* = f32\[32,15,96,384\]\{3,2,1,0:T\(8,128\)", entry)
    leaves = {m.group(2): int(m.group(3)) for m in re.finditer(
        r"%(pools_(\d__\d)_)[\w.]* = \S+ parameter\((\d+)\)", entry)}
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo)}
    assert len(leaves) == 9 and set(leaves.values()) <= aliased, (leaves, aliased)
    # S in 16 bits, in either layout, nowhere
    assert [op for op in own_ops(hlo) if op[1] in ("bf16", "f16") and op[2][1:] in (
        packed[1:], a_head[1:])] == []
    assert not re.search(r"\b(infeed|outfeed|send|recv|send-done|recv-done)\(", hlo)
    for scope in ("mix.gdn.in", "mix.gdn.conv", "mix.gdn.rule", "mix.gdn.out"):
        assert all(f"{block_scope(i)}linear_attn/{scope}/" in hlo for i in cfg.state_layers), scope
    assert "gqa_page_attention" in hlo
    if program == "decode_step":
        assert len(re.findall(r"= \([^=]*\) custom-call\([^\n]*gated_delta_step", hlo)) == 3
        # (32 slots' S a layer are 70.8 MB: the compiler may stage the array
        # through on-chip memory around the kernel, by asynchronous copies in
        # slices that a ConcatBitcast joins: movement once each way, no arithmetic)
        through = [op for op in own_ops(hlo) if op[1] == "f32" and op[2] in (
            packed, a_head, (slots, 3, 5, 96, 384)) and op[3] not in (
                "parameter", "bitcast", "get-tuple-element", "copy-start", "copy-done")
            and "ConcatBitcast" not in op[4]]
        assert through == [], through
        # no gathered view of K or V (30 heads a token are 32 query rows)
        assert exe.memory_analysis().temp_size_in_bytes < slots * length * 3840 * 2
    else:
        assert "gated_delta_step" not in hlo
        # the chunk's one sequence: no op yields every slot's S a head a row
        assert [op for op in own_ops(hlo) if op[2] == a_head] == []


GRANITE4H_CELL = (96, 2048)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_a_mamba_layers_state_goes_through_the_kernel_once_a_layer_in_its_own_buffer(
        v5e, servers, program):
    """granite-4.0-h-micro's kinds of layer at published widths and the cell's
    own shapes (96 slots x 2,048 tokens): each mamba layer's float32 state is
    ``[96, 32, 128, 128]`` (a head's [64, 128] transposed, two heads side by side
    along the lanes: 201 MB, whole (8, 128) tiles, the model's own bytes), a
    parameter that an output aliases; the STEP carries the recurrence's kernel
    (ops/ssd.py), once a mamba layer, and no op of its own yields a whole h (the
    expression read it again for h C); the chunk continues ONE slot's h; h is
    float32 everywhere; the float32 leaves stay float32; the attention read of
    heads of 64 walks the live pages."""
    from seldon_core_tpu.models.cache import init_paged_kv_caches, matrix_state_nbytes
    from seldon_core_tpu.ops.quantize import QuantizedTensor

    server = servers("granite4h")
    cfg = server._cfg
    slots, length = GRANITE4H_CELL
    pages = slots * length // PAGE + 2
    assert cfg.state_layers == (0, 1, 3) and cfg.rope_theta is None and cfg.tie_embeddings
    mamba = server._params["params"]["layer_0"]["mamba"]
    for name, shape in (("in_proj", (2048, 8512)), ("out_proj", (4096, 2048))):
        assert isinstance(mamba[name], QuantizedTensor) and mamba[name].q.shape == shape, name
    small = {name: mamba[name] for name in ("conv1d", "conv_bias", "heads")}
    small["norm"] = mamba["norm"]["weight"]
    assert {k: (v.dtype.name, v.shape) for k, v in small.items()} == {
        "conv1d": ("float32", (4352, 4)), "conv_bias": ("float32", (4352,)),
        "heads": ("float32", (3, 64)), "norm": ("float32", (4096,))}
    assert "lm_head" not in server._params["params"]
    tree = jax.eval_shape(lambda: init_paged_kv_caches(cfg, pages, PAGE, "bf16", state_slots=slots))
    state, a_head = (slots, 32, 128, 128), ((slots, 64, 128, 64), (slots, 64, 64, 128))
    assert [leaf.shape for leaf in tree[0]] == [(slots, 3, 4352), state]
    assert tree[0][1].dtype == jnp.float32
    own, tiled = matrix_state_nbytes(tree)
    assert own == tiled == 3 * slots * 64 * 64 * 128 * 4
    exe = compiled(server, program, v5e, slots=slots, length=length)
    hlo = exe.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    assert re.search(r"%pools_0__1_[\w.]* = f32\[96,32,128,128\]\{3,2,1,0:T\(8,128\)", entry)
    leaves = {m.group(2): int(m.group(3)) for m in re.finditer(
        r"%(pools_(\d__\d)_)[\w.]* = \S+ parameter\((\d+)\)", entry)}
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo)}
    assert len(leaves) == 9 and set(leaves.values()) <= aliased, (leaves, aliased)
    # h in 16 bits nowhere
    assert [op for op in own_ops(hlo) if op[1] in ("bf16", "f16") and op[2][1:] in (
        state[1:], a_head[0][1:], a_head[1][1:])] == []
    assert not re.search(r"\b(infeed|outfeed|send|recv|send-done|recv-done)\(", hlo)
    for scope in ("mix.ssd.in", "mix.ssd.conv", "mix.ssd.rule", "mix.ssd.out"):
        assert all(f"{block_scope(i)}mamba/{scope}/" in hlo for i in cfg.state_layers), scope
    assert "gqa_page_attention" in hlo
    if program == "decode_step":
        assert len(re.findall(r"= \([^=]*\) custom-call\([^\n]*ssd_step", hlo)) == 3
        # no arithmetic over every slot's h outside the kernel (201 MB a layer:
        # nothing stages it through on-chip memory either)
        through = [op for op in own_ops(hlo) if op[1] == "f32" and op[2] in (
            state, (slots, 2, 16, 128, 128), *a_head) and op[3] not in (
                "parameter", "bitcast", "get-tuple-element", "copy-start", "copy-done")
            and "ConcatBitcast" not in op[4]]
        assert through == [], through
        # no gathered view of K or V, and no second copy of a layer's h
        assert exe.memory_analysis().temp_size_in_bytes < slots * 64 * 64 * 128 * 4
    else:
        assert not re.search(r"custom-call\([^\n]*ssd_step", hlo)
        # the chunk's one sequence: the only op over every slot's h is the
        # in-place update of ONE slot's rows, and none holds it a head a row
        assert {op[3] for op in own_ops(hlo) if op[1] == "f32" and op[2] == state} <= {
            "parameter", "bitcast", "get-tuple-element", "dynamic-update-slice"}
        assert [op for op in own_ops(hlo) if op[2] in a_head] == []


PHI4FLASH_CELL = (32, 16384)


@pytest.mark.parametrize("program", ["decode_step", "wide_chunk"])
def test_the_shared_pool_is_read_in_place_and_a_chunk_stops_its_rows_half_way_up(
        v5e, servers, program):
    """Phi-4-mini-flash-reasoning's kinds of layer at published widths and the
    cell's own shapes (32 slots x 16,384 tokens). ONE full page class entry (layer
    5's, 8,194 pages of flat rows of 1,280), two window-class entries, three state
    blocks ``(conv rows [32, 3, 5120], h [32, 16, 5120] float32)`` and two EMPTY
    entries; every leaf a parameter that an output aliases. The full layer and
    the cross layer both walk the live pages of the one pool with the repo's
    kernel (no array of a whole block-table view's shape); h is float32
    everywhere. The wide chunk's scan is the repo's kernel, once an s6 layer,
    and the layers past the shared pool's (the gated memory unit, the cross
    layer), the final norm and the head run INSIDE the conditional that skips
    the head, on one row: no op of theirs is outside it."""
    from seldon_core_tpu.models.cache import init_paged_kv_caches, state_bytes
    from seldon_core_tpu.ops.quantize import QuantizedTensor
    from seldon_core_tpu.ops.selective_scan import KERNEL_NAME

    server = servers("phi4flash")
    cfg = server._cfg
    slots, length = PHI4FLASH_CELL
    pages = slots * length // PAGE + 2
    assert cfg.state_layers == (0, 2, 4, 6, 7) and cfg.window_layers == (1, 3)
    assert cfg.read_heads == (40, 10, 128) and cfg.kv_rows_flat
    s6 = server._params["params"]["layer_0"]["s6"]
    for name, shape in (("in_proj", (2560, 10240)), ("x_proj", (5120, 192)),
                        ("dt_proj", (160, 5120)), ("out_proj", (5120, 2560))):
        assert isinstance(s6[name], QuantizedTensor) and s6[name].q.shape == shape, name
    assert {k: (s6[k].dtype.name, s6[k].shape) for k in ("conv1d", "conv_bias", "A_log_t",
                                                          "b_dt", "D")} == {
        "conv1d": ("float32", (5120, 4)), "conv_bias": ("float32", (5120,)),
        "A_log_t": ("float32", (16, 5120)), "b_dt": ("float32", (5120,)),
        "D": ("float32", (5120,))}
    attention = server._params["params"]["layer_7"]["attention"]
    assert sorted(attention) == ["bo", "bq", "lambdas", "subln", "wo", "wq"]
    assert attention["lambdas"].shape == (4, 64) and attention["lambdas"].dtype == jnp.float32
    tree = jax.eval_shape(lambda: init_paged_kv_caches(
        cfg, pages, PAGE, "bf16", state_slots=slots,
        window_pages=window_pool_pages(cfg, slots)))
    assert [leaf.shape for leaf in tree[0]] == [(slots, 3, 5120), (slots, 16, 5120)]
    assert tree[0][1].dtype == jnp.float32 and tree[6] == () and tree[7] == ()
    assert [leaf.shape for leaf in tree[5]] == [(pages, PAGE, 1280)] * 2 + [(pages, PAGE)]
    assert tree[1][0].shape == (window_pool_pages(cfg, slots), PAGE, 1280)
    assert state_bytes(cfg) == 3 * (16 * 5120 * 4 + 3 * 5120 * 2)
    exe = compiled(server, program, v5e, slots=slots, length=length)
    hlo = exe.as_text()
    entry = hlo[hlo.index("\nENTRY"):]
    leaves = {m.group(2): int(m.group(3)) for m in re.finditer(
        r"%(pools_(\d__\d)_)[\w.]* = \S+ parameter\((\d+)\)", entry)}
    aliased = {int(n) for n in re.findall(r"\}: \((\d+), \{\}, (?:may|must)-alias\)", hlo)}
    assert len(leaves) == 3 * 2 + 3 * 3 and set(leaves.values()) <= aliased, (leaves, aliased)
    assert not re.search(r"\b(infeed|outfeed|send|recv|send-done|recv-done)\(", hlo)
    # no gathered view of the shared pool or of a window layer's, no h in 16 bits
    view = [(1, length, 1280), (slots, length, 1280), (1, length, 10, 128),
            (slots, length, 10, 128)]
    assert floating_arrays(hlo, view) == []
    assert [op for op in own_ops(hlo) if op[1] in ("bf16", "f16") and op[2][1:] == (16, 5120)] == []
    kernels = len(re.findall(r"custom-call\([^\n]*gqa_page_attention", hlo))
    assert kernels >= 4, kernels           # two window layers, the full layer, the cross layer
    scans = len(re.findall(r"custom-call\([^\n]*" + KERNEL_NAME, hlo))
    if program == "decode_step":
        assert scans == 0
        assert exe.memory_analysis().temp_size_in_bytes < 1 << 30
    else:
        assert scans == 3
        # the cross-decoder is the conditional's: the one branch that computes
        # holds the gated memory unit, the cross read and the head's product
        def body(name):     # a computation's own lines
            start = hlo.index(f"%{name} (")
            return hlo[start:hlo.index("\n}", start)]

        zeros, taken = conditional_branches(hlo)
        assert "mix.gmu" in body(taken) and "attn.cross.read" in body(taken)
        assert "mix.gmu" not in body(zeros) + entry and "attn.cross.read" not in entry
        assert "mix.s6.scan" in entry and "mix.s6.scan" not in body(taken)
        # the cross-decoder's int8 matrices go into the conditional as they are held
        # (dequantized ahead of it, an FFN's three matrices a layer were written out
        # whole every chunk: 8 ms of a 30 ms chunk on the chip, PR 55): no op of the
        # entry computation yields a floating FFN matrix
        assert not re.search(r"= (?:f32|bf16)\[(?:2560,10240|10240,2560)\]", entry)


@pytest.mark.parametrize("rows", [256, WIDE])
def test_the_scan_kernel_compiles_at_the_published_sizes(v5e, rows):
    """Phi-4-mini-flash's [16, 5120] over a chunk's and a wide chunk's rows."""
    from seldon_core_tpu.ops.selective_scan import KERNEL_NAME, plan, scan_kernel

    d, n = 5120, 16
    walk = plan(rows, d, n)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=v5e)

    exe = jax.jit(
        lambda x, delta, A, B, C, state: scan_kernel(x, delta, A, B, C, state, walk,
                                                     interpret=False)
    ).lower(shape(1, rows, d), shape(1, rows, d), shape(n, d), shape(1, rows, n),
            shape(1, rows, n), shape(1, n, d)).compile()
    assert KERNEL_NAME in exe.as_text()
