"""What one call of LFM2-8B-A1B's step programs needs, from shapes AND what the
run observed (the routing and the conv layers' live rows from the program's
counters, the chunks' offsets from the flight recorder, the live cache from the
page gauge).

The block (configs/lfm2-8b-a1b-int8.json): a token mixer that is a gated short
convolution in `layer_types`' "conv" layers (W_in [d, 3d], three depthwise taps
over z = B * X with two rows of state a slot, W_out [d, d]) and GQA with a norm
per head elsewhere (32 query / 8 KV heads of 64); then a dense SwiGLU of
`intermediate_size` in the first `num_dense_layers` layers and
`num_experts_per_tok` of `num_experts` experts of `moe_intermediate_size` in the
others (sigmoid scores, a selection bias; the same counters as OLMoE's).

**The conv operator's count (a chunk's: `conv_chunk`) is the least any formulation moves**: per conv layer
W_in and W_out once (int8 values and a float32 scale a channel), the taps once
(float32), the live rows' input read and output written once (bf16), the live
sequences' state read and written once.  FLOPs: 2 x rows x d x 4d for the two
products, and 2 x 3 + 2 multiply-adds a channel a row for the taps and gates.
"""

from __future__ import annotations

from readers import loop, scrape
from work.deepseek_v2 import chunk_shapes
from work.olmoe import routing   # the same counters: calls, live rows, routed pairs, experts touched

ITEM_BYTES = 2         # bf16 activations, state and cache
TAP_ITEM_BYTES = 4     # the taps stay float32
DEFAULT_PAGE = 64


def kinds(cfg: dict) -> dict:
    conv = sum(kind == "conv" for kind in cfg["layer_types"])
    return {"conv": conv, "attention": cfg["num_hidden_layers"] - conv,
            "dense": cfg["num_dense_layers"],
            "moe": cfg["num_hidden_layers"] - cfg["num_dense_layers"]}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications (everything but the routed
    experts) by kind of layer, and their output channels (a float32 scale each)."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"conv": 4 * d * d, "conv_channels": 4 * d,
            "attention": d * q + 2 * d * kv + q * d, "attention_channels": q + 2 * kv + d,
            "dense": 3 * d * cfg["intermediate_size"],
            "dense_channels": 2 * cfg["intermediate_size"] + d,
            "moe": d * cfg["num_experts"], "moe_channels": cfg["num_experts"],
            "head": d * cfg["vocab_size"]}


def per_token_linear(cfg: dict) -> float:
    """Multiply-adds a token needs outside attention proper, the routed
    experts and the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in ("conv", "attention", "dense", "moe"))


def linear_bytes(cfg: dict) -> float:
    """Those weights once, int8 with a float32 scale a channel, the taps, and
    the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return (sum(n[kind] * (lin[kind] + 4 * lin[kind + "_channels"])
                for kind in ("conv", "attention", "dense", "moe"))
            + n["conv"] * cfg["hidden_size"] * cfg["conv_L_cache"] * TAP_ITEM_BYTES
            + lin["head"] + 4 * cfg["vocab_size"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_ffn_cost(cfg: dict, touched: float, pairs: float) -> dict:
    """The routed experts of one call, all MoE layers (work/olmoe.py's count):
    `touched` experts read, `pairs` (token, expert) rows computed."""
    expert_bytes = expert_params(cfg) + 4 * (2 * cfg["moe_intermediate_size"] + cfg["hidden_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg),
            "bytes": touched * expert_bytes + pairs * 2 * 2 * cfg["hidden_size"]}


def moe_ffn_decode(ctx) -> dict | None:
    seen = routing(ctx, "decode")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def moe_ffn_chunk(ctx) -> dict | None:
    seen = routing(ctx, "chunk")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


# ---- the ops under mix.conv.* (perf/readers/hlo_scopes.py) -----------------
def conv_seen(ctx, program: str) -> dict | None:
    """Per call of `program` in the window, from `seldon_llm_conv_*`: the live
    rows each conv layer mixed, and the conv layers."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    label = f'program="{program}"'
    rows = loop.delta(pair, "seldon_llm_conv_rows_total", label)
    layer_calls = loop.delta(pair, "seldon_llm_conv_layer_calls_total", label)
    layers = kinds(ctx.config)["conv"]
    if not rows or not layer_calls:
        return None
    return {"rows": rows / (layer_calls / layers), "layers": layers}


def conv_chunk(ctx) -> dict | None:
    """A chunk's conv layers: the live rows are ONE sequence's, so one state
    block is read and written a layer."""
    seen = conv_seen(ctx, "chunk")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    d, lin = cfg["hidden_size"], linear_params(cfg)
    taps, state_rows = cfg["conv_L_cache"], cfg["conv_L_cache"] - 1
    return {"flops": layers * rows * (2.0 * lin["conv"] + 2.0 * (taps + 2) * d),
            "bytes": layers * (lin["conv"] + 4 * lin["conv_channels"] + d * taps * TAP_ITEM_BYTES
                               + rows * 2 * d * ITEM_BYTES + 2 * state_rows * d * ITEM_BYTES)}


# ---- the whole programs (perf/readers/device.py roofline) ------------------
def attention_flops(cfg: dict, pairs: float) -> float:
    """One attention layer's scores and products over `pairs` causal (query, key) pairs."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def kv_row_bytes(cfg: dict) -> int:
    """One cached token of one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEM_BYTES


def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw: the plain
    matmuls (the conv layers' among them) for the live rows, the routed
    experts as the counters saw them, the six attention layers over the
    chunk's causal pairs, the head once a prompt.  MXU-bound."""
    cfg = ctx.config
    shapes, seen = chunk_shapes(ctx), routing(ctx, "chunk")
    if shapes is None or seen is None:
        return None
    n = kinds(cfg)
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    prompts = sum(1 for _p, rows, context in shapes if context == rows)
    pairs = sum(p for p, _n, _c in shapes) / len(shapes)
    rows = sum(r for _p, r, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    taps = n["conv"] * rows * 2.0 * (cfg["conv_L_cache"] + 2) * cfg["hidden_size"]
    return {"flops": 2.0 * rows * per_token_linear(cfg) + experts["flops"] + taps
            + n["attention"] * attention_flops(cfg, pairs)
            + 2.0 * linear_params(cfg)["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + experts["bytes"]
            + n["attention"] * context * kv_row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every plain int8 weight once, the experts the step
    TOUCHED, the embedding rows, the live K/V rows of the six attention layers
    once, the live slots' conv state both ways.  HBM-bound."""
    cfg = ctx.config
    seen = routing(ctx, "decode")
    pages = scrape.gauge_mean(ctx.scrapes, "seldon_llm_kv_pages_in_use")
    if seen is None or pages is None:
        return None
    n, lin, rows = kinds(cfg), linear_params(cfg), seen["live_rows"]
    kv_tokens = pages * (cfg["server"].get("kv_page_size") or DEFAULT_PAGE)
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    state = n["conv"] * rows * 2 * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * ITEM_BYTES
    return {"flops": 2.0 * rows * (per_token_linear(cfg) + lin["head"]) + experts["flops"]
            + n["attention"] * attention_flops(cfg, kv_tokens),
            "bytes": linear_bytes(cfg) + experts["bytes"] + rows * cfg["hidden_size"] + state
            + n["attention"] * kv_tokens * kv_row_bytes(cfg)}
