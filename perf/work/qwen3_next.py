"""What one call of Qwen3-Next-80B-A3B's step programs needs, from shapes AND
what the run observed (the routing and the linear-attention layers' live rows
from the program's counters, the live cache from the page gauge).

The block (configs/qwen3-next-80b-a3b-int8.json): a token mixer that is a Gated
DeltaNet in `layer_types`' "linear_attention" layers (W_qkvz [d, 2 Hk dk + 2 Hv dv],
W_ba [d, 2 Hv], four depthwise taps over the 2 Hk dk + Hv dv channels of
[q ; k ; v] with three rows of state a slot, the delta rule over a float32 matrix
state [dk, dv] a value head a slot, W_out [Hv dv, d]) and gated GQA elsewhere
(16 query heads each with a gate, 2 KV heads, of 256); then in EVERY layer a
router over `num_experts_published` experts of which `num_experts` are HELD on
this chip (`num_experts_per_tok` a token, a pair whose expert lies elsewhere
costs nothing here; the counters count held pairs and held experts touched) and
one shared SwiGLU behind a scalar gate.

**The Gated DeltaNet's count (`gdn_chunk`, `gdn_state`) is the least any
formulation moves**: per layer the three matrices once (int8 values and a
float32 scale a channel), the taps once (float32), the live rows in and out once
(bf16), and both state arrays of the live sequences once each way.  FLOPs: the
three products, and the RECURRENT form of the rule, 3 x 2 x dk x dv a value head a
row (S^T k, k d^T, S^T q); the chunked form's extra products (the triangular
system, the intra-chunk scores) are the formulation's own and are not counted.
"""

from __future__ import annotations

from readers import loop, scrape
# the routed experts' and the GQA layers' counts are LFM2's (the same keys of the
# configuration's file; the counters count HELD pairs and HELD experts touched)
from work.deepseek_v2 import chunk_shapes
from work.lfm2 import (attention_flops, expert_ffn_cost, kv_row_bytes,  # noqa: F401
                       moe_ffn_chunk, moe_ffn_decode)
from work.olmoe import routing

ITEM_BYTES = 2         # bf16 activations, conv state and cache
STATE_ITEM_BYTES = 4   # S stays float32
TAP_ITEM_BYTES = 4     # the taps stay float32
DEFAULT_PAGE = 64


def kinds(cfg: dict) -> dict:
    linear = sum(kind == "linear_attention" for kind in cfg["layer_types"])
    return {"gdn": linear, "attention": cfg["num_hidden_layers"] - linear,
            "moe": cfg["num_hidden_layers"]}


def gdn_dims(cfg: dict) -> dict:
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return {"key": key, "value": value, "channels": 2 * key + value,
            "state": cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"]}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications (everything but the routed
    experts) by kind of layer, and their output channels (a float32 scale each)."""
    d, g = cfg["hidden_size"], gdn_dims(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shared = cfg["shared_expert_intermediate_size"]
    heads = cfg["linear_num_value_heads"]
    return {"gdn": d * (g["channels"] + g["value"]) + d * 2 * heads + g["value"] * d,
            "gdn_channels": g["channels"] + g["value"] + 2 * heads + d,
            # the query projection makes a gate a head beside the query
            "attention": 2 * d * q + 2 * d * kv + q * d, "attention_channels": 2 * q + 2 * kv + d,
            # router (as wide as the PUBLISHED expert count), shared expert, its scalar gate
            "moe": d * cfg["num_experts_published"] + 3 * d * shared + d,
            "moe_channels": cfg["num_experts_published"] + 2 * shared + d + 1,
            "head": d * cfg["vocab_size"]}


def per_token_linear(cfg: dict) -> float:
    """Multiply-adds a token needs outside attention proper, the delta rule,
    the routed experts and the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in ("gdn", "attention", "moe"))


def linear_bytes(cfg: dict) -> float:
    """Those weights once, int8 with a float32 scale a channel, the taps, and
    the head."""
    lin, n, g = linear_params(cfg), kinds(cfg), gdn_dims(cfg)
    return (sum(n[kind] * (lin[kind] + 4 * lin[kind + "_channels"])
                for kind in ("gdn", "attention", "moe"))
            + n["gdn"] * g["channels"] * cfg["linear_conv_kernel_dim"] * TAP_ITEM_BYTES
            + lin["head"] + 4 * cfg["vocab_size"])


# ---- the ops under mix.gdn.* (perf/readers/hlo_scopes.py) ------------------
def gdn_seen(ctx, program: str) -> dict | None:
    """Per call of `program` in the window, from `seldon_llm_gdn_*`: the live
    rows each linear-attention layer mixed, and those layers."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    label = f'program="{program}"'
    rows = loop.delta(pair, "seldon_llm_gdn_rows_total", label)
    layer_calls = loop.delta(pair, "seldon_llm_gdn_layer_calls_total", label)
    layers = kinds(ctx.config)["gdn"]
    if not rows or not layer_calls:
        return None
    return {"rows": rows / (layer_calls / layers), "layers": layers}


def rule_flops(cfg: dict, rows: float) -> float:
    """The recurrent form a row a layer: S^T k, k d^T and S^T q a value head."""
    return rows * 3 * 2.0 * gdn_dims(cfg)["state"]


def state_bytes(cfg: dict, sequences: float) -> float:
    """Both state arrays of `sequences` sequences of one layer, once each way."""
    g = gdn_dims(cfg)
    conv = (cfg["linear_conv_kernel_dim"] - 1) * g["channels"] * ITEM_BYTES
    return 2.0 * sequences * (conv + g["state"] * STATE_ITEM_BYTES)


def gdn_chunk(ctx) -> dict | None:
    """A chunk's linear-attention layers, everything under mix.gdn: the live
    rows are ONE sequence's, so one slot's state is read and written a layer."""
    seen = gdn_seen(ctx, "chunk")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    d, lin, g = cfg["hidden_size"], linear_params(cfg), gdn_dims(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    return {"flops": layers * (rows * (2.0 * lin["gdn"] + 2.0 * taps * g["channels"])
                               + rule_flops(cfg, rows)),
            "bytes": layers * (lin["gdn"] + 4 * lin["gdn_channels"]
                               + g["channels"] * taps * TAP_ITEM_BYTES
                               + rows * 2 * d * ITEM_BYTES + state_bytes(cfg, 1))}


def gdn_state(ctx) -> dict | None:
    """The ops under mix.gdn.rule in a decode step: the live slots' S read once
    and written once (the rule's other operands are a few rows a slot).  The
    projections' weights are NOT in it: the step prefetches them outside the
    marks (PERF.md section 7, conv_step_roofline's lesson)."""
    seen = gdn_seen(ctx, "decode")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    return {"flops": layers * rule_flops(cfg, rows),
            "bytes": layers * 2.0 * rows * gdn_dims(cfg)["state"] * STATE_ITEM_BYTES}


# ---- the whole programs (perf/readers/device.py roofline) ------------------
def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw: the plain
    matmuls and the taps for the live rows, the rule's recurrent form, the HELD
    experts as the counters saw them, the attention layers over the chunk's
    causal pairs, the head once a prompt; one slot's two state arrays both
    ways.  MXU-bound."""
    cfg = ctx.config
    shapes, seen = chunk_shapes(ctx), routing(ctx, "chunk")
    if shapes is None or seen is None:
        return None
    n, g = kinds(cfg), gdn_dims(cfg)
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    prompts = sum(1 for _p, rows, context in shapes if context == rows)
    pairs = sum(p for p, _n, _c in shapes) / len(shapes)
    rows = sum(r for _p, r, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    taps = n["gdn"] * rows * 2.0 * cfg["linear_conv_kernel_dim"] * g["channels"]
    return {"flops": 2.0 * rows * per_token_linear(cfg) + experts["flops"] + taps
            + n["gdn"] * rule_flops(cfg, rows) + n["attention"] * attention_flops(cfg, pairs)
            + 2.0 * linear_params(cfg)["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + experts["bytes"] + n["gdn"] * state_bytes(cfg, 1)
            + n["attention"] * context * kv_row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every plain int8 weight once, the HELD experts the step
    touched, the embedding rows, the live K/V rows of the attention layers
    once, the live slots' two state arrays both ways.  HBM-bound."""
    cfg = ctx.config
    seen = routing(ctx, "decode")
    pages = scrape.gauge_mean(ctx.scrapes, "seldon_llm_kv_pages_in_use")
    if seen is None or pages is None:
        return None
    n, lin, rows = kinds(cfg), linear_params(cfg), seen["live_rows"]
    kv_tokens = pages * (cfg["server"].get("kv_page_size") or DEFAULT_PAGE)
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    return {"flops": 2.0 * rows * (per_token_linear(cfg) + lin["head"]) + experts["flops"]
            + n["gdn"] * rule_flops(cfg, rows)
            + n["attention"] * attention_flops(cfg, kv_tokens),
            "bytes": linear_bytes(cfg) + experts["bytes"] + rows * cfg["hidden_size"]
            + n["gdn"] * state_bytes(cfg, rows)
            + n["attention"] * kv_tokens * kv_row_bytes(cfg)}
