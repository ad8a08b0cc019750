"""What one call of Olmo-Hybrid-7B's step programs needs, from shapes AND what
the run observed (the linear-attention layers' live rows from the program's
counters, the live cache from the page gauge).

The block (configs/olmo-hybrid-7b-int8.json): a token mixer that is a Gated
DeltaNet in `layer_types`' "linear_attention" layers (W_q, W_k [d, H dk], W_v, W_g
[d, H dv] held as one W_qkvz [d, 2 H dk + 2 H dv]; W_b, W_a as one W_ba [d, 2 H];
four depthwise taps over the 2 H dk + H dv channels of [q ; k ; v] with three rows
of state a slot; the delta rule over a float32 matrix state [dk, dv] = [96, 192] a
head a slot; W_o [H dv, d]) and plain multi-head attention elsewhere (30 heads of
128, K and V of their own, no rotation); then in EVERY layer a dense SwiGLU of
width `intermediate_size`.  The norms stand on the branches and are rows.

**The Gated DeltaNet's count (`gdn_chunk`, `gdn_state`) is the least any
formulation moves, and the MODEL's**: `gdn_state` counts 2 x rows x 30 x 96 x 192
x 4 B a layer whatever layout the program holds S in, so a padded layout reads as
a LOWER share of the roofline, never a higher one.  FLOPs: the products, and the
RECURRENT form of the rule, 3 x 2 x dk x dv a head a row; the chunked form's extra
products are the formulation's own and are not counted (work/qwen3_next.py).
"""

from __future__ import annotations

from readers import scrape
from work.deepseek_v2 import chunk_shapes
from work.lfm2 import attention_flops, kv_row_bytes  # the same keys of the configuration's file
# the delta rule's sizes, its counters' reading and its recurrent FLOPs are
# Qwen3-Next's (the same keys of the configuration's file, the same counters)
from work.qwen3_next import (DEFAULT_PAGE, ITEM_BYTES, STATE_ITEM_BYTES, TAP_ITEM_BYTES,  # noqa: F401
                             gdn_dims, gdn_seen, rule_flops)


def kinds(cfg: dict) -> dict:
    linear = sum(kind == "linear_attention" for kind in cfg["layer_types"])
    return {"gdn": linear, "attention": cfg["num_hidden_layers"] - linear,
            "ffn": cfg["num_hidden_layers"]}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications by kind of layer, and their
    output channels (a float32 scale each)."""
    d, g, ffn = cfg["hidden_size"], gdn_dims(cfg), cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    heads = cfg["linear_num_value_heads"]
    return {"gdn": d * (g["channels"] + g["value"]) + d * 2 * heads + g["value"] * d,
            "gdn_channels": g["channels"] + g["value"] + 2 * heads + d,
            "attention": d * q + 2 * d * kv + q * d, "attention_channels": q + 2 * kv + d,
            "ffn": 3 * d * ffn, "ffn_channels": 2 * ffn + d,
            "head": d * cfg["vocab_size"]}


def params_total(cfg: dict) -> int:
    """The model's matrix parameters: every layer's products, the table and the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in n) + 2 * lin["head"]


def per_token_linear(cfg: dict) -> float:
    """Multiply-adds a token needs outside attention proper, the delta rule and the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in n)


def linear_bytes(cfg: dict) -> float:
    """Those weights once, int8 with a float32 scale a channel, the taps, and the head."""
    lin, n, g = linear_params(cfg), kinds(cfg), gdn_dims(cfg)
    return (sum(n[kind] * (lin[kind] + 4 * lin[kind + "_channels"]) for kind in n)
            + n["gdn"] * g["channels"] * cfg["linear_conv_kernel_dim"] * TAP_ITEM_BYTES
            + lin["head"] + 4 * cfg["vocab_size"])


# ---- the ops under mix.gdn.* (perf/readers/hlo_scopes.py) ------------------
def matrix_state_bytes(cfg: dict, sequences: float) -> float:
    """The MODEL's float32 S of `sequences` sequences of one layer, once each way:
    2 x 30 x 96 x 192 x 4 = 4,423,680 B a sequence."""
    return 2.0 * sequences * gdn_dims(cfg)["state"] * STATE_ITEM_BYTES


def state_bytes(cfg: dict, sequences: float) -> float:
    """Both state arrays of `sequences` sequences of one layer, once each way."""
    conv = (cfg["linear_conv_kernel_dim"] - 1) * gdn_dims(cfg)["channels"] * ITEM_BYTES
    return 2.0 * sequences * conv + matrix_state_bytes(cfg, sequences)


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
    and written once, as the MODEL counts it (no padding of any layout)."""
    seen = gdn_seen(ctx, "decode")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    return {"flops": layers * rule_flops(cfg, rows),
            "bytes": layers * matrix_state_bytes(cfg, rows)}


# ---- the whole programs (perf/readers/device.py roofline) ------------------
def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw: the plain matmuls
    and the taps for the live rows, the rule's recurrent form, the attention
    layers over the chunk's causal pairs, the head once a prompt; one slot's two
    state arrays both ways.  MXU-bound."""
    cfg = ctx.config
    shapes = chunk_shapes(ctx)
    if shapes is None:
        return None
    n, g = kinds(cfg), gdn_dims(cfg)
    prompts = sum(1 for _p, rows, context in shapes if context == rows)
    pairs = sum(p for p, _n, _c in shapes) / len(shapes)
    rows = sum(r for _p, r, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    taps = n["gdn"] * rows * 2.0 * cfg["linear_conv_kernel_dim"] * g["channels"]
    return {"flops": 2.0 * rows * per_token_linear(cfg) + taps
            + n["gdn"] * rule_flops(cfg, rows) + n["attention"] * attention_flops(cfg, pairs)
            + 2.0 * linear_params(cfg)["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + n["gdn"] * state_bytes(cfg, 1)
            + n["attention"] * context * kv_row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every int8 weight once, the embedding rows, the live
    K/V rows of the attention layers once, the live slots' two state arrays
    both ways.  HBM-bound."""
    cfg = ctx.config
    seen = gdn_seen(ctx, "decode")
    pages = scrape.gauge_mean(ctx.scrapes, "seldon_llm_kv_pages_in_use")
    if seen is None or pages is None:
        return None
    n, lin, rows = kinds(cfg), linear_params(cfg), seen["rows"]
    kv_tokens = pages * (cfg["server"].get("kv_page_size") or DEFAULT_PAGE)
    return {"flops": 2.0 * rows * (per_token_linear(cfg) + lin["head"])
            + n["gdn"] * rule_flops(cfg, rows)
            + n["attention"] * attention_flops(cfg, kv_tokens),
            "bytes": linear_bytes(cfg) + rows * cfg["hidden_size"]
            + n["gdn"] * state_bytes(cfg, rows)
            + n["attention"] * kv_tokens * kv_row_bytes(cfg)}
