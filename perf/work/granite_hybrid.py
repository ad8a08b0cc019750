"""What one call of granite-4.0-h-micro's step programs needs, from shapes AND
what the run observed (the mamba layers' live rows from the program's counters,
the live cache from the page gauge).

The block (configs/granite-4.0-h-micro-int8.json): a token mixer that is a
Mamba-2 state-space mixer in `layer_types`' "mamba" layers (W_in [d, 2 d_inner +
2 G N + H] = [2048, 8512]; four depthwise taps and a bias over the d_inner + 2 G N
= 4,352 channels of [x ; B ; C] with three rows of state a slot; the recurrence
over a float32 matrix state [P, N] = [64, 128] a head a slot, 64 heads; W_out
[4096, 2048]) and position-free GQA elsewhere (32 query / 8 KV heads of 64); then
in EVERY layer a dense gated MLP of width `shared_intermediate_size` (W_i
[2048, 16384] = [gate ; up], W_o [8192, 2048]).  The table [100352, 2048] is the
head too (tied): ONE int8 leaf, counted once as held, read whole by every decode
step's head product.

**The mixer's count (`ssd_chunk`, `ssd_state`) is the least any formulation moves,
and the MODEL's**: `ssd_state` counts 2 x rows x 64 x 64 x 128 x 4 B a layer
(2,097,152 B a slot a layer, each way) whatever the program holds beside it.
FLOPs: the products, and the RECURRENT form of the rule a head a row (the decay,
(dt x) B^T and h C: 3 x 2 x P x N); the chunked form's decay-masked products are
the formulation's own and are not counted.
"""

from __future__ import annotations

from readers import loop, scrape
from work.deepseek_v2 import chunk_shapes
from work.lfm2 import attention_flops, kv_row_bytes  # the same keys of the configuration's file

ITEM_BYTES = 2         # bf16 activations, conv rows and cache
STATE_ITEM_BYTES = 4   # h stays float32
TAP_ITEM_BYTES = 4     # the taps and the small leaves stay float32
DEFAULT_PAGE = 64


def kinds(cfg: dict) -> dict:
    mamba = sum(kind == "mamba" for kind in cfg["layer_types"])
    return {"ssd": mamba, "attention": cfg["num_hidden_layers"] - mamba,
            "ffn": cfg["num_hidden_layers"]}


def ssd_dims(cfg: dict) -> dict:
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = heads * p
    return {"inner": inner, "channels": inner + 2 * cfg["mamba_n_groups"] * n,
            "state": heads * p * n}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications by kind of layer, and their
    output channels (a float32 scale each)."""
    d, s, ffn = cfg["hidden_size"], ssd_dims(cfg), cfg["shared_intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    proj = s["inner"] + s["channels"] + cfg["mamba_n_heads"]
    return {"ssd": d * proj + s["inner"] * d, "ssd_channels": proj + d,
            "attention": d * q + 2 * d * kv + q * d, "attention_channels": q + 2 * kv + d,
            "ffn": 3 * d * ffn, "ffn_channels": 2 * ffn + d,
            "head": d * cfg["vocab_size"]}


def params_total(cfg: dict) -> int:
    """The model's matrix parameters AS HELD: every layer's products and the
    table once (it is the head too)."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in n) + lin["head"]


def per_token_linear(cfg: dict) -> float:
    """Multiply-adds a token needs outside attention proper, the recurrence and the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in n)


def small_leaf_bytes(cfg: dict) -> float:
    """A mamba layer's float32 leaves: the taps and the bias a channel, A_log,
    dt_bias and D a head, the gated norm's weight."""
    s = ssd_dims(cfg)
    return TAP_ITEM_BYTES * (s["channels"] * (cfg["mamba_d_conv"] + 1)
                             + 3 * cfg["mamba_n_heads"] + s["inner"])


def linear_bytes(cfg: dict) -> float:
    """Those weights once, int8 with a float32 scale a channel, the mamba
    layers' small leaves, and the table read as the head."""
    lin, n = linear_params(cfg), kinds(cfg)
    return (sum(n[kind] * (lin[kind] + 4 * lin[kind + "_channels"]) for kind in n)
            + n["ssd"] * small_leaf_bytes(cfg) + lin["head"] + 4 * cfg["hidden_size"])


# ---- the ops under mix.ssd.* (perf/readers/hlo_scopes.py) ------------------
def ssd_seen(ctx, program: str) -> dict | None:
    """Per call of `program` in the window, from `seldon_llm_ssd_*`: the live
    rows each mamba layer mixed, and those layers."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    label = f'program="{program}"'
    rows = loop.delta(pair, "seldon_llm_ssd_rows_total", label)
    layer_calls = loop.delta(pair, "seldon_llm_ssd_layer_calls_total", label)
    layers = kinds(ctx.config)["ssd"]
    if not rows or not layer_calls:
        return None
    return {"rows": rows / (layer_calls / layers), "layers": layers}


def rule_flops(cfg: dict, rows: float) -> float:
    """The recurrent form a row a layer: the decay, (dt x) B^T and h C a head."""
    return rows * 3 * 2.0 * ssd_dims(cfg)["state"]


def matrix_state_bytes(cfg: dict, sequences: float) -> float:
    """The float32 h of `sequences` sequences of one layer, once each way:
    2 x 64 x 64 x 128 x 4 = 4,194,304 B a sequence."""
    return 2.0 * sequences * ssd_dims(cfg)["state"] * STATE_ITEM_BYTES


def state_bytes(cfg: dict, sequences: float) -> float:
    """Both state arrays of `sequences` sequences of one layer, once each way."""
    conv = (cfg["mamba_d_conv"] - 1) * ssd_dims(cfg)["channels"] * ITEM_BYTES
    return 2.0 * sequences * conv + matrix_state_bytes(cfg, sequences)


def ssd_chunk(ctx) -> dict | None:
    """A chunk's mamba layers, everything under mix.ssd: the live rows are ONE
    sequence's, so one slot's state is read and written a layer."""
    seen = ssd_seen(ctx, "chunk")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    d, lin, s = cfg["hidden_size"], linear_params(cfg), ssd_dims(cfg)
    return {"flops": layers * (rows * (2.0 * lin["ssd"] + 2.0 * cfg["mamba_d_conv"] * s["channels"])
                               + rule_flops(cfg, rows)),
            "bytes": layers * (lin["ssd"] + 4 * lin["ssd_channels"] + small_leaf_bytes(cfg)
                               + rows * 2 * d * ITEM_BYTES + state_bytes(cfg, 1))}


def ssd_state(ctx) -> dict | None:
    """The ops under mix.ssd.rule in a decode step: the live slots' h read once
    and written once, as the MODEL counts it."""
    seen = ssd_seen(ctx, "decode")
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
    n, s = kinds(cfg), ssd_dims(cfg)
    prompts = sum(1 for _p, rows, context in shapes if context == rows)
    pairs = sum(p for p, _n, _c in shapes) / len(shapes)
    rows = sum(r for _p, r, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    taps = n["ssd"] * rows * 2.0 * cfg["mamba_d_conv"] * s["channels"]
    return {"flops": 2.0 * rows * per_token_linear(cfg) + taps
            + n["ssd"] * rule_flops(cfg, rows) + n["attention"] * attention_flops(cfg, pairs)
            + 2.0 * linear_params(cfg)["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + n["ssd"] * state_bytes(cfg, 1)
            + n["attention"] * context * kv_row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every int8 weight once (the table as the head), the new
    tokens' table rows, the live K/V rows of the attention layers once, the live
    slots' two state arrays both ways.  HBM-bound."""
    cfg = ctx.config
    seen = ssd_seen(ctx, "decode")
    pages = scrape.gauge_mean(ctx.scrapes, "seldon_llm_kv_pages_in_use")
    if seen is None or pages is None:
        return None
    n, lin, rows = kinds(cfg), linear_params(cfg), seen["rows"]
    kv_tokens = pages * (cfg["server"].get("kv_page_size") or DEFAULT_PAGE)
    return {"flops": 2.0 * rows * (per_token_linear(cfg) + lin["head"])
            + n["ssd"] * rule_flops(cfg, rows)
            + n["attention"] * attention_flops(cfg, kv_tokens),
            "bytes": linear_bytes(cfg) + rows * cfg["hidden_size"]
            + n["ssd"] * state_bytes(cfg, rows)
            + n["attention"] * kv_tokens * kv_row_bytes(cfg)}
