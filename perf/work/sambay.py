"""What one call of Phi-4-mini-flash-reasoning's step programs needs, from shapes
AND what the run observed (the s6 layers' live rows, the rows that ran each half
of the stack and the rows each kind of attention layer had to read, all from the
program's counters).

The stack (configs/phi-4-mini-flash-reasoning-int8.json `layer_types`, 32
layers): 9 Mamba-1 mixers (`s6`: W_in [d, 2 E] = [2560, 10240], four taps and a
bias over E = 5,120 channels with three rows of state a slot, W_x [E, R + 2 N] =
[5120, 192], W_dt [R, E] = [160, 5120], the recurrence over a float32 state [N,
E] = [16, 5120] a slot, W_out [5120, 2560]); 8 differential window layers and
ONE full layer (40 query / 20 KV heads of 64: W_q, W_o [2560, 2560], W_k, W_v
[2560, 1280]), whose K and V the 7 cross-attention layers read again (W_q and
W_o alone); 7 gated memory units (W_1 [2560, 5120], W_2 [5120, 2560]); in EVERY
layer a gated MLP (fc1 [2560, 20480] = [gate ; up], fc2 [10240, 2560]).  The table
[200064, 2560] is the head too (tied): ONE int8 leaf, counted once as held, read
whole by every decode step's head product.

**Each count is the MODEL's, the least any formulation moves.**  A token caches
2 x 20 x 64 x 2 B = 5,120 B in ONE layer's pool for the EIGHT layers that read
it: `shared_kv_step` counts those bytes eight times a step (the full layer's read
and the seven cross layers'), because each layer's read is a pass over HBM of its
own.  The padded queries' doubled score product (the served path reads both
softmaxes of a pair as one GQA read over KV heads 128 wide) is the
formulation's, and is NOT counted: `attention_flops` is 4 x 40 x 64 a (query,
key) pair.  The recurrence is counted in its RECURRENT form (the decay, Delta B x
and C h: 3 x 2 x E x N a row); one exp a (channel, state) pair a row is beside
it and is not a FLOP of the roofline's.
"""

from __future__ import annotations

from readers import labelled, loop
from work.deepseek_v2 import chunk_shapes
from work.lfm2 import attention_flops, kv_row_bytes  # the same keys of the configuration's file

ITEM_BYTES = 2         # bf16 activations, conv rows and cache
STATE_ITEM_BYTES = 4   # h stays float32
LEAF_ITEM_BYTES = 4    # the taps and the small leaves stay float32
KINDS = {"s6": "s6", "window": "sliding_attention", "full": "full_attention", "gmu": "gmu",
         "cross": "cross_attention"}
# the kinds of the layers up to the shared pool's (a prompt's rows run these) and
# of those past it (a prompt's ONE row that is read runs these)
SELF_DECODER, CROSS_DECODER = ("s6", "window", "full"), ("gmu", "cross")


def kinds(cfg: dict) -> dict:
    n = {ours: sum(kind == theirs for kind in cfg["layer_types"]) for ours, theirs in KINDS.items()}
    return {**n, "ffn": cfg["num_hidden_layers"]}


def s6_dims(cfg: dict) -> dict:
    inner = cfg["mamba_expand"] * cfg["hidden_size"]
    return {"inner": inner, "rank": cfg["mamba_dt_rank"], "states": cfg["mamba_d_state"],
            "state": inner * cfg["mamba_d_state"]}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications by kind of layer, and their
    output channels (a float32 scale each)."""
    d, s, ffn = cfg["hidden_size"], s6_dims(cfg), cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    x_out = s["rank"] + 2 * s["states"]
    attention = d * q + 2 * d * kv + q * d
    return {"s6": d * 2 * s["inner"] + s["inner"] * x_out + s["rank"] * s["inner"] + s["inner"] * d,
            "s6_channels": 2 * s["inner"] + x_out + s["inner"] + d,
            "window": attention, "window_channels": q + 2 * kv + d,
            "full": attention, "full_channels": q + 2 * kv + d,
            "cross": d * q + q * d, "cross_channels": q + d,
            "gmu": 2 * d * s["inner"], "gmu_channels": s["inner"] + d,
            "ffn": 3 * d * ffn, "ffn_channels": 2 * ffn + d,
            "head": d * cfg["vocab_size"]}


def params_total(cfg: dict) -> int:
    """The model's matrix parameters AS HELD: every layer's products and the
    table once (it is the head too)."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * lin[kind] for kind in n) + lin["head"]


def per_token_linear(cfg: dict, layers: tuple) -> float:
    """Multiply-adds a token needs in the plain products of the layers of
    ``layers``' kinds, their gated MLPs among them."""
    lin, n = linear_params(cfg), kinds(cfg)
    return sum(n[kind] * (lin[kind] + lin["ffn"]) for kind in layers)


def small_leaf_bytes(cfg: dict) -> float:
    """An s6 layer's float32 leaves: the taps and the bias a channel, A_log [N, E],
    b_dt and D a channel."""
    s = s6_dims(cfg)
    return LEAF_ITEM_BYTES * (s["inner"] * (cfg["mamba_d_conv"] + 3) + s["state"])


def linear_bytes(cfg: dict, layers: tuple) -> float:
    """Those layers' weights once, int8 with a float32 scale a channel, and the
    s6 layers' small leaves."""
    lin, n = linear_params(cfg), kinds(cfg)
    return (sum(n[kind] * (lin[kind] + 4 * lin[kind + "_channels"]
                           + lin["ffn"] + 4 * lin["ffn_channels"]) for kind in layers)
            + ("s6" in layers) * n["s6"] * small_leaf_bytes(cfg))


def head_bytes(cfg: dict) -> float:
    return linear_params(cfg)["head"] + 4 * cfg["hidden_size"]


# ---- what the counters saw --------------------------------------------------
def s6_seen(ctx, program: str) -> dict | None:
    """Per call of `program` in the window, from `seldon_llm_s6_*`: the live rows
    each s6 layer mixed, the calls, and those layers."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    label = f'program="{program}"'
    rows = loop.delta(pair, "seldon_llm_s6_rows_total", label)
    layer_calls = loop.delta(pair, "seldon_llm_s6_layer_calls_total", label)
    layers = kinds(ctx.config)["s6"]
    if not rows or not layer_calls:
        return None
    calls = layer_calls / layers
    return {"rows": rows / calls, "calls": calls, "layers": layers}


def decoder_rows(ctx, program: str) -> dict | None:
    """Per call of `program`: the rows that ran the layers up to the shared
    pool's (`self`) and the rows that ran the rest (`cross`: a prompt's chunk, one
    row or none)."""
    pair, seen = loop.ends(ctx), s6_seen(ctx, program)
    if pair is None or seen is None:
        return None
    label = f'program="{program}"'
    got = {half: loop.delta(pair, f"seldon_llm_{half}_decoder_rows_total", label)
           for half in ("self", "cross")}
    if got["self"] is None or got["cross"] is None:
        return None
    return {half: rows / seen["calls"] for half, rows in got.items()}


def context_rows(ctx, program: str) -> dict | None:
    """Per call of `program`, a LAYER of each kind: the cached rows its queries
    had to read (`seldon_llm_attn_context_tokens_total`): `full` the one full
    layer's, `window` a window layer's (the rows inside the windows), `shared` a
    cross layer's (rows of the full layer's pool)."""
    pair, seen = loop.ends(ctx), s6_seen(ctx, program)
    if pair is None or seen is None:
        return None
    got = {}
    for kind in ("full", "window", "shared"):
        rows = labelled.delta(pair, "seldon_llm_attn_context_tokens_total",
                              [f'program="{program}"', f'kind="{kind}"'])
        if rows is None:
            return None
        got[kind] = rows / seen["calls"]
    return got


# ---- the ops under mix.s6.* (perf/readers/hlo_scopes.py) ---------------------
def rule_flops(cfg: dict, rows: float) -> float:
    """The recurrent form a row a layer: the decay, Delta B x and C h a pair."""
    return rows * 3 * 2.0 * s6_dims(cfg)["state"]


def matrix_state_bytes(cfg: dict, sequences: float) -> float:
    """The float32 h of `sequences` sequences of one layer, once each way:
    2 x 16 x 5120 x 4 = 655,360 B a sequence."""
    return 2.0 * sequences * s6_dims(cfg)["state"] * STATE_ITEM_BYTES


def state_bytes(cfg: dict, sequences: float) -> float:
    """Both state arrays of `sequences` sequences of one layer, once each way."""
    conv = (cfg["mamba_d_conv"] - 1) * s6_dims(cfg)["inner"] * ITEM_BYTES
    return 2.0 * sequences * conv + matrix_state_bytes(cfg, sequences)


def s6_chunk(ctx) -> dict | None:
    """A chunk's s6 layers, everything under mix.s6: the four matrices once, the
    small leaves, the rows in and out, ONE slot's state each way; the products,
    the taps and the recurrent form."""
    seen = s6_seen(ctx, "chunk")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    d, lin, s = cfg["hidden_size"], linear_params(cfg), s6_dims(cfg)
    return {"flops": layers * (rows * (2.0 * lin["s6"] + 2.0 * cfg["mamba_d_conv"] * s["inner"])
                               + rule_flops(cfg, rows)),
            "bytes": layers * (lin["s6"] + 4 * lin["s6_channels"] + small_leaf_bytes(cfg)
                               + rows * 2 * d * ITEM_BYTES + state_bytes(cfg, 1))}


def s6_state(ctx) -> dict | None:
    """The recurrence alone in a decode step: the live slots' h read once and
    written once a layer, as the MODEL counts it."""
    seen = s6_seen(ctx, "decode")
    if seen is None:
        return None
    cfg, rows, layers = ctx.config, seen["rows"], seen["layers"]
    return {"flops": layers * rule_flops(cfg, rows),
            "bytes": layers * matrix_state_bytes(cfg, rows)}


# ---- the reads of the shared pool, and the window layers' ---------------------
def shared_kv_step(ctx) -> dict | None:
    """The full layer's read + write and the cross layers' reads in one decode
    step: the live rows of ONE pool x 5,120 B, once for the full layer and once
    for EACH cross layer (8 reads), + the step's rows written once."""
    cfg = ctx.config
    rows, seen = context_rows(ctx, "decode"), s6_seen(ctx, "decode")
    if rows is None or seen is None:
        return None
    read = rows["full"] + kinds(cfg)["cross"] * rows["shared"]
    return {"flops": attention_flops(cfg, read),
            "bytes": (read + seen["rows"]) * kv_row_bytes(cfg)}


def swa_step_attn(ctx) -> dict | None:
    """The ops under attn.window in one decode step, all window layers: the K and
    V rows inside each live slot's window once, the step's rows written."""
    cfg = ctx.config
    rows, seen = context_rows(ctx, "decode"), s6_seen(ctx, "decode")
    if rows is None or seen is None:
        return None
    layers = kinds(cfg)["window"]
    return {"flops": layers * attention_flops(cfg, rows["window"]),
            "bytes": layers * (rows["window"] + seen["rows"]) * kv_row_bytes(cfg)}


# ---- the whole programs (perf/readers/device.py roofline) ------------------
def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw: the layers up to the
    shared pool's (18 of 32) on the chunk's rows, the rest (14), the final norm
    and the head on the ONE row of a prompt's last chunk (the share of chunks
    that ran them: `cross` rows a call, from the two decoder-row counters); the
    taps and the recurrent form; attention over the chunk's (query, key) pairs (a
    window layer's clipped to the window); one slot's state both ways.
    MXU-bound."""
    cfg = ctx.config
    shapes, halves = chunk_shapes(ctx), decoder_rows(ctx, "chunk")
    if shapes is None or halves is None:
        return None
    n, s, window = kinds(cfg), s6_dims(cfg), cfg["sliding_window"]
    lasts = halves["cross"]                      # <= 1 a chunk: the share that ran the cross-decoder
    pairs = sum(p for p, _n, _c in shapes) / len(shapes)
    rows = sum(r for _p, r, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    clipped = sum(min(p, r * window) for p, r, _c in shapes) / len(shapes)
    seen_rows = sum(min(c, window + r) for _p, r, c in shapes) / len(shapes)
    taps = n["s6"] * rows * 2.0 * cfg["mamba_d_conv"] * s["inner"]
    return {"flops": 2.0 * rows * per_token_linear(cfg, SELF_DECODER) + taps
            + n["s6"] * rule_flops(cfg, rows)
            + n["full"] * attention_flops(cfg, pairs) + n["window"] * attention_flops(cfg, clipped)
            + lasts * (2.0 * (per_token_linear(cfg, CROSS_DECODER) + linear_params(cfg)["head"])
                       + n["cross"] * attention_flops(cfg, context)),
            "bytes": linear_bytes(cfg, SELF_DECODER) + n["s6"] * state_bytes(cfg, 1)
            + (n["full"] * context + n["window"] * seen_rows) * kv_row_bytes(cfg)
            + lasts * (linear_bytes(cfg, CROSS_DECODER) + head_bytes(cfg)
                       + n["cross"] * context * kv_row_bytes(cfg))}


def decode_step(ctx) -> dict | None:
    """One decode step: every int8 weight once (the table as the head), the new
    tokens' table rows, the shared pool's live rows EIGHT times, the rows inside
    the windows of the eight window layers, the live slots' state both ways.
    HBM-bound."""
    cfg = ctx.config
    seen, rows = s6_seen(ctx, "decode"), context_rows(ctx, "decode")
    if seen is None or rows is None:
        return None
    n, live = kinds(cfg), seen["rows"]
    read = rows["full"] + n["cross"] * rows["shared"] + n["window"] * rows["window"]
    every = SELF_DECODER + CROSS_DECODER
    return {"flops": 2.0 * live * (per_token_linear(cfg, every) + linear_params(cfg)["head"])
            + n["s6"] * rule_flops(cfg, live) + attention_flops(cfg, read),
            "bytes": linear_bytes(cfg, every) + head_bytes(cfg) + live * cfg["hidden_size"]
            + n["s6"] * state_bytes(cfg, live)
            + (read + (n["full"] + n["window"]) * live) * kv_row_bytes(cfg)}
