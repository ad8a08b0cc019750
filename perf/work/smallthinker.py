"""What one call of SmallThinker-21BA3B-Instruct's step programs needs, from
shapes AND what the run observed (the routing from the program's counters, the
chunks' offsets from the flight recorder, the rows inside the windows from
`seldon_llm_attn_context_tokens_total{kind="window"}`).

The block (configs/smallthinker-21b-a3b-int8.json): GQA, 28 query / 4 KV heads
of 128, in every layer; `sliding_window_layout` 1 = a sliding-attention layer
(a query reads the last 4,096 rows; its pages are of the window class and are
given back behind the window), 0 = a full layer (reads and keeps everything);
then 6 of 64 ReGLU experts of width 768 a token, the router fed the layer's
input.  A cached token of one layer is a K row and a V row of 4 x 128 bf16
values (2,048 B; its int32 position is read from a gathered side array and is
left out, as in the other configurations' counts).

**The window read's count (`swa_step_attn`) is the least any formulation
moves**: the K and V rows inside each live slot's window once, in every window
layer, plus the rows the step writes; not the whole visits the kernel fetches
(`swa_rows_read_share` says how far those overshoot), not the rows behind the
window.
"""

from __future__ import annotations

from readers import labelled, loop
from work.deepseek_v2 import chunk_shapes
from work.olmoe import routing   # the same counters: calls, live rows, routed pairs, experts touched

ITEM_BYTES = 2         # bf16 activations and cache
DEFAULT_PAGE = 64


def kinds(cfg: dict) -> dict:
    window = sum(cfg["sliding_window_layout"])
    return {"window": window, "full": cfg["num_hidden_layers"] - window}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications of one layer (everything but
    the routed experts), their output channels (a float32 scale each), and the head."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    experts = cfg["moe_num_primary_experts"]
    return {"layer": d * q + 2 * d * kv + q * d + d * experts,
            "layer_channels": q + 2 * kv + d + experts,
            "head": d * cfg["vocab_size"]}


def linear_bytes(cfg: dict) -> float:
    lin, layers = linear_params(cfg), cfg["num_hidden_layers"]
    return layers * (lin["layer"] + 4 * lin["layer_channels"]) + lin["head"] + 4 * cfg["vocab_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def expert_ffn_cost(cfg: dict, touched: float, pairs: float) -> dict:
    """The routed experts of one call, all layers (work/olmoe.py's count):
    `touched` experts read, `pairs` (token, expert) rows computed."""
    expert_bytes = expert_params(cfg) + 4 * (2 * cfg["moe_ffn_hidden_size"] + cfg["hidden_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg),
            "bytes": touched * expert_bytes + pairs * 2 * 2 * cfg["hidden_size"]}


def moe_ffn_decode(ctx) -> dict | None:
    seen = routing(ctx, "decode")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def moe_ffn_chunk(ctx) -> dict | None:
    seen = routing(ctx, "chunk")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def attention_flops(cfg: dict, pairs: float) -> float:
    """One attention layer's scores and products over `pairs` (query, key) pairs."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def kv_row_bytes(cfg: dict) -> int:
    """One cached token of one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEM_BYTES


def window_rows(ctx, program: str) -> dict | None:
    """Per call of `program` in the window, a layer: the cached rows INSIDE the
    windows of the call's queries (`context_tokens`, kind=window) and the rows
    a full layer reads for them (kind=full)."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    got = {}
    for kind in ("window", "full"):
        labels = [f'program="{program}"', f'kind="{kind}"']
        calls = labelled.delta(pair, "seldon_llm_attn_calls_total", labels)
        rows = labelled.delta(pair, "seldon_llm_attn_context_tokens_total", labels)
        if not calls or rows is None:
            return None
        got[kind] = rows / calls
    return got


def swa_step_attn(ctx) -> dict | None:
    """The ops under attn.window in one decode step, all window layers: the K
    and V rows inside each live slot's window once, the step's rows written."""
    cfg = ctx.config
    rows, seen = window_rows(ctx, "decode"), routing(ctx, "decode")
    if rows is None or seen is None:
        return None
    layers = kinds(cfg)["window"]
    return {"flops": layers * attention_flops(cfg, rows["window"]),
            "bytes": layers * (rows["window"] + seen["live_rows"]) * kv_row_bytes(cfg)}


def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw: the plain matmuls
    for the live rows, the routed experts as the counters saw them, attention
    over the chunk's (query, key) pairs (a window layer's clipped to the
    window), the head once a prompt.  MXU-bound."""
    cfg = ctx.config
    shapes, seen = chunk_shapes(ctx), routing(ctx, "chunk")
    if shapes is None or seen is None:
        return None
    n, lin, window = kinds(cfg), linear_params(cfg), cfg["sliding_window_size"]
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    prompts = sum(1 for _p, rows, context in shapes if context == rows)
    pairs = sum(p for p, _n, _c in shapes) / len(shapes)
    rows = sum(r for _p, r, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    # a window layer's pairs: each row sees at most `window` keys
    clipped = sum(min(p, r * window) for p, r, _c in shapes) / len(shapes)
    seen_rows = sum(min(c, window + r) for _p, r, c in shapes) / len(shapes)
    return {"flops": 2.0 * rows * cfg["num_hidden_layers"] * lin["layer"] + experts["flops"]
            + n["full"] * attention_flops(cfg, pairs) + n["window"] * attention_flops(cfg, clipped)
            + 2.0 * lin["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + experts["bytes"]
            + (n["full"] * context + n["window"] * seen_rows) * kv_row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every plain int8 weight once, the experts the step
    TOUCHED, the embedding rows, the live K/V rows of the full layers and the
    rows inside the windows of the window layers, once.  HBM-bound."""
    cfg = ctx.config
    seen, rows = routing(ctx, "decode"), window_rows(ctx, "decode")
    if seen is None or rows is None:
        return None
    n, lin, live = kinds(cfg), linear_params(cfg), seen["live_rows"]
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    read = n["full"] * rows["full"] + n["window"] * rows["window"]
    return {"flops": 2.0 * live * (cfg["num_hidden_layers"] * lin["layer"] + lin["head"])
            + experts["flops"] + attention_flops(cfg, read),
            "bytes": linear_bytes(cfg) + experts["bytes"] + live * cfg["hidden_size"]
            + read * kv_row_bytes(cfg)}
