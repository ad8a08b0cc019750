"""What one call of OLMoE's step programs needs, from shapes AND the routing
the run observed.

The block (configs/olmoe-1b-7b-int8.json): MHA with QK-norm, then a sparse
expert FFN: a router over `num_experts`, `num_experts_per_tok` experts a token,
each a SwiGLU of width `intermediate_size`.  A step reads an expert's weights
only if a live row chose it, so the bytes come from the program's counters
(`seldon_llm_moe_*`, docs/observability.md "Expert routing"): experts touched
and routed pairs per call, between the first and the last scrape of the
window.  Never 64 experts always, never 8 experts for a dead row.
"""

from __future__ import annotations

from readers import loop, scrape

KV_ITEM_BYTES = 2      # bf16 cache
DEFAULT_PAGE = 64      # the program's DEFAULT_PAGE_SIZE


def expert_params(cfg: dict) -> int:
    """One expert: gate, up and down projections."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_bytes(cfg: dict) -> int:
    """int8 values and a float32 scale per output channel of each projection."""
    return expert_params(cfg) + 4 * (2 * cfg["intermediate_size"] + cfg["hidden_size"])


def attention_params(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"layer": d * q + 2 * d * kv + q * d + d * cfg["num_experts"],
            "layer_out_channels": q + 2 * kv + d + cfg["num_experts"],
            "head": d * cfg["vocab_size"]}


def kv_bytes_per_token(cfg: dict) -> int:
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * KV_ITEM_BYTES
            * cfg["num_hidden_layers"])


def expert_ffn_cost(cfg: dict, touched: float, pairs: float) -> dict:
    """The expert FFN of one call, all layers: `touched` experts read (summed
    over layers), `pairs` (token, expert) rows computed (summed over layers);
    each row is read and written once in the model's width (bf16)."""
    return {"flops": 2.0 * pairs * expert_params(cfg),
            "bytes": touched * expert_bytes(cfg) + pairs * 2 * 2 * cfg["hidden_size"]}


def routing(ctx, program: str) -> dict | None:
    """Per call of `program` ("decode" or "chunk") in the window: live rows,
    routed pairs and experts touched (the last two summed over layers)."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    label = f'program="{program}"'
    got = {key: loop.delta(pair, f"seldon_llm_moe_{key}_total", label)
           for key in ("calls", "live_rows", "routed_pairs", "experts_touched")}
    if any(v is None for v in got.values()) or not got["calls"]:
        return None
    return {key: got[key] / got["calls"] for key in got if key != "calls"}


def moe_ffn_decode(ctx) -> dict | None:
    seen = routing(ctx, "decode")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def moe_ffn_chunk(ctx) -> dict | None:
    seen = routing(ctx, "chunk")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def decode_step(ctx) -> dict | None:
    """One decode step: the attention, router and head weights once (int8 and
    scales), the experts the step touched, the embedding rows, the live
    cache.  FLOPs for live rows only.  Bound by HBM."""
    cfg = ctx.config
    seen = routing(ctx, "decode")
    pages = scrape.gauge_mean(ctx.scrapes, "seldon_llm_kv_pages_in_use")
    if seen is None or pages is None:
        return None
    kv_tokens = pages * (cfg["server"].get("kv_page_size") or DEFAULT_PAGE)
    att, layers = attention_params(cfg), cfg["num_hidden_layers"]
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    dense = layers * (att["layer"] + 4 * att["layer_out_channels"]) \
        + att["head"] + 4 * cfg["vocab_size"]
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * kv_tokens * layers
    return {"flops": 2.0 * seen["live_rows"] * (layers * att["layer"] + att["head"])
            + attn + experts["flops"],
            "bytes": dense + experts["bytes"] + seen["live_rows"] * cfg["hidden_size"]
            + kv_tokens * kv_bytes_per_token(cfg)}
