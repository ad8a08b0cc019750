"""What one call of DeepSeek-V2's step programs needs, from shapes AND what the
run observed (the routing and the attention's live context, from the program's
counters; the chunks' offsets from the flight recorder).

The block (configs/deepseek-v2-lite-int8.json): multi-head latent attention
(one cached row [c_t ; k^R_t] of kv_lora_rank + qk_rope_head_dim values a token
a layer, shared by all heads), then a dense SwiGLU in the first
`first_k_dense_replace` layers and, in the others, `num_experts_per_tok` of
`n_routed_experts` routed experts plus one shared SwiGLU of width
n_shared_experts x moe_intermediate_size.

**The attention's count is the least any formulation needs.**  Over P causal
(query, key) pairs, q query rows and c context rows a layer, all heads H:

    absorbed  (no K/V of the context is made):  P x 2H(2 dc + dr)  +  q x 2H dc (dn + dv)
    expanded  (K/V of the context made ONCE):   P x 2H(dn + dr + dv) +  c x 2H dc (dn + dv)

and the call is credited with the smaller of the two.  A decode step (q = 8,
c = thousands) needs the absorbed form; a chunk of 256 rows needs the expanded
form from the first token on.  A formulation that re-expands the latents for
every query block, or multiplies the masked part of the block-table view, does
more than this and is credited no more.  The bytes are the LIVE rows, read
once, from `seldon_llm_attn_context_tokens_total` (not the view's 16,384 rows
a slot, which is what the gather moves today), plus the rows written.
"""

from __future__ import annotations

from readers import loop, timeline
from work.olmoe import routing   # the same counters: calls, live rows, routed pairs, experts touched

ROW_ITEM_BYTES = 2     # bf16 latent cache
DEFAULT_PAGE = 64


def dims(cfg: dict) -> dict:
    return {"H": cfg["num_attention_heads"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"], "dc": cfg["kv_lora_rank"],
            "d": cfg["hidden_size"], "row": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]}


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def row_bytes(cfg: dict) -> int:
    """One cached token of one layer."""
    return dims(cfg)["row"] * ROW_ITEM_BYTES


def latent_attention_flops(cfg: dict, pairs: float, queries: float, context: float) -> float:
    """One layer's attention proper (scores, softmax's products, and moving
    W_UK / W_UV to whichever side is cheaper): the smaller of the absorbed and
    the expanded-once counts."""
    m = dims(cfg)
    move = 2.0 * m["H"] * m["dc"] * (m["dn"] + m["dv"])
    absorbed = pairs * 2.0 * m["H"] * (2 * m["dc"] + m["dr"]) + queries * move
    expanded = pairs * 2.0 * m["H"] * (m["dn"] + m["dr"] + m["dv"]) + context * move
    return min(absorbed, expanded)


def latent_scope_cost(cfg: dict, pairs: float, queries: float, context: float) -> dict:
    """What the ops under `attn.latent.*` of one call need, all layers: the
    latent projection of the new rows (wkv_a, int8), the attention proper, the
    live rows read once and the new rows written, W_UK / W_UV (int8) once."""
    m, layers = dims(cfg), cfg["num_hidden_layers"]
    flops = 2.0 * queries * m["d"] * m["row"] + latent_attention_flops(cfg, pairs, queries, context)
    weights = m["d"] * m["row"] + m["H"] * m["dc"] * (m["dn"] + m["dv"])
    return {"flops": layers * flops,
            "bytes": layers * ((context + queries) * row_bytes(cfg) + weights)}


def linear_params(cfg: dict) -> dict:
    """Weights of the plain matrix multiplications (everything but the routed
    experts and W_UK / W_UV): a layer's attention projections, the leading
    dense FFN, an MoE layer's router and shared experts, the head; and their
    output channels (a float32 scale each)."""
    m = dims(cfg)
    q = m["H"] * (m["dn"] + m["dr"])
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return {"attention": m["d"] * (q + m["row"]) + m["H"] * m["dv"] * m["d"],
            "attention_channels": q + m["row"] + m["d"],
            "dense": 3 * m["d"] * cfg["intermediate_size"],
            "dense_channels": 2 * cfg["intermediate_size"] + m["d"],
            "moe": m["d"] * cfg["n_routed_experts"] + 3 * m["d"] * shared,
            "moe_channels": cfg["n_routed_experts"] + 2 * shared + m["d"],
            "head": m["d"] * cfg["vocab_size"]}


def per_token_linear(cfg: dict) -> float:
    """Multiply-adds a token needs outside attention proper, the routed
    experts and the head."""
    lin = linear_params(cfg)
    return (cfg["num_hidden_layers"] * lin["attention"]
            + cfg["first_k_dense_replace"] * lin["dense"] + moe_layers(cfg) * lin["moe"])


def linear_bytes(cfg: dict) -> float:
    """Those weights once, int8 with a float32 scale a channel, and the head."""
    lin = linear_params(cfg)
    return (cfg["num_hidden_layers"] * (lin["attention"] + 4 * lin["attention_channels"])
            + cfg["first_k_dense_replace"] * (lin["dense"] + 4 * lin["dense_channels"])
            + moe_layers(cfg) * (lin["moe"] + 4 * lin["moe_channels"])
            + lin["head"] + 4 * cfg["vocab_size"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_ffn_cost(cfg: dict, touched: float, pairs: float) -> dict:
    """The routed experts of one call, all MoE layers (work/olmoe.py's count):
    `touched` experts read, `pairs` (token, expert) rows computed."""
    expert_bytes = expert_params(cfg) + 4 * (2 * cfg["moe_intermediate_size"] + cfg["hidden_size"])
    return {"flops": 2.0 * pairs * expert_params(cfg),
            "bytes": touched * expert_bytes + pairs * 2 * 2 * cfg["hidden_size"]}


def context_per_call(ctx, program: str) -> float | None:
    """Cached rows one call's attention had to read, a layer: the live context
    of its rows, summed over its rows."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    label = f'program="{program}"'
    calls = loop.delta(pair, "seldon_llm_attn_calls_total", label)
    rows = loop.delta(pair, "seldon_llm_attn_context_tokens_total", label)
    return rows / calls if calls and rows is not None else None


def chunk_shapes(ctx) -> list | None:
    """[(pairs, rows, context)] of the chunks the flight recorder saw."""
    chunks = timeline.chunk_events(ctx)
    if not chunks:
        return None
    return [(n * start + n * (n + 1) / 2.0, n, start + n) for start, n, _pace in chunks]


# ---- the ops under attn.latent.* (perf/readers/hlo_scopes.py) --------------
def mla_chunk_attn(ctx) -> dict | None:
    shapes = chunk_shapes(ctx)
    if shapes is None:
        return None
    costs = [latent_scope_cost(ctx.config, *shape) for shape in shapes]
    return {key: sum(c[key] for c in costs) / len(costs) for key in ("flops", "bytes")}


def mla_decode_attn(ctx) -> dict | None:
    """A step's rows each attend to their own context: pairs = the summed
    context, and every live row is a query."""
    context = context_per_call(ctx, "decode")
    seen = routing(ctx, "decode")
    if context is None or seen is None:
        return None
    return latent_scope_cost(ctx.config, context, seen["live_rows"], context)


# ---- the whole programs (perf/readers/device.py roofline) ------------------
def moe_ffn_chunk(ctx) -> dict | None:
    seen = routing(ctx, "chunk")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def moe_ffn_decode(ctx) -> dict | None:
    seen = routing(ctx, "decode")
    return seen and expert_ffn_cost(ctx.config, seen["experts_touched"], seen["routed_pairs"])


def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw: the plain
    matmuls for the live rows, the routed experts as the counters saw them,
    the attention's least count, the head once a prompt.  MXU-bound."""
    cfg = ctx.config
    shapes, seen = chunk_shapes(ctx), routing(ctx, "chunk")
    if shapes is None or seen is None:
        return None
    layers = cfg["num_hidden_layers"]
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    prompts = sum(1 for _p, n, context in shapes if context == n)
    attention = sum(latent_attention_flops(cfg, *s) for s in shapes) / len(shapes) * layers
    rows = sum(n for _p, n, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    return {"flops": 2.0 * rows * per_token_linear(cfg) + experts["flops"] + attention
            + 2.0 * linear_params(cfg)["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + experts["bytes"] + layers * context * row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every plain int8 weight once, the experts the step
    TOUCHED, W_UK / W_UV, the embedding rows, the live latent rows once.
    HBM-bound."""
    cfg = ctx.config
    seen, context = routing(ctx, "decode"), context_per_call(ctx, "decode")
    if seen is None or context is None:
        return None
    experts = expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    m, layers, lin = dims(cfg), cfg["num_hidden_layers"], linear_params(cfg)
    rows = seen["live_rows"]
    return {"flops": 2.0 * rows * (per_token_linear(cfg) + lin["head"]) + experts["flops"]
            + layers * latent_attention_flops(cfg, context, rows, context),
            "bytes": linear_bytes(cfg) + experts["bytes"] + rows * cfg["hidden_size"]
            + layers * ((context + rows) * row_bytes(cfg) + m["H"] * m["dc"] * (m["dn"] + m["dv"]))}
