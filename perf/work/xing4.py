"""What one call of Xing4.0's step programs needs, from shapes AND what the run
observed (work/deepseek_v2.py's sources: the routing and the attention's live
context from the program's counters, the chunks' offsets from the flight
recorder).

The block (configs/xing4.0-29b-a4b-int8.json) is DeepSeek-V2's with three
differences that cost bytes or time: the queries are compressed (W_qa
[d, q_lora_rank], an RMSNorm, W_qb [q_lora_rank, H (dn + dr)] in place of
W_q [d, H (dn + dr)]; traced under `attn.latent.q`, so inside what the
`attn.latent` metrics time, and counted here), the router is a sigmoid with a
selection bias (the same counters), and the residual is `hc_mult` streams mixed
around BOTH sub-layers of every layer (`resid.hc.*`; `hc_step`).

**The mixing's count is the least any formulation moves**: per sub-layer the
live rows' streams read once and written once (bf16), the sub-layer's output
read once, Phi [n d, 2n + n^2] once in float32.  The maps, the Sinkhorn chain
and the write-back's n^2 products a row are a few hundred operations a row and
no bytes: the chain of small ops is bound by latency, and its share of this
roofline says how far from free it is.
"""

from __future__ import annotations

from work import deepseek_v2 as v2
from work.deepseek_v2 import moe_ffn_chunk, moe_ffn_decode  # noqa: F401 - the same counters and count
from work.olmoe import routing

STREAM_ITEM_BYTES = 2   # bf16 streams
PHI_ITEM_BYTES = 4      # the mixing's leaves stay float32


def query_params(cfg: dict) -> int:
    m = v2.dims(cfg)
    return cfg["q_lora_rank"] * (m["d"] + m["H"] * (m["dn"] + m["dr"]))


def hc_cost(cfg: dict, rows: float) -> dict:
    """The ops under `resid.hc.*` of one call over `rows` live rows, all
    layers, both sub-layers."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    maps = 2 * n + n * n
    sub_layers = 2 * cfg["num_hidden_layers"]
    return {"flops": sub_layers * rows * (2.0 * n * d * maps + 2.0 * n * d * (n + 2)),
            "bytes": sub_layers * (rows * (2 * n + 1) * d * STREAM_ITEM_BYTES
                                   + n * d * maps * PHI_ITEM_BYTES)}


def linear_params(cfg: dict) -> dict:
    """work/deepseek_v2.py's, with the compressed queries in W_q's place."""
    m, lin = v2.dims(cfg), v2.linear_params(cfg)
    q = m["H"] * (m["dn"] + m["dr"])
    lin["attention"] += query_params(cfg) - m["d"] * q
    lin["attention_channels"] += cfg["q_lora_rank"]
    return lin


def per_token_linear(cfg: dict) -> float:
    lin = linear_params(cfg)
    return (cfg["num_hidden_layers"] * lin["attention"]
            + cfg["first_k_dense_replace"] * lin["dense"] + v2.moe_layers(cfg) * lin["moe"])


def linear_bytes(cfg: dict) -> float:
    lin = linear_params(cfg)
    return (cfg["num_hidden_layers"] * (lin["attention"] + 4 * lin["attention_channels"])
            + cfg["first_k_dense_replace"] * (lin["dense"] + 4 * lin["dense_channels"])
            + v2.moe_layers(cfg) * (lin["moe"] + 4 * lin["moe_channels"])
            + lin["head"] + 4 * cfg["vocab_size"])


# ---- the ops under resid.hc.* and attn.latent.* (perf/readers/hlo_scopes.py) ----
def hc_step(ctx) -> dict | None:
    seen = routing(ctx, "decode")
    return seen and hc_cost(ctx.config, seen["live_rows"])


def mla_decode_attn(ctx) -> dict | None:
    """work/deepseek_v2.py's count plus the query compression, which this
    model traces under `attn.latent.q`: its two int8 matrices once a layer."""
    need, seen = v2.mla_decode_attn(ctx), routing(ctx, "decode")
    if need is None or seen is None:
        return None
    layers, q = ctx.config["num_hidden_layers"], query_params(ctx.config)
    return {"flops": need["flops"] + layers * 2.0 * seen["live_rows"] * q,
            "bytes": need["bytes"] + layers * q}


# ---- the whole programs (perf/readers/device.py roofline) ------------------
def prefill_chunk(ctx) -> dict | None:
    """work/deepseek_v2.py's mean need over the chunks seen, with this model's
    plain matmuls and the mixing of the chunk's rows.  MXU-bound."""
    cfg = ctx.config
    shapes, seen = v2.chunk_shapes(ctx), routing(ctx, "chunk")
    if shapes is None or seen is None:
        return None
    layers = cfg["num_hidden_layers"]
    experts = v2.expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    prompts = sum(1 for _p, n, context in shapes if context == n)
    attention = sum(v2.latent_attention_flops(cfg, *s) for s in shapes) / len(shapes) * layers
    rows = sum(n for _p, n, _c in shapes) / len(shapes)
    context = sum(c for _p, _n, c in shapes) / len(shapes)
    mixing = hc_cost(cfg, rows)
    return {"flops": 2.0 * rows * per_token_linear(cfg) + experts["flops"] + attention
            + mixing["flops"] + 2.0 * linear_params(cfg)["head"] * prompts / len(shapes),
            "bytes": linear_bytes(cfg) + experts["bytes"] + mixing["bytes"]
            + layers * context * v2.row_bytes(cfg)}


def decode_step(ctx) -> dict | None:
    """One decode step: every plain int8 weight once, the experts the step
    TOUCHED, W_UK / W_UV, the embedding rows, the live latent rows once, the
    mixing's least bytes.  HBM-bound."""
    cfg = ctx.config
    seen, context = routing(ctx, "decode"), v2.context_per_call(ctx, "decode")
    if seen is None or context is None:
        return None
    experts = v2.expert_ffn_cost(cfg, seen["experts_touched"], seen["routed_pairs"])
    m, layers, lin = v2.dims(cfg), cfg["num_hidden_layers"], linear_params(cfg)
    rows = seen["live_rows"]
    mixing = hc_cost(cfg, rows)
    return {"flops": 2.0 * rows * (per_token_linear(cfg) + lin["head"]) + experts["flops"]
            + mixing["flops"] + layers * v2.latent_attention_flops(cfg, context, rows, context),
            "bytes": linear_bytes(cfg) + experts["bytes"] + mixing["bytes"] + rows * cfg["hidden_size"]
            + layers * ((context + rows) * v2.row_bytes(cfg)
                        + m["H"] * m["dc"] * (m["dn"] + m["dv"]))}
