"""What one call of a decoder's step programs needs, from shapes alone.

The decoder is the pre-norm GQA block of the configuration's source (RMSNorm,
RoPE, SwiGLU, no biases, untied head).  `*_cost` are pure functions of sizes;
`decode_step` and `prefill_chunk` fill in what the run observed (live KV tokens,
chunk offsets) and are what perf/readers/device.py roofline calls.
"""

from __future__ import annotations

from readers import scrape, timeline

KV_ITEM_BYTES = 2      # bf16 cache
DEFAULT_PAGE = 64      # the program's DEFAULT_PAGE_SIZE


def linear_params(cfg: dict) -> dict:
    """Weights of the matrix multiplications, per layer and for the head."""
    d, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return {"layer": d * q + 2 * d * kv + q * d + 3 * d * ffn,
            "head": d * cfg["vocab_size"],
            "layer_out_channels": q + 2 * kv + d + 2 * ffn + d}


def kv_bytes_per_token(cfg: dict) -> int:
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * KV_ITEM_BYTES
            * cfg["num_hidden_layers"])


def decode_step_cost(cfg: dict, slots: int, kv_tokens: float) -> dict:
    """One token for each of `slots` sequences holding `kv_tokens` cached
    tokens between them.  Bytes: every int8 weight once (one byte each, plus a
    float32 scale per output channel), the embedding rows of the new tokens,
    and the live cache once.  Bound by HBM at these batch sizes."""
    lin, layers = linear_params(cfg), cfg["num_hidden_layers"]
    weights = layers * (lin["layer"] + 4 * lin["layer_out_channels"]) \
        + lin["head"] + 4 * cfg["vocab_size"]
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * kv_tokens * layers
    return {"flops": 2.0 * slots * (layers * lin["layer"] + lin["head"]) + attn,
            "bytes": weights + slots * cfg["hidden_size"]
            + kv_tokens * kv_bytes_per_token(cfg)}


def prefill_chunk_cost(cfg: dict, tokens: float, offset: float,
                       head_positions: float = 0.0) -> dict:
    """`tokens` prompt tokens at `offset` in their prompt: the matrix
    multiplications of every layer, causal attention over offset + own, and
    the head for `head_positions` of them (1 on a prompt's last chunk).
    Padding a chunk to its program's length is not needed work.  Bound by the
    MXU from a few dozen tokens on."""
    lin, layers = linear_params(cfg), cfg["num_hidden_layers"]
    pairs = tokens * offset + tokens * (tokens + 1) / 2
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs * layers
    return {"flops": 2.0 * tokens * layers * lin["layer"] + attn
            + 2.0 * head_positions * lin["head"],
            "bytes": layers * lin["layer"] + lin["head"]
            + (offset + tokens) * kv_bytes_per_token(cfg)}


def decode_step(ctx) -> dict | None:
    pages = scrape.gauge_mean(ctx.scrapes, "seldon_llm_kv_pages_in_use")
    if pages is None:
        return None
    page = ctx.config["server"].get("kv_page_size") or DEFAULT_PAGE
    return decode_step_cost(ctx.config, ctx.config["server"]["continuous_batching"],
                            pages * page)


def prefill_chunk(ctx) -> dict | None:
    """The mean need over the chunks the flight recorder saw."""
    chunks = timeline.chunk_events(ctx)
    if not chunks:
        return None
    prompts = sum(1 for start, _n, _p in chunks if start == 0)
    costs = [prefill_chunk_cost(ctx.config, n, start) for start, n, _p in chunks]
    head = 2.0 * linear_params(ctx.config)["head"] * prompts / len(chunks)
    return {"flops": sum(c["flops"] for c in costs) / len(costs) + head,
            "bytes": sum(c["bytes"] for c in costs) / len(costs)}
