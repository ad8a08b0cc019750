"""Grouped-query attention against a per-head reference.

``Attention`` contracts each KV head against its group of query heads
without expanding K/V to n_heads (models/transformer.py
``grouped_query_attention``). The paged, batcher and speculative tests run
MHA configs (2 / 2 heads) and only ``llama-tiny`` (4 / 2) ever folds a
group, so this file holds every cache layout to a plain float32 reference
at rep 1, 2 and 4 (Mistral-7B's): an explicit loop in which head ``h``
reads KV head ``h // rep``. Prefill (right-padded, two prompt lengths),
then two steps of ``s`` tokens: ``s == 1`` is the decode step, ``s == 5``
the speculative verify / chunk shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.cache import (
    PAD_POS,
    RESERVED_PAGES,
    dequantize_kv,
    init_kv_caches,
    init_paged_kv_caches,
    quantize_kv,
)
from seldon_core_tpu.models.transformer import (
    Attention,
    TransformerConfig,
    apply_rotary,
    rotary_embedding,
)

N_HEADS, HEAD_DIM = 8, 4     # rep 4 still leaves two KV heads to tell apart
DIM = N_HEADS * HEAD_DIM
PROMPT_LENS = (6, 4)          # right-padded to 6: PAD_POS columns in row 1
MAX_LEN = 24                  # dense cache length / reference history
PAGE, PAGES_PER_SEQ = 4, 6    # paged pool: 6 pages of 4 tokens a sequence


def _reference(params, x, positions, hist, rep, int8):
    """x [b, s, dim], positions [b, s] -> [b, s, dim]; ``hist`` (k, v, pos)
    numpy arrays [b, MAX_LEN, kvh, hd] / [b, MAX_LEN] addressed by position,
    updated in place with this call's tokens (PAD_POS columns dropped)."""
    kvh = N_HEADS // rep
    b, s, _ = x.shape
    f32 = jnp.float32
    q = (x @ params["wq"]).reshape(b, s, N_HEADS, HEAD_DIM)
    k = (x @ params["wk"]).reshape(b, s, kvh, HEAD_DIM)
    v = (x @ params["wv"]).reshape(b, s, kvh, HEAD_DIM)
    cos, sin = rotary_embedding(positions, HEAD_DIM, 10000.0)
    q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
    if int8:  # what an int8 cache hands back: the storage round trip
        k, v = (dequantize_kv(*quantize_kv(t), f32) for t in (k, v))
    hk, hv, hpos = hist
    pos = np.asarray(positions)
    for i in range(b):
        for j in range(s):
            if pos[i, j] < PAD_POS:
                hk[i, pos[i, j]] = np.asarray(k[i, j])
                hv[i, pos[i, j]] = np.asarray(v[i, j])
                hpos[i, pos[i, j]] = pos[i, j]
    mask = hpos[:, None, :] <= pos[:, :, None]                 # [b, s, L]
    heads = []
    for h in range(N_HEADS):
        g = h // rep                                           # its KV head
        logits = np.einsum("bqd,bkd->bqk", np.asarray(q[:, :, h]), hk[:, :, g])
        logits = np.where(mask, logits * HEAD_DIM**-0.5, np.finfo(np.float32).min)
        probs = np.asarray(jax.nn.softmax(jnp.asarray(logits, f32), axis=-1))
        heads.append(np.einsum("bqk,bkd->bqd", probs, hv[:, :, g]))
    out = np.stack(heads, axis=2).reshape(b, s, DIM)
    return out @ np.asarray(params["wo"])


def _block_tables(b):
    return jnp.asarray(
        RESERVED_PAGES + np.arange(b * PAGES_PER_SEQ).reshape(b, PAGES_PER_SEQ),
        jnp.int32)


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize(
    "layout", ["none", "dense", "dense_int8", "paged", "paged_int8"])
@pytest.mark.parametrize("rep", [1, 2, 4])
def test_attention_matches_per_head_reference(rep, layout, s):
    cfg = TransformerConfig(
        vocab_size=32, dim=DIM, n_layers=1, n_heads=N_HEADS,
        n_kv_heads=N_HEADS // rep, ffn_dim=2 * DIM, max_seq_len=MAX_LEN,
        dtype=jnp.float32)
    attn = Attention(cfg)
    b, plen = len(PROMPT_LENS), max(PROMPT_LENS)
    rng = np.random.default_rng(100 * rep + s)
    lens = np.asarray(PROMPT_LENS)
    kvd = "int8" if layout.endswith("int8") else "bf16"

    # three calls: the padded prompt, then two steps of s tokens a sequence
    col = np.arange(plen)[None, :]
    calls = [np.where(col < lens[:, None], col, PAD_POS)]
    for step in range(2):
        calls.append(lens[:, None] + step * s + np.arange(s)[None, :])
    calls = [(jnp.asarray(rng.standard_normal((b, p.shape[1], DIM)), jnp.float32),
              jnp.asarray(p, jnp.int32)) for p in calls]

    variables = attn.init(jax.random.PRNGKey(rep), *calls[0])
    params = variables["params"]
    kvh = N_HEADS // rep

    def fresh_hist():
        return (np.zeros((b, MAX_LEN, kvh, HEAD_DIM), np.float32),
                np.zeros((b, MAX_LEN, kvh, HEAD_DIM), np.float32),
                np.full((b, MAX_LEN), PAD_POS, np.int64))

    if layout == "none":
        cache, kw = None, {}
    elif layout.startswith("dense"):
        cache, kw = init_kv_caches(cfg, b, MAX_LEN, kvd)[0], {}
    else:
        cache = init_paged_kv_caches(
            cfg, RESERVED_PAGES + b * PAGES_PER_SEQ, PAGE, kvd)[0]
        kw = {"block_tables": _block_tables(b)}

    hist = fresh_hist()
    seen_x, seen_pos = [], []
    for n, (x, pos) in enumerate(calls):
        if layout == "none":
            # no cache: every call is the whole sequence so far, recomputed
            seen_x.append(x)
            seen_pos.append(pos)
            x, pos = jnp.concatenate(seen_x, 1), jnp.concatenate(seen_pos, 1)
            hist = fresh_hist()
            out, _ = attn.apply(variables, x, pos)
        else:
            if layout.startswith("dense"):
                # prefill writes at offset 0; steps at each sequence's length
                kw["cache_index"] = (jnp.int32(0) if n == 0 else
                                     jnp.asarray(lens + (n - 1) * s, jnp.int32))
            out, cache = attn.apply(variables, x, pos, cache, **kw)
        ref = _reference(params, x, pos, hist, rep, kvd == "int8")
        valid = np.asarray(pos) < PAD_POS       # pad queries carry no answer
        np.testing.assert_allclose(
            np.asarray(out)[valid], ref[valid], atol=1e-5, rtol=1e-5,
            err_msg=f"call {n} (rep={rep}, {layout}, s={s})")
