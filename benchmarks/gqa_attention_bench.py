"""One layer's paged GQA read on the chip: the expression over the gathered
view against the live-page kernel, a step's by rows a visit, a chunk's in the
rule's head-block form.

    chiprun -- python benchmarks/gqa_attention_bench.py [out.json]

Times what ``Attention`` does under ``attn.gqa.read`` (the rotated queries are
given; the new rows' write into the pools is included, so no compiler can lift
the view's gather out of the loop) at the call shapes the GQA configurations
serve (PERF.md section 4): Mistral's chat step (32 slots x 1,024 rows, a few
live) and docs step (8 x 4,096, all live), OLMoE's chat step (16 KV heads of
their own), LFM2's step (32 x 4,096, heads of 64), Llama-2-7B's (32 KV heads:
chip_smoke.py), and the prefill chunks: Mistral's 256 tokens at 256, 1,792 and
3,584 live rows of a 4,096-row view (a rerank prompt's first, middle and last
chunk), its 128 and 256 tokens over the chat server's 1,024-row view, and
OLMoE's, LFM2's and Qwen3-Next's 256 tokens over theirs. Variants: ``expression
[kvh, hd]`` is the whole-view read over pools held ``[pages, 64, kvh, hd]``
(what served before PR 36 and still does on a mesh), ``expression flat`` the
same over flat rows ``[pages, 64, kvh x hd]`` (every lowering that is not for
a TPU), ``kernel N`` the live-page walk at N rows a visit: a step's row-wide
by rows a visit, a chunk's a lane block a KV head as ``gqa_plan`` walks it.
Prints one line per (shape, variant) and
writes them all as JSON. A time is the median of ``REPEATS`` calls of a jitted
program that runs the read ``DEPTH`` times in a chain on the device, two depths'
difference divided by the depths'; a kernel's time includes making its visit
list, which a step program makes once for all its layers (``--tiny`` rehearses
it on the CPU under the interpreter).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.latent_attention_bench import seconds, state  # noqa: E402
from seldon_core_tpu.models.transformer import paged_attention_ref  # noqa: E402
from seldon_core_tpu.ops import gqa_attention, page_walk  # noqa: E402

PAGE = 64
SHALLOW, DEEP = 4, 20
# (name, slots, query tokens, heads, KV heads, head_dim, table entries a slot,
#  live rows of each slot; -1 = nobody holds it)
SHAPES = [
    ("mistral chat step", 32, 1, 32, 8, 128, 16, [300, 150, 420, 260, 90] + [-1] * 27),
    ("mistral docs step", 8, 1, 32, 8, 128, 64, list(np.linspace(1600, 3600, 8).astype(int))),
    ("olmoe chat step", 32, 1, 16, 16, 128, 16, [300, 150, 420, 260, 90, 333, 500, 200] + [-1] * 24),
    ("lfm2 step", 32, 1, 32, 8, 64, 64, list(np.linspace(1100, 3400, 32).astype(int))),
    ("llama2-7b step", 8, 1, 32, 32, 128, 17, [600, 300] + [-1] * 6),
    ("mistral chunk at 256", 1, 256, 32, 8, 128, 64, [256]),
    ("mistral chunk at 1,792", 1, 256, 32, 8, 128, 64, [1792]),
    ("mistral chunk at 3,584", 1, 256, 32, 8, 128, 64, [3584]),
    ("mistral chat chunk 128", 1, 128, 32, 8, 128, 16, [128]),
    ("mistral chat chunk 256", 1, 256, 32, 8, 128, 16, [512]),
    ("olmoe chunk at 512", 1, 256, 16, 16, 128, 16, [512]),
    ("lfm2 chunk at 2,048", 1, 256, 32, 8, 64, 64, [2048]),
    ("qwen3next chunk at 4,096", 1, 256, 16, 2, 256, 128, [4096]),
]
VISIT_ROWS = [512, 1024, 2048]


def reader(kvh, walk, interpret):
    """``depth`` reads in a chain: each writes its rows (as the layer does)
    and feeds its output back into the next one's queries."""
    def run(depth, q, k_pool, v_pool, pos_pool, bt, positions):
        b, s = q.shape[:2]
        page = pos_pool.shape[1]
        at = (jnp.take_along_axis(bt, jnp.clip(positions // page, 0, bt.shape[1] - 1), axis=1),
              positions % page)

        def body(_, carry):
            q, k_pool, v_pool = carry
            new = q[:, :, :kvh].reshape((b, s) + k_pool.shape[2:])
            k_pool, v_pool = k_pool.at[at].set(new), v_pool.at[at].set(new)
            if walk is None:
                out = paged_attention_ref(q, (k_pool, v_pool, pos_pool), bt, positions, kvh)
            else:
                out = gqa_attention.gqa_page_attention(q, k_pool, v_pool, pos_pool, bt, positions,
                                                       kvh, walk, interpret=interpret)
            return q + (1e-3 * out).astype(q.dtype), k_pool, v_pool

        return jax.lax.fori_loop(0, depth, body, (q, k_pool, v_pool))[0]
    return jax.jit(run, static_argnums=0)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    out_path = args[0] if args else "chiprun_out/gqa_attention_bench.json"
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    tiny = "--tiny" in sys.argv
    page = 16 if tiny else PAGE
    shapes = [("tiny step", 3, 1, 16, 4, 32, 12, [100, -1, 40]),
              ("tiny chunk", 1, 32, 16, 4, 128, 12, [100])] if tiny else SHAPES
    visit_rows = [64] if tiny else VISIT_ROWS
    shallow, deep = (1, 2) if tiny else (SHALLOW, DEEP)
    device = jax.devices()[0]
    print("device:", device.platform, device.device_kind, flush=True)
    results = []
    for name, slots, s, heads, kvh, hd, n_pages, lens in shapes:
        pages, pos_pool, bt, positions = state(slots, s, n_pages, lens, page)
        key = jax.random.PRNGKey(36)
        row = kvh * hd
        q = jax.random.normal(key, (slots, s, heads, hd), jnp.float32).astype(jnp.bfloat16)
        pools = [jax.random.normal(jax.random.fold_in(key, i), (pages, page, row), jnp.float32
                                   ).astype(jnp.bfloat16) for i in (1, 2)]
        live_rows = int(sum(n for n in lens if n > 0))
        live_us = live_rows * row * 2 * 2 / 819e9 * 1e6
        planned = gqa_attention.gqa_plan(s, heads, kvh, hd, n_pages, page)
        variants = [("expression [kvh, hd]", None, (pages, page, kvh, hd)),
                    ("expression flat", None, (pages, page, row))]
        if planned is not None and planned.blocks > 1:
            variants.append((f"kernel {planned.pages * page} a head block (the rule)", planned,
                             (pages, page, row)))
        elif s * heads < page_walk.QUERY_TILE:
            for rows in visit_rows:
                walk = page_walk.Plan(pages=min(rows // page, -(-n_pages // 2) * 2), q_tile=s * heads)
                if walk.pages * page * row * 4 <= 2 * page_walk.VISIT_BYTES:
                    variants.append((f"kernel {walk.pages * page}"
                                     + (" (the rule)" if walk == planned else ""), walk, (pages, page, row)))
        reference = None
        for variant, walk, held in variants:
            fn = reader(kvh, walk, interpret=tiny)
            call = (q, pools[0].reshape(held), pools[1].reshape(held), pos_pool, bt, positions)
            try:
                got = jax.block_until_ready(fn(1, *call))
            except Exception as exc:   # a walk Mosaic refuses at this shape
                print(f"{name:22s} {variant:28s} FAILED {type(exc).__name__}: {str(exc)[:300]}", flush=True)
                continue
            if reference is None:
                reference = got
            live = np.array([n > 0 for n in lens])
            err = float(jnp.max(jnp.abs((got.astype(jnp.float32) - reference.astype(jnp.float32))[live])))
            us = (seconds(fn, deep, call) - seconds(fn, shallow, call)) / (deep - shallow) * 1e6
            visits = 0
            if walk is not None:
                visits = int(page_walk.make_visits(
                    bt, page_walk.live_pages(bt, positions, page), walk).count)
            results.append(dict(shape=name, slots=slots, s=s, heads=heads, kv_heads=kvh, head_dim=hd,
                                live_rows=live_rows, variant=variant, visits=visits,
                                read_us=round(us, 1), live_bytes_us=round(live_us, 1),
                                max_abs_diff_vs_expression=err))
            print(f"{name:22s} live {live_rows:6d} rows  {variant:28s} visits {visits:4d}  {us:9.1f} us a read "
                  f"(live K + V bytes once {live_us:6.1f} = {100 * live_us / max(us, 1e-9):5.1f} %)  diff {err:.3g}",
                  flush=True)
    with open(out_path, "w") as f_out:
        json.dump({"device": device.device_kind, "results": results}, f_out, indent=1)


if __name__ == "__main__":
    main()
