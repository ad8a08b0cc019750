"""One layer's latent-attention read on the chip: the whole-view expression
against the live-page kernel, by rows a visit and query tile.

    chiprun -- python benchmarks/latent_attention_bench.py [out.json]

Times what ``LatentAttention`` does under ``attn.latent.read`` (the absorbed
query rows are given; the new rows' write into the pool is included, so no
compiler can lift the view's gather out of the loop) at the call shapes the two
latent configurations serve (PERF.md section 4): DeepSeek-V2-Lite's step (8
slots x 16,384 rows, 1-2 live) and chunk (256 tokens x 16 heads against one
slot), Xing4's step (32 slots x 4,096, every slot live with 0.3-3 k rows) and
chunk. Prints one line per (shape, variant) and writes them all as JSON. A time
is the median of ``REPEATS`` calls of a jitted program that runs the read
``DEPTH`` times in a chain on the device, two depths' difference divided by
the depths' (``--tiny`` rehearses it on the CPU under the interpreter).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS, TRASH_PAGE  # noqa: E402
from seldon_core_tpu.ops import latent_attention as la  # noqa: E402
from seldon_core_tpu.ops import page_walk  # noqa: E402

PAGE, WIDTH, LATENT = 64, 640, 512
SHALLOW, DEEP, REPEATS = 4, 24, 7
# (name, slots, query tokens, heads, table entries a slot, live rows of each slot; -1 = nobody holds it)
SHAPES = [
    ("dsv2 step 2 live", 8, 1, 16, 256, [12000, 9000, -1, -1, -1, -1, -1, -1]),
    ("dsv2 step 1 live", 8, 1, 16, 256, [14000, -1, -1, -1, -1, -1, -1, -1]),
    ("dsv2 chunk at 6k", 1, 256, 16, 256, [6250]),
    ("dsv2 chunk at 16k", 1, 256, 16, 256, [16320]),
    ("xing4 step", 32, 1, 32, 64, list(np.linspace(300, 2900, 32).astype(int))),
    ("xing4 chunk", 1, 256, 32, 64, [768]),
]
# (rows a visit, query tile)
WALKS = [(512, 512), (1024, 512), (2048, 512), (1024, 256), (1024, 1024)]


def state(slots, s, n_pages, lens, page, seed=32):
    """A pool in which slot i holds ``lens[i]`` rows on pages drawn in no
    order, its block tables, and query positions (the last ``s`` rows)."""
    rng = np.random.default_rng(seed)
    pages = 2 + slots * n_pages
    pos_pool = np.full((pages, page), PAD_POS, np.int32)
    bt = np.full((slots, n_pages), NULL_PAGE, np.int32)
    positions = np.zeros((slots, s), np.int32)
    free = iter(rng.permutation(np.arange(2, pages)))
    for i, rows in enumerate(lens):
        if rows < 0:
            bt[i] = TRASH_PAGE
            continue
        for j in range(-(-rows // page)):
            bt[i, j] = next(free)
            n = min(page, rows - j * page)
            pos_pool[bt[i, j], :n] = j * page + np.arange(n)
        positions[i] = np.arange(rows - s, rows)
    return pages, jnp.asarray(pos_pool), jnp.asarray(bt), jnp.asarray(positions)


def reader(walk, latent, interpret):
    """``depth`` reads in a chain: each writes its rows (as the layer does)
    and feeds its output back into the next one's queries."""
    def run(depth, q, pool, pos_pool, bt, positions):
        b, s, heads, width = q.shape
        page = pool.shape[1]
        at = (jnp.take_along_axis(bt, jnp.clip(positions // page, 0, bt.shape[1] - 1), axis=1),
              positions % page)

        def body(_, carry):
            q, pool = carry
            pool = pool.at[at].set(q[:, :, 0])
            if walk is None:
                L = bt.shape[1] * page
                rows, pos_view = pool[bt].reshape(b, L, width), pos_pool[bt].reshape(b, L)
                mask = pos_view[:, None, :] <= positions[:, :, None]
                logits = jnp.einsum("bshc,blc->bhsl", q, rows).astype(jnp.float32) * 0.07
                logits = jnp.where(mask[:, None], logits, jnp.finfo(jnp.float32).min)
                ctx = jnp.einsum("bhsl,blc->bshc", jax.nn.softmax(logits, axis=-1).astype(q.dtype),
                                 rows[..., :latent])
            else:
                ctx = la.latent_page_attention(q, pool, pos_pool, bt, positions, 0.07, latent,
                                                  walk, interpret=interpret)
            q = q.at[..., :latent].add((1e-3 * ctx).astype(q.dtype))
            return q, pool

        return jax.lax.fori_loop(0, depth, body, (q, pool))[0]
    return jax.jit(run, static_argnums=0)


def seconds(fn, depth, args) -> float:
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(depth, *args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    out_path = args[0] if args else "chiprun_out/latent_attention_bench.json"
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    tiny = "--tiny" in sys.argv
    page, width, latent = (16, 256, 128) if tiny else (PAGE, WIDTH, LATENT)
    shapes = [("tiny step", 3, 1, 16, 12, [100, -1, 40]), ("tiny chunk", 1, 8, 16, 12, [90])] if tiny else SHAPES
    walks = [(64, 64)] if tiny else WALKS
    shallow, deep = (1, 2) if tiny else (SHALLOW, DEEP)
    device = jax.devices()[0]
    print("device:", device.platform, device.device_kind, flush=True)
    results = []
    for name, slots, s, heads, n_pages, lens in shapes:
        pages, pos_pool, bt, positions = state(slots, s, n_pages, lens, page)
        key = jax.random.PRNGKey(32)
        pool = jax.random.normal(key, (pages, page, width), jnp.float32).astype(jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 1), (slots, s, heads, width),
                              jnp.float32).astype(jnp.bfloat16)
        live_rows = int(sum(n for n in lens if n > 0))
        live_us = live_rows * width * 2 / 819e9 * 1e6
        planned = page_walk.plan(s, heads, n_pages, page, width, latent)
        variants = [("expression", None)]
        for rows, tile in walks:
            if s * heads < tile and (rows, tile) != walks[0] and tile != 512:
                continue   # a step has one query tile whatever the rule says
            walk = page_walk.Plan(pages=min(rows // page, n_pages), q_tile=min(s * heads, tile))
            variants.append((f"kernel {walk.pages * page} x {walk.q_tile}"
                             + (" (the rule)" if walk == planned else ""), walk))
        reference = None
        for variant, walk in variants:
            fn = reader(walk, latent, interpret=tiny)
            call = (q, pool, pos_pool, bt, positions)
            try:
                got = jax.block_until_ready(fn(1, *call))
            except Exception as exc:   # a walk Mosaic refuses at this shape
                print(f"{name:20s} {variant:32s} FAILED {type(exc).__name__}: {str(exc)[:300]}", flush=True)
                continue
            if reference is None:
                reference = got
            held = np.array([n > 0 for n in lens])
            err = float(jnp.max(jnp.abs((got.astype(jnp.float32) - reference.astype(jnp.float32))[held])))
            us = (seconds(fn, deep, call) - seconds(fn, shallow, call)) / (deep - shallow) * 1e6
            visits = 0
            if walk is not None:
                visits = int(page_walk.make_visits(bt, page_walk.live_pages(bt, positions, page),
                                            walk).count) * (s * heads // walk.q_tile)
            row = dict(shape=name, slots=slots, s=s, heads=heads, live_rows=live_rows, variant=variant,
                       visits=visits, read_us=round(us, 1), live_bytes_us=round(live_us, 1),
                       max_abs_diff_vs_expression=err)
            results.append(row)
            print(f"{name:20s} live {live_rows:6d} rows  {variant:32s} visits {visits:4d}  {us:9.1f} us a read "
                  f"(live bytes once {live_us:6.1f} = {100 * live_us / max(us, 1e-9):5.1f} %)  diff {err:.3g}",
                  flush=True)
    with open(out_path, "w") as f_out:
        json.dump({"device": device.device_kind, "results": results}, f_out, indent=1)


if __name__ == "__main__":
    main()
