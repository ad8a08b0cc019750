"""One MoE layer's three grouped projections on the chip, by row tile.

    chiprun -- python benchmarks/grouped_matmul_bench.py [out.json] [--wide | --balanced]

Times what ``MoEFFN`` does between its sort and its combine (gate, up, silu x
up, down; the visit list included) at the six shapes the two MoE
configurations serve, for every row tile of ``ops/grouped_matmul.py`` and for
``jax.lax.ragged_dot`` (XLA's own kernel), on int8 stacks drawn from a seed.
The group sizes are drawn to look like the cells' collapsed routers
(docs/performance.md "The grouped matmul"; PERF.md section 5: 33-36 of 64
experts touched, the largest group 8-8.5x the mean): 40 of the 64 experts
can be chosen, with probabilities proportional to exp(z), and ``live`` of the
call's rows are routed (the rest are dead slots or padding and sit behind the
last group). ``--wide`` times the three 1,024-token chunks whose row tile is 128
(DeepSeek-V2-Lite, LFM2 with its 32 experts, SmallThinker), over three draws of the
group sizes, with the tile's visits multiplying the whole tile and the run of
aligned blocks of 64, 32 and 16 rows that holds their rows (``SUB_BLOCK``), and
prints the fill (routed rows over rows multiplied) beside the time. Prints one
line per (shape, live, variant) and writes them all as JSON. A time is the median of ``REPEATS`` calls of a jitted program
that runs the layer ``DEPTH`` times in a chain, divided by ``DEPTH``.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seldon_core_tpu.ops import grouped_matmul as gm  # noqa: E402

E = 64   # a shape's own where it names another
SHALLOW, DEEP, REPEATS = 8, 72, 9
# (name, rows of the call = tokens x k, live rows, d, f)
SHAPES = [
    ("olmoe step", 256, 120, 2048, 1024), ("olmoe step full", 256, 256, 2048, 1024),
    ("olmoe chunk-128", 1024, 1024, 2048, 1024), ("olmoe chunk-256", 2048, 2048, 2048, 1024),
    ("olmoe chunk-256 3/4", 2048, 1536, 2048, 1024),
    ("dsv2 step", 48, 12, 2048, 1408), ("dsv2 step full", 48, 48, 2048, 1408),
    ("dsv2 chunk-128", 768, 768, 2048, 1408), ("dsv2 chunk-256", 1536, 1536, 2048, 1408),
]
# no cell serves these: a balanced router (every expert the same rows) at
# OLMoE's widths, where the groups are one tile or several
BALANCED = [
    ("balanced 32 a group", 2048, 2048, 2048, 1024), ("balanced 64 a group", 4096, 4096, 2048, 1024),
    ("balanced 128 a group", 8192, 8192, 2048, 1024),
]


# the wide chunks (name, rows, live rows, d, f, experts): 1,024 tokens x 6, 4 and 6
# experts a token; live = the routed pairs of a layer-call as dsv2lite's cell counts them
WIDE = [
    ("dsv2 chunk-1024", 6144, 5422, 2048, 1408, 64), ("lfm2 chunk-1024", 4096, 3615, 2048, 1792, 32),
    ("smallthinker chunk-1024", 6144, 5422, 2560, 768, 64),
]
WIDE_SEEDS = (30, 31, 32)
SUB_BLOCK_SERVED = gm.SUB_BLOCK


def group_sizes(live: int, seed: int, e: int = E) -> np.ndarray:
    """Five experts of eight can be chosen (40 of 64)."""
    rng = np.random.default_rng(seed)
    p = np.exp(rng.standard_normal(e)) * (rng.permutation(e) < e * 5 // 8)
    return rng.multinomial(live, p / p.sum()).astype(np.int32)


def layer(rows_tile, variant):
    """``depth`` layers in a chain (a loop on the device: one program whatever
    the depth, so two depths' difference is device time alone)."""
    def run(depth, x, sizes, w1, s1, w3, s3, w2, s2):
        m = x.shape[0]
        row_expert = jnp.minimum(
            jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(m), side="right"), sizes.shape[0] - 1)

        def body(_, x):
            if variant == "ragged_dot":
                def grouped(lhs, w, s):
                    return jax.lax.ragged_dot(
                        lhs, w, sizes, preferred_element_type=jnp.float32) * s[row_expert]
            else:
                visits = gm.make_visits(sizes, m, rows_tile)
                if variant.endswith("+xla_scale"):
                    def grouped(lhs, w, s):
                        return gm.grouped_matmul(lhs, w, visits) * s[row_expert]
                else:
                    def grouped(lhs, w, s):
                        return gm.grouped_matmul(lhs, w, visits, s)
            h = jax.nn.silu(grouped(x, w1, s1)) * grouped(x, w3, s3)
            y = grouped(h.astype(x.dtype), w2, s2)
            if variant == "ragged_dot":
                y = jnp.where((jnp.arange(m) < jnp.sum(sizes))[:, None], y, 0.0)
            return (x + 1e-3 * y.astype(x.dtype)).astype(x.dtype)

        return jax.lax.fori_loop(0, depth, body, x)
    return jax.jit(run)


def seconds(fn, depth, args) -> float:
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(depth, *args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    out_path = args[0] if args else "chiprun_out/grouped_matmul_bench.json"
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    wide = "--wide" in sys.argv
    shapes = BALANCED if "--balanced" in sys.argv else WIDE if wide else SHAPES
    if "--tiny" in sys.argv:            # a rehearsal on the CPU: the interpreter
        shapes = [("tiny", 256, 150, 128, 256, 4)] if wide else [("tiny", 64, 40, 128, 256)]
    device = jax.devices()[0]
    print("device:", device.platform, device.device_kind, flush=True)
    results = []
    for name, m, live, d, f, *own in shapes:
        e = own[0] if own else E
        key = jax.random.PRNGKey(30)
        stacks = []
        for i, shape in enumerate(((e, d, f), (e, d, f), (e, f, d))):
            stacks.append(jax.random.randint(jax.random.fold_in(key, i), shape, -127, 128, jnp.int8))
            stacks.append(jnp.full((e, shape[2]), 2e-4, jnp.float32))
        x = jax.random.normal(jax.random.fold_in(key, 9), (m, d), jnp.float32).astype(jnp.bfloat16)
        # (variant, tile, rows of a sub-block: the tile's own = the whole tile a visit)
        variants = [("ragged_dot", 0, None)]
        if wide:
            variants += [("kernel", 64, None)] + [("kernel", 128, block) for block in (128, 64, 32, 16)]
        else:
            variants += [("kernel", t, None) for t in (16, 32, 64, 128, 256)]
            variants += [("kernel+xla_scale", 64, None)]
        programs = {}   # one a variant: the draws of a shape share them
        for seed in (WIDE_SEEDS if wide else (30,)):
            sizes_np = group_sizes(live, seed, e)
            if name.startswith("balanced"):
                sizes_np = np.full((e,), live // e, np.int32)
            sizes = jnp.asarray(sizes_np)
            touched, largest = int((sizes_np > 0).sum()), int(sizes_np.max())
            reference = None
            for variant, tile, block in variants:
                # (read when the kernel is traced, which the first call below does)
                gm.SUB_BLOCK = block or SUB_BLOCK_SERVED
                fn = programs.setdefault((variant, tile, block), layer(tile, variant))
                try:
                    got = jax.block_until_ready(fn(1, x, sizes, *stacks))
                except Exception as exc:  # a variant Mosaic refuses at this shape
                    print(f"{name:22s} {variant:16s} tile {tile:3d}  FAILED {type(exc).__name__}: "
                          f"{str(exc)[:200]}", flush=True)
                    continue
                if reference is None:
                    reference = got
                err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - reference.astype(jnp.float32))))
                args = (x, sizes, *stacks)
                us = (seconds(fn, DEEP, args) - seconds(fn, SHALLOW, args)) / (DEEP - SHALLOW) * 1e6
                visits = gm.make_visits(sizes, m, tile) if tile else None
                count, multiplied = (int(visits.count), int(visits.multiplied)) if tile else (0, 0)
                bytes_us = touched * 3 * d * f / 819e9 * 1e6
                took = "" if not tile else "whole" if gm.sub_block(tile) == tile else str(gm.sub_block(tile))
                row = dict(shape=name, rows=m, live=live, d=d, f=f, experts=e, seed=seed,
                           touched=touched, largest=largest, variant=variant, tile=tile,
                           sub_block=took, visits=count, rows_multiplied=multiplied,
                           fill=round(live / multiplied, 3) if multiplied else None,
                           layer_us=round(us, 1), bytes_us=round(bytes_us, 1),
                           max_abs_diff_vs_ragged_dot=err)
                results.append(row)
                print(f"{name:22s} live {live:4d} touched {touched:2d} max {largest:3d}  {variant:16s} "
                      f"tile {tile:3d} {took:5s} visits {count:3d} rows {multiplied:5d} "
                      f"fill {row['fill'] or 0:.2f}  {us:8.1f} us a layer "
                      f"(bytes {bytes_us:6.1f} = {100 * bytes_us / us:5.1f} %)  diff {err:.3g}", flush=True)
    with open(out_path, "w") as f_out:
        json.dump({"device": device.device_kind, "results": results}, f_out, indent=1)


if __name__ == "__main__":
    main()
