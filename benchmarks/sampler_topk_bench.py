"""The sampler's candidates on the chip: direct ``lax.top_k`` against the two
exact stages, by block width.

    chiprun -- python benchmarks/sampler_topk_bench.py [out.json]

Times ``_top_k_candidates`` (servers/llmserver.py: what every emitted token is
chosen among) at the ``[slots, vocab]`` float32 call shapes of the served
cells' decode steps, for ``TOPK_BLOCK`` = 128, 256 and 512, against the form it
replaced (``jnp.argmax`` + ``jax.lax.top_k`` over the whole row), and checks
every blocked result against that form BIT FOR BIT on the device itself
(random rows, rows rounded to bf16 = many exact ties, zeros of both signs, rows
that are -inf but for three columns): the TPU's TopK breaks ties as the CPU's
does only if this says so. The committed ``TOPK_BLOCK`` is the width that wins
at ``[32, 32000]`` and loses at no shape (docs/performance.md "The sampler's
candidates"). A time is the difference between two programs that call the
function ``DEEP`` and ``SHALLOW`` times, a call (`chained`): the host's dispatch
and the program's fixed cost are in neither.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seldon_core_tpu.servers import llmserver  # noqa: E402

TOP_K = 40
BLOCKS = (128, 256, 512)
SHALLOW, DEEP, REPEATS = 2, 12, 15
# (cell, slots, vocab): the decode steps of PERF.md section 4's cells
SHAPES = [
    ("mistral7b chat", 32, 32000), ("olmoe", 32, 50304), ("qwen3next", 64, 37984),
    ("lfm2", 32, 65536), ("granite4h", 96, 100352), ("xing4", 32, 131072),
    ("smallthinker", 24, 151936), ("phi4flash", 32, 200064),
]


def direct(lg):
    """The parent's form: two passes over the row, TopK over the vocabulary."""
    values, indices = jax.lax.top_k(lg, TOP_K)
    return jnp.argmax(lg, axis=-1), values, indices


def blocked(block: int):
    """`_top_k_candidates` traced with ``TOPK_BLOCK = block`` (the constant is
    read while tracing: set around the trace, restored behind it)."""
    def fn(lg):
        committed = llmserver.TOPK_BLOCK
        llmserver.TOPK_BLOCK = block
        try:
            return llmserver._top_k_candidates(lg, TOP_K)
        finally:
            llmserver.TOPK_BLOCK = committed
    return fn


def chained(fn):
    """``depth`` calls in one program, each on a PARAMETER of its own (the one
    buffer handed in ``depth`` times: the compiler cannot know), so every call
    reads its logits row-major from HBM as the step reads the head's and every
    candidate is consumed. (A loop on the device will not do: inside a
    ``while``, and wherever only a slice of the result is used, the compiler
    makes a full sort of a ``top_k``, not the TopK call the step program gets.)"""
    def run(*rows):
        seen = jnp.zeros((rows[0].shape[0],), jnp.float32)
        for lg in rows:
            greedy, values, indices = fn(lg)
            seen = seen + greedy + indices.sum(-1) + values.sum(-1)
        return seen
    jitted = jax.jit(run)
    return lambda depth, lg: jitted(*([lg] * depth))


def seconds(fn, depth, lg) -> float:
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(depth, lg))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def rows_of(kind: str, slots: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng(56)
    lg = rng.standard_normal((slots, vocab)).astype(np.float32)
    if kind == "bf16":
        lg = np.asarray(jnp.asarray(lg).astype(jnp.bfloat16).astype(jnp.float32))
    elif kind == "zeros":
        zero = rng.random((slots, vocab)) < 0.01
        lg = np.where(zero, np.where(rng.random((slots, vocab)) < 0.3, 0.0, -0.0),
                      -1.0 - np.abs(lg)).astype(np.float32)
    elif kind == "-inf":
        kept = lg[:, [3, vocab // 2, vocab - 1]]
        lg[:] = -np.inf
        lg[:, [3, vocab // 2, vocab - 1]] = kept
    return lg


def unequal_kinds(fn, kinds, want) -> list:
    """The kinds of row at which ``fn``'s result is not the direct form's bit
    for bit. (Zeros of both signs: ``greedy`` is the first +0.0 by design,
    where argmax takes the first zero of either sign, so there the values and
    the indices are what is compared.)"""
    found, jitted = [], jax.jit(fn)
    for kind, lg in kinds.items():
        got = jitted(lg)
        pairs = list(zip(got, want[kind]))[1 if kind == "zeros" else 0:]
        if not all(np.array_equal(np.asarray(a).view(np.int32), np.asarray(b).view(np.int32))
                   for a, b in pairs):
            found.append(kind)
    return found


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    out_path = args[0] if args else "chiprun_out/sampler_topk_bench.json"
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    shapes, shallow, deep = SHAPES, SHALLOW, DEEP
    if "--tiny" in sys.argv:            # a rehearsal on the CPU
        shapes, shallow, deep = [("tiny", 4, 41 * 1024 + 96)], 1, 2
    device = jax.devices()[0]
    print("device:", device.platform, device.device_kind, flush=True)
    results = []
    for cell, slots, vocab in shapes:
        kinds = {kind: jnp.asarray(rows_of(kind, slots, vocab))
                 for kind in ("random", "bf16", "zeros", "-inf")}
        want = {kind: jax.block_until_ready(jax.jit(direct)(lg)) for kind, lg in kinds.items()}
        for name, fn in [("direct", direct)] + [(f"block {b}", blocked(b)) for b in BLOCKS]:
            columns = vocab
            if name != "direct":
                block = int(name.split()[1])
                if vocab <= 2 * TOP_K * block:      # the rule keeps the direct form here
                    continue
                columns = TOP_K * block + vocab % block
            unequal = [] if name == "direct" else unequal_kinds(fn, kinds, want)
            run = chained(fn)
            jax.block_until_ready(run(1, kinds["random"]))
            us = (seconds(run, deep, kinds["random"]) - seconds(run, shallow, kinds["random"])) \
                / (deep - shallow) * 1e6
            row = dict(cell=cell, slots=slots, vocab=vocab, form=name, topk_columns=columns,
                       call_us=round(us, 1), read_once_us=round(slots * vocab * 4 / 819e9 * 1e6, 1),
                       unequal_to_direct=unequal)
            results.append(row)
            print(f"{cell:16s} [{slots:3d}, {vocab:6d}]  {name:10s} TopK over {columns:6d}  "
                  f"{us:8.1f} us a call  unequal to direct: {unequal or 'none'}", flush=True)
    with open(out_path, "w") as out:
        json.dump({"device": device.device_kind, "top_k": TOP_K, "results": results}, out, indent=1)


if __name__ == "__main__":
    main()
