"""The benchmark's copy of the plain reference for SmallThinker-21BA3B-Instruct:
builds the seeded weights by the rule the configuration states (the program's own
random init, on the CPU: weights are data, and the seed in <llm_kwargs.json> gives
the int8 tree the server holds), then answers one question with
seldon_core_tpu/models/reference.py: float32, highest matmul precision, no cache,
no pages, no kernel, no batching; the router's logits from each layer's INPUT,
rotate-half RoPE in the layers the layout turns and no position in the others,
the mask `k_pos <= q_pos` and, in a sliding-attention layer, `k_pos > q_pos -
4096` over the WHOLE sequence at once (attention in blocks of 512 query rows),
softmax scores renormalised over the six chosen, ReGLU experts as a loop.  A
helper child beside the server:

    python smallthinker.py <llm_kwargs.json> <ask.json> <answer.npz>

It builds the weights at once (hidden behind the server's own start: the
question comes about when they are ready, so no forward is rehearsed meanwhile: a
rehearsal of one period over the probe's 6,154 tokens held the first answer back
by ~45 s on the chip's host, PERF.md section 6, PR 49), COMPILES the attention
pieces of a forward of the probe's length into the persistent compile cache
until the question is there (`compile_ahead`: nothing runs; a warm run finds
them there already), then waits for <ask.json>: {"tokens": prompt + chosen tokens, "rows": [first, end),
"follow": the experts the served path took, [tokens, MoE layers, 6]} and writes
the reference's logits for those positions with the served experts followed
(planes/llm_rest_followed_reference.py says why: the router renormalises its
top-6, so one flipped near-tie moves everything behind it), how near the
router's own choices were to the next expert (`margins`), how far behind its own
the followed ones were (`behind`), and its own timings.
"""

import json
import os
import sys
import time

import numpy as np


def probe_tokens(kwargs_path: str) -> int:
    """How long the question will be: the probe of the cell whose run this is
    (<kwargs_path> lies in perf/out/<cell>/), prompt + decoded tokens; 0 where
    that cannot be read."""
    perf = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = os.path.basename(os.path.dirname(os.path.abspath(kwargs_path)))
    try:
        with open(os.path.join(perf, "workloads", cell + ".json")) as f:
            probe = json.load(f)["probe"]
        return int(probe["prompt_tokens"]) + int(probe["output_tokens"])
    except (OSError, KeyError, ValueError):
        return 0


def compile_ahead(reference, cfg, tokens: int, ask_path: str) -> None:
    """While the question is still out: COMPILE (nothing runs) the attention
    pieces a forward over ``tokens`` rows will call, a (block, keys) shape at a
    time, into the persistent compile cache, where the forward finds them. A
    cold run's forward compiled for ~18 s of its 72 (the chip's host, PR 49);
    stops the moment the question is there."""
    import inspect

    import jax
    import jax.numpy as jnp

    if tokens <= 1:
        return
    block = inspect.signature(reference._attention).parameters["block"].default
    heads, groups, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    windows = sorted({cfg.layer_window(i) for i in range(cfg.n_layers)})
    done, t0 = set(), time.monotonic()
    with jax.default_matmul_precision("highest"):
        for start in range(0, tokens, block):
            end = min(start + block, tokens)
            for window in windows:
                lo = max(start - window + 1, 0) if window else 0
                shape = (end - start, end - lo, window)
                if os.path.exists(ask_path):
                    break
                if shape not in done:
                    done.add(shape)
                    reference._attend_block.lower(
                        jax.ShapeDtypeStruct((end - start, heads, hd), jnp.float32),
                        jax.ShapeDtypeStruct((end - lo, groups, hd), jnp.float32),
                        jax.ShapeDtypeStruct((end - lo, groups, hd), jnp.float32),
                        start, lo, window=window).compile()
    print(f"compiled {len(done)} attention pieces ahead in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)


def main() -> None:
    kwargs_path, ask_path, answer_path = sys.argv[1:4]
    t0 = time.monotonic()
    from seldon_core_tpu.models import reference
    from seldon_core_tpu.servers.llmserver import LLMServer

    with open(kwargs_path) as f:
        server = LLMServer(**json.load(f))
    server.load()
    built = time.monotonic() - t0
    print(f"weights built in {built:.1f}s", file=sys.stderr, flush=True)
    compile_ahead(reference, server._cfg, probe_tokens(kwargs_path), ask_path)
    while not os.path.exists(ask_path):
        time.sleep(0.1)
    with open(ask_path) as f:
        ask = json.load(f)
    t1 = time.monotonic()
    first, end = ask["rows"]
    follow = np.asarray(ask["follow"], np.int32) if "follow" in ask else None
    logits, routing = reference.forward(server._params, server._cfg, ask["tokens"],
                                        rows=slice(first, end), follow=follow)
    out = {"logits": np.asarray(logits, np.float32)}
    for key in ("margin", "behind"):   # [moe layers, tokens up to the last row judged]
        out[key + ("s" if key == "margin" else "")] = np.stack(
            [np.asarray(layer[key]) for layer in routing])[:, :end]
    out["seconds"] = np.asarray([built, time.monotonic() - t1])
    np.savez(answer_path + ".tmp.npz", **out)
    os.replace(answer_path + ".tmp.npz", answer_path)


if __name__ == "__main__":
    main()
