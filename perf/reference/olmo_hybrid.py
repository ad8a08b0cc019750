"""The benchmark's copy of the plain reference for Olmo-Hybrid-7B: builds the
seeded weights by the rule the configuration states (the program's own random
init, on the CPU: weights are data, and the seed in <llm_kwargs.json> gives the
int8 tree the server holds, the float32 taps, A_log, dt_bias and norm weights
among them), then answers one question with
seldon_core_tpu/models/reference.py: float32, highest matmul precision, no
cache, no state, no batching, no chunking.  A block is x + RMSNorm(f(x)) twice
(the norm on the BRANCH, nothing normed before the mixer or the FFN); a
linear-attention layer's causal taps are an explicit shifted sum over the WHOLE
sequence and its delta rule a `lax.scan` over the tokens from S = 0 with beta =
2 sigmoid(b) and a state [96, 192] a head (the served path carries three rows of
[q ; k ; v] and the float32 S, two heads side by side along the lanes, across
every chunk and step, and runs a chunked form in the prefill); an attention
layer norms the WHOLE q and k projections and rotates nothing; the FFN is a
dense SwiGLU.  A helper child beside the server:

    python olmo_hybrid.py <llm_kwargs.json> <ask.json> <answer.npz>

7.43 B parameters do not fit as float32 beside the server's own host copy: the
tree stays int8 with its scales (7.5 GB), and the forward makes ONE matrix
float32 at a time (`reference._f32`, as it is used), the table and the head
among them (1.5 GB each while they are read).  It builds the weights at once
(hidden behind the server's own start), runs one forward over made-up tokens of
the probe's length through ONE period of the layers while the server is still
starting (so that the real one compiles nothing), then waits for <ask.json>: {"tokens": prompt + chosen
tokens, "rows": [first, end)} and writes the reference's logits for those
positions and its own timings.  A dense model: there is no routing to follow
(planes/llm_rest_reference.py).
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lfm2 import probe_tokens  # noqa: E402 - the probe's length, from the cell's own file


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
    tokens = probe_tokens(kwargs_path)
    if tokens > 1 and not os.path.exists(ask_path):
        # ONE period of the layers over made-up tokens of the question's length:
        # every layer of a kind has the same shapes, so this compiles every piece
        # the real forward runs, in an eighth of its time
        t1 = time.monotonic()
        cfg = server._cfg
        period = cfg.layer_types.index("full_attention") + 1
        made_up = np.random.default_rng(0).integers(97, 123, size=tokens).tolist()
        np.asarray(reference.forward(
            server._params, dataclasses.replace(cfg, n_layers=period,
                                                layer_types=cfg.layer_types[:period]),
            made_up, rows=slice(tokens - 2, tokens))[0])
        print(f"rehearsed {period} layers over {tokens} tokens in {time.monotonic() - t1:.1f}s",
              file=sys.stderr, flush=True)
    while not os.path.exists(ask_path):
        time.sleep(0.1)
    with open(ask_path) as f:
        ask = json.load(f)
    t1 = time.monotonic()
    first, end = ask["rows"]
    logits, _ = reference.forward(server._params, server._cfg, ask["tokens"],
                                  rows=slice(first, end))
    out = {"logits": np.asarray(logits, np.float32),
           "seconds": np.asarray([built, time.monotonic() - t1])}
    np.savez(answer_path + ".tmp.npz", **out)
    os.replace(answer_path + ".tmp.npz", answer_path)


if __name__ == "__main__":
    main()
