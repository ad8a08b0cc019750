"""The benchmark's copy of the plain reference for Xing4.0-29B-A4B: builds the
seeded weights by the rule the configuration states (the program's own random
init, on the CPU: weights are data, and the seed in <llm_kwargs.json> gives the
int8 tree the server holds, the float32 mixing leaves and the selection bias
among them), then answers one question with seldon_core_tpu/models/reference.py:
float32, highest matmul precision, no cache, no batching; the four residual
streams mixed by a plain loop of 20 Sinkhorn iterations over a [t, 4, 4] array,
the queries through W_qa, RMSNorm_q and W_qb, every head's keys and values
EXPANDED from the latents (the served path never expands them), sigmoid scores
chosen by score + selection bias and weighed without it, a loop over experts,
the shared expert added, YaRN and its m^2.  A helper child beside the server:

    python xing4.py <llm_kwargs.json> <ask.json> <answer.npz>

It builds the weights at once (most of its time, hidden behind the server's
own start), then waits for <ask.json>: {"tokens": prompt + chosen tokens,
"rows": [first, end), "follow": the experts the served path took, [tokens,
MoE layers, 4]} and writes the reference's logits for those positions with the
served experts followed (planes/llm_rest_followed_reference.py says why), how
near the router's own choices were to the next expert (`margins`), how far
behind its own the followed ones were (`behind`), and its own timings.
"""

import json
import os
import sys
import time

import numpy as np


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
