"""The benchmark's copy of the plain reference for DeepSeek-V2: builds the seeded
weights by the rule the configuration states (the program's own random init,
on the CPU: weights are data, and the seed in <llm_kwargs.json> gives the int8
tree the server holds), then answers one question with
seldon_core_tpu/models/reference.py: float32, highest matmul precision, no
cache, no batching, every head's keys and values EXPANDED from the latents
(the served path never expands them), a loop over experts, the shared experts
added, YaRN and its m^2 as the published model has them.  A helper child
beside the server:

    python deepseek_v2.py <llm_kwargs.json> <ask.json> <answer.npz>

It builds the weights at once (most of its time, hidden behind the server's
own start), then waits for <ask.json>: {"tokens": prompt + chosen tokens,
"rows": [first, end)} and writes the reference's logits for those positions,
how near the router's choices were to the next expert, and its own timings.
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
    logits, routing = reference.forward(server._params, server._cfg, ask["tokens"],
                                        rows=slice(first, end))
    out = {"logits": np.asarray(logits, np.float32)}
    if routing:
        margins = np.stack([np.asarray(layer["margin"]) for layer in routing])
        out["margins"] = margins[:, :end]
    out["seconds"] = np.asarray([built, time.monotonic() - t1])
    np.savez(answer_path + ".tmp.npz", **out)
    os.replace(answer_path + ".tmp.npz", answer_path)


if __name__ == "__main__":
    main()
