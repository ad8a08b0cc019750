"""The benchmark's copy of the plain reference for LFM2-8B-A1B: builds the seeded
weights by the rule the configuration states (the program's own random init, on
the CPU: weights are data, and the seed in <llm_kwargs.json> gives the int8 tree
the server holds, the float32 taps, head norms and selection bias among them),
then answers one question with seldon_core_tpu/models/reference.py: float32,
highest matmul precision, no cache, no state, no batching; a conv layer's causal
taps as an explicit shifted sum over the WHOLE sequence (the served path carries
two rows of state across every chunk and step), the gates split B, C, X, each
head's q and k normed before RoPE, sigmoid scores chosen by score + selection
bias and weighed without it over (sum + 1e-6), a loop over experts.  A helper
child beside the server:

    python lfm2.py <llm_kwargs.json> <ask.json> <answer.npz>

It builds the weights at once (most of its time, hidden behind the server's
own start), runs one forward over made-up tokens of the probe's length while the
server is still starting (so that the real one compiles nothing: `rehearse`),
then waits for <ask.json>: {"tokens": prompt + chosen tokens,
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


def probe_tokens(kwargs_path: str) -> int:
    """How long the question will be: the probe of the cell whose run this is
    (<kwargs_path> lies in perf/out/<cell>/), prompt + decoded tokens; 0 where
    that cannot be read (a toy rehearsal's sizes are its own: nothing is lost)."""
    perf = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = os.path.basename(os.path.dirname(os.path.abspath(kwargs_path)))
    try:
        with open(os.path.join(perf, "workloads", cell + ".json")) as f:
            probe = json.load(f)["probe"]
        return int(probe["prompt_tokens"]) + int(probe["output_tokens"])
    except (OSError, KeyError, ValueError):
        return 0


def rehearse(reference, server, tokens: int, ask_path: str) -> None:
    """While the server is still starting: one forward over made-up tokens of the
    question's length, following made-up experts, so that the pieces the real
    forward runs are compiled when the question comes (XLA's CPU compiles of
    ~150 small pieces are most of a first forward's time, and the question
    waits for the probe, which waits for the server)."""
    cfg = server._cfg
    if tokens <= 1 or os.path.exists(ask_path):
        return
    rng = np.random.default_rng(0)
    follow = np.stack([rng.permutation(cfg.n_experts)[:cfg.n_experts_per_token]
                       for _ in range((tokens - 1) * cfg.n_moe_layers)])
    follow = follow.reshape(tokens - 1, cfg.n_moe_layers, -1).astype(np.int32)
    t0 = time.monotonic()
    logits, _ = reference.forward(server._params, cfg, rng.integers(97, 123, size=tokens).tolist(),
                                  rows=slice(tokens - 2, tokens), follow=follow)
    np.asarray(logits)
    print(f"rehearsed a forward of {tokens} tokens in {time.monotonic() - t0:.1f}s",
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
    rehearse(reference, server, probe_tokens(kwargs_path), ask_path)
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
