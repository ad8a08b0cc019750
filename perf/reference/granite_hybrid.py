"""The benchmark's copy of the plain reference for granite-4.0-h-micro: builds the
seeded weights by the rule the configuration states (the program's own random
init, on the CPU: weights are data, and the seed in <llm_kwargs.json> gives the
int8 tree the server holds, the float32 taps, conv bias, A_log, dt_bias, D and
norm weights among them), then answers one question with
seldon_core_tpu/models/reference.py: float32, highest matmul precision, no
cache, no state, no batching, no chunking.  A block is x + 0.22 f(RMSNorm(x))
twice; a mamba layer's causal taps are an explicit shifted sum over the WHOLE
sequence (+ the bias, then SiLU) and its recurrence a `lax.scan` over the tokens
from h = 0 with a state [64, 128] a head (the served path carries three rows of
[x ; B ; C] and the float32 h across every chunk and step, and runs a chunked
form in the prefill); the gate is applied BEFORE the one norm over 4,096
channels; an attention layer sees no position and scales its scores by 1/64; the
table's rows are multiplied by 12 and the logits, out of the SAME table, divided
by 8.  A helper child beside the server:

    python granite_hybrid.py <llm_kwargs.json> <ask.json> <answer.npz>

The tree stays int8 with its scales (3.2 GB), and the forward makes ONE matrix
float32 at a time (`reference._f32`, as it is used), the table among them
(0.8 GB while it is read).  It builds the weights at once (hidden behind the
server's own start), runs one forward over made-up tokens of the probe's length
through the layers up to the first attention layer (every layer of a kind has
the same shapes, so the real forward compiles nothing) while the server is
still starting, then waits for <ask.json>: {"tokens": prompt + chosen tokens,
"rows": [first, end)} and writes the reference's logits for those positions and
its own timings.  A dense model: there is no routing to follow
(planes/llm_rest_reference.py).  Then, where planes/llm_rest_state_reference.py
asks (<answer's directory>/reference_state_ask.json: {"tokens", "layer"}), the h
[64, 64, 128] that mamba layer holds after those tokens, token by token from
zeros, twice: in float32 ("state") and rounded to bf16 after every token
("state_bf16": what a cache that held h in bf16 would do, the reading the
plane's limit has to lie under), and how many tokens each head carries its
state ("memory": 1 / (|A| softplus(dt_bias)), from the layer's own leaf), into
reference_state_answer.npz.  That question's pieces are compiled ahead too, over
made-up tokens of the cell's `state_probe` length.
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lfm2 import probe_tokens  # noqa: E402 - the probe's length, from the cell's own file


def state_probe_tokens(kwargs_path: str) -> int:
    """How many tokens the state's question will hold (the cell's `state_probe`:
    the prompt and every sampled token but the last), as ``probe_tokens``."""
    perf = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = os.path.basename(os.path.dirname(os.path.abspath(kwargs_path)))
    try:
        with open(os.path.join(perf, "workloads", cell + ".json")) as f:
            probe = json.load(f)["state_probe"]
        return int(probe["prompt_tokens"]) + int(probe["output_tokens"]) - 1
    except (OSError, KeyError, ValueError):
        return 0


def states(reference, server, tokens, layer: int) -> dict:
    """The layer's h after ``tokens`` in float32 and held in bf16, and each
    head's memory in tokens."""
    tree = server._params.get("params", server._params)
    a_log, dt_bias, _ = np.asarray(tree[f"layer_{layer}"]["mamba"]["heads"])
    out = {name: np.asarray(reference.ssd_state(server._params, server._cfg, tokens, layer,
                                                **wrong), np.float32)
           for name, wrong in (("state", {}), ("state_bf16", {"ssd_state_bf16": True}))}
    out["memory"] = 1.0 / (np.exp(a_log) * np.logaddexp(0.0, dt_bias))
    return out


def rehearse_states(reference, server, kwargs_path: str, ask_path: str) -> bool:
    """The state's question over made-up tokens of its length, unless the question
    at ``ask_path`` is out already (False): XLA's CPU compiles of the pieces at
    a new length are most of a first answer's time."""
    fed = state_probe_tokens(kwargs_path)
    if fed <= 0 or os.path.exists(ask_path):
        return False
    t0 = time.monotonic()
    states(reference, server, [97] * fed, server._cfg.layer_types.index("mamba"))
    print(f"rehearsed the state's question over {fed} tokens in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return True


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
        # the layers up to the FIRST attention layer over made-up tokens of the
        # question's length: every layer of a kind has the same shapes, so this
        # compiles every piece the real forward runs, in a seventh of its time
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
    rehearsed = rehearse_states(reference, server, kwargs_path, ask_path)
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
    # the state's question, if the plane has one (it kills this child when it is done)
    out_dir = os.path.dirname(answer_path)
    ask_path = os.path.join(out_dir, "reference_state_ask.json")
    if not rehearsed:   # the question came early: now, while the server decodes the state's probe
        rehearse_states(reference, server, kwargs_path, ask_path)
    while not os.path.exists(ask_path):
        time.sleep(0.1)
    with open(ask_path) as f:
        ask = json.load(f)
    t1 = time.monotonic()
    held = states(reference, server, ask["tokens"], ask["layer"])
    held["seconds"] = np.asarray(time.monotonic() - t1)
    state_path = os.path.join(out_dir, "reference_state_answer.npz")
    np.savez(state_path + ".tmp.npz", **held)
    os.replace(state_path + ".tmp.npz", state_path)


if __name__ == "__main__":
    main()
