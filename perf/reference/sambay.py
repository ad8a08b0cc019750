"""The benchmark's copy of the plain reference for Phi-4-mini-flash-reasoning: builds
the seeded weights by the rule the configuration states (the program's own random
init, on the CPU: weights are data, and the seed in <llm_kwargs.json> gives the
int8 tree the server holds, the float32 taps, conv bias, A_log, b_dt, D, lambda
vectors, projection and LayerNorm biases and norm weights among them), then
answers one question with seldon_core_tpu/models/reference.py: float32, highest
matmul precision, no cache, no state, no batching, no chunking, EVERY layer on
EVERY row (the served path runs 18 of the 32 layers on a prompt's rows and the
other 14 on its last row alone: the architecture's property, which this forward
does not use).  A block is x + f(LayerNorm(x)) twice, the norm with its mean and its
bias; an s6 layer's causal taps are an explicit shifted sum over the WHOLE
sequence (+ the bias, then SiLU) and its recurrence a `lax.scan` over the tokens
from h = 0 with a state [5120, 16]; layer 16 hands its scan output (before the
gate, D x in it) to the seven gated memory units; a differential attention layer
computes its two softmaxes a pair of heads as written, in blocks of 256 query
rows (a block's scores are [20, 256, rows seen] float32: it is the blocks that
make a prompt of thousands of rows fit beside the weights), the window layers
over the 512 rows a query sees, layer 17 over everything, the seven cross layers
with queries of their own over layer 17's k and v; no position anywhere.  A
helper child beside the server:

    python sambay.py <llm_kwargs.json> <ask.json> <answer.npz>

The tree stays int8 with its scales (3.9 GB), and the forward makes ONE matrix
float32 at a time (`reference._f32`, as it is used), the table among them (2 GB
while it is read).  It builds the weights at once (hidden behind the server's own
start), runs one forward over made-up tokens of the probe's length through EIGHT
layers, one or two of every kind (the plan at 8 layers over the tree's own layers
0-3 and 16-19: every layer of a kind has the same shapes, so the real forward
compiles nothing) while the server is still starting, then waits for <ask.json>:
{"tokens": prompt + chosen tokens, "rows": [first, end)} and writes the
reference's logits for those positions and its own timings.  A dense model: there
is no routing to follow (planes/llm_rest_reference.py).  Then, where
planes/llm_rest_state_reference.py asks (<answer's directory>/
reference_state_ask.json: {"tokens", "layer"}), the h that s6 layer holds after
those tokens, token by token from zeros, as 40 blocks of 128 channels [40, 128,
16] standing where another model's heads do, twice: in float32 ("state") and
rounded to bf16 after every token ("state_bf16": what a cache that held h in bf16
would do, the reading the plane's limit has to lie under), and how many tokens a
block carries its state ("memory": the median over its channels and states of
1 / (|A| softplus(b_dt)), from the layer's own leaves), into
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

from granite_hybrid import state_probe_tokens  # noqa: E402 - the state question's length, from the cell's file
from lfm2 import probe_tokens  # noqa: E402 - the probe's length, likewise

BLOCK = 128     # channels of h that stand where a head does in the state's answer


def blocks(h) -> np.ndarray:
    """h [E, N] -> [E / 128, 128, N] (all of E where it is no whole number of blocks)."""
    h = np.asarray(h, np.float32)
    lanes = BLOCK if h.shape[0] % BLOCK == 0 else h.shape[0]
    return h.reshape(h.shape[0] // lanes, lanes, h.shape[1])


def states(reference, server, tokens, layer: int) -> dict:
    """The layer's h after ``tokens`` in float32 and held in bf16, a block of
    channels at a time, and each block's memory in tokens."""
    tree = server._params.get("params", server._params)
    leaves = tree[f"layer_{layer}"]["s6"]
    out = {name: blocks(reference.s6_state(server._params, server._cfg, tokens, layer, **wrong))
           for name, wrong in (("state", {}), ("state_bf16", {"s6_state_bf16": True}))}
    rate = (np.exp(np.asarray(leaves["A_log_t"], np.float32)).T
            * np.logaddexp(0.0, np.asarray(leaves["b_dt"], np.float32))[:, None])      # [E, N]
    out["memory"] = np.median(blocks(1.0 / rate).reshape(len(out["state"]), -1), axis=1)
    return out


def rehearse_states(reference, server, kwargs_path: str, ask_path: str) -> bool:
    """The state's question over made-up tokens of its length, unless the question
    at ``ask_path`` is out already (False)."""
    fed = state_probe_tokens(kwargs_path)
    if fed <= 0 or os.path.exists(ask_path):
        return False
    t0 = time.monotonic()
    states(reference, server, [97] * fed, server._cfg.layer_types.index("s6"))
    print(f"rehearsed the state's question over {fed} tokens in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return True


def every_kind_once(server):
    """(params, cfg) of the plan at 8 layers over the tree's own layers: the first
    four and the four from the layer that hands its scan output up (s6, window,
    s6, window | s6, full | gmu, cross)."""
    from seldon_core_tpu.models.convert import sambay_layer_types

    cfg, tree = server._cfg, server._params.get("params", server._params)
    half = cfg.n_layers // 2
    taken = list(range(4)) + list(range(half, half + 4))
    small = {**{k: v for k, v in tree.items() if not k.startswith("layer_")},
             **{f"layer_{i}": tree[f"layer_{j}"] for i, j in enumerate(taken)}}
    return small, dataclasses.replace(cfg, n_layers=8, layer_types=sambay_layer_types(8),
                                      memory_source=4, kv_source=5)


def main() -> None:
    kwargs_path, ask_path, answer_path = sys.argv[1:4]
    t0 = time.monotonic()
    # behind the server on the host's cores: while both start, the server's compiles
    # are what the run waits for (this child's answer is asked for minutes later)
    os.nice(10)
    from seldon_core_tpu.models import reference
    from seldon_core_tpu.servers.llmserver import LLMServer

    with open(kwargs_path) as f:
        server = LLMServer(**json.load(f))
    server.load()
    built = time.monotonic() - t0
    print(f"weights built in {built:.1f}s", file=sys.stderr, flush=True)
    tokens = probe_tokens(kwargs_path)
    if tokens > 1 and not os.path.exists(ask_path) and server._cfg.n_layers >= 8:
        t1 = time.monotonic()
        made_up = np.random.default_rng(0).integers(97, 123, size=tokens).tolist()
        small, cfg = every_kind_once(server)
        np.asarray(reference.forward(small, cfg, made_up, rows=slice(tokens - 2, tokens))[0])
        print(f"rehearsed 8 layers over {tokens} tokens in {time.monotonic() - t1:.1f}s",
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
