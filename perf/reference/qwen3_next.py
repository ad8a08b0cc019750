"""The benchmark's copy of the plain reference for Qwen3-Next-80B-A3B-Instruct as one
chip of its 4-way expert-parallel deployment holds it: builds the seeded weights
by the rule the configuration states (the program's own random init, on the CPU:
weights are data, and the seed in <llm_kwargs.json> gives the int8 tree the
server holds, the float32 taps, A_log, dt_bias, norm weights and the shared
expert's gate among them), then answers one question with
seldon_core_tpu/models/reference.py: float32, highest matmul precision, no
cache, no state, no batching, no chunking; a linear-attention layer's causal
taps as an explicit shifted sum over the WHOLE sequence and its delta rule as a
`lax.scan` over the tokens from S = 0 (the served path carries three rows of
[q ; k ; v] and a float32 matrix a head across every chunk and step, and runs a
chunked form in the prefill), q and k L2-normalised a head; the gated attention
with a norm a head, RoPE over the first 64 of 256 values, the heads' output
times sigmoid(gate); softmax scores over all 512 experts, top-10, renormalised
over all ten, of which the 128 held here compute and the others add NOTHING (the
same share the served tree holds); the shared expert behind its scalar gate, a
loop over experts.  A helper child beside the server, whose code is
perf/reference/lfm2.py's `main` (it builds whatever model <llm_kwargs.json>
names, and `reference.forward` reads the tree's layer kinds):

    python qwen3_next.py <llm_kwargs.json> <ask.json> <answer.npz>

It builds the weights at once (most of its time, hidden behind the server's
own start), runs one forward over made-up tokens of the probe's length while the
server is still starting (so that the real one compiles nothing: `rehearse`),
then waits for <ask.json>: {"tokens": prompt + chosen tokens,
"rows": [first, end), "follow": the experts the served path took, [tokens,
MoE layers, 10]} and writes the reference's logits for those positions with the
served experts followed (planes/llm_rest_followed_reference.py says why), how
near the router's own choices were to the next expert (`margins`), how far
behind its own the followed ones were (`behind`), and its own timings.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lfm2 import main  # noqa: E402 - the same child: the configuration's kwargs choose the model

if __name__ == "__main__":
    main()
