"""Llama-2-7B-width decoder with int8 weights behind continuous batching —
the LLM phase of chip_smoke.py and the LLM recipe of the verify skill.

Serve it from this directory:

    PYTHONPATH=<checkout> python -m seldon_core_tpu.transport.cli \
        microservice Llama7BInt8 REST --port 8127

Weights are random from the seed (the streamed int8 init: no 27 GB f32 tree
is ever materialised). Sized for one 16 GB v5e chip: ~6.9 GB of int8
weights, and a slot pool of 8 slots x (2 x 512 + 64) tokens of bf16 KV =
138 pages x 64 tokens x 0.5 MB/token = 4.6 GB. Everything not named below
is LLMServer's default — paged KV, 64-token pages, 256-token prefill
chunks, decode pipeline depth 2.

Typed unit parameters (PREDICTIVE_UNIT_PARAMETERS) override any of these:
chip_smoke.py passes ``tensor_parallel`` that way for ``--tp``, and toy
dims for its CPU rehearsal.
"""

from seldon_core_tpu.servers.llmserver import LLMServer


class Llama7BInt8(LLMServer):
    def __init__(self, **overrides):
        kwargs = dict(
            model="llama2-7b", quantize="int8", init_random=True, seed=0,
            continuous_batching=8, len_buckets=(128, 256, 512),
            max_new_tokens=64, temperature=0.7, eos_id=-1,
        )
        kwargs.update(overrides)
        super().__init__(**kwargs)
