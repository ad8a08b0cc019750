"""The LLM deployment the benchmark serves: LLMServer with the constructor
arguments its configuration file states.  LLMServer is reached only from a
user model file (it takes lists and dicts that typed unit parameters cannot
carry), so this is that file; `cli microservice PerfLLM REST` is the normal
entry point.  The arguments arrive as JSON in the file PERF_LLM_KWARGS names.
"""

import json
import os

from seldon_core_tpu.servers.llmserver import LLMServer


class PerfLLM(LLMServer):
    def __init__(self, **overrides):
        with open(os.environ["PERF_LLM_KWARGS"]) as f:
            kwargs = json.load(f)
        kwargs.update(overrides)
        super().__init__(**kwargs)
