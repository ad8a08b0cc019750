"""The start ledger (ISSUE 51 tentpole, seldon_core_tpu/tracing/start.py): the
stages of a server's start partition the wall from the process's creation to
the first ``/ready`` 200, and every build of a program is booked by program,
leg and the compile cache's verdict, on the thread that did it.

The contract: five stages whose sum IS that wall; a persistent-cache miss is
a ``compile``, the hit that follows a ``cache_load``; a function traced inside
another is ``nested="1"`` and the ``nested="0"`` seconds of a thread fit its
wall; a warm batcher's turns fire no listener; a program's first call is
counted once and tagged on the flight event of the call that paid for it.
CPU toy model (page 8, chunk 8)."""

from __future__ import annotations

import asyncio
import textwrap
import time

import pytest

from seldon_core_tpu import tracing
from seldon_core_tpu.metrics.registry import MetricsRegistry
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import BUILD_PROGRAMS, LLMServer
from seldon_core_tpu.tracing import Tracer, get_tracer, set_tracer
from seldon_core_tpu.tracing.start import (
    STAGES,
    TO_READY,
    StartLedger,
    get_ledger,
    name_programs,
    set_ledger,
)

SERVER_KW = dict(
    model="transformer", init_random=True, max_new_tokens=8, temperature=0.0, eos_id=-1,
    model_kwargs=dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
                      ffn_dim=64, max_seq_len=96),
    seed=3, kv_page_size=8, prefill_chunk=8, continuous_batching=2,
    continuous_batching_max_len=64)

# the component `cli microservice TinyLLM` would import from its working directory
TINY = textwrap.dedent(f"""
    from seldon_core_tpu.servers.llmserver import LLMServer


    class TinyLLM(LLMServer):
        def __init__(self):
            super().__init__(**{SERVER_KW!r})
""")

PROMPT = [5, 9, 17, 40, 3, 22, 8, 11, 60, 2]


@pytest.fixture()
def ledger():
    """A ledger of the test's own, listening, in the process's place."""
    fresh = StartLedger(age_s=0.0)
    fresh.listen()
    set_ledger(fresh)
    yield fresh
    fresh.close()
    set_ledger(None)


def seconds_of(ledger: StartLedger, program: str) -> dict:
    return {(leg, nested): s for (p, leg, nested), s in ledger.series()[1].items()
            if p == program}


def builds_of(ledger: StartLedger, program: str) -> dict:
    return {cache: n for (p, cache), n in ledger.series()[2].items() if p == program}


# ------------------------------------------------------------- the stages
def test_the_five_stages_partition_the_wall_to_the_first_ready(tmp_path, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from seldon_core_tpu.transport import cli, rest

    (tmp_path / "TinyLLM.py").write_text(TINY)
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("PREDICTIVE_UNIT_PARAMETERS", raising=False)
    old = get_tracer()
    tracer = Tracer(enabled=True)
    set_tracer(tracer)
    born_ago = 0.25                     # the stand-in for the kernel's creation time
    t0 = tracing.now()
    ledger = StartLedger(age_s=born_ago)
    set_ledger(ledger)
    try:
        component, _ = cli.build_component("TinyLLM")
        app = rest.make_component_app(component)

        async def go():
            async with TestClient(TestServer(app)) as client:
                first = await client.get("/ready")
                wall = tracing.now() - t0 + born_ago
                again = await client.get("/ready")
                return first.status, again.status, wall

        first, again, wall = asyncio.run(go())
        spans = tracer.drain()
    finally:
        set_ledger(None)
        set_tracer(old)
    assert (first, again) == (200, 200)
    stages = dict(ledger.stage_seconds)
    assert tuple(stages) == TO_READY            # each once, in order; nothing after /ready yet
    assert sum(stages.values()) == pytest.approx(wall, rel=0.02, abs=0.05)
    assert ledger.ready_s == pytest.approx(sum(stages.values()), abs=1e-6)   # a second /ready moved nothing
    assert stages["import"] >= born_ago and stages["load.weights"] > stages["load.rest"] > 0.0
    assert stages["construct"] > 0.0 and stages["listen"] > 0.0
    # one tree for the collector: the root spans the whole start, a child a stage
    by_name = {s.name: s for s in spans}
    root = by_name["server.start"]
    assert set(by_name) == {"server.start"} | {"start." + s for s in TO_READY}
    assert root.parent_id is None and root.end - root.start == pytest.approx(ledger.ready_s)
    for name in TO_READY:
        child = by_name["start." + name]
        assert (child.trace_id, child.parent_id) == (root.trace_id, root.span_id)
        assert child.end - child.start == pytest.approx(stages[name], abs=1e-6)
    # ... each beginning where the one before it ended
    ordered = [by_name["start." + s] for s in TO_READY]
    assert ordered[0].start == root.start and ordered[-1].end == root.end
    assert all(a.end == b.start for a, b in zip(ordered, ordered[1:]))


def test_the_partition_moves_forward_only_and_a_stage_outside_it_adds_up():
    ledger = StartLedger(age_s=0.0)
    ledger.advance("load.weights")
    ledger.advance("construct")                 # a second component's load(): nothing moves
    assert list(ledger.stage_seconds) == ["import"]
    ledger.ready()
    ledger.advance("listen")                    # after the first /ready: nothing moves
    assert list(ledger.stage_seconds) == ["import", "load.weights"]
    for _ in range(2):                          # a rebuilt batcher adds to the stage
        with ledger.stage("batcher.build"):
            time.sleep(0.01)
    assert ledger.stage_seconds["batcher.build"] >= 0.02
    with pytest.raises(ValueError, match="unknown start stage"):
        with ledger.stage("warmup"):
            pass
    assert set(ledger.stage_seconds) <= set(STAGES)


# ------------------------------------------------------------- the builds
def test_a_miss_is_a_compile_and_the_build_after_it_a_cache_load(ledger, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    # (the CPU's small programs are stored only with both thresholds at 0)
    options = {"jax_compilation_cache_dir": str(tmp_path),
               "jax_persistent_cache_min_compile_time_secs": 0,
               "jax_persistent_cache_min_entry_size_bytes": 0}
    old = {k: getattr(jax.config, k) for k in options}

    @jax.jit
    def ledger_cached_toy(x):
        return jnp.sin(x) @ x.T

    name_programs([("toy_cached", ("ledger_cached_toy",))])
    x = jnp.ones((4, 4))
    for key, value in options.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    try:
        ledger_cached_toy(x).block_until_ready()
        cold = (seconds_of(ledger, "toy_cached"), builds_of(ledger, "toy_cached"))
        jax.clear_caches()
        ledger_cached_toy(x).block_until_ready()
        ledger_cached_toy(x).block_until_ready()        # a cached call: no listener runs
    finally:
        for key, value in old.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
    assert set(cold[0]) == {("trace", "0"), ("lower", "0"), ("compile", "0")}
    assert cold[1] == {"miss": 1}
    warm = seconds_of(ledger, "toy_cached")
    assert set(warm) == set(cold[0]) | {("cache_load", "0")}
    assert warm["compile", "0"] == cold[0]["compile", "0"]      # the hit booked no compile
    assert warm["trace", "0"] > cold[0]["trace", "0"]           # traced and lowered again
    assert builds_of(ledger, "toy_cached") == {"miss": 1, "hit": 1}
    reg = MetricsRegistry(deployment="d", predictor="p")
    reg.sync_start()
    reg.sync_start()                                            # catch-up: the second adds nothing
    base = {"deployment_name": "d", "predictor_name": "p", "program": "toy_cached"}
    get = reg.registry.get_sample_value
    assert get("seldon_program_builds_total", {**base, "cache": "hit"}) == 1
    assert get("seldon_program_builds_total", {**base, "cache": "miss"}) == 1
    assert get("seldon_program_build_seconds_total",
               {**base, "leg": "cache_load", "nested": "0"}) == pytest.approx(warm["cache_load", "0"])


def test_a_function_traced_inside_another_is_nested_and_top_level_seconds_fit_the_wall(ledger):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ledger_inner_toy(x):
        return jnp.tanh(x) * 2

    @jax.jit
    def ledger_outer_toy(x):
        return ledger_inner_toy(x) + 1

    name_programs([("toy_outer", ("ledger_outer_toy",)), ("toy_inner", ("ledger_inner_toy",))])
    x = jnp.ones((3, 5))
    before = sum(s for (_p, _leg, nested), s in ledger.series()[1].items() if nested == "0")
    t0 = time.perf_counter()
    ledger_outer_toy(x).block_until_ready()
    wall = time.perf_counter() - t0
    inner, outer = seconds_of(ledger, "toy_inner"), seconds_of(ledger, "toy_outer")
    assert set(inner) == {("trace", "1")}       # inside the outer's trace: no build of its own
    assert {leg for leg, _ in outer} == {"trace", "lower", "compile"} or \
        {leg for leg, _ in outer} == {"trace", "lower", "cache_load"}
    assert all(nested == "0" for _, nested in outer)
    assert inner["trace", "1"] <= outer["trace", "0"]
    top = sum(s for (_p, _leg, nested), s in ledger.series()[1].items() if nested == "0") - before
    assert 0.0 < top <= wall
    assert ledger.listener_calls > 0 and ledger.listener_s < wall


# ------------------------------------------------- the batcher's programs
def test_a_first_call_is_counted_and_tagged_and_warm_turns_fire_no_listener(ledger):
    assert {"decode_step", "prefill_chunk", "first_token", "page_ops", "weights"} <= {
        program for program, _functions in BUILD_PROGRAMS}
    server = LLMServer(**SERVER_KW)
    server.load()
    assert builds_of(ledger, "decode_step") == builds_of(ledger, "prefill_chunk") == {}

    async def go():
        b = ContinuousBatcher(server, max_slots=2, max_len=64, tracing=True)
        try:
            await b.submit(PROMPT, 52)          # every program's first call, page growth's too
            (cold,) = b._flight.timelines()
            built = sum(builds_of(ledger, "decode_step").values())
            mark = (ledger.listener_calls, ledger.series(), b._phases.turns)
            await b.submit(PROMPT, 52)          # the same shapes again: fifty turns and more
            warm = b._flight.timelines()[-1]
            return cold, built, mark, b._phases.turns, warm
        finally:
            await b.close()

    cold, built, mark, turns, warm = asyncio.run(go())
    # the calls that paid for a build say which, and no other call says anything
    chunks = [e for e in cold["events"] if e["kind"] == "prefill_chunk"]
    steps = [e for e in cold["events"] if e["kind"] == "step"]
    assert [e.get("built") for e in chunks] == ["prefill_chunk", None]
    assert steps[0]["built"] == "decode_step"
    assert not any("built" in e for e in steps[1:])
    assert built == 1 and sum(builds_of(ledger, "prefill_chunk").values()) == 1
    # warm: no listener ran and no series moved over fifty decode turns
    assert turns - mark[2] >= 50
    assert (ledger.listener_calls, ledger.series()) == mark[:2]
    assert not any("built" in e for e in warm["events"])
