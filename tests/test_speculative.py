"""Speculative decoding parity + bookkeeping (ISSUE 8 tentpole).

The bar is the same one the pipelined batcher (PR 3) and the paged cache
(PR 7) already hold: speculation may change HOW MANY tokens arrive per
target forward, never WHICH tokens. Greedy and seeded-sampled outputs
through the speculative batcher must be bit-exact vs non-speculative
``generate()`` across K in {1, 2, 4} and both KV dtypes —
the rng chain advances per ACCEPTED token, so the key state after any
prefix equals sequential decode's after the same prefix.

Redundant-coverage combos are marked ``slow`` (the 870s tier-1 budget);
all of them run in CI's unfiltered unit step, and this file is pinned as
its own CI step like the paged parity suite.
"""

import asyncio

import pytest

from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.runtime.spec import SpecController, normalize_spec_mode
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)

# one shape vocabulary for every batcher in this file, so jit caches hit
# across tests (each (S, K, hist_len, mode, n_pages) tuple is a compile)
BKW = dict(max_slots=2, max_len=32, len_buckets=(8,), pipeline_depth=2,
           page_size=8)


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def server():
    return make_server()


@pytest.fixture(scope="module")
def sampled_server():
    return make_server(temperature=0.8, top_k=20, seed=5)


@pytest.fixture(scope="module")
def int8_server():
    return make_server(kv_cache_dtype="int8", temperature=0.8, top_k=20,
                       seed=5)


@pytest.fixture(scope="module")
def draft_server():
    # draft config == target config and both random-init from the server
    # seed -> the draft is a bit-identical copy: the PERFECT drafter, whose
    # proposals the target must accept wholesale (greedy). Any parity break
    # here is a chain bug, never a drafting-quality artifact.
    return make_server(spec_mode="draft", draft_model="transformer",
                       draft_model_kwargs=KW)


def run_batch(server, prompts, *, n=8, seeds=None, **batcher_kw):
    kw = dict(BKW)
    kw.update(batcher_kw)

    async def go():
        b = ContinuousBatcher(server, **kw)
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=n,
                     seed=None if seeds is None else seeds[i])
            for i, p in enumerate(prompts)])
        stats = b.spec_stats()
        stats["admit_inflight"] = b._last_admit_inflight
        stats["hwm"] = b._inflight_hwm
        await b.close()
        return outs, stats

    return asyncio.run(go())


PROMPTS = [[5, 9, 17], [40, 3, 22, 8, 11], [7], [60, 61, 62, 63]]
# repetitive prompt: the n-gram proposer's home turf (and greedy decode
# falls into a cycle it then predicts perfectly)
REP = [3, 7, 11, 3, 7, 11, 3, 7, 11, 3, 7]


@pytest.fixture(scope="module")
def expected(server):
    return [server.generate([p], max_new_tokens=8)["tokens"][0]
            for p in PROMPTS]


# ----------------------------------------------------------- greedy parity
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_ngram_greedy_parity_paged(server, expected, kv):
    """int8: quantize-on-write of a K-token verify block into the pool
    must round-trip identically to sequential single-token writes."""
    if kv == "int8":
        server = make_server(kv_cache_dtype="int8")
        expected = [server.generate([p], max_new_tokens=8)["tokens"][0]
                    for p in PROMPTS]
    outs, _ = run_batch(server, PROMPTS, spec_mode="ngram", spec_k=4)
    assert server.kv_cache_dtype == kv
    assert outs == expected


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 2])
def test_ngram_greedy_parity_paged_small_k(server, expected, k):
    outs, _ = run_batch(server, PROMPTS, spec_mode="ngram", spec_k=k)
    assert outs == expected


# ------------------------------------------------------ seeded-sampled parity
SEEDED_PROMPTS = [[5, 9, 17, 2], [40, 3, 22], [7, 7, 7, 7, 7]]
SEEDS = [42, 1234, 7]


def test_ngram_seeded_parity(sampled_server):
    """Seeded sampling through the verify step stays on generate()'s exact
    per-slot rng chain: one split per ACCEPTED token, never per forward."""
    expected = [sampled_server.generate([p], max_new_tokens=8, seed=s)["tokens"][0]
                for p, s in zip(SEEDED_PROMPTS, SEEDS)]
    outs, _ = run_batch(sampled_server, SEEDED_PROMPTS, seeds=SEEDS,
                        spec_mode="ngram", spec_k=4)
    assert outs == expected


# tier-1 870s budget: seeded int8+spec rides CI's unfiltered speculative
# step; tier-1 keeps seeded spec via test_ngram_seeded_parity and int8
# spec via test_ngram_greedy_parity_paged[int8]
@pytest.mark.slow
def test_int8_seeded_parity(int8_server):
    """int8 KV: quantize-on-write of a K-token verify block
    must round-trip identically to sequential single-token writes (scales
    are per-position, so block width cannot change them)."""
    expected = [int8_server.generate([p], max_new_tokens=8, seed=s)["tokens"][0]
                for p, s in zip(SEEDED_PROMPTS, SEEDS)]
    outs, _ = run_batch(int8_server, SEEDED_PROMPTS, seeds=SEEDS,
                        spec_mode="ngram", spec_k=4)
    assert outs == expected


# --------------------------------------------------------- draft-model path
def test_draft_model_greedy_parity(draft_server):
    expected = [draft_server.generate([p], max_new_tokens=8)["tokens"][0]
                for p in PROMPTS]
    outs, st = run_batch(draft_server, PROMPTS, spec_mode="draft", spec_k=4)
    assert outs == expected
    # the perfect drafter's proposals all verify: acceptance 1.0 and the
    # multiplier approaches K+1 (EOS-less 8-token budgets cap the tail)
    assert st["spec_accept_rate"] == pytest.approx(1.0)
    assert st["spec_tokens_per_forward"] > 2.0


@pytest.mark.slow
def test_draft_model_seeded_parity_paged():
    s = make_server(spec_mode="draft", draft_model="transformer",
                    draft_model_kwargs=KW, temperature=0.8, top_k=20, seed=5)
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(SEEDED_PROMPTS, SEEDS)]
    outs, _ = run_batch(s, SEEDED_PROMPTS, seeds=SEEDS, spec_mode="draft",
                        spec_k=4)
    assert outs == expected


# ------------------------------------------------- EOS inside a draft block
@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_eos_inside_accepted_draft_block():
    """The device accepts past EOS (it cannot see host semantics); the
    drain must cut the credit loop AT the EOS and drop the trailing
    accepted tokens — same posture as a trailing run-ahead step."""
    s = make_server(spec_mode="draft", draft_model="transformer",
                    draft_model_kwargs=KW, eos_id=6)
    expected = s.generate([REP], max_new_tokens=8)["tokens"][0]
    outs, st = run_batch(s, [REP], spec_mode="draft", spec_k=4)
    assert outs[0] == expected
    # proof the EOS really landed INSIDE an accepted block: the device
    # advanced further per forward than the host surfaced (trailing
    # accepted tokens after EOS were dropped, never credited)
    assert st["spec_tokens_per_forward"] > len(expected) / max(
        st["spec_slot_steps_total"], 1)


# ------------------------------------------------------- mid-stream admission
def test_midstream_admit_with_steps_in_flight(server, expected):
    """An admission landing while verify steps are in flight: the insert
    queues behind them in device program order and the gen counter masks
    the old occupant's trailing variable-advance tokens."""
    prompts = PROMPTS + [[12, 13], [80, 2, 5]]
    exp = expected + [server.generate([p], max_new_tokens=8)["tokens"][0]
                      for p in [[12, 13], [80, 2, 5]]]
    outs, st = run_batch(server, prompts, spec_mode="ngram", spec_k=4)
    assert outs == exp
    # 6 requests through 2 slots: later admits MUST have found steps in
    # flight (the pipeline keeps dispatching while slots turn over)
    assert st["admit_inflight"] >= 1


# ------------------------------------------------- acceptance-rate criterion
@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_repetitive_text_beats_1_5_tokens_per_forward(server):
    """The ISSUE 8 acceptance bar: >1.5 accepted tokens per target forward
    at K=4 with the n-gram drafter on repetitive text."""
    expected = server.generate([REP], max_new_tokens=18)["tokens"][0]
    outs, st = run_batch(server, [REP], n=18, spec_mode="ngram", spec_k=4)
    assert outs[0] == expected
    assert st["spec_tokens_per_forward"] > 1.5, st
    assert st["spec_accept_rate"] > 0.0


# ----------------------------------------------------------------- metrics
@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_spec_metrics_reach_llm_stats_and_metrics():
    """spec series flow llm_stats -> sync_llm -> /metrics (the graftlint
    metrics-drift round-trip: recorded => declared, declared => recorded)."""
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import BatcherService

    s = make_server(continuous_batching=2, continuous_batching_max_len=32,
                    kv_page_size=8, spec_mode="ngram", spec_k=4)
    svc = BatcherService(s, max_slots=2)
    s._batcher_service = svc
    try:
        out = svc.submit_sync(REP, 8)
        assert len(out) == 8
        st = s.llm_stats()
        assert st["spec_mode"] == "ngram"
        assert st["spec_k"] == 4
        assert st["spec_slot_steps_total"] > 0
        assert st["spec_tokens_per_forward"] > 0.0
        assert len(st["spec_accept_rate_per_slot"]) == 2
        assert 0.0 <= st["spec_draft_overhead_fraction"] <= 1.0
        assert st["spec_accepted_per_step"], "no accepted-tokens observations"
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(s)
        text = reg.expose().decode()
        assert "seldon_llm_spec_accept_rate" in text
        assert "seldon_llm_spec_accept_rate_per_slot" in text
        assert "seldon_llm_spec_tokens_per_forward" in text
        assert "seldon_llm_spec_accepted_tokens_per_step" in text
        assert "seldon_llm_spec_draft_overhead_fraction" in text
        assert "seldon_llm_spec_slot_verify_steps_total" in text
    finally:
        svc.close()


# ------------------------------------------------------------- validation
def test_fuse_steps_with_speculation_rejected(server):
    """Fused fixed-K scan and variable accept length are incompatible: the
    combination must fail loudly at construction, not corrupt advance
    bookkeeping at runtime."""
    with pytest.raises(ValueError, match="decode_fuse_steps"):
        ContinuousBatcher(server, max_slots=2, max_len=32,
                          len_buckets=(8,), fuse_steps=4, spec_mode="ngram")


def test_spec_mode_validated_at_load():
    with pytest.raises(ValueError, match="spec_mode"):
        make_server(spec_mode="warp-drive")
    with pytest.raises(ValueError, match="spec_k"):
        make_server(spec_mode="ngram", spec_k=-1)
    with pytest.raises(ValueError, match="draft model"):
        make_server(spec_mode="draft")  # no draft_model given


def test_draft_vocab_mismatch_rejected():
    bad = dict(KW)
    bad["vocab_size"] = 64
    with pytest.raises(ValueError, match="vocab"):
        make_server(spec_mode="draft", draft_model="transformer",
                    draft_model_kwargs=bad)


def test_spec_mode_normalization():
    assert normalize_spec_mode("") == "off"
    assert normalize_spec_mode(None) == "off"
    assert normalize_spec_mode("prompt-lookup") == "ngram"
    assert normalize_spec_mode("DRAFT") == "draft"
    with pytest.raises(ValueError):
        normalize_spec_mode("banana")


# ------------------------------------------------- draft-length controller
def test_controller_warmup_then_adapts():
    c = SpecController(slots=2, k=4)
    # warmup: full depth regardless of early luck
    assert c.cap(0) == 4
    c.observe(0, 0, 4, 1)
    assert c.cap(0) == 4  # still warming up (1 < WARMUP_STEPS)
    c.observe(0, 0, 4, 1)
    # two full rejections: EMA fell below 0.5 -> depth steps down
    assert c.cap(0) < 4
    # the OTHER slot is untouched
    assert c.cap(1) == 4


def test_controller_floor_is_one_probe_not_zero():
    """Cap 0 would stop producing observations and strand the EMA forever;
    the floor is one probe draft per forward."""
    c = SpecController(slots=1, k=4)
    for _ in range(20):
        c.observe(0, 0, 4, 1)  # relentless rejection
    assert c.cap(0) == 1
    # acceptance returning lifts the cap back up
    for _ in range(20):
        c.observe(0, 1, 1, 2)  # the probe draft starts landing
    assert c.cap(0) >= 2


def test_controller_reset_forgets_previous_occupant():
    c = SpecController(slots=1, k=4)
    for _ in range(10):
        c.observe(0, 0, 4, 1)
    assert c.cap(0) == 1
    c.reset(0)
    assert c.cap(0) == 4  # fresh occupant starts at full depth


def test_controller_snapshot_math():
    c = SpecController(slots=1, k=4)
    c.observe(0, 3, 4, 4)   # 3 of 4 drafts accepted, 4 tokens emitted
    c.observe(0, 1, 4, 2)   # 1 of 4 accepted, 2 tokens
    snap = c.snapshot()
    assert snap["spec_slot_steps_total"] == 2
    assert snap["spec_accept_rate"] == pytest.approx(0.5)
    assert snap["spec_tokens_per_forward"] == pytest.approx(3.0)
    # 8 drafted + 2 base columns = 10 columns, 4 rejected drafts wasted
    assert snap["spec_draft_overhead_fraction"] == pytest.approx(0.4)
