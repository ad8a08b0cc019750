"""A long prompt prefills in wide chunks while no other slot streams (ISSUE 48).

The width of a prompt's NEXT chunk is chosen when the chunk is built
(``ContinuousBatcher._chunk_width``): the wide program (``WIDE_PREFILL_CHUNK``
rows) while the narrow program would compute at least that many rows for what
is left of the prompt anyway (more than ``wide - narrow`` rows left, ISSUE 58;
a SEEDED request: more than ``wide``, as every request before it) and no other
live slot has ``on_token``; the job's own width otherwise. Held here, on the
CPU at rehearsal widths (8-row chunks, 16-row wide chunks: the batcher's
attribute is set by the test, there is no option for it):

(a) for the seven token mixers (GQA pages, latent rows, a conv state, a
    delta-rule state, a window page class, a state-space state, a selective
    scan under a cross-decoder) a prompt prefilled in wide chunks, its tail ONE
    padded wide chunk that runs the head or a narrow one, gives the tokens of
    the all-narrow run, its logits within the tolerance the chunk-against-whole
    tests use, and the same pool and state behind the prompt's last chunk;
(b) the rule itself from host state alone;
(c) under a streaming neighbour no chunk is wide, the job's own stream does not
    count, and ``seldon_llm_chunk_rows_total{width}`` says so on ``/metrics``;
(d) a server's start: nobody waits for the wide program, so a thread of its
    own builds it once a request has finished, and a chunk is wide once it is
    there, a LoRA server's too; a SEEDED request's wide chunk waits for it
    (ISSUE 54; PRs 48-53 built both programs at the first chunk).
"""

from __future__ import annotations

import asyncio
import threading

import jax
import numpy as np
import pytest
from test_chunk_head import MODELS as CHUNK_HEAD_MODELS
from test_chunk_head import built, chunk_events, set_wide_chunk
from test_hybrid_state import MAMBA_KW, SAMBAY_KW
from test_reference_smallthinker import KW as WINDOW_KW

from seldon_core_tpu.models import cache as kvcache
from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS

from seldon_core_tpu.runtime.batcher import (
    DEFAULT_PAGE_SIZE,
    DEFAULT_PREFILL_CHUNK,
    WIDE_PREFILL_CHUNK,
    ContinuousBatcher,
    _PrefillJob,
)
from seldon_core_tpu.servers.llmserver import LLMServer

CHUNK, WIDE, PAGE, MAX_LEN = 8, 16, 4, 64
MODELS = {
    "gqa_pages": CHUNK_HEAD_MODELS["dense_gqa"],
    "latent_rows": CHUNK_HEAD_MODELS["latent_moe"],
    "conv_state": CHUNK_HEAD_MODELS["state_layers"],
    # Qwen3-Next's period in small (tests/test_reference_qwen3_next.py): three
    # Gated DeltaNet layers, whose matrix state a chunk hands to the next, and
    # one gated GQA layer
    "delta_rule_state": dict(
        vocab_size=96, dim=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=16,
        n_experts=16, n_experts_per_token=4, router_renormalize=True, n_shared_experts=1,
        shared_expert_gate=True, qk_norm="head", attn_gate=True, partial_rotary_factor=0.25,
        max_seq_len=96, norm_eps=1e-6, rope_theta=1e7, dtype="float32",
        layer_types=["linear_attention"] * 3 + ["full_attention"], linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=8,
        linear_conv_kernel_dim=4),
    # SmallThinker's period in small (tests/test_reference_smallthinker.py): a
    # global layer and three window layers, whose pages behind a window of 12
    # rows are given back chunk by chunk
    "window_pages": WINDOW_KW,
    # granite-4.0-h's and Phi-4-mini-flash's in small (tests/test_hybrid_state.py):
    # mamba layers (a float32 h a head), and s6 layers under window, full and
    # cross-attention layers, the layers past cfg.kv_source run on ``head_row`` alone
    "state_space": MAMBA_KW,
    "selective_scan_cross_decoder": SAMBAY_KW,
}
# two wide chunks (16 + 16), then 13 rows: more than a wide chunk less a narrow
# one, which the narrow program would compute as 16 (8 + 5 padded to 8), so ONE
# wide chunk of 13 rows and 3 of padding, whose row 12 the head reads
PROMPT = np.random.default_rng(48).integers(1, 96, size=45).tolist()
# the last chunk by the prompt's length: (rows of the prompt, its chunks as the
# flight recorder has them, rows by the width of the program that took them)
TAILS = {
    "a tail of 13 rows is one padded wide chunk": (
        45, [(0, 16, 0), (16, 16, 0), (32, 13, 1)], {"16": 45}),
    "a tail of 8 rows, a narrow chunk's, stays narrow": (
        40, [(0, 16, 0), (16, 16, 0), (32, 8, 1)], {"16": 32, "8": 8}),
    "a tail of 6 rows stays narrow": (38, [(0, 16, 0), (16, 16, 0), (32, 6, 1)], {"16": 32, "8": 6}),
    "a prompt of 13 rows is one padded wide chunk from row 0": (13, [(0, 13, 1)], {"16": 13}),
}


@pytest.fixture(scope="module")
def servers():
    made = {}

    def get(model):
        if model not in made:
            s = LLMServer(model="transformer", model_kwargs=MODELS[model], init_random=True,
                          max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                          eos_id=-1, seed=3, temperature=0.0)
            s.load()
            made[model] = s
        return made[model]

    return get


def make_batcher(server, wide=WIDE, **kw) -> ContinuousBatcher:
    base = dict(max_slots=3, max_len=MAX_LEN, len_buckets=(CHUNK,), page_size=PAGE,
                prefill_chunk=CHUNK)
    base.update(kw)
    # an explicit width is every chunk's, so the rehearsal sets the wide one by hand
    return set_wide_chunk(ContinuousBatcher(server, **base), wide)


# ------------------------------------------- (a) the same answer
def held_behind_the_last_chunk(b) -> dict:
    """What the batcher holds when a prompt's last chunk has been dispatched
    (``_activate`` is its last act), copied to the host: the cache tree, the
    job's table rows of both page classes and its slot."""
    seen = {}
    activate = b._activate

    def spy(job, logits):
        seen.update(tree=jax.tree.map(np.asarray, b._caches), slot=job.slot,
                    rows=(np.asarray(job.bt_row[0]), None if job.wrow is None else job.wrow.copy()))
        return activate(job, logits)

    b._activate = spy
    return seen


def assert_close_at_scale(got, want, rel):
    """Within ``rel`` of the array's SCALE (max |want|, at least 1)."""
    np.testing.assert_allclose(got, want, atol=rel * max(1.0, float(np.abs(want).max())), rtol=0)


def assert_the_same_pool_and_state(cfg, got, want, rel):
    """Layer by layer: a state layer's arrays of the slot, and a paged layer's
    LIVE rows and every position on the pages both runs hold (the window class
    has given back what lies behind the last chunk's first row, which is another
    row at another width), read through each run's own table: a page's number is
    the allocator's, and the trash page is nobody's."""
    assert got["slot"] == want["slot"]
    for i, (a, b) in enumerate(zip(got["tree"], want["tree"])):
        if kvcache.is_state_entry(a):
            for x, y in zip(a, b):
                assert_close_at_scale(x[got["slot"]], y[want["slot"]], rel)
            continue
        row_a, row_b = (rows[int(i in cfg.window_layers)] for rows in (got["rows"], want["rows"]))
        both = (row_a != NULL_PAGE) & (row_b != NULL_PAGE)
        assert both.any()
        pos_a, pos_b = a[-1][row_a[both]], b[-1][row_b[both]]
        np.testing.assert_array_equal(pos_a, pos_b)
        for x, y in zip(a[:-1], b[:-1]):
            live = pos_a < PAD_POS
            assert_close_at_scale(x[row_a[both]][live], y[row_b[both]][live], rel)


@pytest.mark.parametrize("tail", TAILS)
@pytest.mark.parametrize("model", MODELS)
def test_wide_then_narrow_chunks_give_the_all_narrow_runs_answer(servers, model, tail):
    server = servers(model)
    length, want_chunks, want_rows = TAILS[tail]
    prompt = PROMPT[:length]

    async def go(wide):
        b = make_batcher(server, wide=wide, tracing=True)
        info, held = {"logits": []}, held_behind_the_last_chunk(b)
        out = await b.submit(prompt, 4, info=info)
        stats, timelines = b._phases.stats(), b._flight.timelines()
        await b.close()
        return out, np.stack(info["logits"]), stats, chunk_events(timelines), held

    out, logits, stats, chunks, held = asyncio.run(go(WIDE))
    narrow_out, narrow_logits, narrow_stats, narrow_chunks, narrow_held = asyncio.run(go(0))
    assert chunks == want_chunks
    assert narrow_chunks == [(s, min(8, length - s), int(s + 8 >= length))
                             for s in range(0, length, 8)]
    assert stats["chunk_rows"] == want_rows
    assert narrow_stats["chunk_rows"] == {"8": length}
    # the last chunk ran the head, in the program of its width, part full or not
    last = "8" if "8" in want_rows else "16"
    assert chunks[-1][1] < WIDE and chunks[-1][2] == 1
    assert stats["chunk_head"]["1"] == {last: 1}
    assert stats["chunk_head"]["0"] == ({"16": len(chunks) - 1} if len(chunks) > 1 else {})
    assert narrow_stats["chunk_head"] == {"1": {"8": 1}, "0": {"8": len(narrow_chunks) - 1}
                                          if len(narrow_chunks) > 1 else {}}
    # two chunk programs, no ladder
    assert sorted(k[1] for k in server._prefill_cache if k[0] == "pchunk") == [CHUNK, WIDE]
    assert out == narrow_out
    assert out == server.generate([prompt], max_new_tokens=4)["tokens"][0]
    np.testing.assert_allclose(logits, narrow_logits, atol=3e-5, rtol=0)
    assert_the_same_pool_and_state(server._cfg, held, narrow_held, rel=3e-5)


# ------------------------------------------- (b) the rule, from host state
def job_of(slot: int, length: int, done: int, chunk: int = CHUNK, on_token=None,
           seed=None) -> _PrefillJob:
    job = _PrefillJob(slot, list(range(length)), 0, chunk, 4, None, on_token, None, seed, None, [])
    job.next = done
    return job


def stream(tok):
    """A caller's ``on_token``."""


# rows left of the prompt, who else holds a slot (live?, streams?), the job's
# own width and the wide one (the rehearsal's or the served ones), the seed the
# request came with -> the width
TOY, SERVED = (CHUNK, WIDE), (DEFAULT_PREFILL_CHUNK, WIDE_PREFILL_CHUNK)
NARROW, WIDEST = DEFAULT_PREFILL_CHUNK, WIDE_PREFILL_CHUNK
RULE = {
    "more than a wide chunk left, alone": (WIDE + 1, [], TOY, None, WIDE),
    "a whole prompt left, alone": (45, [], TOY, None, WIDE),
    "exactly a wide chunk left: one wide chunk, full": (WIDE, [], TOY, None, WIDE),
    "a row more than a wide chunk less a narrow one left: one wide chunk, padded": (
        WIDE - CHUNK + 1, [], TOY, None, WIDE),
    "a wide chunk less a narrow one left: the narrow program computes fewer rows": (
        WIDE - CHUNK, [], TOY, None, CHUNK),
    "less than a narrow chunk left": (5, [], TOY, None, CHUNK),
    "a live neighbour that streams": (45, [(True, True)], TOY, None, CHUNK),
    "a padded wide chunk's rows left, a live neighbour that streams": (
        WIDE - 3, [(True, True)], TOY, None, CHUNK),
    "a live neighbour that waits for a plain reply": (45, [(True, False)], TOY, None, WIDE),
    "a padded wide chunk's rows left, a neighbour that waits for a plain reply": (
        WIDE - 3, [(True, False)], TOY, None, WIDE),
    "a slot whose stream has ended (not live)": (45, [(False, True)], TOY, None, WIDE),
    "one neighbour streams, one does not": (45, [(True, False), (True, True)], TOY, None, CHUNK),
    # a request that came with a seed keeps the widths its length gave it before PR 58
    "seeded: more than a wide chunk left": (WIDE + 1, [], TOY, 7, WIDE),
    "seeded: exactly a wide chunk left: the last chunks are narrow": (WIDE, [], TOY, 7, CHUNK),
    "seeded: a row more than a wide chunk less a narrow one left": (
        WIDE - CHUNK + 1, [], TOY, 7, CHUNK),
    "seeded: a wide chunk less a narrow one left": (WIDE - CHUNK, [], TOY, 7, CHUNK),
    "seeded, seed 0: exactly a wide chunk left": (WIDE, [], TOY, 0, CHUNK),
    "served widths: a row more than a wide chunk left": (WIDEST + 1, [], SERVED, None, WIDEST),
    "served widths: exactly a wide chunk left": (WIDEST, [], SERVED, None, WIDEST),
    "served widths: 769 rows left": (WIDEST - NARROW + 1, [], SERVED, None, WIDEST),
    "served widths: 768 rows left": (WIDEST - NARROW, [], SERVED, None, NARROW),
    "served widths: a live neighbour that streams": (
        4 * WIDEST, [(True, True)], SERVED, None, NARROW),
    "served widths: 900 rows left, a live neighbour that streams": (
        900, [(True, True)], SERVED, None, NARROW),
    "served widths, seeded: a row more than a wide chunk left": (
        WIDEST + 1, [], SERVED, 1234, WIDEST),
    "served widths, seeded: exactly a wide chunk left": (WIDEST, [], SERVED, 1234, NARROW),
    "served widths, seeded: 769 rows left": (WIDEST - NARROW + 1, [], SERVED, 1234, NARROW),
    "served widths, seeded: 768 rows left": (WIDEST - NARROW, [], SERVED, 1234, NARROW),
}


@pytest.mark.parametrize("case", RULE)
@pytest.mark.parametrize("own_stream", [False, True])
def test_the_width_follows_rows_left_and_the_other_slots_streams(servers, case, own_stream):
    left, neighbours, (chunk, wide), seed, want = RULE[case]
    b = make_batcher(servers("gqa_pages"), wide=wide, max_slots=4)
    for slot, (live, streams) in zip(b._slots[1:], neighbours):
        slot.active, slot.on_token = live, stream if streams else None
    job = job_of(0, left + 5, 5, chunk=chunk, on_token=stream if own_stream else None, seed=seed)
    # the job's own slot holds the caller's on_token from admission on
    b._slots[0].prefilling, b._slots[0].on_token = True, job.on_token
    assert b._chunk_width(job) == want


def test_an_explicit_prefill_chunk_is_every_chunks_and_the_default_widens(servers):
    server = servers("latent_rows")
    explicit = ContinuousBatcher(server, max_slots=2, max_len=MAX_LEN, page_size=PAGE,
                                 prefill_chunk=CHUNK)
    assert (explicit.prefill_chunk, explicit.prefill_wide) == (CHUNK, 0)
    assert explicit._chunk_width(job_of(0, 50, 0)) == CHUNK
    default = ContinuousBatcher(server, max_slots=2, max_len=MAX_LEN, page_size=PAGE)
    assert (default.prefill_chunk, default.prefill_wide) == (
        DEFAULT_PREFILL_CHUNK, WIDE_PREFILL_CHUNK) == (256, 1024)
    # no chunk is wide before the wide program is there (section d)
    assert default._chunk_width(job_of(0, WIDE_PREFILL_CHUNK + 1, 0, chunk=256)) == 256
    default._wide_build = built()
    # every width is whole pages and whole sub-chunks of the delta rule
    assert WIDE_PREFILL_CHUNK % DEFAULT_PREFILL_CHUNK == 0 and DEFAULT_PREFILL_CHUNK % 64 == 0
    # a job's own width is its bucket where that is smaller: it cannot be wide
    assert default._chunk_width(job_of(0, 40, 0, chunk=64)) == 64
    wide = WIDE_PREFILL_CHUNK
    assert default._chunk_width(job_of(0, wide + 1, 0, chunk=256)) == wide
    assert default._chunk_width(job_of(0, wide + 1, 1, chunk=256)) == wide    # (full)
    assert default._chunk_width(job_of(0, wide + 1, 1, chunk=256, seed=3)) == 256
    assert default._chunk_width(job_of(0, 2 * wide + 1, wide, chunk=256)) == wide
    assert default._chunk_width(job_of(0, 2 * wide + 1, 2 * wide, chunk=256)) == 256
    # what is left against the two widths: 769 rows are four narrow calls or one wide
    assert default._chunk_width(job_of(0, 2 * wide + 769, 2 * wide, chunk=256)) == wide
    assert default._chunk_width(job_of(0, 2 * wide + 768, 2 * wide, chunk=256)) == 256
    # the server's own prefill_chunk is an explicit one too
    server.prefill_chunk = CHUNK
    try:
        assert ContinuousBatcher(server, max_slots=2, max_len=MAX_LEN,
                                 page_size=PAGE).prefill_wide == 0
    finally:
        server.prefill_chunk = 0


@pytest.mark.parametrize("model", ["gqa_pages", "latent_rows", "conv_state", "delta_rule_state"])
def test_the_default_widens_whatever_the_model(servers, model):
    """The rule reads rows left, the other slots' streams and whether a width
    was given: nothing of the model's kind (``gqa_pages`` is a dense model, the
    three with state or latent rows route experts, as the served ones do).
    Until PR 54 a dense server kept its one program, for what a second one cost
    its start; a program's layers share one trace of their block now."""
    server = servers(model)
    b = ContinuousBatcher(server, max_slots=2, max_len=MAX_LEN, page_size=PAGE)
    assert (b.prefill_chunk, b.prefill_wide) == (DEFAULT_PREFILL_CHUNK, WIDE_PREFILL_CHUNK)
    job = job_of(0, WIDE_PREFILL_CHUNK + 88, 0, chunk=256)
    assert b._chunk_width(job) == 256        # its program is not there yet (section d)
    b._wide_build = built()
    assert b._chunk_width(job) == WIDE_PREFILL_CHUNK
    # narrow under a live stream, wide again beside a caller who waits for a plain reply
    b._slots[1].active, b._slots[1].on_token = True, stream
    assert b._chunk_width(job) == 256
    b._slots[1].on_token = None
    assert b._chunk_width(job) == WIDE_PREFILL_CHUNK
    # narrow where three narrow chunks hold what is left, one padded wide chunk past that
    assert b._chunk_width(job_of(0, WIDE_PREFILL_CHUNK - 256, 0, chunk=256)) == 256
    assert b._chunk_width(job_of(0, WIDE_PREFILL_CHUNK - 1, 0, chunk=256)) == WIDE_PREFILL_CHUNK
    assert b._chunk_width(job_of(0, WIDE_PREFILL_CHUNK - 1, 0, chunk=256, seed=1)) == 256
    # and a width somebody gave is every chunk's
    given = ContinuousBatcher(server, max_slots=2, max_len=MAX_LEN, page_size=PAGE,
                              prefill_chunk=CHUNK)
    assert given.prefill_wide == 0 and given._chunk_width(job_of(0, 4000, 0)) == CHUNK


@pytest.fixture(scope="module")
def long_slot_server():
    # (a prompt is admitted by its length bucket: 2,400 tokens are 4,096)
    server = LLMServer(model="transformer",
                       model_kwargs=dict(MODELS["gqa_pages"], max_seq_len=4096 + 64),
                       init_random=True, max_new_tokens=8, eos_id=-1, seed=3, temperature=0.0)
    server.load()
    return server


@pytest.mark.parametrize("tail,last_chunks,wide_rows", [
    (DEFAULT_PREFILL_CHUNK + 96, [(2048, 256, 0), (2304, 96, 1)], 2048),
    (900, [(2048, 900, 1)], 2048 + 900),
], ids=["a tail of 352 rows is two narrow chunks", "a tail of 900 rows is one padded wide chunk"])
def test_a_dense_prompt_through_wide_and_narrow_chunks_gives_the_all_narrow_tokens(
        long_slot_server, tail, last_chunks, wide_rows):
    """At the SERVED widths, a dense model: a prompt of 2,400 tokens takes two
    chunks of 1,024 rows, a full one of 256 and the last of 96 (the head's); one
    of 2,948 takes its last 900 rows in ONE chunk of 1,024 rows, 124 of them
    padding, whose row 899 the head reads (the narrow program would have run
    four times, the last call 132 rows and 124 of padding). The tokens are those
    of the same prompt through chunks of 256 alone."""
    server, slot = long_slot_server, 4096 + 64
    length = 2 * WIDE_PREFILL_CHUNK + tail
    prompt = np.random.default_rng(54).integers(1, 96, size=length).tolist()

    async def go(**width):
        b = ContinuousBatcher(server, max_slots=2, max_len=slot, tracing=True, **width)
        # a first request: when it has finished, the wide program's build starts
        await b.submit(prompt[:200], 2)
        if b._wide_build is not None:
            await asyncio.to_thread(b._wide_build.join)
        out = await b.submit(prompt, 6)
        stats, chunks = b._phases.stats(), chunk_events(b._flight.timelines()[-1:])
        await b.close()
        return out, stats, chunks

    out, stats, chunks = asyncio.run(go())
    narrow_out, narrow_stats, narrow_chunks = asyncio.run(go(prefill_chunk=DEFAULT_PREFILL_CHUNK))
    assert chunks == [(0, 1024, 0), (1024, 1024, 0)] + last_chunks
    assert stats["chunk_rows"] == {"1024": wide_rows, "256": length - wide_rows + 200}
    assert narrow_stats["chunk_rows"] == {"256": length + 200}
    assert stats["chunk_head"]["1"] == ({"256": 1, "1024": 1} if tail == 900 else {"256": 2})
    assert len(narrow_chunks) == -(-length // 256) and narrow_chunks[-1][2] == 1
    assert sorted(k[1] for k in server._prefill_cache if k[0] == "pchunk") == [
        DEFAULT_PREFILL_CHUNK, WIDE_PREFILL_CHUNK]
    assert out == narrow_out


def test_every_chunk_of_an_explicit_width_is_that_wide(servers):
    async def go():
        b = ContinuousBatcher(servers("gqa_pages"), max_slots=2, max_len=MAX_LEN,
                              len_buckets=(CHUNK,), page_size=PAGE, prefill_chunk=CHUNK)
        out = await b.submit(PROMPT, 2)
        stats = b._phases.stats()
        await b.close()
        return out, stats

    _, stats = asyncio.run(go())
    assert stats["chunk_rows"] == {"8": 45}
    assert stats["chunk_head"] == {"1": {"8": 1}, "0": {"8": 5}}


# ------------------------------------------- (c) streams, and the counter
def exposed_chunk_rows(stats) -> dict:
    from types import SimpleNamespace

    from seldon_core_tpu.metrics.registry import MetricsRegistry

    registry = MetricsRegistry()
    registry.sync_llm(SimpleNamespace(llm_stats=lambda: stats))
    found = {}
    for line in registry.expose().decode().splitlines():
        if line.startswith("seldon_llm_chunk_rows_total{"):
            found[line.split('width="')[1].split('"')[0]] = float(line.rsplit(" ", 1)[1])
    return found


def test_no_wide_chunk_under_a_streaming_neighbour_and_the_counter_says_so(servers):
    """A streams 40 tokens; B's 45-row prompt arrives once A's first token is
    out and is prefilled in narrow chunks alone (six turns beside A's steps);
    when A is done, the same prompt takes wide chunks again, its 13-row tail
    one of them."""
    server = servers("gqa_pages")

    async def go():
        b = make_batcher(server)
        loop = asyncio.get_running_loop()
        streaming, streamed = asyncio.Event(), []

        def on_token(tok):     # (the batcher's worker thread)
            streamed.append(tok)
            loop.call_soon_threadsafe(streaming.set)

        a = asyncio.ensure_future(b.submit(PROMPT[:5], 40, on_token=on_token))
        await streaming.wait()
        b_out = await b.submit(PROMPT, 2)
        a_still_streams = not a.done()
        beside = b._phases.stats()
        a_out = await a
        alone_out = await b.submit(PROMPT, 2)
        after = b._phases.stats()
        await b.close()
        return a_out, streamed, a_still_streams, b_out, alone_out, beside, after

    a_out, streamed, a_still_streams, b_out, alone_out, beside, after = asyncio.run(go())
    assert a_still_streams, "the neighbour has to stream through all of the prefill"
    assert streamed == a_out + [None]
    # A's own 5 rows and B's 45, none of them through the wide program
    assert beside["chunk_rows"] == {"8": 50}
    assert exposed_chunk_rows(beside) == {"8": 50.0}
    assert after["chunk_rows"] == {"8": 50, "16": 45}
    assert exposed_chunk_rows(after) == {"8": 50.0, "16": 45.0}
    assert after["chunk_head"]["1"] == {"8": 2, "16": 1}
    assert b_out == alone_out


def test_the_jobs_own_stream_does_not_count_and_agrees_with_the_plain_reply(servers):
    """The seeded probe of perf/planes/llm_rest.py: a plain reply and a stream
    of one prompt take the same chunks and give the same tokens; with its seed
    the prompt's 13-row tail is the two narrow chunks it was before PR 58."""
    server = LLMServer(model="transformer", model_kwargs=MODELS["gqa_pages"], init_random=True,
                       max_new_tokens=8, len_buckets=(16,), eos_id=-1, seed=3,
                       temperature=0.8, top_k=20)
    server.load()

    async def go():
        b = make_batcher(server)
        plain = await b.submit(PROMPT, 4, seed=1234)
        after_plain = b._phases.stats()["chunk_rows"]
        streamed, lock = [], threading.Lock()

        def on_token(tok):
            with lock:
                streamed.append(tok)

        stream_out = await b.submit(PROMPT, 4, seed=1234, on_token=on_token)
        rows = b._phases.stats()["chunk_rows"]
        await b.close()
        return plain, after_plain, stream_out, streamed, rows

    plain, after_plain, stream_out, streamed, rows = asyncio.run(go())
    assert after_plain == {"16": 32, "8": 13}
    assert rows == {"16": 64, "8": 26}
    assert stream_out == plain and streamed == plain + [None]


# ------------------------------------------- (d) a server's start
def serve(max_len):
    from seldon_core_tpu.runtime.batcher import BatcherService

    server = LLMServer(model="transformer",
                       model_kwargs=dict(MODELS["latent_rows"], max_seq_len=SLOT),
                       init_random=True, max_new_tokens=4, eos_id=-1, seed=3, temperature=0.0,
                       continuous_batching=2, continuous_batching_max_len=max_len)
    server.load()
    return server, BatcherService(server, max_slots=2)


# a slot that holds a wide chunk and 192 rows more; a prompt of a wide chunk and 88
SLOT, FEW, MANY = WIDE_PREFILL_CHUNK + 192, 300, WIDE_PREFILL_CHUNK + 88
LONG = np.random.default_rng(5).integers(1, 96, size=MANY).tolist()


@pytest.mark.parametrize("first", [FEW, MANY], ids=["a short prompt is the first",
                                                    "a long prompt is the first"])
def test_the_wide_program_is_built_behind_the_first_requests_and_never_again(caplog, first):
    """A batcher of the default widths (256 and 1,024) whose slots can hold a
    wide chunk: its first request's chunks are narrow however long its prompt
    (nobody waits for the wide program, and a program's load holds back whatever
    loads behind it); when that request finishes a thread traces, lowers and
    compiles the wide program; a long prompt after that takes it, and its first
    call lowers and compiles nothing (jax's own caches hold it)."""
    import logging

    def logged(what):
        return [r.getMessage() for r in caplog.records if what + "prefill_chunk" in r.getMessage()]

    server, svc = serve(SLOT)
    with caplog.at_level(logging.DEBUG, logger="jax._src.dispatch"):
        one = svc.submit_sync(LONG[:first], 2)
        assert svc.batcher._phases.stats()["chunk_rows"] == {"256": first}
        build = svc.batcher._wide_build
        assert build is not None and build.name == f"chunk-{WIDE_PREFILL_CHUNK}-build"
        build.join(120)
        assert not build.is_alive()
        assert len(logged("Finished XLA compilation of jit(")) == 2
        assert len(logged("Finished jaxpr to MLIR module conversion jit(")) == 2
        caplog.clear()
        other = svc.submit_sync(LONG, 2)
        assert logged("Finished XLA compilation of jit(") == []
        assert logged("Finished jaxpr to MLIR module conversion jit(") == []
        traces = [float(m.split(" in ")[1].split()[0])
                  for m in logged("Finished tracing + transforming ")]
        assert max(traces, default=0) < 0.01, traces     # found, not traced
    stats = svc.batcher._phases.stats()
    svc.close()
    assert stats["chunk_rows"] == {str(WIDE_PREFILL_CHUNK): WIDE_PREFILL_CHUNK,
                                   "256": first + MANY - WIDE_PREFILL_CHUNK}
    assert sorted(k[1] for k in server._prefill_cache if k[0] == "pchunk") == [
        256, WIDE_PREFILL_CHUNK]
    assert one == server.generate([LONG[:first]], max_new_tokens=2)["tokens"][0]
    assert other == server.generate([LONG], max_new_tokens=2)["tokens"][0]


def test_a_server_that_never_decodes_builds_it_when_its_first_request_has_finished():
    """Prompts that ask for ONE token finish at their first token and dispatch
    no decode step (a reranker's traffic): the first request's chunks are
    narrow, its end starts the build (the one trigger, whatever the traffic),
    and a prompt after the build takes wide chunks."""
    _, svc = serve(SLOT)
    b = svc.batcher
    svc.submit_sync(LONG, 1)
    assert b._phases.stats()["chunk_rows"] == {"256": MANY}
    assert b._wide_build is not None
    b._wide_build.join(120)
    svc.submit_sync(LONG, 1)
    rows = b._phases.stats()["chunk_rows"]
    svc.close()
    assert rows == {str(WIDE_PREFILL_CHUNK): WIDE_PREFILL_CHUNK,
                    "256": 2 * MANY - WIDE_PREFILL_CHUNK}


def test_a_lora_servers_wide_program_is_built_on_the_thread_and_serves_the_narrow_tokens():
    """A server with an adapter pool calls its chunk program with the pool and
    the slot's adapter: the thread builds THAT program for those shapes, and an
    adapted prompt through wide chunks, the last one padded, gives the
    all-narrow tokens (at rehearsal widths, the wide one set by hand as in (a))."""
    from test_adapters import load_adapters, make_server

    server = make_server()
    name = load_adapters(server, 1)[0]
    prompt = np.random.default_rng(7).integers(1, 96, size=45).tolist()

    async def go(wide):
        b = ContinuousBatcher(server, max_slots=2, max_len=MAX_LEN, len_buckets=(CHUNK,),
                              page_size=PAGE, prefill_chunk=CHUNK)
        b.prefill_wide = wide
        await b.submit(prompt[:20], 2, adapter=name)
        if wide:
            assert b._wide_build.name == f"chunk-{wide}-build"
            await asyncio.to_thread(b._wide_build.join)
        out = await b.submit(prompt, 4, adapter=name)
        rows = b._phases.stats()["chunk_rows"]
        await b.close()
        return out, rows

    out, rows = asyncio.run(go(WIDE))
    narrow_out, narrow_rows = asyncio.run(go(0))
    assert rows == {"16": 45, "8": 20} and narrow_rows == {"8": 65}
    # the programs that take the pool, and no other
    assert sorted(k[1:] for k in server._prefill_cache if k[0] == "pchunk") == [
        (CHUNK, MAX_LEN // PAGE, True), (WIDE, MAX_LEN // PAGE, True)]
    assert out == narrow_out


def test_a_seeded_request_waits_for_the_wide_program_and_repeats_its_tokens():
    """A request that comes with a seed asks for the same tokens whenever it
    comes, and a chunk's width is in its roundings: while the wide program is
    being built an unseeded prompt takes narrow chunks, a seeded one waits for
    the program and takes the chunks it takes once the program is there (the
    benchmark's seeded probe before and after a window; found on the chip in
    `smallthinker-longqa-mixed`, whose probe is 6,146 tokens: PERF.md
    section 6, PR 54). Its wide chunks are always full (PR 58): a seeded prompt
    of 900 rows is four narrow chunks, as before, where the same prompt without
    a seed is ONE padded wide chunk, and both give the tokens of ``generate()``."""
    server, svc = serve(SLOT)
    b = svc.batcher
    svc.submit_sync(LONG[:FEW], 2)
    build = b._wide_build
    # (the rule, while the thread runs or after: what a seed changes)
    held = threading.Thread(target=lambda: None)     # a thread that has not ended: never started
    held.is_alive = lambda: True
    b._wide_build = held
    unseeded = _PrefillJob(1, LONG, 0, 256, 4, None, None, None, None, None, [])
    seeded = _PrefillJob(1, LONG, 0, 256, 4, None, None, None, 7, None, [])
    assert (b._chunk_width(unseeded), b._chunk_width(seeded)) == (256, WIDE_PREFILL_CHUNK)
    b._wide_build = build
    first = svc.submit_sync(LONG, 4, seed=7)         # the build may still run: it waits
    assert not build.is_alive()
    rows = dict(b._phases.stats()["chunk_rows"])
    again = svc.submit_sync(LONG, 4, seed=7)
    assert rows == {str(WIDE_PREFILL_CHUNK): WIDE_PREFILL_CHUNK, "256": FEW + MANY - WIDE_PREFILL_CHUNK}
    assert first == again
    # a tail a wide chunk would hold, with a seed and without
    before = b._phases.stats()
    with_seed = svc.submit_sync(LONG[:900], 4, seed=7)
    between = b._phases.stats()
    without = svc.submit_sync(LONG[:900], 4)
    after = b._phases.stats()
    svc.close()

    def since(new, old, key, width):
        return new[key].get(width, 0) - old[key].get(width, 0)

    wide = str(WIDE_PREFILL_CHUNK)
    assert (since(between, before, "chunk_rows", "256"), since(between, before, "chunk_rows", wide)) == (900, 0)
    assert (since(after, between, "chunk_rows", "256"), since(after, between, "chunk_rows", wide)) == (0, 900)
    assert after["chunk_head"]["1"][wide] == 1 and between["chunk_head"]["1"].get(wide, 0) == 0
    assert with_seed == without == server.generate([LONG[:900]], max_new_tokens=4)["tokens"][0]


def test_a_batcher_that_cannot_reach_a_wide_chunk_builds_its_one_program_when_called():
    # no prompt of a slot of a wide chunk's rows has more than those left
    _, svc = serve(WIDE_PREFILL_CHUNK)
    svc.submit_sync(LONG[:FEW], 2)
    assert svc.batcher._wide_build is None
    svc.close()


# ------------------------------------------- (e) the served configurations' wide chunk
def served_wide_cells() -> dict:
    """cell -> (model kwargs, slots, tokens a slot) of the benchmark's cells
    whose server can reach a wide chunk (slots longer than one and a row, no
    width given), as perf/planes/llm_rest.py builds them from perf/configs and
    perf/workloads."""
    import glob
    import json
    import os

    perf = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
    cells = {}
    for path in sorted(glob.glob(os.path.join(perf, "workloads", "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        with open(os.path.join(perf, "configs", cell["config"] + ".json")) as f:
            config = json.load(f)
        server = {**config.get("server", {}), **cell.get("server", {})}
        kwargs = {ours: config[theirs]
                  for ours, theirs in config.get("model_kwargs_from", {}).items()}
        slot = server.get("continuous_batching_max_len", 0)
        if not server.get("prefill_chunk") and slot - 1 > WIDE_PREFILL_CHUNK:
            cells[cell["name"]] = (kwargs, server["continuous_batching"], slot)
    return cells


WIDE_CELLS = served_wide_cells()


def test_the_cells_that_reach_a_wide_chunk_are_the_nine():
    """The five whose model routes experts (since PR 48) and, since PR 54, the
    dense servers of slots longer than 1,025 tokens: Mistral's docs and rerank
    cells, whose prompts of 1,536-3,584 tokens take it, granite's sessions, which
    build the program and never run it (prompts of 64-512), and (PR 55)
    Phi-4-mini-flash's long traces, whose prompts of 4,096-12,288 are 4-12 wide
    chunks through 18 of its 32 layers."""
    assert sorted(WIDE_CELLS) == [
        "dsv2lite-longdocs-batch", "granite4h-sessions-decode", "lfm2-rag-mixed",
        "mistral7b-docs-batch", "mistral7b-rerank-prefill", "phi4flash-longtrace-decode",
        "qwen3next-longctx-mixed", "smallthinker-longqa-mixed", "xing4-reasoning-decode"]


@pytest.mark.parametrize("cell", sorted(WIDE_CELLS))
def test_a_served_wide_chunk_takes_the_kernels_read_and_the_page_wise_write(cell):
    """Static facts alone, no program: at the cell's slot length the read of a
    chunk of ``WIDE_PREFILL_CHUNK`` rows has a walk (``paged_read_walk`` gives a
    ``Plan``; None would gather the slot's whole block-table view a layer a
    chunk) in the tiles the narrow chunk's has, or, over latent attention
    (PR 52), the expanded-once read's: ALL the chunk's tokens one tile at
    DeepSeek-V2-Lite's 16 heads, two tiles of 512 at Xing4's 32, over the
    visits the narrow chunk's absorbed walk makes; its rows reach the pool as
    whole pages, and the window class holds a wide chunk behind its window."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models import cache as kvcache
    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.models.transformer import paged_read_walk, read_form
    from seldon_core_tpu.ops.latent_attention import ExpandedWalk
    from seldon_core_tpu.ops.page_walk import Plan

    kwargs, slots, slot = WIDE_CELLS[cell]
    cfg = get_model("transformer", **kwargs).cfg
    page = DEFAULT_PAGE_SIZE
    assert WIDE_PREFILL_CHUNK % page == 0 and slot % page == 0
    wide = paged_read_walk(cfg, WIDE_PREFILL_CHUNK, slot // page, page, jnp.bfloat16)
    narrow = paged_read_walk(cfg, DEFAULT_PREFILL_CHUNK, slot // page, page, jnp.bfloat16)
    if cfg.kv_lora_rank:
        tokens = {16: WIDE_PREFILL_CHUNK, 32: WIDE_PREFILL_CHUNK // 2}[cfg.n_heads]
        assert wide == ExpandedWalk(narrow.pages, tokens) and isinstance(narrow, Plan), (wide, narrow)
        assert read_form(wide) == "expanded" and read_form(narrow) == "absorbed"
    else:
        # the narrow chunk's walk, or its walk in a larger tile (Mistral: a block's
        # 256 x 4 query rows are one tile of 1,024, the wide chunk's 4,096 two of 2,048)
        assert isinstance(wide, Plan) and wide.q_tile >= narrow.q_tile, (wide, narrow)
        assert (wide.pages, wide.blocks) == (narrow.pages, narrow.blocks), (wide, narrow)
        # the tile divides the wide chunk's query rows a block: only the grid grows
        assert WIDE_PREFILL_CHUNK * cfg.n_heads % (wide.q_tile * wide.blocks) == 0
        assert read_form(wide) == "absorbed"
    windowed = {"window_pages": 8} if cfg.window_layers else {}
    pools = jax.eval_shape(lambda: kvcache.init_paged_kv_caches(
        cfg, 8, page, "bf16", state_slots=slots, **windowed))
    assert kvcache.paged_write_by_page(kvcache.first_paged(pools), 1, WIDE_PREFILL_CHUNK)
    if cfg.window_layers:
        held = kvcache.window_slot_pages(cfg.sliding_window, WIDE_PREFILL_CHUNK, page)
        assert cfg.sliding_window + WIDE_PREFILL_CHUNK <= held * page < slot
