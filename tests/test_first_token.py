"""First-token activation without a host sync (ISSUE 26 tentpole).

The contract: a prompt's first token is sampled on the device by the sampler
every decode step uses, threaded into the slot on the device, and read through
the drain pipeline behind the steps that were queued before its last chunk. So
nothing in ``_prefill_step`` reads the device; decode
steps are dispatched and older ones drained between a last chunk's enqueue and
its token's read; with no step in flight the next request's chunks are enqueued
before anything waits for the token; a record whose occupant is gone surfaces
nothing; EOS and ``max_new == 1`` finish at the read; and every emitted token,
the first included, is the one ``generate(seed=...)`` draws. Each of these
holds for a cold admission (the whole prompt in chunks) and for one behind a
radix-trie hit (``prefix_hit``: the prompt's family was served before, so
``match_and_pin`` shares its blocks and the first token comes from a suffix
chunk that starts mid-prompt). CPU toy model."""

from __future__ import annotations

import asyncio
import threading

import numpy as np
import pytest

from seldon_core_tpu.runtime import batcher as batcher_module
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.runtime.resilience import ShedError
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)

SHORT = [5, 9, 17]
LONG = [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13, 14, 15, 16, 17, 18, 19]   # 3 chunks of 8
OTHER = [60, 61, 62, 63, 64, 65, 66, 67, 68, 69]                             # 2 chunks


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(8, 16, 32), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def greedy():
    return make_server()


@pytest.fixture(scope="module")
def sampled():
    return make_server(temperature=0.8, top_k=20, seed=5)


@pytest.fixture(scope="module")
def greedy_radix():
    return make_server(prefix_cache_size=8)


@pytest.fixture(scope="module")
def sampled_radix():
    return make_server(temperature=0.8, top_k=20, seed=5, prefix_cache_size=8)


ADMISSIONS = ["cold", "prefix_hit"]


def served_by(request, name, admission):
    """The server the batcher runs on: the same weights, with the radix
    prefix cache on for ``prefix_hit``. References come from the plain one."""
    return request.getfixturevalue(
        name + ("_radix" if admission == "prefix_hit" else ""))


def make_batcher(server, **kw) -> ContinuousBatcher:
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousBatcher(server, **kw)


async def serve_once(b, admission, prompts, **kw):
    """``prefix_hit``: each prompt's family is served once first, so the
    trie holds its blocks when the admission under test arrives."""
    if admission == "prefix_hit":
        for p in prompts:
            await b.submit(p, 2, **kw)


def check_hits(b, admission, prompts):
    """``prefix_hit``: ``match_and_pin`` shared all of each prompt but its
    last token (capped at L-1: that one's logits seed the first token). A
    batcher without a trie, or a miss, fails here."""
    if admission == "prefix_hit":
        assert b._radix.stats()["prefix_hit_tokens"] == sum(
            len(p) - 1 for p in prompts)


def log_calls(b: ContinuousBatcher, names) -> list:
    """(name, "in" | "out", first positional argument) of every call, in the
    order the loop made them (the loop awaits its workers one at a time)."""
    events = []

    def wrap(name):
        fn = getattr(b, name)

        def run(*args, **kwargs):
            events.append((name, "in", args[0] if args else None))
            try:
                return fn(*args, **kwargs)
            finally:
                events.append((name, "out", args[0] if args else None))

        setattr(b, name, run)

    for name in names:
        wrap(name)
    return events


class _NumpyWatch:
    """``numpy`` for the batcher module, with ``asarray`` watched: a device
    array read while ``inside`` is set is a host sync where none may be."""

    def __init__(self):
        self.inside = threading.local()
        self.reads = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *args, **kwargs):
        import jax

        if getattr(self.inside, "name", None) and isinstance(x, jax.Array):
            self.reads.append(self.inside.name)
        return np.asarray(x, *args, **kwargs)


async def live_stream(b, prompt, n):
    """A request that is decoding (its first token surfaced) when this
    returns: (its future, the tokens streamed so far)."""
    loop = asyncio.get_running_loop()
    started = asyncio.Event()
    seen = []

    def on_token(tok):
        seen.append(tok)
        loop.call_soon_threadsafe(started.set)

    fut = asyncio.ensure_future(b.submit(prompt, n, on_token=on_token))
    await started.wait()
    return fut, seen


# ------------------------------------------------ (a) nothing stands still
@pytest.mark.parametrize("admission", ADMISSIONS)
def test_steps_flow_between_a_last_chunk_and_its_token(greedy, admission, request,
                                                       monkeypatch):
    watch = _NumpyWatch()
    monkeypatch.setattr(batcher_module, "np", watch)
    admit = "_prefill_step"

    async def go():
        b = make_batcher(served_by(request, "greedy", admission), pipeline_depth=2)
        await serve_once(b, admission, [LONG])
        before = sum(b._phases.first_token_reads.values())
        events = log_calls(b, [admit, "_commit_slot", "_dispatch",
                               "_drain_step", "_drain_first"])
        inner = getattr(b, admit)

        def watched(*args, **kwargs):
            watch.inside.name = admit
            try:
                return inner(*args, **kwargs)
            finally:
                watch.inside.name = None

        setattr(b, admit, watched)
        fut, _ = await live_stream(b, SHORT, 40)
        out = await b.submit(LONG, 4)
        first = await fut
        reads = sum(b._phases.first_token_reads.values()) - before
        check_hits(b, admission, [LONG])
        await b.close()
        return events, out, first, reads

    events, out, first, reads = asyncio.run(go())
    assert out == greedy.generate([LONG], max_new_tokens=4)["tokens"][0]
    assert first == greedy.generate([SHORT], max_new_tokens=40)["tokens"][0]
    assert watch.reads == [], "a device array was read inside " + admit
    # the second activation is LONG's: between its enqueue and its token's
    # read the loop dispatched a step and drained an older one
    commits = [n for n, e in enumerate(events) if e[:2] == ("_commit_slot", "out")]
    reads_at = [n for n, e in enumerate(events) if e[:2] == ("_drain_first", "in")]
    assert len(commits) == len(reads_at) == 2
    between = [e[0] for e in events[commits[1]:reads_at[1]] if e[1] == "in"]
    assert "_dispatch" in between and "_drain_step" in between, between
    assert reads == 2


class _NeverReady:
    """A first token that says it is still behind queued device work."""

    def __init__(self, token):
        self.token = token

    def is_ready(self):
        return False

    def __array__(self, *args, **kwargs):
        return np.asarray(self.token)


def test_next_request_is_enqueued_before_a_lone_token_is_read(greedy):
    """No decode step anywhere (``max_new == 1``: the rerank cell's regime):
    R+1's first chunk is queued behind R's last chunk before the loop waits
    for R's token, and no further ahead than that."""

    async def go():
        b = make_batcher(greedy, pipeline_depth=2)
        commit = b._commit_slot

        def commit_unready(*args, **kwargs):
            commit(*args, **kwargs)
            b._inflight[-1].token = _NeverReady(b._inflight[-1].token)

        b._commit_slot = commit_unready
        events = log_calls(b, ["_admit_begin", "_prefill_step", "_commit_slot",
                               "_dispatch", "_drain_first"])
        outs = await asyncio.gather(*[b.submit(p, 1) for p in (LONG, OTHER, SHORT)])
        reads = dict(b._phases.first_token_reads)
        await b.close()
        return events, outs, reads

    events, outs, reads = asyncio.run(go())
    assert outs == [greedy.generate([p], max_new_tokens=1)["tokens"][0]
                    for p in (LONG, OTHER, SHORT)]
    assert not any(e[0] == "_dispatch" for e in events)     # no decode step at all
    names = [e[0] for e in events if e[1] == "in"]
    first_commit = names.index("_commit_slot")
    first_read = names.index("_drain_first")
    # R+1's admission and first chunk went out first; with that behind R's
    # last chunk the read may block, and the rest of R+1 follows it
    assert names[first_commit + 1:first_read] == ["_admit_begin", "_prefill_step"]
    assert names[first_read + 1:first_read + 3] == ["_prefill_step", "_commit_slot"]
    assert reads == {"yes": 0, "no": 3}


@pytest.mark.parametrize("ready, behind, enqueued, waits", [
    (False, None, True, True),        # nothing behind it and more to enqueue
    (False, None, False, False),      # nothing left to enqueue: the read may block
    (False, "record", True, False),   # a step or a later activation is queued behind it
    (False, "chunk", True, False),    # the staged job's first chunk is
    (False, "staged", True, True),    # a job staged, no chunk of it out yet
    (True, None, True, False),        # the token is there: read it now
])
def test_when_a_first_token_can_wait(greedy, ready, behind, enqueued, waits):
    from seldon_core_tpu.runtime.batcher import _FirstToken, _PrefillJob

    class Token:
        def is_ready(self):
            return ready

    b = make_batcher(greedy)
    b._inflight.append(_FirstToken(0, 1, Token(), None, [], None, None))
    if behind == "record":
        b._inflight.append(_FirstToken(1, 1, Token(), None, [], None, None))
    elif behind is not None:
        b._prefill = _PrefillJob(1, LONG, 0, 8, 4, None, None, None, None, None, [])
        b._prefill.next = 8 if behind == "chunk" else 0
    assert b._first_token_can_wait(enqueued) is waits


# ------------------------------------------- (b) one sampler, one key chain
@pytest.mark.parametrize("admission", ADMISSIONS)
@pytest.mark.parametrize("fixt", ["greedy", "sampled"])
def test_seeded_tokens_equal_generate(fixt, admission, request):
    s = request.getfixturevalue(fixt)
    prompts, seeds = [SHORT, LONG, OTHER], [42, 1234, 7]
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(prompts, seeds)]

    async def go():
        b = make_batcher(served_by(request, fixt, admission))
        await serve_once(b, admission, prompts, seed=1)
        outs = await asyncio.gather(*[b.submit(p, 8, seed=sd)
                                      for p, sd in zip(prompts, seeds)])
        check_hits(b, admission, prompts)
        await b.close()
        return outs

    outs = asyncio.run(go())
    assert outs == expected


@pytest.mark.parametrize("admission", ADMISSIONS)
@pytest.mark.parametrize("delivered", [1, 4])
def test_resumed_generation_continues_the_chain(sampled, admission, delivered, request):
    """``prefix_hit``: the resume lands where the interrupted request ran,
    so the trie already holds the prompt and what was delivered."""
    whole = sampled.generate([OTHER], max_new_tokens=8, seed=99)["tokens"][0]
    resumed = OTHER + whole[:delivered]

    async def go():
        b = make_batcher(served_by(request, "sampled", admission))
        if admission == "prefix_hit":
            assert await b.submit(OTHER, 8, seed=99) == whole
        rest = await b.submit(resumed, 8 - delivered, seed=99,
                              resume_tokens=delivered)
        check_hits(b, admission, [resumed])
        await b.close()
        return rest

    rest = asyncio.run(go())
    assert rest == whole[delivered:]


def test_first_token_program_is_the_step_sampler_on_one_row(sampled):
    """Ties included: the row's top-k in ``lax.top_k``'s order, one split of
    the request's key, the categorical over it."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.servers.llmserver import _slot_sampler

    row = np.zeros((96,), np.float32)
    row[[3, 50, 51, 90]] = 2.0          # four-way tie at the top
    temp = jnp.asarray(0.8, jnp.float32)
    for seed in range(8):
        key = jax.random.PRNGKey(seed)
        tok, key_out, row_out = sampled._get_first_token()(
            jnp.asarray(row[None, None], jnp.bfloat16), key, temp)
        keys, want = _slot_sampler(sampled.top_k)(key[None], jnp.asarray(row)[None], temp)
        assert int(tok) == int(want[0])
        assert np.array_equal(np.asarray(key_out), np.asarray(keys[0]))
        assert row_out.dtype == jnp.float32 and np.array_equal(np.asarray(row_out), row)


# ----------------------------- (c) an occupant gone before its token is read
@pytest.mark.parametrize("how", ["shed", "finished"])
def test_stale_first_token_surfaces_nothing_and_frees_pages_once(greedy, how):
    async def go():
        b = make_batcher(greedy, pipeline_depth=2)
        gone = []

        def chaos(batcher):
            # top of a loop turn: an activation is queued behind live steps
            for rec in batcher._inflight:
                if rec.k == 0 and not gone:
                    gone.append(rec.slot)
                    if how == "shed":
                        batcher._shed_slot(rec.slot, "test")
                    else:
                        batcher._finish(rec.slot)

        fut, _ = await live_stream(b, SHORT, 30)
        b._chaos = chaos
        seen = []
        try:
            out = await b.submit(LONG, 6, on_token=seen.append)
        except ShedError as e:
            out = e
        b._chaos = None
        first = await fut
        again = await b.submit(LONG, 6)         # the slot and its pages serve on
        stats = (b.page_stats(), dict(b._phases.first_token_reads),
                 b._phases.counts["first_token"])
        await b.close()
        return out, seen, first, again, gone, stats

    out, seen, first, again, gone, (pages, reads, commits) = asyncio.run(go())
    assert len(gone) == 1
    if how == "shed":
        assert isinstance(out, ShedError)
    else:
        assert out == []        # finished with what the host had credited: nothing
    assert seen == [None]       # the stream's end, and no token before it
    assert first == greedy.generate([SHORT], max_new_tokens=30)["tokens"][0]
    assert again == greedy.generate([LONG], max_new_tokens=6)["tokens"][0]
    # a double free raises in the allocator and would have killed the loop
    assert pages["kv_pages_in_use"] == 0
    assert sum(reads.values()) == commits == 3      # the stale record was read too


# --------------------------------- (d) requests that end at their first token
@pytest.mark.parametrize("admission", ADMISSIONS)
@pytest.mark.parametrize("ends_by", ["max_new", "eos"])
def test_request_finishes_at_the_read_of_its_first_token(greedy, admission, ends_by,
                                                         request):
    first = greedy.generate([LONG], max_new_tokens=1)["tokens"][0][0]
    if ends_by == "max_new":
        server = served_by(request, "greedy", admission)
    else:
        server = make_server(eos_id=first,
                             prefix_cache_size=8 * (admission == "prefix_hit"))
    neighbour = server.generate([SHORT], max_new_tokens=30)["tokens"][0]

    async def go():
        b = make_batcher(server, pipeline_depth=2)
        await serve_once(b, admission, [LONG])
        events = log_calls(b, ["_drain_first", "_finish", "_dispatch"])
        fut, _ = await live_stream(b, SHORT, 30)
        seen = []
        out = await b.submit(LONG, 1 if ends_by == "max_new" else 8,
                             on_token=seen.append)
        rest = await fut
        # pages some slot still holds: what the trie caches is not a leak
        held = b.page_stats()["kv_pages_in_use"] - (
            b._radix.stats()["prefix_cached_blocks"] if b._radix else 0)
        check_hits(b, admission, [LONG])
        await b.close()
        return events, out, seen, rest, held

    events, out, seen, rest, held = asyncio.run(go())
    if ends_by == "max_new":
        assert out == [first] and seen == [first, None]
    else:
        assert out == [] and seen == [None]     # EOS is trimmed, never streamed
    # the neighbour's steps carried the slot along meanwhile: masked
    assert rest == neighbour
    assert held == 0
    # LONG's _finish ran inside the read of its first token
    depth, finished_inside = 0, 0
    for name, edge, _ in events:
        if name == "_drain_first":
            depth += 1 if edge == "in" else -1
        elif name == "_finish" and edge == "in" and depth:
            finished_inside += 1
    assert finished_inside == 1


# ------------------------------------------------------ (e) the logits probe
@pytest.mark.parametrize("admission", ADMISSIONS)
def test_probe_gets_the_prompts_last_row_first_then_a_row_a_step(greedy, admission,
                                                                 request):
    async def go():
        b = make_batcher(served_by(request, "greedy", admission))
        await serve_once(b, admission, [LONG])
        info = {"logits": []}
        plain, out = await asyncio.gather(b.submit(SHORT, 6), b.submit(LONG, 6, info=info))
        check_hits(b, admission, [LONG])
        await b.close()
        return plain, out, info

    plain, out, info = asyncio.run(go())
    assert out == greedy.generate([LONG], max_new_tokens=6)["tokens"][0]
    assert plain == greedy.generate([SHORT], max_new_tokens=6)["tokens"][0]
    rows = np.stack(info["logits"])
    assert rows.shape == (6, 96) and rows.dtype == np.float32
    # greedy: row j is what token j was taken from
    assert rows.argmax(-1).tolist() == out
