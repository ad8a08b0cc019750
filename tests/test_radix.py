"""Radix-tree paged prefix cache (ISSUE 12 tentpole).

The contract: rebuilding the prefix cache as a token-block trie whose
nodes own refcounted pages in the global pool changes NOTHING about
tokens — radix-served decode is bit-exact against cold prefill for greedy
and seeded sampling, bf16 and int8 KV, disaggregation on and off — while
a hit costs block-table entries (zero page copies; a partial-block
continuation pays exactly ONE copy-on-write page copy), completed
requests insert their blocks back in place (no dense export), eviction is
LRU-by-leaf and can never take a page a live slot references, and the
fleet layer routes on cached-prefix length (ReplicaSet) / ships only the
uncached suffix (disaggregated prefill workers). Runs on the virtual
8-device CPU mesh (tests/conftest.py)."""

from __future__ import annotations

import asyncio
import threading

import pytest

from seldon_core_tpu.models.cache import RESERVED_PAGES
from seldon_core_tpu.runtime.batcher import ContinuousBatcher, PageAllocator
from seldon_core_tpu.runtime.radix import RadixPrefixCache
from seldon_core_tpu.servers.llmserver import LLMServer

pytestmark = pytest.mark.leakcheck  # conftest leak canary (ISSUE 19)

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1, 4),
                temperature=0.0, eos_id=-1, seed=3, prefix_cache_size=8)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


@pytest.fixture(scope="module")
def server():
    return make_server()


@pytest.fixture(scope="module")
def int8_server():
    return make_server(kv_cache_dtype="int8")


@pytest.fixture(scope="module")
def sampled_server():
    return make_server(temperature=0.8, top_k=20, seed=5)


def chat_turns(server, turns, *, n=6, seeds=None, disaggregation=None,
               **batcher_kw):
    """Drive a multi-turn chat shape through ONE batcher: each turn's
    prompt = previous prompt + previous answer + the turn's user tokens
    (exactly the traffic the radix trie exists for). Returns (outputs,
    per-turn radix stats snapshots, final page stats)."""
    batcher_kw.setdefault("page_size", 4)
    batcher_kw.setdefault("max_len", 64)
    batcher_kw.setdefault("len_buckets", (16, 32))
    batcher_kw.setdefault("prefill_chunk", 8)

    async def go():
        b = ContinuousBatcher(server, disaggregation=disaggregation,
                              max_slots=2, **batcher_kw)
        outs, snaps = [], []
        prompt = list(turns[0])
        for i, user in enumerate(turns):
            if i > 0:
                prompt = prompt + outs[-1] + list(user)
            out = await b.submit(
                prompt, max_new_tokens=n,
                seed=None if seeds is None else seeds[i])
            outs.append(out)
            snaps.append(dict(b._radix.stats()) if b._radix is not None
                         else {})
        pages = b.page_stats()
        await b.close()
        return outs, snaps, pages

    return asyncio.run(go())


def cold_expected(server, turns, *, n=6, seeds=None):
    """The same chat transcript decoded COLD (generate(): per-request
    dense caches, no batcher, no trie) — the bit-exactness oracle."""
    outs = []
    prompt = list(turns[0])
    for i, user in enumerate(turns):
        if i > 0:
            prompt = prompt + outs[-1] + list(user)
        outs.append(server.generate(
            [prompt], max_new_tokens=n,
            seed=None if seeds is None else seeds[i])["tokens"][0])
    return outs


TURNS = ([9, 8, 7, 6, 5, 4, 3, 2, 1, 11, 12], [30, 31, 32], [44, 45])


# ----------------------------------------------------------------- parity
@pytest.mark.parametrize("fixt", [
    "server",
    pytest.param("int8_server", marks=pytest.mark.slow),  # tier-1 keeps
    # bf16 greedy + int8 seeded (the densest pair); the rest rides CI's
    # unfiltered radix step
])
def test_multi_turn_greedy_parity_vs_cold(fixt, request):
    """Three chat turns through the trie == three cold generate() calls,
    token for token, while the hit counters show the reuse actually
    happened (turn 2+ prompts are served mostly from shared pages)."""
    s = request.getfixturevalue(fixt)
    expected = cold_expected(s, TURNS)
    outs, snaps, _ = chat_turns(s, TURNS)
    assert outs == expected
    assert snaps[0]["prefix_hit_tokens"] == 0      # cold trie: no hit
    assert snaps[1]["prefix_hit_tokens"] >= 8      # turn 2 reused turn 1
    assert snaps[2]["prefix_hit_tokens"] > snaps[1]["prefix_hit_tokens"]
    assert snaps[2]["prefix_bytes_saved"] > 0


@pytest.mark.parametrize("fixt", [
    pytest.param("sampled_server", marks=pytest.mark.slow),
    # tier-1 870s budget: seeded-through-the-trie rides CI's unfiltered
    # radix step; tier-1 keeps the greedy bf16 multi-turn above plus the
    # seeded parity anchors in test_paged_kv/test_disagg
    pytest.param("int8_server", marks=pytest.mark.slow),
])
def test_multi_turn_seeded_parity_vs_cold(fixt, request):
    """Seeded sampling through radix-served slots reproduces generate()'s
    exact chain — shared pages change where KV lives, never the rng."""
    s = request.getfixturevalue(fixt)
    seeds = [42, 1234, 7]
    expected = cold_expected(s, TURNS, seeds=seeds)
    outs, snaps, _ = chat_turns(s, TURNS, seeds=seeds)
    assert outs == expected
    assert snaps[2]["prefix_hit_tokens"] > 0


@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered radix step
def test_multi_turn_parity_disagg(server):
    """Disaggregated remote prefill consults the decode-side trie: the
    worker computes only the uncached suffix, and tokens stay bit-exact
    vs the cold oracle AND vs single-slice radix serving."""
    expected = cold_expected(server, TURNS)
    outs, snaps, _ = chat_turns(server, TURNS,
                                disaggregation="remote_prefill")
    assert outs == expected
    assert snaps[1]["prefix_hit_blocks"] > 0       # remote path hit too


def test_disagg_suffix_only_handoff(server):
    """The D2D handoff carries ONLY the uncached suffix: a turn-2 prompt
    that extends turn 1 ships fewer bytes than its cold equivalent even
    though its prompt is LONGER."""
    batcher_kw = dict(page_size=4, max_len=64,
                      len_buckets=(16, 32), prefill_chunk=8)

    async def go():
        b = ContinuousBatcher(server, disaggregation="remote_prefill",
                              max_slots=2, **batcher_kw)
        o1 = await b.submit(list(TURNS[0]), max_new_tokens=6)
        bytes1 = b.handoff_stats()["handoff_transfer_bytes_total"]
        prompt2 = list(TURNS[0]) + o1 + list(TURNS[1])
        await b.submit(prompt2, max_new_tokens=6)
        bytes2 = b.handoff_stats()["handoff_transfer_bytes_total"] - bytes1
        st = dict(b._radix.stats())
        await b.close()
        return len(prompt2), bytes1, bytes2, st

    plen2, bytes1, bytes2, st = asyncio.run(go())
    assert plen2 > len(TURNS[0])
    assert 0 < bytes2 <= bytes1      # longer prompt, no more handoff bytes
    assert st["prefix_hit_blocks"] > 0


# ------------------------------------------------------- trie unit behavior
def test_trie_insert_match_dedup_refcounts():
    alloc = PageAllocator(total_pages=32, page_size=4)
    trie = RadixPrefixCache(alloc, page_size=4, bytes_per_block=100)
    seq = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]          # 2 full blocks + tail 2
    pages = alloc.alloc(3)
    consumed = trie.insert(seq, pages, 0)
    assert consumed == set(pages)                   # all adopted in place
    assert trie.stats()["prefix_cached_blocks"] == 3
    # trie-only pages: refcount 1 each
    assert all(alloc.refs_of(p) == 1 for p in pages)

    # a repeat pins the full blocks (limit caps at L-1 -> 2 full blocks
    # match whole, the tail node serves 1 token copy-on-write); the cow
    # SOURCE is pinned too — the caller's next allocation may evict, and
    # the pending copy must never race a reuse of its source
    k0, shared, cow = trie.match_and_pin(seq, limit=len(seq) - 1)
    assert k0 == 9 and shared == pages[:2]
    assert cow == (pages[2], 1)
    assert alloc.refs_of(pages[0]) == 2             # pinned by the "slot"
    assert alloc.refs_of(pages[2]) == 2             # cow source pinned
    assert trie.stats()["prefix_shared_pages"] == 3
    alloc.free([cow[0]])                            # copy dispatched: unpin
    alloc.free(shared)                              # slot release: unpin
    assert alloc.refs_of(pages[0]) == 1
    assert alloc.refs_of(pages[2]) == 1

    # re-inserting the same history frees the duplicate owned pages
    dup = alloc.alloc(3)
    consumed2 = trie.insert(seq, dup, 0)
    assert consumed2 == set(dup)
    assert trie.stats()["prefix_cached_blocks"] == 3
    assert all(alloc.refs_of(p) == 0 for p in dup)  # freed back to pool


def test_failed_admission_retry_does_not_inflate_hit_counters():
    """A match that cannot fund its fresh pages unpins and retries every
    batcher loop turn — the reuse counters must count SERVED hits only
    (match_and_pin pins, record_hit tallies; only a funded admission
    calls record_hit)."""
    alloc = PageAllocator(total_pages=32, page_size=4)
    trie = RadixPrefixCache(alloc, page_size=4, bytes_per_block=100)
    pages = alloc.alloc(2)
    trie.insert([1, 2, 3, 4, 5, 6, 7, 8], pages, 0)
    for _ in range(5):                       # simulated retry loop
        _, shared, cow = trie.match_and_pin([1, 2, 3, 4, 5, 6, 7, 8, 9],
                                            limit=8)
        alloc.free(shared + ([cow[0]] if cow is not None else []))
    st = trie.stats()
    assert st["prefix_hit_blocks"] == 0
    assert st["prefix_cow_copies"] == 0
    assert st["prefix_bytes_saved"] == 0
    trie.record_hit(8, 2, False)             # the one funded admission
    assert trie.stats()["prefix_hit_blocks"] == 2


def test_trie_partial_tail_upgrade_and_covering():
    alloc = PageAllocator(total_pages=32, page_size=4)
    trie = RadixPrefixCache(alloc, page_size=4)
    short = alloc.alloc(1)
    trie.insert([5, 6], short, 0)                   # partial leaf, 2 valid
    assert trie.match_len([5, 6, 7]) == 2
    # a longer history through the same block UPGRADES the cold leaf in
    # place (its page frees, ours takes over)
    longer = alloc.alloc(1)
    trie.insert([5, 6, 7], longer, 0)
    assert alloc.refs_of(short[0]) == 0
    assert trie.match_len([5, 6, 7, 8]) == 3
    # a shorter history adds nothing when a covering node exists
    shorter = alloc.alloc(1)
    trie.insert([5, 6], shorter, 0)
    assert alloc.refs_of(shorter[0]) == 0
    assert trie.stats()["prefix_cached_blocks"] == 1


def test_trie_eviction_lru_and_pinned_never_evicted():
    alloc = PageAllocator(total_pages=8, page_size=4)   # 6 usable
    trie = RadixPrefixCache(alloc, page_size=4)
    a = alloc.alloc(2)
    trie.insert([1] * 8, a, 0)                      # path A: 2 blocks
    b = alloc.alloc(2)
    trie.insert([2] * 8, b, 0)                      # path B: 2 blocks
    # touch A so B holds the LRU leaf
    _, pa, cow_a = trie.match_and_pin([1] * 8, limit=7)
    alloc.free(pa + [cow_a[0]])                     # unpin again (incl. cow)
    # pin B's leaf: it must survive eviction even as LRU
    _, pb, _ = trie.match_and_pin([2] * 8, limit=8)
    assert pb == b
    assert not trie.evict(7)      # 2 free + A's 2 evictable < 7: fails...
    assert alloc.refs_of(b[0]) == 2 and alloc.refs_of(b[1]) == 2  # B held
    assert trie.evict(4)          # A (both leaves, deepest first) suffices
    assert alloc.refs_of(a[1]) == 0
    assert trie.stats()["prefix_cached_blocks"] == 2   # B remains


def test_cow_pin_never_starves_an_idle_minimum_pool(server):
    """An admission can always fit an otherwise-idle pool (the PR 7
    invariant). The COW pin makes its source page unevictable while
    held, which on a minimum-size pool can leave eviction one page
    short — the admission must DROP the partial-block match (keeping
    the full-block shares) and proceed, never shed 503."""

    async def go():
        # capacity 4 = exactly one max_len sequence's pages
        b = ContinuousBatcher(server, max_slots=2, max_len=16,
                              len_buckets=(16,), page_size=4, pool_pages=6, prefill_chunk=4)
        o1 = await b.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=3)
        st1 = dict(b._radix.stats())
        # 15-token prompt: matches 2 full blocks + part-way into the
        # cached tail (the cow source) — fresh pages needed exceed the
        # free list, and the pinned cow source blocks eviction
        prompt2 = [1, 2, 3, 4, 5, 6, 7, 8] + list(range(20, 27))
        o2 = await b.submit(prompt2, max_new_tokens=1)
        st2 = dict(b._radix.stats())
        pages = b.page_stats()
        await b.close()
        return o1, o2, st1, st2, pages

    o1, o2, st1, st2, pages = asyncio.run(go())
    assert len(o2) == 1                      # admitted, never shed
    assert pages["kv_page_sheds"] == 0
    assert st1["prefix_cached_blocks"] == 3  # 2 full + partial tail
    # the hit degraded to the full blocks (the cow was dropped to fund
    # the admission) — still counted once, as a 2-block hit
    assert st2["prefix_hit_blocks"] - st1["prefix_hit_blocks"] == 2
    # and bit-exactness holds through the degraded hit
    prompt2 = [1, 2, 3, 4, 5, 6, 7, 8] + list(range(20, 27))
    assert o2 == server.generate([prompt2], max_new_tokens=1)["tokens"][0]


def test_batcher_eviction_relieves_pool_pressure(server):
    """A full trie is a cache, not a tenant: admissions that would shed
    on a dry pool evict LRU leaves instead, and live slots' shared pages
    survive."""

    async def go():
        b = ContinuousBatcher(server, max_slots=2, max_len=32,
                              len_buckets=(16,), page_size=4, pool_pages=12,  # 10 usable
                              prefill_chunk=8)
        # fill the trie: two distinct 4-token prompts x (4 + 5 written)
        o1 = await b.submit([10, 11, 12, 13], max_new_tokens=6)
        await b.submit([20, 21, 22, 23], max_new_tokens=6)
        held = b._allocator.stats()[1]
        assert held > 0                          # blocks stayed cached
        # a third distinct prompt needs pages the free list can't cover:
        # eviction (not shed) must fund it
        o3 = await b.submit([30] * 16, max_new_tokens=8)
        st = dict(b._radix.stats())
        pages = b.page_stats()
        await b.close()
        return o1, o3, st, pages

    o1, o3, st, pages = asyncio.run(go())
    assert len(o3) == 8
    assert st["prefix_evicted_blocks"] > 0
    assert pages["kv_page_sheds"] == 0           # eviction, never shed


# ------------------------------------------------- concurrency (satellite)
def test_hot_prefix_shared_by_8_threads():
    """8 threads hammer one hot prefix: match_and_pin / release cycles
    against a concurrent inserter — refcounts return to exactly the
    trie's own reference, counters are exact, and no page double-frees
    (the allocator raises if one does)."""
    alloc = PageAllocator(total_pages=64, page_size=4)
    trie = RadixPrefixCache(alloc, page_size=4, bytes_per_block=64)
    hot = list(range(1, 17))                     # 4 full blocks
    base = alloc.alloc(4)
    trie.insert(hot, base, 0)
    N = 200
    errs = []
    barrier = threading.Barrier(8)

    def worker(wid):
        try:
            barrier.wait()
            for _ in range(N):
                k0, shared, cow = trie.match_and_pin(hot, limit=len(hot) - 1)
                assert k0 >= 12 and len(shared) >= 3
                assert cow is None or alloc.refs_of(cow[0]) >= 2
                trie.record_hit(k0, len(shared), cow is not None)
                trie.match_len(hot)              # probe path, no pin
                pins = shared + ([cow[0]] if cow is not None else [])
                alloc.free(pins)                 # copy dispatched + release
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    # every pin released: back to the trie's own single references
    assert all(alloc.refs_of(p) == 1 for p in base)
    st = trie.stats()
    assert st["prefix_hit_blocks"] >= 8 * N * 3
    assert st["prefix_shared_pages"] == 0


# ----------------------------------------------------- fleet-level routing
@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered radix step
# (tier-1 keeps the end-to-end ReplicaSet routing test in test_disagg)
def test_replica_set_routes_to_prefix_owner():
    """ReplicaSet.generate dispatches to the replica whose trie holds the
    longest cached prefix; with no coverage anywhere it falls back to
    least-loaded (lowest index on ties)."""
    from seldon_core_tpu.runtime.batcher import BatcherService
    from seldon_core_tpu.runtime.engine import ReplicaSet

    r1 = make_server(continuous_batching=2, continuous_batching_max_len=32,
                     kv_page_size=4)
    r2 = make_server(continuous_batching=2, continuous_batching_max_len=32,
                     kv_page_size=4)
    s1 = BatcherService(r1, max_slots=2)
    r1._batcher_service = s1
    s2 = BatcherService(r2, max_slots=2)
    r2._batcher_service = s2
    try:
        rs = ReplicaSet([r1, r2])
        prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1]
        # warm replica 2 ONLY (submitting through its own service)
        expected = s2.submit_sync(prompt, 6)
        assert r2.prefix_match_len(prompt) > 0
        assert r1.prefix_match_len(prompt) == 0
        assert rs.prefix_match_len(prompt) == r2.prefix_match_len(prompt)
        # prefix routing beats the least-loaded lowest-index tiebreak
        assert rs.pick_for(prompt) is r2
        # a cold prompt falls back to least-loaded (tie -> lowest index)
        assert rs.pick_for([50, 51, 52]) is r1
        # and generate() itself routes (tokens exact through the trie)
        out = rs.generate([prompt], max_new_tokens=6)
        assert out["tokens"][0] == expected
    finally:
        s1.close()
        s2.close()


# -------------------------------------------------- observability plumbing
def test_prefix_metrics_flow_llm_stats_to_registry(server):
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import BatcherService

    s = make_server(continuous_batching=2, continuous_batching_max_len=32,
                    kv_page_size=4)
    svc = BatcherService(s, max_slots=2)
    s._batcher_service = svc
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        svc.submit_sync(prompt, 6)
        svc.submit_sync(prompt, 6)               # second pass hits
        st = s.llm_stats()
        assert st["prefix_cached_blocks"] > 0
        assert st["prefix_hit_blocks"] > 0
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(s)
        text = reg.expose().decode()
        assert "seldon_llm_prefix_hit_blocks_total" in text
        assert "seldon_llm_prefix_shared_pages" in text
        assert "seldon_llm_prefix_cached_blocks" in text
        assert "seldon_llm_prefix_cow_copies_total" in text
        assert "seldon_llm_prefix_evicted_blocks_total" in text
        assert "seldon_llm_prefix_bytes_saved_total" in text
    finally:
        svc.close()


def test_flight_recorder_prefix_hit_span_carries_blocks(server):
    """The llm.prefix_hit timeline event (and span child) carries the
    matched token AND block counts (ISSUE 12 satellite)."""

    async def go():
        b = ContinuousBatcher(server, max_slots=2, max_len=32,
                              len_buckets=(16,), page_size=4, prefill_chunk=8, tracing=True)
        prompt = [7, 6, 5, 4, 3, 2, 1, 0, 9]
        await b.submit(prompt, max_new_tokens=6)
        await b.submit(prompt, max_new_tokens=6)
        lines = b._flight.timelines()
        await b.close()
        return lines

    lines = asyncio.run(go())
    hits = [ev for tl in lines for ev in tl["events"]
            if ev["kind"] == "prefix_hit"]
    assert hits, "second pass must record a prefix_hit event"
    assert hits[-1]["tokens"] == 8 and hits[-1]["blocks"] == 2


def test_clear_prefix_cache_clears_trie_too(server):
    s = make_server(continuous_batching=2, continuous_batching_max_len=32,
                    kv_page_size=4)
    from seldon_core_tpu.runtime.batcher import BatcherService

    svc = BatcherService(s, max_slots=2)
    s._batcher_service = svc
    try:
        svc.submit_sync([1, 2, 3, 4, 5, 6], 6)
        assert s.llm_stats()["prefix_cached_blocks"] > 0
        s.clear_prefix_cache()
        st = s.llm_stats()
        assert st["prefix_cached_blocks"] == 0
        assert st["kv_pages_in_use"] == 0
    finally:
        svc.close()
