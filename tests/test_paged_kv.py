"""Paged KV cache + chunked prefill correctness (ISSUE 7 tentpole).

The contract: serving from the global page pool + block tables changes
NOTHING about tokens against ``generate()``'s dense caches —
greedy and seeded-sampled decode are bit-exact against ``generate()`` under
both KV dtypes (the gather read feeds the identical masked einsum) —
while admission prefill chunks interleave with in-flight decode, pages
recycle exactly through the allocator, prefix-cache hits land directly in
paged slots, and pool exhaustion sheds (503 + Retry-After) instead of
raising from the decode loop."""

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.runtime.batcher import ContinuousBatcher, PageAllocator
from seldon_core_tpu.runtime.resilience import ShedError
from seldon_core_tpu.servers.llmserver import LLMServer

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)


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
def int8_server():
    return make_server(kv_cache_dtype="int8")


@pytest.fixture(scope="module")
def sampled_server():
    return make_server(temperature=0.8, top_k=20, seed=5)


@pytest.fixture(scope="module")
def sampled_int8_server():
    return make_server(temperature=0.8, top_k=20, seed=5,
                       kv_cache_dtype="int8")


def run_batch(server, prompts, *, n=8, seeds=None, **batcher_kw):
    batcher_kw.setdefault("page_size", 8)

    async def go():
        b = ContinuousBatcher(server, **batcher_kw)
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=n,
                     seed=None if seeds is None else seeds[i])
            for i, p in enumerate(prompts)])
        stats = {"hwm": b._inflight_hwm,
                 "admit_inflight": b._last_admit_inflight,
                 "pages": b.page_stats()}
        await b.close()
        return outs, stats

    return asyncio.run(go())


# ---------------------------------------------------------------- parity
@pytest.mark.parametrize("fixt", [
    "server",
    # tier-1 keeps the bf16 greedy pair; int8 greedy rides CI's unfiltered
    # step (int8 paged parity stays tier-1-covered by the seeded-sampled
    # variant below, which exercises the same cache path plus the rng chain)
    pytest.param("int8_server", marks=pytest.mark.slow),
])
def test_paged_greedy_parity_with_generate(fixt, request):
    """Mixed-occupancy batch with wildly different prompt lengths: every
    slot's paged decode must equal its solo generate() exactly, under both
    KV dtypes (the acceptance bar: bit-exact, not close)."""
    s = request.getfixturevalue(fixt)
    prompts = [[5, 9, 17], [40, 3, 22, 8, 11, 60, 2, 33, 7, 7, 12, 13],
               [7], [60, 61, 62, 63, 64, 65]]
    expected = [s.generate([p], max_new_tokens=8)["tokens"][0]
                for p in prompts]
    outs, stats = run_batch(s, prompts, max_slots=3, max_len=40,
                            len_buckets=(8,), pipeline_depth=3)
    assert outs == expected
    assert stats["hwm"] >= 2, "paged pipeline never got >=2 steps in flight"
    assert stats["pages"]["kv_pages_in_use"] == 0  # all freed at the end
    assert stats["pages"]["kv_page_sheds"] == 0


@pytest.mark.parametrize("fixt", [
    # tier-1 870s budget keeps the int8 seeded pair (the densest coverage:
    # same cache path + rng chain + dequant); bf16 seeded rides CI's
    # unfiltered unit step, bf16 greedy parity stays tier-1 above
    pytest.param("sampled_server", marks=pytest.mark.slow),
    "sampled_int8_server",
])
def test_paged_seeded_sampled_parity_with_generate(fixt, request):
    """A seeded request through the PAGED batcher decodes the IDENTICAL
    token sequence generate() produces for the same seed — the per-slot
    device rng chain is untouched by the pool."""
    s = request.getfixturevalue(fixt)
    prompts = [[5, 9, 17, 2], [40, 3, 22], [7, 7, 7, 7, 7]]
    seeds = [42, 1234, 7]
    expected = [s.generate([p], max_new_tokens=8, seed=sd)["tokens"][0]
                for p, sd in zip(prompts, seeds)]
    outs, _ = run_batch(s, prompts, seeds=seeds, max_slots=3, max_len=40,
                        len_buckets=(8,), pipeline_depth=2)
    assert outs == expected


@pytest.mark.slow
def test_paged_fused_steps_parity(server):
    """decode_fuse_steps with the paged pool: K device-side steps per host
    sync, page growth provisioned k steps ahead — same tokens."""
    prompts = [[5, 9, 17], [40, 3, 22, 8, 11]]
    expected = [server.generate([p], max_new_tokens=12)["tokens"][0]
                for p in prompts]
    outs, _ = run_batch(server, prompts, n=12, max_slots=2, max_len=40,
                        len_buckets=(8,), pipeline_depth=2, fuse_steps=4)
    assert outs == expected


# ------------------------------------------------------- chunked prefill
@pytest.mark.slow
@pytest.mark.parametrize("fixt", ["server", "int8_server"])
def test_chunked_prefill_parity(fixt, request):
    """A prompt spanning multiple chunks decodes exactly like generate()'s
    one-shot prefill (chunks write through the same block table the reads
    gather back). int8 included: later chunks attend earlier chunks' K/V
    through the quantized pool, but one-shot prefill ALSO reads every
    just-written row back through the quantize/dequantize round-trip
    (transformer.py dequantizes the whole cache), and quantization is
    per-position with no cross-position state — so chunking must not move
    a single bit."""
    s = request.getfixturevalue(fixt)
    long_p = list(range(1, 30))  # 29 tokens, chunk 8 -> 4 chunks
    expected = s.generate([long_p], max_new_tokens=8)["tokens"][0]
    outs, _ = run_batch(s, [long_p], max_slots=2, max_len=48,
                        len_buckets=(32,), prefill_chunk=8)
    assert outs[0] == expected


def test_chunked_prefill_admission_mid_decode(server):
    """A chunked admission landing while >=2 decode steps are in flight:
    the in-flight request's tokens are untouched, the admitted prompt
    decodes exactly its solo tokens, and decode stepped BETWEEN chunks
    (dispatches interleave instead of stalling for the whole prefill)."""
    p1 = [5, 9, 17, 33]
    p2 = list(range(2, 31))  # 29 tokens, chunk 8 -> 4 interleaved chunks
    e1 = server.generate([p1], max_new_tokens=24)["tokens"][0]
    e2 = server.generate([p2], max_new_tokens=6)["tokens"][0]

    async def go():
        b = ContinuousBatcher(server, max_slots=2, max_len=64,
                              len_buckets=(32,), pipeline_depth=3,
                              page_size=8, prefill_chunk=8)
        t1 = asyncio.ensure_future(b.submit(p1, max_new_tokens=24))
        for _ in range(400):
            if b._inflight_hwm >= 2 and any(s.active for s in b._slots):
                break
            await asyncio.sleep(0.005)
        t2 = asyncio.ensure_future(b.submit(p2, max_new_tokens=6))
        o1, o2 = await asyncio.gather(t1, t2)
        admit_inflight = b._last_admit_inflight
        hwm = b._inflight_hwm
        await b.close()
        return o1, o2, admit_inflight, hwm

    o1, o2, admit_inflight, hwm = asyncio.run(go())
    assert o1 == e1
    assert o2 == e2
    assert hwm >= 2
    # the admission completed while decode steps were in flight
    assert admit_inflight >= 1


# ------------------------------------------------------ pages & allocator
def test_page_reuse_after_slot_free(server):
    """Sequential requests through a pool too small to hold both at once:
    the second recycles the first's freed pages (same ids — the allocator
    hands out lowest-first) and still decodes exactly."""
    p1, p2 = [5, 9, 17, 2, 8, 40, 3, 22, 11, 6], [60, 61, 62]
    e1 = server.generate([p1], max_new_tokens=8)["tokens"][0]
    e2 = server.generate([p2], max_new_tokens=8)["tokens"][0]

    async def go():
        # 2 slots x 3 pages would need 14 pages fully provisioned; 7 (5
        # usable) forces reuse across sequential occupancies
        b = ContinuousBatcher(server, max_slots=2, max_len=24,
                              len_buckets=(16,), page_size=8, pool_pages=7)
        o1 = await b.submit(p1, max_new_tokens=8)
        first_pages_in_use = b.page_stats()["kv_pages_in_use"]
        o2 = await b.submit(p2, max_new_tokens=8)
        stats = b.page_stats()
        await b.close()
        return o1, o2, first_pages_in_use, stats

    o1, o2, mid_in_use, stats = asyncio.run(go())
    assert o1 == e1
    assert o2 == e2
    assert mid_in_use == 0          # first request's pages all returned
    assert stats["kv_pages_in_use"] == 0
    assert stats["kv_pages_total"] == 7
    assert stats["kv_page_sheds"] == 0


def test_pool_exhaustion_sheds_newest_503(server):
    """Two concurrent generations outgrow an oversubscribed pool: the
    NEWEST sheds with 503/RESOURCE_EXHAUSTED + Retry-After (never an
    exception out of the decode loop), the oldest completes bit-exact,
    and the shed is visible in the page gauges."""
    p1, p2 = [5, 9, 17, 33], [40, 3, 22, 8]
    e1 = server.generate([p1], max_new_tokens=24)["tokens"][0]

    async def go():
        # capacity 8 pages of 4 tokens: two 4-token prompts decoding 24
        # tokens each need ~7 pages apiece — the pool can only feed one
        b = ContinuousBatcher(server, max_slots=2, max_len=32,
                              len_buckets=(8,), page_size=4, pool_pages=10)
        t1 = asyncio.ensure_future(b.submit(p1, max_new_tokens=24))
        await asyncio.sleep(0)  # keep admission order deterministic
        t2 = asyncio.ensure_future(b.submit(p2, max_new_tokens=24))
        results = await asyncio.gather(t1, t2, return_exceptions=True)
        stats = b.page_stats()
        await b.close()
        return results, stats

    (r1, r2), stats = asyncio.run(go())
    assert r1 == e1, "oldest request must complete untouched"
    assert isinstance(r2, ShedError)
    assert r2.status_code == 503
    assert r2.reason == "RESOURCE_EXHAUSTED"
    assert r2.retry_after_s > 0
    assert stats["kv_page_sheds"] >= 1
    assert stats["kv_pages_in_use"] == 0


def test_admission_that_can_never_fit_sheds_immediately(server):
    """An admission that fails to allocate while NOTHING is in flight must
    shed immediately — no active slot will ever free a page, so queueing
    it would hang forever. (Prompts themselves always fit an empty pool:
    _truncate_prompt caps them at max_len-1 and the constructor rejects
    pools smaller than one slot's worth of pages.)"""

    async def go():
        b = ContinuousBatcher(server, max_slots=1, max_len=24,
                              len_buckets=(16,), page_size=8, pool_pages=5)  # capacity 3
        try:
            with pytest.raises(ShedError):
                # 16-token bucket needs 2 pages — fits; drain the pool
                # with no slot active so no completion can ever refill it
                held = b._allocator.alloc(3)
                assert held is not None
                await b.submit([1] * 16, max_new_tokens=4)
        finally:
            await b.close()

    asyncio.run(go())


def test_page_allocator_exact_accounting():
    a = PageAllocator(total_pages=8, page_size=16)
    assert a.capacity == 6
    g1 = a.alloc(4)
    assert g1 is not None and len(set(g1)) == 4
    assert all(2 <= p < 8 for p in g1)       # reserved pages never granted
    assert a.alloc(3) is None                 # all-or-nothing
    g2 = a.alloc(2)
    assert g2 is not None and not (set(g1) & set(g2))
    assert a.stats()[1] == 6
    a.free(g1)
    assert a.stats()[1] == 2
    with pytest.raises(ValueError):
        a.free(g1)                            # double free
    with pytest.raises(ValueError):
        a.free([0])                           # reserved page
    a.free(g2)
    assert a.stats() == (8, 0, 0)


# ------------------------------------------------------------ prefix cache
@pytest.mark.parametrize("kvd", [
    "bf16",
    pytest.param("int8", marks=pytest.mark.slow),  # tier-1 keeps bf16;
    # the int8 sharing path still runs in CI's unfiltered unit step
])
def test_radix_prefix_hit_lands_in_paged_slot(kvd):
    """The radix prefix cache (runtime/radix.py): a completed request's
    prompt+generated blocks re-enter the trie IN PLACE, so a repeat of
    the same prompt serves its prefix as shared block-table entries (only
    the final token chunk-prefills — the match caps at L-1) and a
    chat-style continuation part-way into a cached block pays exactly one
    copy-on-write page copy — tokens bit-exact vs cold generate() either
    way (both KV dtypes: sharing covers value AND scale planes)."""
    s = make_server(prefix_cache_size=4, len_buckets=(16,),
                    kv_cache_dtype=kvd)
    system = [9, 8, 7, 6, 5, 4, 3, 2, 1]
    full = s.generate([system], max_new_tokens=8)["tokens"][0]
    longer = system + [30, 31, 32]
    e_longer = s.generate([longer], max_new_tokens=8)["tokens"][0]

    async def go():
        b = ContinuousBatcher(s, max_slots=2, max_len=32, len_buckets=(16,),
                              page_size=4, prefill_chunk=4)
        assert b._radix is not None
        o1 = await b.submit(system, max_new_tokens=8)
        st1 = dict(b._radix.stats())
        o2 = await b.submit(system, max_new_tokens=8)
        st2 = dict(b._radix.stats())
        o3 = await b.submit(longer, max_new_tokens=8)
        st3 = dict(b._radix.stats())
        pages = b.page_stats()
        await b.close()
        return o1, o2, o3, st1, st2, st3, pages

    o1, o2, o3, st1, st2, st3, pages = asyncio.run(go())
    assert o1 == full and o2 == full        # repeat: bit-exact via sharing
    assert o3 == e_longer                   # continuation: bit-exact
    # first completion populated the trie (prompt 9 + 7 provably-written
    # generated tokens = 16 tokens = 4 blocks of 4)
    assert st1["prefix_cached_blocks"] == 4
    assert st1["prefix_hit_tokens"] == 0
    # the repeat matched 8 tokens (two whole blocks; L-1 cap leaves the
    # last prompt token to prefill) with ZERO page copies
    assert st2["prefix_hit_tokens"] - st1["prefix_hit_tokens"] == 8
    assert st2["prefix_hit_blocks"] - st1["prefix_hit_blocks"] == 2
    assert st2["prefix_cow_copies"] == st1["prefix_cow_copies"]
    # the continuation ran INTO block 2 (its 9th token matches the cached
    # history's) — two shared blocks plus one copy-on-write page
    assert st3["prefix_hit_tokens"] - st2["prefix_hit_tokens"] >= 8
    assert st3["prefix_cow_copies"] == st2["prefix_cow_copies"] + 1
    assert st3["prefix_bytes_saved"] > 0
    # cached blocks stay resident (that is the cache); no slot holds pages
    assert pages["kv_pages_in_use"] == st3["prefix_cached_blocks"]
    assert pages["kv_page_sheds"] == 0


def test_radix_lookup_work_independent_of_population():
    """The O(entries x prefix) scan regression (ISSUE 12 satellite): trie
    match work scales with the PROBE length, not with how many sequences
    the cache holds. Measured in node visits on the real trie."""
    from seldon_core_tpu.runtime.radix import RadixPrefixCache

    def populate(n_seqs):
        alloc = PageAllocator(total_pages=4 * n_seqs + 8, page_size=4)
        trie = RadixPrefixCache(alloc, page_size=4)
        for i in range(n_seqs):
            pages = alloc.alloc(2)
            # every sequence starts with a distinct token: the probe can
            # reject each candidate at its first block token
            trie.insert([100 + i, 1, 2, 3, 4, 5, 6, 7], pages, 0)
        return trie

    probe = [7, 7, 7, 7, 7, 7, 7, 7]
    small = populate(4)
    small.match_len(probe)
    work_small = small.match_work_total
    big = populate(64)
    big.match_len(probe)
    work_big = big.match_work_total
    # the old OrderedDict scan did O(entries) comparisons per lookup; the
    # trie visits the (at most one) candidate bucket per block step
    assert work_big <= work_small + 2
    # and a full-path match costs O(blocks), entries notwithstanding
    big.match_len([100, 1, 2, 3, 4, 5, 6, 7])
    assert big.match_work_total - work_big <= 4


# ------------------------------------------------------------- metrics
@pytest.mark.slow  # tier-1 870s budget: runs in CI's unfiltered paged step
def test_page_gauges_reach_llm_stats_and_metrics(server):
    """kv_pages_in_use/total + fragmentation flow llm_stats -> sync_llm ->
    /metrics series."""
    from seldon_core_tpu.metrics.registry import MetricsRegistry
    from seldon_core_tpu.runtime.batcher import BatcherService

    s = make_server(continuous_batching=2, continuous_batching_max_len=32,
                    kv_page_size=8)
    svc = BatcherService(s, max_slots=2)
    s._batcher_service = svc
    try:
        out = svc.submit_sync([3, 1, 4, 1, 5], 8)
        assert len(out) == 8
        st = s.llm_stats()
        assert st["kv_pages_total"] > 0
        assert st["kv_page_size"] == 8
        assert 0.0 <= st["kv_page_fragmentation"] <= 1.0
        reg = MetricsRegistry(deployment="d", predictor="p")
        reg.sync_llm(s)
        text = reg.expose().decode()
        assert "seldon_llm_kv_pages_in_use" in text
        assert "seldon_llm_kv_pages_total" in text
        assert "seldon_llm_kv_page_fragmentation" in text
        # exhaustion sheds bypass the AdmissionController, so they need
        # their own series for operators alerting on shed rates
        assert "seldon_llm_kv_page_sheds_total" in text
    finally:
        svc.close()


@pytest.mark.slow
def test_fragmentation_gauge_math(server):
    """Mid-generation, fragmentation == 1 - tokens/(pages*page_size) for
    the tokens actually dispatched into pages."""

    async def go():
        b = ContinuousBatcher(server, max_slots=1, max_len=32,
                              len_buckets=(8,), page_size=8)
        out = await b.submit([5, 9, 17], max_new_tokens=4)
        # after completion everything is freed -> fragmentation 0
        st = b.page_stats()
        await b.close()
        return out, st

    out, st = asyncio.run(go())
    assert len(out) == 4
    assert st["kv_pages_in_use"] == 0
    assert st["kv_page_fragmentation"] == 0.0


# ------------------------------------------------------------ validation
def test_layout_validated_at_load():
    with pytest.raises(ValueError, match="kv_page_size"):
        make_server(kv_page_size=-1)
    with pytest.raises(ValueError, match="prefill_chunk"):
        make_server(prefill_chunk=-2)
    with pytest.raises(ValueError, match="kv_pool_pages"):
        make_server(kv_pool_pages=-3)


def test_pool_too_small_for_one_sequence_rejected(server):
    with pytest.raises(ValueError, match="kv_pool_pages"):
        ContinuousBatcher(server, max_slots=1, max_len=32, len_buckets=(8,),
                          page_size=8, pool_pages=3)


# ------------------------------------------------------------- writes
@pytest.mark.pallas
def test_paged_write_targets_redirect_garbage():
    """Device-side write-safety invariants: NULL table entries and
    past-table positions redirect to TRASH_PAGE; the NULL page is never a
    write target, so its PAD_POS rows (the 'masked forever' guarantee)
    cannot be corrupted by any host bug."""
    import jax.numpy as jnp

    from seldon_core_tpu.models.cache import NULL_PAGE, PAD_POS, TRASH_PAGE, paged_write_targets

    bt = jnp.asarray([[2, 3, NULL_PAGE]], jnp.int32)
    positions = jnp.asarray(
        [[0, 9, 16, 23, 24, 999, PAD_POS]], jnp.int32)  # ps=8, 3 pages
    entry, off = paged_write_targets(bt, positions, 8)
    entry = np.asarray(entry)[0]
    assert entry[0] == 2 and entry[1] == 3          # in-table writes
    assert entry[2] == TRASH_PAGE                   # NULL entry redirected
    assert entry[3] == TRASH_PAGE
    assert entry[4] == TRASH_PAGE                   # past-table position
    assert entry[5] == TRASH_PAGE
    assert entry[6] == TRASH_PAGE                   # PAD query token
    assert NULL_PAGE not in entry
