"""Batched LoRA multi-tenancy (ISSUE 15 tentpole, runtime/adapters.py).

The acceptance bar this file pins (CI "Multi-tenant suite"):
heterogeneous-adapter parity — a continuous batch mixing >= 3 adapters
plus the identity is BIT-EXACT per slot against each adapter served solo
(greedy + seeded-sampled, bf16 + int8 KV, and the
speculative verify path), the identity slots additionally bit-exact
against plain base-model generate(); plus the registry's load/evict/
refcount discipline (k/v rejection, pinned-eviction refusal, pool
accounting) and the adapter metrics flowing llm_stats -> /metrics.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from seldon_core_tpu.runtime.adapters import (
    ADAPTED_PROJECTIONS,
    AdapterRegistry,
)
from seldon_core_tpu.runtime.batcher import ContinuousBatcher
from seldon_core_tpu.servers.llmserver import LLMServer

pytestmark = pytest.mark.leakcheck  # conftest leak canary (ISSUE 19)

KW = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
          ffn_dim=64, max_seq_len=96)
RANK = 4
PROMPTS = [
    [5, 9, 17, 3],
    [11, 2, 63, 40, 7],
    [29, 29, 4],
    [77, 13, 8, 1, 90, 33],
]


def make_server(**extra) -> LLMServer:
    base = dict(model="transformer", model_kwargs=KW, init_random=True,
                max_new_tokens=8, len_buckets=(16,), batch_buckets=(1,),
                temperature=0.0, eos_id=-1, seed=3,
                lora_rank=RANK, lora_max_adapters=6)
    base.update(extra)
    s = LLMServer(**base)
    s.load()
    return s


def load_adapters(server, n: int = 3, scale: float = 0.25):
    """n distinct random adapters covering every adapted projection.
    ``server`` is an LLMServer or a bare make_registry() registry."""
    reg = getattr(server, "adapter_registry", None) or server
    rng = np.random.default_rng(1234)
    cfg = server._cfg
    L = cfg.n_layers
    dims = {"wq": (cfg.dim, cfg.n_heads * cfg.head_dim),
            "wo": (cfg.n_heads * cfg.head_dim, cfg.dim),
            "w1": (cfg.dim, cfg.ffn_dim),
            "w2": (cfg.ffn_dim, cfg.dim),
            "w3": (cfg.dim, cfg.ffn_dim)}
    names = []
    for i in range(n):
        w = {proj: (rng.normal(size=(L, din, RANK)) * scale,
                    rng.normal(size=(L, RANK, dout)) * scale)
             for proj, (din, dout) in dims.items()}
        name = f"tenant-{i}"
        reg.load(name, w, alpha=2 * RANK)
        names.append(name)
    return names

def make_registry(max_adapters=6):
    """A bare AdapterRegistry on the test dims — the registry-discipline
    tests need no server, params, or compiled programs (each extra
    LLMServer.load() costs seconds against the tier-1 budget)."""
    from seldon_core_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(tie_embeddings=True, **KW)
    reg = AdapterRegistry(cfg, RANK, max_adapters)
    reg._cfg = cfg  # load_adapters reads dims from here
    return reg


def batch_serve(server, prompts, adapters, *, seed=None,
                max_new=6, slots=None):
    """Serve all prompts CONCURRENTLY through one batcher (mixed batch)
    and return the per-request token lists."""

    async def go():
        b = ContinuousBatcher(server, max_slots=slots or len(prompts),
                              max_len=40, len_buckets=(8,), page_size=8)
        outs = await asyncio.gather(*[
            b.submit(p, max_new_tokens=max_new, adapter=a, seed=seed,
                     tenant=a or "base")
            for p, a in zip(prompts, adapters)])
        await b.close()
        return outs

    return asyncio.run(go())


def solo_serve(server, prompt, adapter, *, seed=None, max_new=6):
    """The same request alone in a fresh single-slot batcher — the solo
    reference the mixed batch must match bit-for-bit."""
    return batch_serve(server, [prompt], [adapter],
                       seed=seed, max_new=max_new, slots=1)[0]


# ---------------------------------------------------------------------------
# registry discipline
# ---------------------------------------------------------------------------

def test_kv_projection_factors_rejected():
    reg = make_registry()
    L = reg.n_layers
    bad = {"wk": (np.zeros((L, 32, RANK)), np.zeros((RANK, 32)))}
    with pytest.raises(ValueError, match="k/v"):
        reg.load("bad", bad)
    with pytest.raises(ValueError, match="k/v"):
        reg.load("bad", {"wv": (np.zeros((L, 32, RANK)),
                                np.zeros((L, RANK, 32)))})


def test_unknown_projection_and_shape_rejected():
    reg = make_registry()
    L = reg.n_layers
    with pytest.raises(ValueError, match="unknown projection"):
        reg.load("x", {"lm_head": (np.zeros((L, 32, RANK)),
                                   np.zeros((L, RANK, 96)))})
    with pytest.raises(ValueError, match="shapes"):
        reg.load("x", {"wq": (np.zeros((L, 16, RANK)),
                              np.zeros((L, RANK, 32)))})
    with pytest.raises(ValueError, match="rank"):
        reg.load("x", {}, rank=RANK + 1)


def test_evict_refuses_while_pinned_frees_after():
    """The refcount invariant (acceptance bar): evict can never free an
    adapter a live slot references. The interleaving proof lives in
    tests/test_schedules.py; this is the direct surface check."""
    reg = make_registry()
    (name,) = load_adapters(reg, 1)
    aid = reg.resolve(name)
    reg.pin(aid)
    assert reg.evict(name) is False          # pinned: refused
    assert name in reg.names()
    reg.pin(aid)
    reg.unpin(aid)
    assert reg.evict(name) is False          # still one pin out
    reg.unpin(aid)
    assert reg.evict(name) is True           # last pin dropped: freed
    assert name not in reg.names()
    assert reg.stats()["adapter_evictions_total"] == 1
    with pytest.raises(KeyError):
        reg.resolve(name)
    # the freed row is reusable
    load_adapters(reg, 1)
    assert reg.stats()["adapter_loaded"] == 1


def test_reload_pinned_adapter_refused():
    reg = make_registry()
    (name,) = load_adapters(reg, 1)
    reg.pin(reg.resolve(name))
    with pytest.raises(ValueError, match="pinned"):
        load_adapters(reg, 1)  # same name -> reload attempt


def test_pool_full_and_pin_freed_row():
    reg = make_registry(max_adapters=2)  # one usable row + identity
    load_adapters(reg, 1)
    with pytest.raises(ValueError, match="pool full"):
        reg.load("overflow", {}, alpha=1.0)
    with pytest.raises(KeyError):
        reg.pin(99)


def test_registry_stats_flow_llm_stats():
    s = make_server()
    load_adapters(s, 2)
    stats = s.llm_stats()
    assert stats["adapter_loaded"] == 2
    assert stats["adapter_pool_bytes"] > 0
    assert stats["adapter_evictions_total"] == 0
    # and into the Prometheus text via sync_llm
    from seldon_core_tpu.metrics.registry import MetricsRegistry

    m = MetricsRegistry(deployment="d", predictor="p")
    m.sync_llm(s)
    text = m.expose().decode()
    assert "seldon_llm_adapter_loaded" in text
    assert "seldon_llm_adapter_pool_bytes" in text


def test_load_uri_roundtrip(tmp_path):
    """Adapter artifacts fetch through the storage layer: adapter.json +
    weights.npz."""
    import json

    s = make_server()
    cfg = s._cfg
    L = cfg.n_layers
    rng = np.random.default_rng(5)
    a = rng.normal(size=(L, cfg.dim, RANK)).astype(np.float32)
    b = rng.normal(size=(L, RANK, cfg.n_heads * cfg.head_dim)).astype(
        np.float32)
    d = tmp_path / "adapter"
    d.mkdir()
    (d / "adapter.json").write_text(json.dumps({"rank": RANK, "alpha": 8}))
    np.savez(d / "weights.npz", **{"wq.A": a, "wq.B": b})
    aid = s.adapter_registry.load_uri("stored", str(d))
    assert s.adapter_registry.resolve("stored") == aid
    # the stored artifact serves
    out_uri = solo_serve(s, PROMPTS[0], "stored")
    assert len(out_uri) == 6
    # and lands the IDENTICAL pool row an in-memory load would: the wq
    # factors cast to the pool dtype, everything else zeros, scale =
    # alpha/rank (the serving-parity twin is the mixed-batch matrix)
    import jax.numpy as jnp

    pool = s.adapter_registry.pool()
    dt = s.adapter_registry.dtype
    np.testing.assert_array_equal(np.asarray(pool["wq"][0][aid]),
                                  np.asarray(jnp.asarray(a, dt)))
    np.testing.assert_array_equal(np.asarray(pool["wq"][1][aid]),
                                  np.asarray(jnp.asarray(b, dt)))
    assert not np.asarray(pool["wo"][0][aid]).any()
    assert float(pool["scale"][aid]) == 8.0 / RANK


def test_lora_with_disaggregation_rejected():
    with pytest.raises(ValueError, match="disaggregation"):
        make_server(disaggregation="remote_prefill")


def test_unknown_adapter_and_class_rejected_at_submit():
    from seldon_core_tpu.contracts.payload import SeldonError

    s = make_server()

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=40, len_buckets=(8,),
                              page_size=8)
        with pytest.raises(SeldonError, match="unknown adapter"):
            await b.submit(PROMPTS[0], max_new_tokens=2, adapter="nope")
        with pytest.raises(SeldonError, match="SLO class"):
            await b.submit(PROMPTS[0], max_new_tokens=2, slo_class="gold")
        await b.close()

    asyncio.run(go())


# ---------------------------------------------------------------------------
# heterogeneous-adapter parity (the acceptance bar)
# ---------------------------------------------------------------------------

# tier-1 runs one representative (seeded+int8 — each param builds and
# compiles its own server, ~25 s apiece against the 870 s verify budget); the
# slow-marked rest of the matrix runs UNFILTERED in CI's pinned
# Multi-tenant suite step, the PR 7/9/10 rebalancing idiom.
@pytest.mark.parametrize(
    "kv_dtype,seed",
    [pytest.param("bf16", None, marks=pytest.mark.slow),
     # tier-1 870s budget: one rep — int8/seeded is the densest cell
     ("int8", 1234),
     pytest.param("bf16", 1234, marks=pytest.mark.slow),
     pytest.param("int8", None, marks=pytest.mark.slow)])
def test_mixed_batch_bit_exact_vs_solo(kv_dtype, seed):
    """>= 3 adapters + identity in ONE continuous batch: every slot's
    tokens equal the same request served solo, and the identity slot
    equals plain base generate(). Greedy (seed=None at temperature 0)
    and seeded-sampled."""
    temp = 0.0 if seed is None else 0.8
    s = make_server(kv_cache_dtype=kv_dtype, temperature=temp)
    names = load_adapters(s, 3)
    adapters = names + [None]                 # 3 tenants + identity
    mixed = batch_serve(s, PROMPTS, adapters, seed=seed)
    for prompt, adapter, got in zip(PROMPTS, adapters, mixed):
        solo = solo_serve(s, prompt, adapter, seed=seed)
        assert got == solo, (adapter, kv_dtype, seed)
    # at least one adapted slot must actually diverge from base output
    base = [solo_serve(s, p, None, seed=seed) for p in PROMPTS[:3]]
    assert any(m != b for m, b in zip(mixed[:3], base))
    # identity slot == plain generate() (the zero-delta bitwise guarantee)
    g = s.generate([PROMPTS[3]], max_new_tokens=6, seed=seed)
    assert mixed[3] == g["tokens"][0]


@pytest.mark.slow
def test_mixed_batch_parity_spec_verify():
    """The speculative verify path (llm.lora_verify_step): mixed
    adapters through ngram speculation stay bit-exact vs solo AND vs the
    non-speculative adapted batcher — speculation changes tokens per
    forward, never token values, adapters included."""
    s = make_server(spec_mode="ngram", spec_k=2)
    names = load_adapters(s, 3)
    adapters = names + [None]
    # repetitive prompts so the ngram proposer actually fires
    prompts = [[7, 8, 9, 7, 8, 9, 7, 8], [4, 4, 4, 4, 4],
               [1, 2, 1, 2, 1, 2], [5, 6, 5, 6, 5, 6, 5]]
    mixed = batch_serve(s, prompts, adapters, max_new=8)
    for prompt, adapter, got in zip(prompts, adapters, mixed):
        assert got == solo_serve(s, prompt, adapter, max_new=8)
    # vs the NON-speculative adapted batcher (identical model seed +
    # identical adapter factors — load_adapters is deterministic)
    plain = make_server()
    load_adapters(plain, 3)
    ref = batch_serve(plain, prompts, adapters, max_new=8)
    assert mixed == ref


def test_identity_program_matches_unadapted_program():
    """adapter_id 0 through the ADAPTED compiled step reproduces the
    UNADAPTED server's batcher byte-for-byte — one program shape serves
    base traffic with zero output drift (the S-LoRA identity-row
    property the budgets band also bounds in cost)."""
    s_lora = make_server()
    s_base = make_server(lora_rank=0)
    a = batch_serve(s_lora, PROMPTS[:2], [None, None])
    b = batch_serve(s_base, PROMPTS[:2], [None, None])
    assert a == b


def test_adapted_requests_skip_radix_trie():
    """KV-purity design point (docs/multitenancy.md): the radix prefix
    trie serves base-adapter traffic only. An adapted request never
    matches NOR inserts — its deep-layer KV embeds its deltas — and a
    base request right after an identical adapted prompt gets base
    results (no cross-tenant KV)."""
    s = make_server(prefix_cache_size=4)
    (name,) = load_adapters(s, 1)
    prompt = [9, 9, 9, 9, 9, 9, 9, 9, 9, 3]

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=48, len_buckets=(16,),
                              page_size=4)
        assert b._radix is not None
        adapted = await b.submit(prompt, max_new_tokens=4, adapter=name)
        stats_after_adapted = b._radix.stats()
        base1 = await b.submit(prompt, max_new_tokens=4)
        base2 = await b.submit(prompt, max_new_tokens=4)
        hits = b._radix.stats()
        await b.close()
        return adapted, stats_after_adapted, base1, base2, hits

    adapted, st0, base1, base2, st1 = asyncio.run(go())
    # the adapted completion inserted nothing
    assert st0["prefix_cached_blocks"] == 0
    # base traffic caches + hits as before
    assert base1 == base2
    assert st1["prefix_hit_tokens"] > 0
    # and the adapted answer differs from base (the adapters are real)
    assert adapted != base1


def test_eviction_blocked_while_request_queued_or_active():
    """End-to-end refcount: from submit() until release, the adapter is
    pinned — evict during a live generation is refused, after it
    succeeds."""
    s = make_server()
    (name,) = load_adapters(s, 1)
    reg = s.adapter_registry

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=40, len_buckets=(8,),
                              page_size=8)
        fut = asyncio.ensure_future(
            b.submit(PROMPTS[0], max_new_tokens=16, adapter=name))
        # while queued/active the pin holds (poll until the pin appears,
        # then evict must refuse)
        for _ in range(200):
            if reg.refs_of(name) > 0:
                break
            await asyncio.sleep(0.005)
        assert reg.refs_of(name) > 0
        assert reg.evict(name) is False
        await fut
        assert reg.refs_of(name) == 0
        assert reg.evict(name) is True
        await b.close()

    asyncio.run(go())


def test_staged_prefill_shed_releases_adapter_pin():
    """Terminal shed of a STAGED (pre-commit) adapted prefill job must
    drop the queue entry's adapter pin: the slot release can't (pin
    ownership only moves to the slot at _commit_slot), so a leak here
    would wedge evict/reload for that adapter until process restart.
    Staged directly, no batcher loop — the shed path is the unit."""
    from seldon_core_tpu.runtime.resilience import ShedError
    from seldon_core_tpu.runtime.scheduler import PendingRequest

    s = make_server()
    (name,) = load_adapters(s, 1)
    reg = s.adapter_registry
    prompt = list(np.random.default_rng(3).integers(1, 90, size=14))

    async def go():
        b = ContinuousBatcher(s, max_slots=1, max_len=48, len_buckets=(16,),
                              page_size=4, prefill_chunk=2)
        b._loop = asyncio.get_running_loop()  # submit() normally sets it
        aid = reg.resolve_and_pin(name)
        fut = asyncio.get_running_loop().create_future()
        req = PendingRequest(ids=prompt, max_new=4, fut=fut, tenant="t",
                             slo_class="batch", adapter_id=aid)
        assert b._pending.push(req)
        assert b._admit_begin(req)        # host-side staging only
        b._pending.commit(req)
        assert b._prefill is not None and reg.refs_of(name) == 1
        b._shed_prefill_job("test: forced staged shed")
        with pytest.raises(ShedError):
            await fut
        assert reg.refs_of(name) == 0     # the fix: pin died with the job
        assert reg.evict(name) is True    # management plane unwedged
        await b.close()

    asyncio.run(go())


def test_lora_decode_budget_within_band_of_plain_step():
    """The identity-adapter step's compiled cost must sit within the
    hlolint tolerance band of the plain step's committed budget — the
    'near-base-model throughput' claim, enforced against budgets.json
    (the same band CI enforces per-contract)."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "hlolint", "budgets.json")
    with open(path) as f:
        budgets = json.load(f)
    entries = budgets["entries"]
    tol = float(budgets.get("tolerance", 0.25))
    plain = entries["llm.paged_decode_step_s4"]
    lora = entries["llm.lora_decode_step"]
    for kind in ("flops", "bytes_accessed"):
        assert lora[kind] <= plain[kind] * (1.0 + tol), (
            f"lora step {kind} {lora[kind]} exceeds the band over the "
            f"plain step's {plain[kind]}")
