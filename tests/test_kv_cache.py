"""Quantized (int8) KV cache and decode-bandwidth layer correctness:
quantize/dequantize numerics, int8-vs-bf16 greedy decode parity, cache
donation (in-place decode updates, verified via lowered-HLO aliasing),
prefix-cache behaviour under both KV dtypes, and the /metrics surface."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import get_model
from seldon_core_tpu.models.cache import (
    dequantize_kv,
    init_kv_caches,
    kv_cache_bytes_per_token,
    normalize_kv_cache_dtype,
    quantize_kv,
)
from seldon_core_tpu.models.transformer import TransformerConfig
from seldon_core_tpu.servers.llmserver import LLMServer


def make_server(**extra):
    kwargs = dict(
        model="llama-tiny", init_random=True, max_new_tokens=40,
        len_buckets=(16, 32), batch_buckets=(1, 4), temperature=0.0,
        eos_id=-1, seed=7,
    )
    kwargs.update(extra)
    s = LLMServer(**kwargs)
    s.load()
    return s


@pytest.fixture(scope="module")
def bf16_server():
    return make_server()


@pytest.fixture(scope="module")
def int8_server():
    return make_server(kv_cache_dtype="int8")


# ------------------------------------------------------------ quantization
@pytest.mark.pallas
def test_quantize_kv_roundtrip_error_bound():
    """Per-head per-position symmetric int8: reconstruction error is bounded
    by half a quantization step (scale/2 = amax/254) per element."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 4, 16)), jnp.float32)
    q, scale = quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert q.shape == x.shape and scale.shape == x.shape[:-1]
    back = dequantize_kv(q, scale, jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-7
    assert (err <= bound).all()


@pytest.mark.pallas
def test_quantize_kv_zero_vector_dequantizes_to_zero():
    x = jnp.zeros((1, 3, 2, 8), jnp.float32)
    q, scale = quantize_kv(x)
    assert np.asarray(scale).min() == 1.0  # guarded against div-by-zero
    assert np.asarray(dequantize_kv(q, scale, jnp.float32)).max() == 0.0


def test_int8_cache_structure_and_bytes():
    cfg = TransformerConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                            dtype=jnp.bfloat16)
    bf = init_kv_caches(cfg, 2, 32)
    q = init_kv_caches(cfg, 2, 32, "int8")
    assert len(bf[0]) == 3 and len(q[0]) == 5
    kq, ks, vq, vs, pos = q[0]
    assert kq.dtype == jnp.int8 and ks.dtype == jnp.float32
    assert kq.shape == (2, 32, 2, 16) and ks.shape == (2, 32, 2)
    bf_bytes = sum(a.nbytes for layer in bf for a in layer)
    q_bytes = sum(a.nbytes for layer in q for a in layer)
    # int8 values + f32 per-head scales: well under the bf16 footprint
    assert q_bytes < 0.65 * bf_bytes
    # the reporting helper agrees with the real buffers (per token position)
    assert kv_cache_bytes_per_token(cfg, "int8") == q_bytes // (2 * 32)
    assert kv_cache_bytes_per_token(cfg, "bf16") == bf_bytes // (2 * 32)


def test_normalize_kv_cache_dtype():
    assert normalize_kv_cache_dtype("") == "bf16"
    assert normalize_kv_cache_dtype(None) == "bf16"
    assert normalize_kv_cache_dtype("bfloat16") == "bf16"
    assert normalize_kv_cache_dtype("INT8") == "int8"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        normalize_kv_cache_dtype("fp4")


# ------------------------------------------------------- decode parity
@pytest.mark.pallas
@pytest.mark.slow  # tier-1 870s budget: int8 parity also rides the pinned pallas + paged CI steps
def test_int8_kv_greedy_matches_bf16_for_32_steps(bf16_server, int8_server):
    """The acceptance bar: int8-KV greedy token output matches the bf16-KV
    decode for >=32 steps on a small config."""
    prompt = [5, 9, 17, 33, 2, 7, 40, 3]
    want = bf16_server.generate([prompt], max_new_tokens=40)["tokens"][0]
    got = int8_server.generate([prompt], max_new_tokens=40)["tokens"][0]
    assert len(want) == 40
    assert got == want


@pytest.mark.pallas
@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_int8_kv_ragged_batch_matches_solo(int8_server):
    """PAD_POS masking stays exact under quantization: right-padded ragged
    rows reproduce their solo int8 decode."""
    p1, p2 = [5, 9, 17], [40, 3, 22, 8, 11, 60, 2]
    solo1 = int8_server.generate([p1], max_new_tokens=5)["tokens"][0]
    solo2 = int8_server.generate([p2], max_new_tokens=5)["tokens"][0]
    both = int8_server.generate([p1, p2], max_new_tokens=5)["tokens"]
    assert both[0] == solo1
    assert both[1] == solo2


def test_int8_kv_continuous_batcher_matches_solo(int8_server):
    """The batcher's slot caches inherit the int8 layout (per-slot write
    offsets take the vector-cache_index quantized path)."""
    from seldon_core_tpu.runtime.batcher import ContinuousBatcher

    prompts = [[5, 9, 17], [40, 3, 22, 8, 11]]
    expected = [int8_server.generate([p], max_new_tokens=6)["tokens"][0]
                for p in prompts]

    async def go():
        batcher = ContinuousBatcher(int8_server, max_slots=2, max_len=32,
                                    len_buckets=(8,))
        assert len(batcher._caches[0]) == 5  # int8 slot layout
        outs = await asyncio.gather(
            *[batcher.submit(p, max_new_tokens=6) for p in prompts])
        await batcher.close()
        return outs

    assert asyncio.run(go()) == expected


# ------------------------------------------------------------ validation
def test_unknown_kv_cache_dtype_fails_at_load():
    s = LLMServer(model="llama-tiny", init_random=True, kv_cache_dtype="fp4")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        s.load()


def test_unknown_param_dtype_fails_at_load():
    s = LLMServer(model="llama-tiny", init_random=True, param_dtype="bogus16")
    with pytest.raises(ValueError, match="param_dtype"):
        s.load()


def test_model_kwargs_kv_cache_dtype_validated():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        get_model("transformer", vocab_size=16, dim=8, n_layers=1, n_heads=1,
                  n_kv_heads=1, ffn_dim=16, max_seq_len=16,
                  kv_cache_dtype="int4")


# ------------------------------------------------------------- donation
def _decode_args(server, max_len):
    caches = init_kv_caches(server._cfg, 1, max_len, server.kv_cache_dtype)
    return (server._params, caches, jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32), 4, jax.random.PRNGKey(0),
            jnp.asarray(0.0, jnp.float32))


@pytest.mark.parametrize("fixture", ["bf16_server", "int8_server"])
def test_decode_donates_cache_buffers(fixture, request):
    """The donating decode must alias its cache inputs onto outputs in the
    lowered module (tf.aliasing_output) — the in-place-update contract; the
    prefix-cache variant (donate=False) must NOT alias (its caches stay
    live as stored entries)."""
    server = request.getfixturevalue(fixture)
    args = _decode_args(server, 48)
    donating = server._get_decode(1, 48, donate=True)
    plain = server._get_decode(1, 48, donate=False)
    assert "tf.aliasing_output" in donating.lower(*args).as_text()
    assert "tf.aliasing_output" not in plain.lower(*args).as_text()


def test_extend_defaults_to_copying(bf16_server):
    """_get_extend's default must keep the input cache alive (prefix-cache
    continuations extend an entry that remains stored)."""
    server = bf16_server
    caches = init_kv_caches(server._cfg, 1, 48)
    extend = server._get_extend(1, 16, 48)
    toks = jnp.zeros((1, 16), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(16)[None, :], (1, 16))
    low = extend.lower(server._params, caches, toks, pos, jnp.asarray(0, jnp.int32))
    assert "tf.aliasing_output" not in low.as_text()
    donating = server._get_extend(1, 16, 48, donate=True)
    low2 = donating.lower(server._params, caches, toks, pos, jnp.asarray(0, jnp.int32))
    assert "tf.aliasing_output" in low2.as_text()


def test_prefix_cache_entry_survives_decode(bf16_server):
    """End-to-end guard for the donation/prefix interaction: a prompt served
    twice through the prefix cache must hit the second time (the stored
    entry's buffers were not donated away) and decode identically."""
    s = make_server(prefix_cache_size=4)
    prompt = [9, 4, 7, 33, 2, 5]
    first = s.generate([prompt], max_new_tokens=6)["tokens"][0]
    again = s.generate([prompt], max_new_tokens=6)["tokens"][0]
    assert again == first
    assert s._prefix_hits == 1
    # the stored caches are still readable (not invalidated by donation)
    entry = next(iter(s._prefix_cache.values()))
    np.asarray(jax.tree.leaves(entry[2])[0])


# ------------------------------------------- prefix cache under KV dtypes
@pytest.mark.parametrize("kvd", [
    "bf16",
    # tier-1 870s budget keeps bf16; int8 rides CI's unfiltered steps
    pytest.param("int8", marks=pytest.mark.slow),
])
def test_prefix_store_lookup_roundtrip(kvd):
    s = make_server(prefix_cache_size=4, kv_cache_dtype=kvd)
    prompt = [5, 9, 17, 33, 2, 7, 40, 3]
    s.generate([prompt], max_new_tokens=1)
    assert len(s._prefix_cache) == 1
    max_len = next(iter(s._prefix_cache.values()))[0]
    hit = s._prefix_lookup(prompt, max_len)
    assert hit is not None and hit[0] == len(prompt)
    layer0 = hit[2][0]
    assert len(layer0) == (5 if kvd == "int8" else 3)
    # longest-prefix continuation also hits
    hit2 = s._prefix_lookup(prompt + [1, 2], max_len)
    assert hit2 is not None and hit2[0] == len(prompt)


def test_prefix_lookup_work_independent_of_entry_count():
    """The ISSUE 12 satellite regression: _prefix_lookup walks the trie
    index in O(prompt) node steps under _prefix_lock — its work must NOT
    scale with how many entries the cache holds (the old implementation
    compared the probe against EVERY entry)."""
    s = make_server(prefix_cache_size=256, prefix_cache_bytes=1 << 40)
    probe = [200 + i for i in range(12)]  # shares no prefix with entries

    def store(n):
        # synthetic entries (lookup only reads the key/metadata tuple):
        # distinct first tokens, so the index rejects each at one node
        for i in range(n):
            s._prefix_store([i, 1, 2, 3, 4, 5, 6, 7], 64, [], None)

    store(4)
    s._prefix_index.work = 0
    assert s._prefix_lookup(probe, 64) is None
    work_small = s._prefix_index.work
    store(128)
    s._prefix_index.work = 0
    assert s._prefix_lookup(probe, 64) is None
    work_big = s._prefix_index.work
    assert work_big == work_small, (
        f"lookup work scaled with entries: {work_small} -> {work_big}")
    # a real longest-prefix hit costs O(prompt), entries notwithstanding
    s._prefix_index.work = 0
    hit = s._prefix_lookup([3, 1, 2, 3, 4, 5, 6, 7, 9, 9], 64)
    assert hit is not None and hit[0] == 8
    assert s._prefix_index.work <= 11  # root + one node per probe token


@pytest.mark.parametrize("kvd", [
    "bf16",
    # tier-1 870s budget keeps bf16; int8 rides CI's unfiltered steps
    pytest.param("int8", marks=pytest.mark.slow),
])
def test_prefix_eviction_accounting(kvd):
    """_prefix_bytes must track the sum of _entry_nbytes over live entries
    across stores and evictions, for either cache layout."""
    s = make_server(prefix_cache_size=2, kv_cache_dtype=kvd)
    for seed in range(4):
        prompt = np.random.default_rng(seed).integers(1, 255, size=6).tolist()
        s.generate([prompt], max_new_tokens=1)
    assert len(s._prefix_cache) <= 2
    expect = sum(
        s._entry_nbytes(entry[2], entry[3]) for entry in s._prefix_cache.values()
    )
    assert s._prefix_bytes == expect
    assert all(entry[1] == kvd for entry in s._prefix_cache.values())
    s.clear_prefix_cache()
    assert s._prefix_bytes == 0 and len(s._prefix_cache) == 0


@pytest.mark.slow  # tier-1 870s budget: dtype guard also asserted at entry-store time; runs in CI's unfiltered unit step
def test_prefix_entry_not_served_across_kv_dtypes():
    """A bf16-stored entry must read as a MISS for an int8-configured
    decode (and vice versa) — serving it would hand the decode a cache of
    the wrong structure."""
    prompt = [5, 9, 17, 33, 2, 7, 40, 3]

    s = make_server(prefix_cache_size=4)  # bf16
    s.generate([prompt], max_new_tokens=1)
    max_len = next(iter(s._prefix_cache.values()))[0]
    assert s._prefix_lookup(prompt, max_len) is not None
    s.kv_cache_dtype = "int8"  # simulated dtype flip
    assert s._prefix_lookup(prompt, max_len) is None

    q = make_server(prefix_cache_size=4, kv_cache_dtype="int8")
    q.generate([prompt], max_new_tokens=1)
    max_len = next(iter(q._prefix_cache.values()))[0]
    assert q._prefix_lookup(prompt, max_len) is not None
    q.kv_cache_dtype = "bf16"
    assert q._prefix_lookup(prompt, max_len) is None


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_prefix_cache_int8_multi_turn_matches_plain():
    """Turn-2 extends turn-1 under int8 KV: the cache must hit and the
    output must match a cache-less int8 twin."""
    base = make_server(kv_cache_dtype="int8", max_new_tokens=6)
    cached = make_server(kv_cache_dtype="int8", max_new_tokens=6,
                         prefix_cache_size=4)
    rng = np.random.default_rng(3)
    turn1 = rng.integers(1, 255, size=12).tolist()
    a1 = cached.generate([turn1], max_new_tokens=6)["tokens"][0]
    assert a1 == base.generate([turn1], max_new_tokens=6)["tokens"][0]
    turn2 = turn1 + a1 + [20, 21]
    a2 = cached.generate([turn2], max_new_tokens=6)["tokens"][0]
    assert cached._prefix_hits >= 1
    assert a2 == base.generate([turn2], max_new_tokens=6)["tokens"][0]


# ------------------------------------------------- sharded int8 caches
@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_seq_sharded_int8_cache_layout(eight_devices):
    """int8 cache sharding: values split max_len over 'seq' and kv_heads
    over 'model' like bf16, with the f32 scale planes sharded alongside."""
    from seldon_core_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": 1, "seq": 4, "model": 2}, eight_devices)
    s = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=4,
        len_buckets=(32,), batch_buckets=(1,), mesh=mesh,
        kv_cache_dtype="int8",
    )
    s.load()
    prefill = s._get_prefill(1, 32, 36)
    tokens = jnp.zeros((1, 32), jnp.int32)
    positions = jnp.arange(32)[None, :]
    _, caches = prefill(s._params, tokens, positions)
    kq, ks, vq, vs, pos = caches[0]
    assert kq.dtype == jnp.int8 and ks.dtype == jnp.float32
    assert kq.shape == (1, 36, 2, 16) and ks.shape == (1, 36, 2)
    assert "seq" in str(kq.sharding.spec), kq.sharding
    assert kq.sharding.shard_shape(kq.shape)[1] == 9
    assert ks.sharding.shard_shape(ks.shape)[1] == 9
    assert pos.sharding.shard_shape(pos.shape)[1] == 9


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_seq_sharded_int8_decode_matches_unsharded(eight_devices):
    """Greedy int8-KV decode over a seq/model-sharded mesh reproduces the
    unsharded int8 decode exactly."""
    from seldon_core_tpu.parallel.mesh import make_mesh

    base = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=6,
        len_buckets=(32,), batch_buckets=(1,), temperature=0.0, seed=3,
        kv_cache_dtype="int8",
    )
    base.load()
    mesh = make_mesh({"data": 1, "seq": 4, "model": 2}, eight_devices)
    sharded = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=6,
        len_buckets=(32,), batch_buckets=(1,), temperature=0.0, seed=3,
        mesh=mesh, kv_cache_dtype="int8",
    )
    sharded.load()
    prompt = np.random.default_rng(11).integers(1, 255, size=20).tolist()
    want = base.generate([prompt], max_new_tokens=6)["tokens"][0]
    got = sharded.generate([prompt], max_new_tokens=6)["tokens"][0]
    assert got == want


# --------------------------------------------------------------- metrics
def test_llm_stats_and_metrics_sync(int8_server):
    from seldon_core_tpu.metrics.registry import MetricsRegistry

    int8_server.generate([[5, 9, 17]], max_new_tokens=4)
    stats = int8_server.llm_stats()
    assert stats["kv_cache_dtype"] == "int8"
    assert stats["kv_bytes_per_step"] > 0
    assert stats["decode_step_times_s"]  # pending observations drained here

    reg = MetricsRegistry(deployment="d", predictor="p")
    int8_server.generate([[5, 9, 17]], max_new_tokens=4)
    reg.sync_llm(int8_server)
    text = reg.expose().decode()
    assert "seldon_llm_kv_bytes_per_step" in text
    assert "seldon_llm_kv_cache_occupancy" in text
    assert 'seldon_llm_decode_step_seconds_count{deployment_name="d"' in text
    # a second scrape with no new decodes keeps the histogram count stable
    count_line = [l for l in text.splitlines()
                  if l.startswith("seldon_llm_decode_step_seconds_count")][0]
    reg.sync_llm(int8_server)
    text2 = reg.expose().decode()
    assert count_line in text2


def test_metrics_endpoint_exposes_kv_gauges():
    """The /metrics REST handler syncs llm stats for generate-capable
    components."""
    from seldon_core_tpu.transport.rest import make_component_app

    s = make_server()
    s.generate([[1, 2, 3]], max_new_tokens=3)
    app = make_component_app(s)

    async def scrape():
        from aiohttp.test_utils import TestClient, TestServer

        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/metrics")
            return await resp.text()

    body = asyncio.run(scrape())
    assert "seldon_llm_kv_cache_bytes" in body
    assert "seldon_llm_decode_step_seconds" in body
