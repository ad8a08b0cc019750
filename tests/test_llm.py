"""LLM serving correctness: ring attention parity, KV-cache decode vs full
recompute (including ragged batches under right-padding), and the LLMServer
component surface."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import get_model
from seldon_core_tpu.ops.ring_attention import ring_attention
from seldon_core_tpu.parallel.mesh import make_mesh
from seldon_core_tpu.servers.llmserver import ByteTokenizer, LLMServer, _bucket


# ------------------------------------------------------------ ring attention
def full_attention(q, k, v, pos, causal=True):
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d**-0.5
    if causal:
        mask = pos[:, None, None, :] <= pos[:, None, :, None]
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    b, s, h, d = 2, 16, 4, 8
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return mk(), mk(), mk(), pos


def test_ring_attention_matches_full(eight_devices, qkv):
    q, k, v, pos = qkv
    mesh = make_mesh({"data": 2, "seq": 4}, eight_devices)
    ref = full_attention(q, k, v, pos)
    out = jax.jit(lambda *a: ring_attention(*a, mesh=mesh))(q, k, v, pos, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_noncausal(eight_devices, qkv):
    q, k, v, pos = qkv
    mesh = make_mesh({"seq": 8}, eight_devices)
    ref = full_attention(q, k, v, pos, causal=False)
    out = ring_attention(q, k, v, pos, pos, mesh=mesh, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_gradients(eight_devices, qkv):
    q, k, v, pos = qkv
    mesh = make_mesh({"data": 2, "seq": 4}, eight_devices)
    g_ref = jax.grad(lambda q: full_attention(q, k, v, pos).sum())(q)
    g_ring = jax.grad(lambda q: ring_attention(q, k, v, pos, pos, mesh=mesh).sum())(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=1e-4)


def test_ring_attention_gqa_unrepeated_kv(eight_devices):
    """KV with fewer heads than Q rides the ring unrepeated; result matches
    dense attention over repeated KV."""
    rng = np.random.default_rng(2)
    b, s, h, hk, d = 2, 16, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    ref = full_attention(q, jnp.repeat(k, h // hk, 2), jnp.repeat(v, h // hk, 2), pos)
    mesh = make_mesh({"data": 2, "seq": 4}, eight_devices)
    out = ring_attention(q, k, v, pos, pos, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_no_mesh_fallback(qkv):
    q, k, v, pos = qkv
    ref = full_attention(q, k, v, pos)
    out = ring_attention(q, k, v, pos, pos, mesh=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_transformer_ring_matches_full(eight_devices):
    """Same params, attention_impl full vs ring on a seq-sharded mesh."""
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2}, eight_devices)
    full = get_model("llama-tiny")
    ring = get_model("llama-tiny", attention_impl="ring", mesh=mesh)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 255, (2, 16)), jnp.int32)
    variables = full.init(jax.random.PRNGKey(0), tokens)
    ref, _ = full.apply(variables, tokens)
    with mesh:
        out, _ = jax.jit(lambda v, t: ring.apply(v, t))(variables, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- LLM server
@pytest.fixture(scope="module")
def server():
    s = LLMServer(
        model="llama-tiny",
        init_random=True,
        max_new_tokens=8,
        len_buckets=(16, 32),
        batch_buckets=(1, 4),
        seed=7,
    )
    s.load()
    return s


def naive_greedy(server, prompt_ids, n_new):
    """Reference decode: full forward (no cache) + argmax, one token a time."""
    toks = list(prompt_ids)
    for _ in range(n_new):
        t = jnp.asarray(np.asarray(toks)[None, :], jnp.int32)
        logits, _ = server._module.apply(server._params, t)
        nxt = int(jnp.argmax(logits[0, -1]))
        if nxt == server.eos_id:
            break
        toks.append(nxt)
    return toks[len(prompt_ids):] + ([server.eos_id] if len(toks) - len(prompt_ids) < n_new else [])


def test_greedy_decode_matches_full_recompute(server):
    prompt = [5, 9, 17, 33, 2]
    out = server.generate([prompt], max_new_tokens=6)["tokens"][0]
    ref = naive_greedy(server, prompt, 6)
    ref = [t for t in ref if t != server.eos_id][: len(out)]
    assert out == ref or out == ref[: len(out)], (out, ref)


def test_ragged_batch_matches_single(server):
    """Right-padded ragged batch must reproduce each prompt's solo decode —
    the correctness property of PAD_POS masking."""
    p1, p2 = [5, 9, 17], [40, 3, 22, 8, 11, 60, 2]
    solo1 = server.generate([p1], max_new_tokens=5)["tokens"][0]
    solo2 = server.generate([p2], max_new_tokens=5)["tokens"][0]
    both = server.generate([p1, p2], max_new_tokens=5)["tokens"]
    assert both[0] == solo1
    assert both[1] == solo2


def test_generate_text_roundtrip(server):
    out = server.generate(["hello"], max_new_tokens=4)
    assert isinstance(out["texts"][0], str)
    assert len(out["tokens"][0]) <= 4


def test_sampling_is_seeded(server):
    a = server.generate(["abc"], max_new_tokens=6, temperature=0.9, seed=3)["tokens"]
    b = server.generate(["abc"], max_new_tokens=6, temperature=0.9, seed=3)["tokens"]
    c = server.generate(["abc"], max_new_tokens=6, temperature=0.9, seed=4)["tokens"]
    assert a == b
    assert a != c or len(a[0]) <= 1  # different seed, very likely different path


def test_predict_json_payload(server):
    out = server.predict({"prompts": ["hi", "yo"], "max_new_tokens": 3}, [])
    assert len(out["texts"]) == 2
    assert all(len(t) <= 3 for t in out["tokens"])


def test_predict_str_payload(server):
    out = server.predict("hello world", [])
    assert isinstance(out, str)


def test_predict_token_array_payload(server):
    arr = np.array([[5, 9, 17, -1, -1], [4, 2, 8, 20, 7]], dtype=np.int64)
    out = server.predict(arr, [])
    assert out.shape[0] == 2
    assert out.dtype == np.int64


def test_batch_larger_than_biggest_bucket(server):
    """More prompts than the largest batch bucket: split + merge, same result
    as solo generation."""
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]  # batch_buckets max is 4
    out = server.generate(prompts, max_new_tokens=3)["tokens"]
    assert len(out) == 6
    for p, o in zip(prompts, out):
        assert o == server.generate([p], max_new_tokens=3)["tokens"][0]


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_growing_max_new_tokens_recompiles_prefill(server):
    """Regression: prefill cache keyed without max_len reused undersized KV
    caches, silently truncating attention for longer generations."""
    prompt = [9, 4, 7]
    short = server.generate([prompt], max_new_tokens=2)["tokens"][0]
    long = server.generate([prompt], max_new_tokens=12)["tokens"][0]
    assert long[: len(short)] == short  # greedy prefix property
    ref = naive_greedy(server, prompt, 12)
    ref = [t for t in ref if t != server.eos_id][: len(long)]
    assert long == ref or long == ref[: len(long)]


def test_bucket_helper():
    assert _bucket(3, (4, 8)) == 4
    assert _bucket(9, (4, 8)) == 16  # beyond largest: round up to multiple of it
    assert _bucket(17, (4, 8)) == 24


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    assert tok.decode(tok.encode("héllo")) == "héllo"


# ---------------------------------------------------------------------------
# Sequence-sharded KV-cache serving (long context over the mesh)
# ---------------------------------------------------------------------------

def test_seq_sharded_kv_decode_matches_unsharded(eight_devices):
    """Serving with the KV cache sharded over a 'seq' axis (context larger
    than one device's cache slice: 96-token prompt over 4 shards of <=32
    slots) must reproduce the unsharded greedy decode exactly."""
    base = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=8,
        len_buckets=(96,), batch_buckets=(1, 2), temperature=0.0, seed=3,
    )
    base.load()

    mesh = make_mesh({"data": 1, "seq": 4, "model": 2}, eight_devices)
    sharded = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=8,
        len_buckets=(96,), batch_buckets=(1, 2), temperature=0.0, seed=3,
        mesh=mesh,
    )
    sharded.load()

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 255, size=96).tolist(),
               rng.integers(1, 255, size=70).tolist()]
    want = base.generate(prompts, max_new_tokens=8)["tokens"]
    got = sharded.generate(prompts, max_new_tokens=8)["tokens"]
    assert got == want


def test_seq_sharded_cache_layout(eight_devices):
    """The prefill output cache must actually carry the seq-sharding: each
    (k, v) leaf splits max_len across the 'seq' axis, pos maps alongside."""
    mesh = make_mesh({"data": 1, "seq": 4, "model": 2}, eight_devices)
    s = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=4,
        len_buckets=(32,), batch_buckets=(1,), mesh=mesh,
    )
    s.load()
    prefill = s._get_prefill(1, 32, 36)
    tokens = jnp.zeros((1, 32), jnp.int32)
    positions = jnp.arange(32)[None, :]
    _, caches = prefill(s._params, tokens, positions)
    k0, v0, pos0 = caches[0]
    assert k0.shape == (1, 36, 2, 16)
    assert "seq" in str(k0.sharding.spec), k0.sharding
    # per-device slice holds a quarter of the cache slots
    assert k0.sharding.shard_shape(k0.shape)[1] == 9
    assert pos0.sharding.shard_shape(pos0.shape)[1] == 9


def test_spec_driven_sequence_parallel(eight_devices):
    """sequence_parallel/tensor_parallel as typed unit parameters build the
    serving mesh at load — long-context serving reachable from a CR."""
    s = LLMServer(
        model="llama-tiny", init_random=True, max_new_tokens=4,
        len_buckets=(32,), batch_buckets=(1,),
        sequence_parallel=4, tensor_parallel=2,
    )
    s.load()
    assert dict(s.mesh.shape) == {"data": 1, "seq": 4, "model": 2}
    out = s.generate([[7, 12, 80, 4]], max_new_tokens=4)["tokens"][0]
    assert len(out) <= 4


# ---------------------------------------------------------------------------
# Prefix caching
# ---------------------------------------------------------------------------

def make_servers(**extra):
    base = LLMServer(model="llama-tiny", init_random=True, max_new_tokens=6,
                     len_buckets=(16, 32), batch_buckets=(1,), temperature=0.0,
                     seed=9)
    base.load()
    cached = LLMServer(model="llama-tiny", init_random=True, max_new_tokens=6,
                       len_buckets=(16, 32), batch_buckets=(1,), temperature=0.0,
                       seed=9, prefix_cache_size=4, **extra)
    cached.load()
    return base, cached


@pytest.mark.slow  # tier-1 870s budget: prefix parity also covered by test_kv_cache/test_paged_kv prefix suites; CI unit step unfiltered
def test_prefix_cache_exact_hit_matches_uncached():
    base, cached = make_servers()
    prompt = [5, 9, 17, 33, 2, 7, 40, 3]
    want = base.generate([prompt], max_new_tokens=6)["tokens"][0]
    first = cached.generate([prompt], max_new_tokens=6)["tokens"][0]
    again = cached.generate([prompt], max_new_tokens=6)["tokens"][0]
    assert first == want and again == want
    assert cached._prefix_hits == 1  # second call skipped prefill entirely
    assert cached.tags()["prefix_cache_hits"] == 1


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_prefix_cache_shared_system_prompt():
    """Two prompts sharing a system prefix: the second reuses the prefix KV
    and still decodes exactly like an uncached server."""
    base, cached = make_servers()
    rng = np.random.default_rng(3)
    system = rng.integers(1, 255, size=12).tolist()
    p1 = system + [10, 11, 12]
    p2 = system + [20, 21]

    want1 = base.generate([p1], max_new_tokens=6)["tokens"][0]
    want2 = base.generate([p2], max_new_tokens=6)["tokens"][0]

    # seed the cache with the bare system prefix, then serve both prompts
    cached.generate([system], max_new_tokens=1)
    got1 = cached.generate([p1], max_new_tokens=6)["tokens"][0]
    got2 = cached.generate([p2], max_new_tokens=6)["tokens"][0]
    assert got1 == want1, (got1, want1)
    assert got2 == want2, (got2, want2)
    assert cached._prefix_hits >= 2  # both continuations hit the prefix


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_prefix_cache_lru_eviction():
    _, cached = make_servers()
    cached.prefix_cache_size = 2
    for seed in range(4):
        prompt = np.random.default_rng(seed).integers(1, 255, size=6).tolist()
        cached.generate([prompt], max_new_tokens=1)
    assert len(cached._prefix_cache) <= 2


@pytest.mark.slow  # tier-1 870s budget: prefix edge cases also covered in test_kv_cache/test_paged_kv; CI unit step unfiltered
def test_prefix_cache_off_for_batches():
    _, cached = make_servers()
    # batch requests bypass the cache (nb > 1 would need per-row prefixes)
    cached.batch_buckets = (2,)
    cached.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=2)
    assert len(cached._prefix_cache) == 0


@pytest.mark.slow  # tier-1 870s budget: prefix edge cases also covered in test_kv_cache/test_paged_kv; CI unit step unfiltered
def test_prefix_cache_overlong_prompt():
    """A prompt past the top length bucket must still get a cache that fits
    it (regression: cached-mode max_len could undercut plen)."""
    _, cached = make_servers()
    prompt = np.random.default_rng(5).integers(1, 255, size=40).tolist()
    out = cached.generate([prompt], max_new_tokens=3)["tokens"][0]
    assert len(out) <= 3
    again = cached.generate([prompt], max_new_tokens=3)["tokens"][0]
    assert again == out


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_streamed_quantized_init(monkeypatch):
    """Big-config path: when the f32 init tree would exceed the streaming
    threshold and int8 serving is requested, params are initialized
    leaf-by-leaf already quantized (never materializing the full f32 tree),
    and generate() works end to end. Forced here by dropping the threshold
    to zero on a tiny config."""
    import seldon_core_tpu.servers.llmserver as llmserver_mod
    from seldon_core_tpu.ops.quantize import QuantizedTensor
    from seldon_core_tpu.servers.llmserver import LLMServer

    monkeypatch.setattr(llmserver_mod, "STREAM_INIT_THRESHOLD_BYTES", 0)
    kwargs = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
                  ffn_dim=128, max_seq_len=128)
    server = LLMServer(
        model="transformer", model_kwargs=kwargs, init_random=True,
        max_new_tokens=8, len_buckets=(16,), batch_buckets=(2,),
        temperature=0.0, eos_id=-1, quantize="int8",
    )
    server.load()
    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    leaves = jax.tree.leaves(server._params, is_leaf=is_q)
    n_q = sum(map(is_q, leaves))
    # 7 matmul weights per layer (wq/wk/wv/wo/w1/w2/w3) + embed + head
    assert n_q == 2 + 7 * kwargs["n_layers"]
    # every >=2-D float leaf is quantized; 1-D norm weights are ones
    assert all(is_q(l) or getattr(l, "ndim", 0) <= 1 for l in leaves)
    out = server.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=8)
    assert [len(t) for t in out["tokens"]] == [8, 8]


def test_clear_prefix_cache_resets_byte_accounting():
    """Round-5 7B finding: clearing the OrderedDict directly leaves
    _prefix_bytes at the old total, and once that phantom total nears
    prefix_cache_bytes every later store self-evicts — 0% hits forever.
    The public clear must reset both."""
    from seldon_core_tpu.servers.llmserver import LLMServer

    kw = dict(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
              ffn_dim=64, max_seq_len=96)
    s = LLMServer(model="transformer", model_kwargs=kw, init_random=True,
                  max_new_tokens=4, len_buckets=(16,), batch_buckets=(1,),
                  temperature=0.0, eos_id=-1, seed=0, prefix_cache_size=4)
    s.load()
    s.generate([[5, 9, 11, 2]], max_new_tokens=1)
    entry_bytes = s._prefix_bytes
    assert entry_bytes > 0 and len(s._prefix_cache) == 1
    # budget that fits exactly one entry: any phantom leftover evicts it
    s.prefix_cache_bytes = entry_bytes
    s.clear_prefix_cache()
    assert s._prefix_bytes == 0
    s.generate([[5, 9, 11, 2]], max_new_tokens=1)
    assert len(s._prefix_cache) == 1  # stored, not self-evicted
    s.generate([[5, 9, 11, 2, 7]], max_new_tokens=1)
    assert s._prefix_hits >= 1


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_multi_turn_prefix_cache_e2e():
    """Conversation-shaped e2e (VERDICT r4 #8): turn-2's prompt extends
    turn-1's, the prefix cache must HIT, and the cached generation must be
    token-identical to a cache-less twin. Runs at toy dims on CPU; the 7B
    latency pair on the chip: not measured on today's code."""
    import numpy as np

    from seldon_core_tpu.servers.llmserver import LLMServer

    kw = dict(vocab_size=128, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
              ffn_dim=64, max_seq_len=256)
    base = dict(model="transformer", model_kwargs=kw, init_random=True,
                max_new_tokens=8, len_buckets=(16, 32, 64), batch_buckets=(1,),
                temperature=0.0, eos_id=-1, seed=5)
    cached = LLMServer(prefix_cache_size=4, **base)
    plain = LLMServer(**base)
    cached.load()
    plain.load()

    rng = np.random.default_rng(2)
    turn1 = rng.integers(1, 127, size=16).tolist()
    ans_cached = cached.generate([turn1])["tokens"][0]
    ans_plain = plain.generate([turn1])["tokens"][0]
    assert ans_cached == ans_plain

    follow = rng.integers(1, 127, size=8).tolist()
    turn2 = turn1 + ans_cached + follow
    out_cached = cached.generate([turn2])["tokens"][0]
    out_plain = plain.generate([turn2])["tokens"][0]
    assert cached._prefix_hits >= 1  # turn-2 reused turn-1's KV
    assert out_cached == out_plain  # cache changes cost, never tokens

    # turn 3 extends turn 2 — the conversation keeps hitting
    hits_before = cached._prefix_hits
    turn3 = turn2 + out_cached + rng.integers(1, 127, size=8).tolist()
    out3_cached = cached.generate([turn3])["tokens"][0]
    out3_plain = plain.generate([turn3])["tokens"][0]
    assert cached._prefix_hits > hits_before
    assert out3_cached == out3_plain
