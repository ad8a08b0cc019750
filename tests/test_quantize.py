"""Int8 weight-only PTQ: round-trip accuracy, footprint, serving path
through JAXServer + engine, and the spec-reachable `quantize` parameter."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import get_model
from seldon_core_tpu.ops.quantize import (
    QuantizedTensor,
    dequantize_params,
    quantize_params,
    quantized_bytes,
)


def run(coro):
    return asyncio.run(coro)


def test_quantize_round_trip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.2, size=(64, 128)).astype(np.float32))
    qp = quantize_params({"w": w})
    assert isinstance(qp["w"], QuantizedTensor)
    assert qp["w"].q.dtype == jnp.int8
    back = dequantize_params(qp)["w"]
    assert back.dtype == jnp.float32  # restores the original dtype
    # symmetric per-channel int8: worst-case error is half a quantization step
    step = np.abs(np.asarray(w)).max(axis=0) / 127
    err = np.abs(np.asarray(back) - np.asarray(w))
    assert (err <= step[None, :] * 0.5 + 1e-7).all()


def test_non_matrix_leaves_pass_through():
    params = {
        "kernel": jnp.ones((8, 4)),
        "bias": jnp.ones((4,)),       # 1-D: precision-critical, skipped
        "step": jnp.asarray(3, jnp.int32),  # integer: skipped
    }
    qp = quantize_params(params)
    assert isinstance(qp["kernel"], QuantizedTensor)
    assert not isinstance(qp["bias"], QuantizedTensor)
    assert not isinstance(qp["step"], QuantizedTensor)
    # footprint: the 8x4 f32 kernel (128B) became int8 (32B) + 4 f32 scales
    assert quantized_bytes(qp) < quantized_bytes(params)


def test_quantized_forward_close_and_argmax_stable():
    """Model-level check: int8 weights keep logits close enough that the
    predicted class never flips on well-separated inputs."""
    model = get_model("mlp", features=[64, 32], num_classes=5, dtype="float32")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 10)).astype(np.float32))
    params = model.init(jax.random.PRNGKey(0), x)

    ref = model.apply(params, x)
    qp = quantize_params(params)

    @jax.jit
    def fwd(qp, x):
        return model.apply(dequantize_params(qp), x)

    got = np.asarray(fwd(qp, x))
    ref = np.asarray(ref)
    np.testing.assert_allclose(got, ref, atol=0.02)
    # argmax must hold wherever the reference margin exceeds the noise floor
    # (a random-init model has near-tie rows where any epsilon flips it)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 0.04
    assert decided.any()
    assert (np.argmax(got[decided], -1) == np.argmax(ref[decided], -1)).all()


def test_jaxserver_int8_through_engine(tmp_path):
    from seldon_core_tpu.contracts.graph import PredictorSpec
    from seldon_core_tpu.contracts.payload import SeldonError, SeldonMessage
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.servers.jaxserver import JAXServer, export_checkpoint

    model = get_model("mlp", features=[32], num_classes=3, dtype="float32")
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4)))
    ckpt = export_checkpoint(
        str(tmp_path / "ckpt"), model="mlp",
        kwargs={"features": [32], "num_classes": 3, "dtype": "float32"},
        params=params, input_shape=[4], use_orbax=False,
    )
    spec = PredictorSpec.from_dict({
        "name": "p",
        "graph": {"name": "m", "type": "MODEL", "implementation": "JAX_SERVER",
                  "modelUri": ckpt,
                  "parameters": [{"name": "quantize", "value": "int8", "type": "STRING"}]},
    })
    engine = GraphEngine(spec)
    server = engine.state.root.component
    from seldon_core_tpu.ops.quantize import QuantizedTensor as QT

    n_quant = sum(isinstance(l, QT) for l in
                  jax.tree.flatten(server._params, is_leaf=lambda x: isinstance(x, QT))[0])
    assert n_quant >= 2  # both dense kernels

    msg = SeldonMessage.from_dict({"data": {"tensor": {"shape": [2, 4], "values": [0.5] * 8}}})
    out = run(engine.predict(msg)).to_dict()
    probs = np.asarray(out["data"]["tensor"]["values"]).reshape(2, 3)
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-3)

    # int8 composes with a mesh now (the old exclusion is lifted; an
    # axis-less model like this MLP just replicates) — only bad quantize
    # values fail
    JAXServer(model_uri=ckpt, quantize="int8", tensor_parallel=2).load()
    with pytest.raises(SeldonError, match="int8 only"):
        JAXServer(model_uri=ckpt, quantize="int4").load()


def test_bfloat16_checkpoint_quantizes():
    """bf16 is the primary serving dtype: its leaves MUST quantize (numpy
    classifies bfloat16 as void, which silently skipped them before)."""
    w = jnp.asarray(np.random.default_rng(2).normal(size=(16, 8)), jnp.bfloat16)
    qp = quantize_params({"w": w})
    assert isinstance(qp["w"], QuantizedTensor)
    back = dequantize_params(qp)["w"]
    assert back.dtype == jnp.bfloat16
    err = np.abs(np.asarray(back, np.float32) - np.asarray(w, np.float32))
    assert err.max() < 0.05


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_llmserver_int8_generates():
    """Quantized LLM decode: int8 weights through prefill + scan decode;
    greedy output stays close to the fp32 server (same seed/params)."""
    from seldon_core_tpu.servers.llmserver import LLMServer

    base = LLMServer(model="llama-tiny", init_random=True, max_new_tokens=6,
                     len_buckets=(16,), batch_buckets=(1,), temperature=0.0, seed=5)
    base.load()
    quant = LLMServer(model="llama-tiny", init_random=True, max_new_tokens=6,
                      len_buckets=(16,), batch_buckets=(1,), temperature=0.0, seed=5,
                      quantize="int8")
    quant.load()

    from seldon_core_tpu.ops.quantize import QuantizedTensor as QT

    n_quant = sum(isinstance(l, QT) for l in
                  jax.tree.flatten(quant._params, is_leaf=lambda x: isinstance(x, QT))[0])
    assert n_quant > 0

    prompt = [5, 9, 17, 33, 2, 7]
    out_q = quant.generate([prompt], max_new_tokens=6)["tokens"][0]
    assert all(0 <= t < 256 for t in out_q)

    # robust numeric check: prefill logits of the quantized path stay within
    # the int8 noise floor of the fp32 path (token-exact greedy agreement
    # would hinge on near-tie argmaxes of a random-init model)
    import jax.numpy as jnp

    tokens = jnp.asarray([prompt], jnp.int32)
    positions = jnp.arange(len(prompt))[None, :]
    pf_f = base._get_prefill(1, len(prompt), 16)
    pf_q = quant._get_prefill(1, len(prompt), 16)
    logits_f, _ = pf_f(base._params, tokens, positions)
    logits_q, _ = pf_q(quant._params, tokens, positions)
    err = np.abs(np.asarray(logits_q, np.float32) - np.asarray(logits_f, np.float32))
    assert err.max() < 0.15, err.max()


def test_shard_params_quantized_leaves(eight_devices):
    """int8 + TP compose (VERDICT r2 item 4): shard_params places q under
    the weight's logical spec and scale [C] under the channel (last) axis,
    and dequantizing the sharded tree reproduces the unsharded dequant."""
    import jax

    from seldon_core_tpu.ops.quantize import QuantizedTensor as QT
    from seldon_core_tpu.parallel.mesh import make_mesh
    from seldon_core_tpu.parallel.sharding import shard_params

    mesh = make_mesh({"data": 2, "model": 4})
    rng = np.random.default_rng(0)
    params = {"params": {
        "w_col": rng.standard_normal((16, 8)).astype(np.float32),  # shard C
        "w_row": rng.standard_normal((8, 16)).astype(np.float32),  # shard rows
        "bias": rng.standard_normal((8,)).astype(np.float32),      # passthrough
    }}
    logical = {"w_col": ("embed", "mlp"), "w_row": ("mlp", "embed"),
               "bias": ("embed",)}
    # default rules map 'mlp'->'model', 'embed'->None (replicated)
    qp = quantize_params(params)
    sharded = shard_params(qp, mesh, {"params": logical})

    w_col = sharded["params"]["w_col"]
    w_row = sharded["params"]["w_row"]
    assert isinstance(w_col, QT) and isinstance(w_row, QT)
    # w_col: channel dim sharded over 'model' -> q shard [16, 2], scale [2]
    assert w_col.q.sharding.shard_shape(w_col.q.shape) == (16, 2)
    assert w_col.scale.sharding.shard_shape(w_col.scale.shape) == (2,)
    # w_row: leading dim sharded -> scale replicated (channel dim unsharded)
    assert w_row.q.sharding.shard_shape(w_row.q.shape) == (2, 16)
    assert w_row.scale.sharding.shard_shape(w_row.scale.shape) == (16,)

    back = dequantize_params(sharded)
    want = dequantize_params(qp)
    for k in ("w_col", "w_row"):
        np.testing.assert_allclose(np.asarray(back["params"][k]),
                                   np.asarray(want["params"][k]), rtol=0, atol=0)


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_llmserver_int8_with_mesh_generates(eight_devices):
    """int8 LLM decode under a ('data','seq','model') mesh: loads, shards
    quantized leaves, and generates greedily with bounded drift vs the
    unsharded int8 path."""
    from seldon_core_tpu.servers.llmserver import LLMServer

    base = LLMServer(model="llama-tiny", init_random=True, max_new_tokens=4,
                     len_buckets=(16,), batch_buckets=(1,), temperature=0.0,
                     seed=5, quantize="int8")
    base.load()
    tp = LLMServer(model="llama-tiny", init_random=True, max_new_tokens=4,
                   len_buckets=(16,), batch_buckets=(1,), temperature=0.0,
                   seed=5, quantize="int8", tensor_parallel=2)
    tp.load()
    assert dict(tp.mesh.shape).get("model") == 2

    prompt = [5, 9, 17, 33, 2, 7]
    out_base = base.generate([prompt], max_new_tokens=4)["tokens"][0]
    out_tp = tp.generate([prompt], max_new_tokens=4)["tokens"][0]
    # same compiled math up to GSPMD reduction order; greedy tokens of a
    # random-init model can tie-break differently, so compare logits
    import jax.numpy as jnp

    tokens = jnp.asarray([prompt], jnp.int32)
    positions = jnp.arange(len(prompt))[None, :]
    lf, _ = base._get_prefill(1, len(prompt), 16)(base._params, tokens, positions)
    lq, _ = tp._get_prefill(1, len(prompt), 16)(tp._params, tokens, positions)
    err = np.abs(np.asarray(lq, np.float32) - np.asarray(lf, np.float32))
    assert err.max() < 1e-3, err.max()
    assert len(out_base) <= 4 and len(out_tp) <= 4


# ---------------------------------------------------------------------------
# stacks of matrices (expert stacks [e, d, f]): a scale per matrix per channel
# ---------------------------------------------------------------------------

def test_stack_keeps_a_scale_per_matrix():
    """Two experts of very different magnitude each keep their own error
    bound (half a step of THEIR largest weight, per channel): under one
    shared scale the small one would round to nothing."""
    rng = np.random.default_rng(1)
    big = rng.normal(0, 1.0, size=(32, 48)).astype(np.float32)
    small = rng.normal(0, 1e-3, size=(32, 48)).astype(np.float32)
    qt = quantize_params({"w": jnp.asarray(np.stack([big, small]))})["w"]
    assert qt.q.shape == (2, 32, 48) and qt.scale.shape == (2, 48) and qt.stacked
    back = np.asarray(dequantize_params({"w": qt})["w"])
    for e, w in enumerate((big, small)):
        step = np.abs(w).max(axis=0) / 127.0
        assert (np.abs(back[e] - w) <= step / 2 + 1e-9).all()
    assert np.abs(back[1] - small).max() < 3e-5       # not the big one's 1e-2
    # keep_consumed: the stack stays int8 for a consumer that takes it so
    kept = dequantize_params({"w": qt, "m": quantize_params({"m": jnp.asarray(big)})["m"]},
                             keep_consumed=True)
    assert isinstance(kept["w"], QuantizedTensor) and kept["m"].dtype == jnp.float32


def test_matrix_quantization_is_what_it_was():
    """2-D leaves are untouched by the stack rule, bit for bit: the scale is
    max|w| / 127 over the rows, [channels], and the int8 values follow."""
    rng = np.random.default_rng(2)
    w = rng.normal(0, 0.3, size=(40, 24)).astype(np.float32)
    qt = quantize_params({"w": jnp.asarray(w)})["w"]
    scale = np.abs(w).max(axis=0) / 127.0
    assert qt.scale.shape == (24,) and not qt.stacked
    assert np.array_equal(np.asarray(qt.scale), scale.astype(np.float32))
    assert np.array_equal(np.asarray(qt.q),
                          np.clip(np.round(w / scale.astype(np.float32)), -128, 127))


def test_streamed_init_gives_each_expert_its_own_fan_in(monkeypatch):
    """The streamed random init (the 7B path) draws an expert stack
    [e, d, f] with std 1/sqrt(d), not 1/sqrt(e x d): counted over the stack,
    every expert's output is sqrt(e) too small and a wrong expert layer
    passes any comparison of logits unseen. A 2-D leaf's draw is unchanged
    (same key, same std: the dense cells' weights stay the same per seed)."""
    import seldon_core_tpu.servers.llmserver as llmserver_mod
    from seldon_core_tpu.servers.llmserver import LLMServer

    monkeypatch.setattr(llmserver_mod, "STREAM_INIT_THRESHOLD_BYTES", 0)
    kwargs = dict(vocab_size=64, dim=256, n_layers=1, n_heads=4, n_kv_heads=4,
                  ffn_dim=96, max_seq_len=32, n_experts=8, n_experts_per_token=2)
    server = LLMServer(model="transformer", model_kwargs=kwargs, init_random=True,
                       quantize="int8", seed=5, len_buckets=(16,))
    server.load()
    layer = dequantize_params(server._params)["params"]["layer_0"]
    w1 = np.asarray(layer["moe"]["w1"], np.float32)          # [8, 256, 96]
    assert abs(w1.std() * np.sqrt(256) - 1.0) < 0.02
    assert all(abs(w1[e].std() * np.sqrt(256) - 1.0) < 0.05 for e in range(8))
    w2 = np.asarray(layer["moe"]["w2"], np.float32)          # [8, 96, 256]
    assert abs(w2.std() * np.sqrt(96) - 1.0) < 0.02
    wq = np.asarray(layer["attention"]["wq"], np.float32)    # [256, 256]
    assert abs(wq.std() * np.sqrt(256) - 1.0) < 0.02
    # the 2-D draw, re-made here by the rule the docstring states
    import zlib

    key = jax.random.fold_in(
        jax.random.PRNGKey(5),
        zlib.crc32(b"['params']['layer_0']['attention']['wq']") & 0x7FFFFFFF)
    drawn = jax.random.normal(key, (256, 256), jnp.float32) / 16.0
    want = quantize_params({"w": drawn}, out_major={"w": True})["w"]
    got = server._params["params"]["layer_0"]["attention"]["wq"]
    assert got.out_major  # held in the order its consumer reads (same values)
    # (the server draws and quantizes inside one jit: the scale may differ
    # from this eager one in its last bit, and a value on a rounding edge by 1)
    np.testing.assert_allclose(np.asarray(got.scale), np.asarray(want.scale), rtol=1e-6)
    off = np.asarray(got.q, np.int32) - np.asarray(want.q, np.int32)
    assert np.abs(off).max() <= 1 and np.mean(off != 0) < 1e-3


# ---------------------------------------------------------------------------
# leaves held output-major (the q/k/v projections): the same matrix, the same
# scales, the int8 bytes in the order the consumer reads them
# ---------------------------------------------------------------------------

TINY = dict(vocab_size=96, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
            ffn_dim=64, max_seq_len=96)
HELD_OUT_MAJOR = ("wq", "wk", "wv")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(40, 24), (32, 32), (24, 40)])
def test_out_major_leaf_is_the_same_matrix(shape, dtype):
    """Only the order of the bytes changes: q is the transpose, the scales are
    the same array, every dequantized value is the same bit for bit (in the
    stored dtype and in another), and the reported size is the same."""
    from seldon_core_tpu.ops.quantize import dequantize_array, quantize_array, quantized_bytes

    w = jnp.asarray(np.random.default_rng(3).normal(0, 0.3, size=shape), dtype)
    plain, held = quantize_array(w), quantize_array(w, out_major=True)
    assert held.out_major and not plain.out_major
    assert held.q.shape == shape[::-1] and held.shape == plain.shape == shape
    assert np.array_equal(np.asarray(held.q), np.asarray(plain.q).T)
    assert np.array_equal(np.asarray(held.scale), np.asarray(plain.scale))
    for to in (None, jnp.bfloat16, jnp.float32):
        a, b = dequantize_array(plain, to), dequantize_array(held, to)
        assert a.shape == b.shape == shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert quantized_bytes({"w": held}) == quantized_bytes({"w": plain})
    # static metadata: the orientation survives a jit boundary and a tree map
    back = jax.jit(lambda t: t)(held)
    assert back.out_major and jax.tree.map(lambda x: x, held).out_major
    with pytest.raises(ValueError):
        quantize_array(jnp.zeros((2, 4, 4)), out_major=True)


def test_out_major_is_read_off_the_logical_axes():
    """Which leaves: those whose OUTPUT axis is split into attention heads
    (wq, wk, wv), by the module's own axis names; the projections that a plain
    matmul consumes (wo, the FFN, the head, the embedding) stay as they were,
    and so does everything for a module that names no axes."""
    from seldon_core_tpu.parallel.sharding import head_split_outputs, logical_axis_tree

    module = get_model("transformer", qk_norm=True, **TINY)
    example = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    chosen = head_split_outputs(shapes, logical_axis_tree(module, example))
    flat = {jax.tree_util.keystr(path): v
            for path, v in jax.tree_util.tree_flatten_with_path(chosen)[0]}
    assert len(flat) == len(jax.tree.leaves(shapes))
    assert {k for k, v in flat.items() if v} == {
        f"['params']['layer_{i}']['attention']['{w}']"
        for i in range(TINY["n_layers"]) for w in HELD_OUT_MAJOR}
    assert not any(jax.tree.leaves(head_split_outputs(shapes, None)))


def _int8_server(**kwargs):
    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(model="transformer", model_kwargs=dict(TINY, qk_norm=True),
                       init_random=True, quantize="int8", seed=5, len_buckets=(16,),
                       batch_buckets=(1,), temperature=0.0, eos_id=-1, **kwargs)
    server.load()
    return server


def test_checkpoint_and_streamed_init_hold_the_same_tree(monkeypatch, caplog):
    """The two ways a served int8 tree is made, the streamed random init (the
    7B cells) and quantize_params on a checkpoint's floats, give the same
    tree for the same floats: same structure and orientation per leaf, same
    int8 values, same scales. And the load log says how many leaves are held
    output-major."""
    import logging

    import seldon_core_tpu.servers.llmserver as llmserver_mod
    from seldon_core_tpu.parallel.sharding import head_split_outputs, row_lookups

    monkeypatch.setattr(llmserver_mod, "STREAM_INIT_THRESHOLD_BYTES", 0)
    with caplog.at_level(logging.INFO, logger="seldon_core_tpu.servers.llmserver"):
        streamed = _int8_server()
    assert "6 leaves held output-major" in caplog.text  # 3 a layer, 2 layers
    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    floats = dequantize_params(streamed._params)  # the checkpoint these weights would be
    from_checkpoint = quantize_params(
        floats, out_major=head_split_outputs(floats, streamed._logical_axes()),
        lookup=row_lookups(floats, streamed._logical_axes()))
    assert (jax.tree.structure(from_checkpoint) == jax.tree.structure(streamed._params))
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(streamed._params, is_leaf=is_q)[0],
            jax.tree.leaves(from_checkpoint, is_leaf=is_q)):
        name = jax.tree_util.keystr(path)
        if not is_q(a):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
            continue
        assert a.out_major == b.out_major == (path[-1].key in HELD_OUT_MAJOR), name
        assert a.lookup == b.lookup == (path[-1].key == "tok_embeddings"), name
        assert np.array_equal(np.asarray(a.q), np.asarray(b.q)), name
        np.testing.assert_allclose(np.asarray(a.scale), np.asarray(b.scale), rtol=1e-6)


def test_served_logits_equal_the_float_orientation_paths():
    """Prefill then decode through the served programs, with the q/k/v leaves
    held output-major (as load() makes them) and with the same leaves held
    [in, out] as before: the same logits, the same greedy tokens."""
    from seldon_core_tpu.ops.quantize import dequantize_array, quantize_array

    server = _int8_server(max_new_tokens=6)
    attention = server._params["params"]["layer_0"]["attention"]
    assert all(attention[w].out_major for w in HELD_OUT_MAJOR)
    assert not attention["wo"].out_major
    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    as_before = jax.tree.map(
        lambda t: quantize_array(dequantize_array(t)) if is_q(t) and t.out_major else t,
        server._params, is_leaf=is_q)
    assert not any(t.out_major for t in jax.tree.leaves(as_before, is_leaf=is_q) if is_q(t))

    prompt = [5, 9, 17, 33, 2, 7]
    tokens = jnp.asarray([prompt], jnp.int32)
    positions = jnp.arange(len(prompt))[None, :]
    prefill = server._get_prefill(1, len(prompt), 16)
    got, got_cache = prefill(server._params, tokens, positions)
    want, want_cache = prefill(as_before, tokens, positions)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=0, atol=1e-5)
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=0, atol=1e-5)
    out = server.generate([prompt], max_new_tokens=6)["tokens"][0]
    server._params = as_before
    assert server.generate([prompt], max_new_tokens=6)["tokens"][0] == out


def test_shard_params_follows_the_held_orientation(eight_devices):
    """Under tensor parallelism a leaf held output-major is split along the
    SAME logical axis as before: q [out, in] takes the float leaf's spec
    reversed, the scale [out] the channel axis, and the sharded tree
    dequantizes to the unsharded one's values."""
    from seldon_core_tpu.parallel.mesh import make_mesh
    from seldon_core_tpu.parallel.sharding import head_split_outputs, shard_params

    mesh = make_mesh({"data": 2, "model": 4})
    rng = np.random.default_rng(0)
    params = {"params": {
        "wq": rng.standard_normal((16, 32)).astype(np.float32),   # heads over 'model'
        "wk": rng.standard_normal((16, 8)).astype(np.float32),
        "wo": rng.standard_normal((32, 16)).astype(np.float32),
    }}
    logical = {"params": {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
                          "wo": ("heads", "embed")}}
    chosen = head_split_outputs(params, logical)
    assert chosen == {"params": {"wq": True, "wk": True, "wo": False}}
    qp = quantize_params(params, out_major=chosen)
    sharded = shard_params(qp, mesh, logical)
    wq, wk, wo = (sharded["params"][k] for k in ("wq", "wk", "wo"))
    assert wq.out_major and wk.out_major and not wo.out_major
    # wq held [32, 16]: the heads axis (now the first) over 'model' (4)
    assert wq.q.sharding.shard_shape(wq.q.shape) == (8, 16)
    assert wq.scale.sharding.shard_shape(wq.scale.shape) == (8,)
    assert wk.q.sharding.shard_shape(wk.q.shape) == (2, 16)
    assert wk.scale.sharding.shard_shape(wk.scale.shape) == (2,)
    # wo as it was: rows (heads) over 'model', channel scale replicated
    assert wo.q.sharding.shard_shape(wo.q.shape) == (8, 16)
    assert wo.scale.sharding.shard_shape(wo.scale.shape) == (16,)
    back, want = dequantize_params(sharded), dequantize_params(qp)
    for k in ("wq", "wk", "wo"):
        assert back["params"][k].shape == params["params"][k].shape
        np.testing.assert_allclose(np.asarray(back["params"][k]),
                                   np.asarray(want["params"][k]), rtol=0, atol=0)


def test_int8_dense_takes_an_out_major_leaf():
    """The explicit kernel's wrapper reads the container, not its bytes."""
    from seldon_core_tpu.ops.pallas_int8 import int8_dense
    from seldon_core_tpu.ops.quantize import quantize_array

    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(0, 0.3, size=(128, 256)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1.0, size=(8, 128)), jnp.float32)
    a = int8_dense(x, quantize_array(w))
    b = int8_dense(x, quantize_array(w, out_major=True))
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# a table whose rows are looked up (the token embeddings): it reaches the module
# int8, the int8 rows are gathered and those alone dequantized; the same values
# as the rows of the dequantized table, to the bit
# ---------------------------------------------------------------------------

def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("held", ["bfloat16", "float32"])
def test_gathered_rows_equal_the_dequantized_tables_rows(held, dtype):
    """Gather-then-dequantize is dequantize-then-gather bit for bit, whatever
    dtype the table was quantized from and whatever the module computes in:
    repeated tokens, the first and the last row, a [b, s] batch; eagerly and
    inside a jit; and a floating table is indexed as it always was."""
    from seldon_core_tpu.ops.quantize import dequantize_array, lookup_rows, quantize_array

    vocab, dim = 97, 48
    w = jnp.asarray(np.random.default_rng(7).normal(0, 0.3, size=(vocab, dim)), held)
    table = quantize_array(w, lookup=True)
    assert table.lookup and table.consumed_int8 and not table.stacked and not table.out_major
    assert table.q.shape == (vocab, dim) and table.scale.shape == (dim,)
    assert jax.jit(lambda t: t)(table).lookup and jax.tree.map(lambda x: x, table).lookup
    def whole_table(t, index):       # the parent's expression
        return dequantize_array(t).astype(dtype)[index]

    def gathered(t, index):
        return lookup_rows(t, index, dtype)

    for index in ([3, 3, 3, 3], [0], [vocab - 1], [0, vocab - 1, 0, vocab - 1, 41],
                  [[5, 9, 9, 0], [vocab - 1, 1, 5, 5]]):
        index = jnp.asarray(index, jnp.int32)
        # like with like: inside a jit XLA may keep the product's excess
        # precision through the cast on both sides, which no eager op does
        for run in (lambda f: f, jax.jit):
            got, want = run(gathered)(table, index), run(whole_table)(table, index)
            assert got.shape == index.shape + (dim,) and got.dtype == want.dtype == jnp.dtype(dtype)
            assert np.array_equal(_bits(got), _bits(want))
        assert np.array_equal(_bits(lookup_rows(w, index, dtype)), _bits(w.astype(dtype)[index]))
    with pytest.raises(ValueError):
        quantize_array(jnp.zeros((2, 4, 4)), lookup=True)
    with pytest.raises(ValueError):
        quantize_array(w, out_major=True, lookup=True)


def test_the_lookup_table_is_read_off_the_logical_axes():
    """Which leaf: the one whose FIRST axis is the vocabulary (rows that token
    ids index), by the module's own axis names: the embeddings, not the head
    (``[embed, vocab]``, a matmul) and no leaf of a module that names no axes.
    ``dequantize_params(keep_consumed=True)`` keeps it and the stacks int8 and
    dequantizes the rest; without the flag everything comes back floating."""
    from seldon_core_tpu.parallel.sharding import logical_axis_tree, row_lookups

    module = get_model("transformer", n_experts=4, n_experts_per_token=2, **TINY)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    axes = logical_axis_tree(module, jax.ShapeDtypeStruct((1, 8), jnp.int32))
    chosen = row_lookups(shapes, axes)
    flat = {jax.tree_util.keystr(path): v
            for path, v in jax.tree_util.tree_flatten_with_path(chosen)[0]}
    assert len(flat) == len(jax.tree.leaves(shapes))
    assert {k for k, v in flat.items() if v} == {"['params']['tok_embeddings']"}
    assert not any(jax.tree.leaves(row_lookups(shapes, None)))

    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    held = quantize_params(params, lookup=row_lookups(params, axes))
    kept = dequantize_params(held, keep_consumed=True)["params"]
    assert isinstance(kept["tok_embeddings"], QuantizedTensor) and kept["tok_embeddings"].lookup
    assert isinstance(kept["layer_0"]["moe"]["w1"], QuantizedTensor)
    assert not isinstance(kept["lm_head"], QuantizedTensor)
    assert not isinstance(kept["layer_0"]["attention"]["wo"], QuantizedTensor)
    assert not any(isinstance(leaf, QuantizedTensor) for leaf in jax.tree.leaves(
        dequantize_params(held), is_leaf=lambda x: isinstance(x, QuantizedTensor)))


@pytest.mark.parametrize("case,extra", [
    ("untied", {}), ("tied", {"tie_embeddings": True}), ("mtp", {"mtp_layers": 1}),
    ("tied-mtp", {"tie_embeddings": True, "mtp_layers": 1})])
def test_served_logits_equal_the_whole_table_dequants(case, extra):
    """A quantized LLMServer's prefill then decode, the table gathered int8 (as
    load() marks it), against the parent's expression on the same tree (the
    table dequantized whole ahead of the module, then indexed): the same
    logits and the same caches exactly, with an untied head, a tied one (which
    dequantizes the table for its matmul inside the module) and, cache-less,
    the MTP module's second lookup, before an untied head and before a tied one
    (the table transposed and dequantized where the MTP's rows multiply by it)."""
    import dataclasses

    from seldon_core_tpu.servers.llmserver import LLMServer

    server = LLMServer(model="transformer", model_kwargs=dict(TINY, **extra),
                       init_random=True, quantize="int8", seed=5, len_buckets=(16,),
                       batch_buckets=(1,), temperature=0.0, eos_id=-1, max_new_tokens=6)
    server.load()
    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    table = server._params["params"]["tok_embeddings"]
    assert table.lookup and ("lm_head" in server._params["params"]) == ("tie_embeddings" not in extra)
    assert is_q(server._dequant(server._params)["params"]["tok_embeddings"])
    as_before = jax.tree.map(
        lambda t: dataclasses.replace(t, lookup=False) if is_q(t) else t,
        server._params, is_leaf=is_q)
    assert not is_q(server._dequant(as_before)["params"]["tok_embeddings"])

    def same(got, want):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))

    vocab = TINY["vocab_size"]
    prompt = [5, 9, 9, 0, vocab - 1, 7]     # a repeat, the first row, the last
    tokens = jnp.asarray([prompt], jnp.int32)
    positions = jnp.arange(len(prompt))[None, :]
    prefill = server._get_prefill(1, len(prompt), 16)
    got, got_cache = prefill(server._params, tokens, positions)
    want, want_cache = prefill(as_before, tokens, positions)
    assert float(jnp.abs(want).max()) > 0.1
    same((got, got_cache), (want, want_cache))
    step = server._get_extend(1, 1, 16)
    last = jnp.argmax(got[:, -1:], axis=-1).astype(jnp.int32)
    at = jnp.asarray([[len(prompt)]], jnp.int32)
    same(step(server._params, got_cache, last, at, jnp.asarray([len(prompt)])),
         step(as_before, want_cache, last, at, jnp.asarray([len(prompt)])))
    if "mtp_layers" in extra:
        whole = jax.jit(lambda p: server._module.apply(
            server._dequant(p), tokens[:, :-1], next_tokens=tokens[:, 1:]))
        served = whole(server._params)
        assert len(served) == 3 and served[2].shape == (1, len(prompt) - 1, vocab)
        same(served, whole(as_before))
    out = server.generate([prompt], max_new_tokens=6)["tokens"][0]
    server._params = as_before
    assert server.generate([prompt], max_new_tokens=6)["tokens"][0] == out
