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
    # keep_stacks: the stack stays int8 for a consumer that takes it so
    kept = dequantize_params({"w": qt, "m": quantize_params({"m": jnp.asarray(big)})["m"]},
                             keep_stacks=True)
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
    want = quantize_params({"w": drawn})["w"]
    got = server._params["params"]["layer_0"]["attention"]["wq"]
    # (the server draws and quantizes inside one jit: the scale may differ
    # from this eager one in its last bit, and a value on a rounding edge by 1)
    np.testing.assert_allclose(np.asarray(got.scale), np.asarray(want.scale), rtol=1e-6)
    off = np.asarray(got.q, np.int32) - np.asarray(want.q, np.int32)
    assert np.abs(off).max() <= 1 and np.mean(off != 0) < 1e-3
