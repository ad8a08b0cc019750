"""Model-family tests: forward shapes, KV-cache decode parity with full
prefill, MoE routing, registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models import get_model
from seldon_core_tpu.models.cache import init_kv_caches


def test_registry_unknown():
    with pytest.raises(KeyError):
        get_model("no-such-model")


def test_mlp_forward():
    model = get_model("mlp", features=[16], num_classes=3, dtype="float32")
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 4)))
    out = model.apply(params, jnp.ones((2, 4)))
    assert out.shape == (2, 3)
    np.testing.assert_allclose(np.asarray(out).sum(axis=-1), 1.0, rtol=1e-5)


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_resnet_forward_small():
    model = get_model("resnet18", num_classes=10, dtype="float32")
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    out = model.apply(variables, jnp.ones((2, 32, 32, 3)), train=False)
    assert out.shape == (2, 10)


def test_transformer_forward():
    model = get_model("llama-tiny")
    tokens = jnp.array([[1, 2, 3, 4]], dtype=jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits, _ = model.apply(variables, tokens)
    assert logits.shape == (1, 4, 256)


def test_transformer_decode_matches_prefill():
    """Incremental decode with the static KV cache must reproduce full-context
    logits — the correctness property of the serving decode path."""
    model = get_model("llama-tiny")
    cfg = model.cfg
    T = 6
    tokens = jnp.array([[5, 9, 2, 7, 1, 3]], dtype=jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)

    full_logits, _ = model.apply(variables, tokens)

    caches = init_kv_caches(cfg, batch=1, max_len=8)
    step_logits = []
    for t in range(T):
        tok = tokens[:, t : t + 1]
        pos = jnp.array([[t]], dtype=jnp.int32)
        logits, caches = model.apply(variables, tok, positions=pos, caches=caches, cache_index=t)
        step_logits.append(logits[:, 0])
    step_logits = jnp.stack(step_logits, axis=1)

    np.testing.assert_allclose(np.asarray(step_logits), np.asarray(full_logits), atol=2e-4, rtol=2e-4)


def test_transformer_prefill_then_decode():
    """Prefill a prefix through the cache, then decode one token — matches the
    full-context forward at the final position."""
    model = get_model("llama-tiny")
    cfg = model.cfg
    tokens = jnp.array([[5, 9, 2, 7]], dtype=jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)

    full_logits, _ = model.apply(variables, tokens)

    caches = init_kv_caches(cfg, batch=1, max_len=8)
    prefix = tokens[:, :3]
    pos = jnp.arange(3)[None, :]
    _, caches = model.apply(variables, prefix, positions=pos, caches=caches, cache_index=0)
    logits, _ = model.apply(
        variables, tokens[:, 3:4], positions=jnp.array([[3]]), caches=caches, cache_index=3
    )
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]), np.asarray(full_logits[:, 3]), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize("case", ["renormalised-top2-of-4", "olmoe"])
def test_transformer_moe(case):
    """The MoE block against the plain reference (models/reference.py), as a
    case of tests/test_reference.py's comparison: today's semantics (4
    experts, top-2, weights renormalised: what this test covered by shape
    and finiteness alone before there was a reference) and OLMoE's."""
    from test_reference import check_full_forward

    check_full_forward(case)
    model = get_model("llama-tiny", n_experts=4)
    tokens = jnp.array([[1, 2, 3]], dtype=jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits, _ = model.apply(variables, tokens)
    assert logits.shape == (1, 3, 256)
    assert np.isfinite(np.asarray(logits)).all()


def test_llama2_7b_has_untied_head():
    model = get_model("transformer", vocab_size=64, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, ffn_dim=64, dtype="float32")
    tokens = jnp.array([[1]], dtype=jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    assert "lm_head" in variables["params"], "untied lm_head required for Llama-2 checkpoints"


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_fold_batchnorm_matches_unfused():
    """fused=True + fold_batchnorm(vars) must reproduce the unfused
    inference forward exactly (with non-trivial running stats, so the fold
    arithmetic — not just identity stats — is exercised)."""
    import flax

    from seldon_core_tpu.models.resnet import fold_batchnorm

    m = get_model("resnet18", num_classes=10, dtype="float32")
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 32, 32, 3), dtype=np.float32)
    )
    v = m.init(jax.random.PRNGKey(0), x)
    flat = flax.traverse_util.flatten_dict(v["batch_stats"])
    rng = np.random.default_rng(1)
    flat = {
        k: jnp.asarray(
            rng.uniform(0.5, 2.0, a.shape) if k[-1] == "var" else rng.normal(0, 0.3, a.shape),
            a.dtype,
        )
        for k, a in flat.items()
    }
    v = {"params": v["params"], "batch_stats": flax.traverse_util.unflatten_dict(flat)}

    ref = m.apply(v, x, train=False)
    fused = get_model("resnet18", num_classes=10, dtype="float32", fused=True)
    got = fused.apply(fold_batchnorm(v), x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4)

    # fused is inference-only
    with pytest.raises(ValueError, match="inference-only"):
        fused.apply(fold_batchnorm(v), x, train=True)


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_space_to_depth_stem_matches_folded():
    """stem_s2d=True + fold_space_to_depth must reproduce the folded-BN
    forward up to float summation order, both when the module packs the
    input itself and when the caller stages pre-packed (B,H/2,W/2,12)."""
    from seldon_core_tpu.models.resnet import (
        fold_batchnorm,
        fold_space_to_depth,
        space_to_depth,
    )

    m = get_model("resnet18", num_classes=10, dtype="float32")
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((2, 32, 32, 3), dtype=np.float32)
    )
    v = fold_batchnorm(m.init(jax.random.PRNGKey(0), x))
    fused = get_model("resnet18", num_classes=10, dtype="float32", fused=True)
    s2d = get_model("resnet18", num_classes=10, dtype="float32", fused=True, stem_s2d=True)
    vs = fold_space_to_depth(v)

    ref = np.asarray(fused.apply(v, x, train=False))
    np.testing.assert_allclose(
        np.asarray(s2d.apply(vs, x, train=False)), ref, atol=1e-5, rtol=1e-5
    )
    # host-side packing (numpy in, same packing order as the device path)
    packed = space_to_depth(np.asarray(x))
    assert isinstance(packed, np.ndarray) and packed.shape == (2, 16, 16, 12)
    np.testing.assert_allclose(
        np.asarray(s2d.apply(vs, jnp.asarray(packed), train=False)), ref, atol=1e-5, rtol=1e-5
    )
    # s2d stem is inference-only
    with pytest.raises(ValueError, match="requires fused"):
        get_model("resnet18", num_classes=10, dtype="float32", stem_s2d=True).init(
            jax.random.PRNGKey(0), x
        )


def test_seq2seq_bad_sequence_length_raises():
    from seldon_core_tpu.analytics import Seq2SeqOutlierDetector

    det = Seq2SeqOutlierDetector(timesteps=8)
    with pytest.raises(ValueError, match="sequence length 8"):
        det._frame(np.zeros((4, 16, 2), np.float32))


def test_vit_forward_and_serving(tmp_path):
    """ViT family: forward shape, GSPMD logical axes present, and the full
    JAXServer serving path (export -> engine predict)."""
    import asyncio

    from seldon_core_tpu.contracts.graph import PredictorSpec
    from seldon_core_tpu.contracts.payload import SeldonMessage
    from seldon_core_tpu.runtime.engine import GraphEngine
    from seldon_core_tpu.servers.jaxserver import export_checkpoint

    model = get_model("vit-tiny", num_classes=5)
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)
    assert out.shape == (2, 5)
    assert "params_axes" in variables  # sharding rules can apply

    ckpt = export_checkpoint(
        str(tmp_path / "ckpt"), model="vit-tiny",
        kwargs={"num_classes": 5},
        params=variables, input_shape=[16, 16, 3], use_orbax=False,
    )
    spec = PredictorSpec.from_dict({
        "name": "p",
        "graph": {"name": "m", "type": "MODEL",
                  "implementation": "JAX_SERVER", "modelUri": ckpt},
    })
    engine = GraphEngine(spec)
    msg = SeldonMessage.from_dict(
        {"data": {"tensor": {"shape": [1, 16, 16, 3], "values": [0.5] * (16 * 16 * 3)}}}
    )
    resp = asyncio.run(engine.predict(msg)).to_dict()
    assert resp["data"]["tensor"]["shape"] == [1, 5]


def test_vit_shards_over_model_axis(eight_devices):
    from seldon_core_tpu.parallel.mesh import make_mesh
    from seldon_core_tpu.parallel.sharding import shard_apply, sharding_report

    mesh = make_mesh({"data": 4, "model": 2}, eight_devices)
    model = get_model("vit-tiny", num_classes=4)
    x = jnp.zeros((4, 16, 16, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)

    def apply_fn(v, x):
        return model.apply(v, x)

    jitted, sharded = shard_apply(
        apply_fn, model, variables, mesh,
        example_input=jax.ShapeDtypeStruct((1, 16, 16, 3), jnp.float32),
        strict=True,
    )
    report = sharding_report(sharded)
    assert "model" in report["axes"], report
    out = jitted(sharded, x)
    assert out.shape == (4, 4)
