"""Fused residual+RMSNorm Pallas kernel: interpret-mode parity with the
reference XLA expression (and the model's unfused path), padding behaviour,
jit-ability, and the TransformerBlock fused_norm flag. Runs the kernel body
under the Pallas interpreter on CPU; on a TPU the same entry point compiles
the kernel or raises (tests/test_kernel_lowering.py checks that lowering
from here)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seldon_core_tpu.models import get_model
from seldon_core_tpu.models.transformer import rms_norm
from seldon_core_tpu.ops.fused_norm import (
    fused_residual_rmsnorm,
    residual_rmsnorm_ref,
)

pytestmark = pytest.mark.pallas


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("shape,dtype", [
    ((2, 5, 64), jnp.float32),
    ((8, 2048), jnp.bfloat16),   # the decode shape the profile flags
    ((3, 100), jnp.float32),     # lane dim padded to 128 inside the kernel
    ((7, 130), jnp.bfloat16),    # both dims padded
])
def test_interpret_parity_with_reference(shape, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    h = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
    y, o = fused_residual_rmsnorm(x, h, w, 1e-5, interpret=True)
    y_ref, o_ref = residual_rmsnorm_ref(x, h, w, 1e-5)
    assert y.dtype == x.dtype and o.dtype == x.dtype
    # acceptance bar: <=1e-5 relative for f32; bf16-relative means within
    # ~1 ulp of bf16 (eps = 2^-8 ~= 4e-3) — the kernel replays the same
    # dtype chain, the residual difference is reduction order (sum/d vs mean)
    if dtype == jnp.bfloat16:
        rtol, atol = 8e-3, 8e-3
    else:
        rtol, atol = 1e-5, 1e-5
    np.testing.assert_allclose(_f32(y), _f32(y_ref), rtol=rtol, atol=atol)
    np.testing.assert_allclose(_f32(o), _f32(o_ref), rtol=rtol, atol=atol)


def test_parity_with_model_rms_norm():
    """The kernel's contract is rms_norm(x + h, w, eps) from the model."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(64), jnp.float32)
    y, o = fused_residual_rmsnorm(x, h, w, 1e-5, interpret=True)
    np.testing.assert_allclose(_f32(y), _f32(x + h), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(_f32(o), _f32(rms_norm(x + h, w, 1e-5)),
                               rtol=1e-5, atol=1e-5)


def test_kernel_is_jittable():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    w = jnp.ones((64,), jnp.float32)

    @jax.jit
    def f(x, h, w):
        return fused_residual_rmsnorm(x, h, w, 1e-5, interpret=True)

    y, o = f(x, h, w)
    y_ref, o_ref = residual_rmsnorm_ref(x, h, w, 1e-5)
    np.testing.assert_allclose(_f32(o), _f32(o_ref), rtol=1e-5, atol=1e-5)


def test_off_tpu_default_runs_the_kernel_not_a_reference():
    """With no ``interpret`` argument a non-TPU backend interprets the SAME
    kernel — no XLA reference stands in. The padded (2, 16) case makes the
    two distinguishable: the kernel divides a padded-lane sum by d, the
    reference takes a mean."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 16)), jnp.float32)
    w = jnp.ones((16,), jnp.float32)
    y, o = fused_residual_rmsnorm(x, x, w, 1e-5)
    yi, oi = fused_residual_rmsnorm(x, x, w, 1e-5, interpret=True)
    np.testing.assert_array_equal(_f32(o), _f32(oi))
    y_ref, o_ref = residual_rmsnorm_ref(x, x, w, 1e-5)
    np.testing.assert_allclose(_f32(o), _f32(o_ref), rtol=1e-5, atol=1e-5)


def test_transformer_fused_norm_flag_matches_unfused():
    """Same params, fused_norm on vs off: the same logits to f32 rounding
    (the flag runs the kernel — interpreted here — whose reduction order
    differs from the unfused mean)."""
    full = get_model("llama-tiny")
    fused = get_model("llama-tiny", fused_norm=True)
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, 255, (2, 16)), jnp.int32)
    variables = full.init(jax.random.PRNGKey(0), tokens)
    ref, _ = full.apply(variables, tokens)
    out, _ = fused.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # tier-1 870s budget: redundant coverage — runs in CI's unfiltered unit step
def test_llmserver_generate_with_fused_norm():
    """End-to-end: a fused-norm server produces the same greedy tokens as
    the unfused twin (flag changes cost, never tokens)."""
    from seldon_core_tpu.servers.llmserver import LLMServer

    def build(fused):
        s = LLMServer(model="llama-tiny",
                      model_kwargs={"fused_norm": True} if fused else {},
                      init_random=True, max_new_tokens=8, len_buckets=(16,),
                      batch_buckets=(1,), temperature=0.0, eos_id=-1, seed=5)
        s.load()
        return s

    prompt = [5, 9, 17, 33]
    want = build(False).generate([prompt], max_new_tokens=8)["tokens"][0]
    got = build(True).generate([prompt], max_new_tokens=8)["tokens"][0]
    assert got == want
