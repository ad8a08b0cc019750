"""ops/gated_delta.py: one decode step of the gated delta rule with every
slot's matrix state read once and written once, held to the expression
(models/transformer.py ``gated_delta_rule`` at s = 1, which is the written-out
recurrence: tests/test_reference_qwen3_next.py) under the Pallas interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.transformer import gated_delta_rule, l2_normalize
from seldon_core_tpu.ops.gated_delta import gated_delta_step, plan


def operands(b, heads, dk, dv, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = l2_normalize(jax.random.normal(keys[0], (b, heads, dk))) * dk ** -0.5
    k = l2_normalize(jax.random.normal(keys[1], (b, heads, dk)))
    v = jax.random.normal(keys[2], (b, heads, dv))
    g = -2.0 * jax.nn.softplus(jax.random.normal(keys[3], (b, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, heads)))
    state = jax.random.normal(keys[5], (b, heads, dk, dv))
    starts = jax.random.bernoulli(keys[6], 0.3, (b,))
    return q, k, v, g, beta, state, starts


@pytest.mark.parametrize("b,heads,dk,dv", [(3, 8, 128, 128), (2, 32, 128, 128), (5, 16, 128, 256),
                                           (1, 3, 128, 128)])
def test_the_kernel_is_the_expression(b, heads, dk, dv):
    q, k, v, g, beta, state, starts = operands(b, heads, dk, dv, seed=heads)
    walk = plan(heads, dk, dv)
    assert walk is not None and heads % walk.heads == 0
    o, new_state = gated_delta_step(q, k, v, g, beta, state, starts, walk, interpret=True)
    want_o, want_state = gated_delta_rule(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None], state, starts, kernel=False)
    np.testing.assert_allclose(o, want_o[:, 0], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(new_state, want_state, atol=2e-5, rtol=1e-5)
    # a sequence that starts reads no past: its new S is k d^T alone
    fresh = np.asarray(starts)
    if fresh.any():
        d = np.asarray(beta)[fresh][..., None] * np.asarray(v)[fresh]
        np.testing.assert_allclose(np.asarray(new_state)[fresh],
                                   np.asarray(k)[fresh][..., None] * d[..., None, :], atol=1e-5)


def test_a_row_that_is_no_token_leaves_its_state_bit_for_bit():
    q, k, v, g, beta, state, _ = operands(4, 8, 128, 128)
    dead = jnp.asarray([False, True, False, True])
    g = jnp.where(dead[:, None], 0.0, g)
    beta = jnp.where(dead[:, None], 0.0, beta)
    _, new_state = gated_delta_step(q, k, v, g, beta, state, jnp.zeros((4,), bool),
                                    plan(8, 128, 128), interpret=True)
    np.testing.assert_array_equal(np.asarray(new_state)[np.asarray(dead)],
                                  np.asarray(state)[np.asarray(dead)])


def test_the_plan_is_from_static_shapes_alone():
    """Qwen3-Next's 32 value heads of [128, 128] float32: sixteen a grid step
    (a megabyte of S); shapes that are not whole tiles keep the expression."""
    assert plan(32, 128, 128).heads == 16
    assert plan(8, 128, 128).heads == 8
    assert plan(32, 128, 256).heads == 8
    assert plan(4, 16, 8) is None and plan(32, 64, 128) is None and plan(32, 128, 64) is None
    assert plan(24, 128, 128).heads == 8  # whole sublane tiles of heads that divide them
    assert plan(20, 128, 128) is None     # none does, and all 20 are over a block
    assert plan(3, 128, 128).heads == 3   # ... all of them in one block does
