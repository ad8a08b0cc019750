"""ops/gated_delta.py: one decode step of the gated delta rule with every
slot's matrix state read once and written once, held to the expression
(models/state_mixers.py ``gated_delta_rule`` at s = 1, which is the written-out
recurrence: tests/test_reference_qwen3_next.py) under the Pallas interpreter;
the state as the cache lays it (models/cache.py ``pack_state``: heads side by
side along the lanes where dv is no whole lane tile: Olmo-Hybrid's 30 heads of
[96, 192] are 15 units of [96, 384])."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.models.cache import pack_state, unpack_state
from seldon_core_tpu.models.state_mixers import gated_delta_rule, l2_normalize
from seldon_core_tpu.ops.gated_delta import Plan, gated_delta_step, heads_a_lane_row, plan


def operands(b, heads, dk, dv, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = l2_normalize(jax.random.normal(keys[0], (b, heads, dk))) * dk ** -0.5
    k = l2_normalize(jax.random.normal(keys[1], (b, heads, dk)))
    v = jax.random.normal(keys[2], (b, heads, dv))
    g = -2.0 * jax.nn.softplus(jax.random.normal(keys[3], (b, heads)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, heads)))    # up to 2: negative eigenvalues
    state = jax.random.normal(keys[5], (b, heads, dk, dv))
    starts = jax.random.bernoulli(keys[6], 0.3, (b,))
    return q, k, v, g, beta, state, starts


@pytest.mark.parametrize("b,heads,dk,dv", [(3, 8, 128, 128), (2, 32, 128, 128), (5, 16, 128, 256),
                                           (1, 3, 128, 128), (3, 30, 96, 192), (2, 6, 24, 64),
                                           (2, 20, 128, 128), (2, 16, 64, 32)])
def test_the_kernel_is_the_expression(b, heads, dk, dv):
    q, k, v, g, beta, state, starts = operands(b, heads, dk, dv, seed=heads)
    walk = plan(heads, dk, dv)
    assert walk is not None and heads % walk.heads == 0 and walk.heads % walk.side == 0
    packed = pack_state(state, walk.side)
    assert packed.shape == (b, heads // walk.side, dk, walk.side * dv)
    o, new_state = gated_delta_step(q, k, v, g, beta, packed, starts, walk, interpret=True)
    want_o, want_state = gated_delta_rule(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None], packed, starts, kernel=False)
    np.testing.assert_allclose(o, want_o[:, 0], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(new_state, want_state, atol=2e-5, rtol=1e-5)
    assert new_state.shape == packed.shape
    new_state = unpack_state(new_state, walk.side)
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
    assert plan(32, 128, 128) == Plan(16) == Plan(16, 1)
    assert plan(4, 16, 8) is None         # 4 heads of 8 lanes make no lane tile
    assert plan(32, 100, 128) is None     # dk is no whole sublane tile
    assert plan(24, 128, 128).heads == 8  # whole sublane tiles of heads that divide them
    assert plan(20, 128, 128).heads == 10  # none does: the most heads that divide them and fit
    assert plan(3, 128, 128).heads == 3   # ... all of them in one block does
    # dk need be whole SUBLANE tiles only (k and q come in as columns)
    assert plan(32, 64, 128) == Plan(32)
    # Olmo-Hybrid's 30 heads of [96, 192]: two side by side are [96, 384], three
    # lane tiles; 15 such units, five a grid step (737 KB of S)
    assert heads_a_lane_row(30, 192) == 2 and heads_a_lane_row(32, 128) == 1
    assert plan(30, 96, 192) == Plan(10, 2)
    assert plan(32, 128, 64) == Plan(32, 2)      # two heads of 64 lanes a row
    assert heads_a_lane_row(15, 192) == 1 and plan(15, 96, 192) is None   # an odd head is left over
