"""ops/ssd.py: Mamba-2's recurrence h <- e^(dt A) h + (dt x) B^T, y = h C + D x.
The chunked form (a prefill chunk, the cache-less forward) is held to the
recurrence row by row under decays of e^-6.4 and ~1 a token; the decode step's
kernel (h read once and written once, as the cache lays it: a head's h
transposed, heads side by side along the lanes) to the expression under the
Pallas interpreter; a row that is no token (dt = 0) leaves h as it came."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops.gated_delta import heads_a_lane_row, pack_state, unpack_state
from seldon_core_tpu.ops.ssd import SSD_CHUNK, Plan, plan, ssd, ssd_step


def laid(h):
    """h [b, H, P, N] as the cache holds it: [b, H / side, N, side * P]."""
    return pack_state(jnp.swapaxes(h, 2, 3), heads_a_lane_row(h.shape[1], h.shape[2]))


def a_head(state, heads):
    """The cache's array back as h [b, H, P, N]."""
    return jnp.swapaxes(unpack_state(state, heads // state.shape[1]), 2, 3)


def operands(b, s, heads, groups, p, n, seed=0, dt_low=1e-3, dt_high=1e-1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (b, s, heads, p))
    dt = jnp.exp(jax.random.uniform(keys[1], (b, s, heads), jnp.float32,
                                    np.log(dt_low), np.log(dt_high)))
    A = -jnp.arange(1, heads + 1, dtype=jnp.float32)
    B = jax.random.normal(keys[2], (b, s, groups, n))
    C = jax.random.normal(keys[3], (b, s, groups, n))
    D = 1.0 + 0.1 * jax.random.normal(keys[4], (heads,))
    state = laid(jax.random.normal(keys[5], (b, heads, p, n)))
    starts = jax.random.bernoulli(keys[6], 0.3, (b,))
    return x, dt, A, B, C, D, state, starts


def row_by_row(x, dt, A, B, C, D, state, starts):
    """The recurrence as written, one row at a time, in numpy float64; h [b, H, P, N]."""
    x, dt, A, B, C, D, h = (np.asarray(v, np.float64)
                            for v in (x, dt, A, B, C, D, a_head(state, x.shape[2])))
    h = np.where(np.asarray(starts)[:, None, None, None], 0.0, h)
    rep = x.shape[2] // B.shape[2]
    ys = []
    for t in range(x.shape[1]):
        Bt, Ct = np.repeat(B[:, t], rep, axis=1), np.repeat(C[:, t], rep, axis=1)   # [b, H, N]
        h = (np.exp(dt[:, t] * A)[..., None, None] * h
             + (dt[:, t, :, None] * x[:, t])[..., None] * Bt[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", h, Ct) + D[:, None] * x[:, t])
    return np.stack(ys, axis=1), h


@pytest.mark.parametrize("b,s,heads,groups,p,n", [
    (2, 40, 4, 1, 8, 16), (1, SSD_CHUNK, 6, 2, 8, 16), (2, 2 * SSD_CHUNK + 3, 4, 1, 8, 16),
    (1, 17, 8, 4, 16, 128), (2, 9, 4, 2, 64, 128)])
def test_the_chunked_form_is_the_recurrence(b, s, heads, groups, p, n):
    args = operands(b, s, heads, groups, p, n, seed=s)
    y, h = ssd(*args)
    want_y, want_h = row_by_row(*args)
    assert h.shape == args[6].shape       # laid out as it came
    np.testing.assert_allclose(y, want_y, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(a_head(h, heads), want_h, atol=2e-4, rtol=2e-5)


@pytest.mark.parametrize("what,dt_low,dt_high", [
    # A = -64 at dt 0.1: a decay of e^-6.4 a token; G falls by 800 over a sub-chunk,
    # and e^G underflows while the differences the form exponentiates do not
    ("fast", 0.1, 0.1),
    # A = -1 at dt 1e-5: ~1 a token, the state of 300 rows ago still whole
    ("slow", 1e-5, 1e-5)])
def test_the_chunked_form_keeps_its_digits_under_both_decays(what, dt_low, dt_high):
    x, dt, A, B, C, D, state, _ = operands(1, 300, 64, 1, 8, 16, seed=3,
                                           dt_low=dt_low, dt_high=dt_high)
    args = (x, dt, A, B, C, D, state, jnp.zeros((1,), bool))
    y, h = ssd(*args)
    want_y, want_h = row_by_row(*args)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(h)).all()
    scale = np.abs(want_y).max()
    np.testing.assert_allclose(np.asarray(y) / scale, want_y / scale, atol=1e-5)
    h = np.asarray(a_head(h, 64))
    np.testing.assert_allclose(h, want_h, atol=1e-5 * np.abs(want_h).max())
    if what == "slow":   # the state the call came with is still there
        assert np.abs(want_h - np.asarray(a_head(state, 64))).max() > 0.1
        np.testing.assert_allclose(h[0, 0] / np.exp(-300e-5), want_h[0, 0] / np.exp(-300e-5),
                                   atol=1e-3)


def test_the_step_is_a_chunk_of_one_row_and_chunks_compose():
    x, dt, A, B, C, D, state, starts = operands(2, 37, 4, 2, 8, 128, seed=5)
    y_all, h_all = ssd(x, dt, A, B, C, D, state, starts)
    # 20 rows as a chunk, then 17 single steps from the state it left
    y, h = ssd(x[:, :20], dt[:, :20], A, B[:, :20], C[:, :20], D, state, starts)
    ys = [y]
    for t in range(20, 37):
        y, h = ssd(x[:, t:t + 1], dt[:, t:t + 1], A, B[:, t:t + 1], C[:, t:t + 1], D, h)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), y_all, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(h, h_all, atol=2e-4, rtol=2e-5)


def test_a_row_that_is_no_token_decays_nothing_and_adds_nothing():
    x, dt, A, B, C, D, state, _ = operands(2, 24, 4, 1, 128, 8, seed=7)
    live = jnp.arange(24) < 9                     # a padded chunk: 9 tokens, 15 rows of padding
    dt_padded = jnp.where(live[None, :, None], dt, 0.0)
    none = jnp.zeros((2,), bool)
    _, h = ssd(x, dt_padded, A, B, C, D, state, none)
    _, want = ssd(x[:, :9], dt[:, :9], A, B[:, :9], C[:, :9], D, state, none)
    np.testing.assert_allclose(h, want, atol=1e-5, rtol=1e-6)
    # a whole call of such rows: the state bit for bit, the chunk's and the step's
    _, h = ssd(x, jnp.zeros_like(dt), A, B, C, D, state, none)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(state))
    _, h = ssd(x[:, :1], jnp.zeros_like(dt[:, :1]), A, B[:, :1], C[:, :1], D, state, none)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(state))
    _, h = ssd_step(x[:, 0], jnp.zeros_like(dt[:, 0]), A, B[:, 0], C[:, 0], state, none,
                    plan(4, 1, 128, 8), interpret=True)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(state))


@pytest.mark.parametrize("b,heads,groups,p,n", [(3, 64, 1, 64, 128), (2, 16, 2, 16, 128),
                                                (4, 6, 1, 128, 64), (1, 48, 1, 64, 128),
                                                (2, 32, 4, 32, 16), (2, 4, 1, 256, 8)])
def test_the_kernel_is_the_expression(b, heads, groups, p, n):
    x, dt, A, B, C, D, state, starts = operands(b, 1, heads, groups, p, n, seed=heads)
    walk = plan(heads, groups, p, n)
    assert walk is not None and (heads // groups) % walk.heads == 0 and walk.heads % walk.side == 0
    assert state.shape == (b, heads // walk.side, n, walk.side * p)
    y, h = ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], state, starts, walk, interpret=True)
    want_y, want_h = ssd(x, dt, A, B, C, jnp.zeros_like(D), state, starts)
    np.testing.assert_allclose(y, want_y[:, 0], atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(h, want_h, atol=1e-6, rtol=1e-6)
    assert h.shape == state.shape
    # a sequence that starts reads no past: its new h is (dt x) B^T alone
    fresh = np.asarray(starts)
    if fresh.any():
        dtx = np.asarray(dt[:, 0, :, None] * x[:, 0])[fresh]
        rows = np.repeat(np.asarray(B[:, 0]), heads // groups, axis=1)[fresh]
        np.testing.assert_allclose(np.asarray(a_head(h, heads))[fresh],
                                   dtx[..., None] * rows[:, :, None, :], atol=1e-6)


def test_a_state_of_nan_does_not_reach_a_sequence_that_starts():
    x, dt, A, B, C, D, state, _ = operands(2, 1, 4, 1, 128, 8, seed=9)
    starts = jnp.asarray([True, False])
    poisoned = state.at[0].set(jnp.nan)
    for y, h in (ssd(x, dt, A, B, C, D, poisoned, starts),
                 ssd_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], poisoned, starts,
                          plan(4, 1, 128, 8), interpret=True)):
        assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(h)).all()


def test_the_plan_is_from_static_shapes_alone():
    """granite-4.0-h-micro's 64 heads of [64, 128] float32 in one group: two
    heads' transposed h side by side are a unit [128, 128], sixteen units (32
    heads, a megabyte of h) a grid step; a block never spans two groups; shapes
    whose unit is not whole (8, 128) tiles keep the expression."""
    assert plan(64, 1, 64, 128) == Plan(32, 2)
    assert heads_a_lane_row(64, 64) == 2 and heads_a_lane_row(8, 8) == 1
    assert plan(128, 8, 64, 128) == Plan(16, 2)   # a group's 16 heads
    assert plan(48, 1, 64, 128) == Plan(16, 2)    # of 24 units: 8, whole sublane tiles of rows
    assert plan(64, 1, 64, 256) == Plan(16, 2)
    assert plan(8, 1, 128, 16) == Plan(8, 1)      # a head of 128 is a lane tile alone
    assert plan(8, 1, 8, 16) is None              # eight heads of 8 lanes make no lane tile
    assert plan(4, 1, 128, 12) is None            # 12 state rows are no sublane tile
    assert plan(6, 4, 128, 8) is None             # the heads do not divide into the groups
    assert plan(8, 4, 64, 8) == Plan(2, 2)        # a group's two heads: one unit
