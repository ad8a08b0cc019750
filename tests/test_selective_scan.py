"""ops/selective_scan.py: Mamba-1's recurrence h <- e^(Delta A) h + Delta B x, y =
C h + D x, every (channel, state) pair decaying at its own rate. The kernel of a
chunk's scan (h kept on chip across the rows; here under the Pallas interpreter)
is held to ``lax.scan`` and both to the recurrence written out in float64, under
decays of e^-6.4 and ~1 a token; a row that is no token (Delta = 0) leaves h as it
came; a sequence that starts mid-batch reads h as zeros; the decode step (s = 1)
is the same function. (tests/test_tpu_program.py compiles the kernel for a described
v5e at the published sizes.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops.selective_scan import (
    Plan, plan, scan_kernel, scan_rows, selective_scan)


def operands(b, s, d, n, seed=0, dt_low=1e-3, dt_high=1e-1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (b, s, d))
    delta = jnp.exp(jax.random.uniform(keys[1], (b, s, d), jnp.float32,
                                       np.log(dt_low), np.log(dt_high)))
    # A = -(1 .. N) a channel (the published init), times a spread a channel
    A = -(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None]
          * jnp.exp(0.3 * jax.random.normal(keys[2], (1, d))))
    B = jax.random.normal(keys[3], (b, s, n))
    C = jax.random.normal(keys[4], (b, s, n))
    D = 1.0 + 0.1 * jax.random.normal(keys[5], (d,))
    state = jax.random.normal(keys[6], (b, n, d))
    starts = jnp.arange(b) % 2 == 1
    return x, delta, A, B, C, D, state, starts


def row_by_row(x, delta, A, B, C, D, state, starts):
    """The recurrence as written, one row at a time, in numpy float64."""
    x, delta, A, B, C, D, h = (np.asarray(v, np.float64) for v in (x, delta, A, B, C, D, state))
    h = np.where(np.asarray(starts)[:, None, None], 0.0, h)
    ys = []
    for t in range(x.shape[1]):
        h = (np.exp(delta[:, t, None, :] * A) * h
             + (delta[:, t] * x[:, t])[:, None, :] * B[:, t, :, None])
        ys.append(np.einsum("bnd,bn->bd", h, C[:, t]) + D * x[:, t])
    return np.stack(ys, axis=1), h


# slow decays (Delta A ~ -1e-3: h remembers thousands of rows) and fast ones
# (Delta A down to -6.4 a token: h is the last row or two)
@pytest.mark.parametrize("dt_low,dt_high", [(1e-3, 1e-1), (1e-4, 1e-3), (0.1, 0.4)])
@pytest.mark.parametrize("b,s,d,n", [(2, 16, 128, 8), (1, 72, 256, 16), (3, 8, 384, 8)])
def test_the_kernel_and_the_scan_are_the_recurrence(b, s, d, n, dt_low, dt_high):
    args = operands(b, s, d, n, seed=s, dt_low=dt_low, dt_high=dt_high)
    x, delta, A, B, C, D, state, starts = args
    want_y, want_h = row_by_row(*args)
    zeroed = jnp.where(starts[:, None, None], 0.0, state)
    walk = plan(s, d, n)
    assert walk is not None and s % walk.rows == 0 and d % walk.lanes == 0
    for scan in (lambda: scan_rows(x, delta, A, B, C, zeroed),
                 lambda: scan_kernel(x, delta, A, B, C, zeroed, walk, interpret=True)):
        y, h = scan()
        np.testing.assert_allclose(y + D * x, want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
    # the one function every call shape goes through (here: the scan)
    y, h = selective_scan(*args)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)


def test_the_kernel_carries_h_across_its_time_blocks():
    """Two time blocks a channel block (rows 512 = 2 x 256): h stays in scratch
    between them, and the second block's rows see the first's."""
    args = operands(1, 512, 128, 8, seed=5)
    x, delta, A, B, C, D, state, _ = args
    walk = plan(512, 128, 8)
    assert walk == Plan(128, 256)
    y, h = scan_kernel(x, delta, A, B, C, state, walk, interpret=True)
    want_y, want_h = scan_rows(x, delta, A, B, C, state)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s", [1, 8, 11])
def test_a_row_that_is_no_token_leaves_h_untouched(s):
    x, delta, A, B, C, D, state, _ = operands(2, s, 128, 8, seed=3)
    # sequence 0: every row padding; sequence 1: tokens, then padding
    live = jnp.asarray([[False] * s, [t < s // 2 for t in range(s)]])
    delta = jnp.where(live[..., None], delta, 0.0)
    _, h = selective_scan(x, delta, A, B, C, D, state)
    np.testing.assert_array_equal(h[0], state[0])
    if s > 1:
        n = s // 2
        _, short = selective_scan(x[1:, :n], delta[1:, :n], A, B[1:, :n], C[1:, :n], D, state[1:])
        np.testing.assert_allclose(h[1], short[0], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("s", [1, 16])
def test_a_sequence_that_starts_mid_batch_reads_h_as_zeros(s):
    x, delta, A, B, C, D, state, _ = operands(3, s, 128, 8, seed=9)
    starts = jnp.asarray([False, True, False])
    y, h = selective_scan(x, delta, A, B, C, D, state, starts)
    fresh_y, fresh_h = selective_scan(x[1:2], delta[1:2], A, B[1:2], C[1:2], D,
                                      jnp.zeros_like(state[1:2]))
    np.testing.assert_allclose(y[1], fresh_y[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(h[1], fresh_h[0], rtol=1e-6, atol=1e-6)
    kept_y, _ = selective_scan(x[:1], delta[:1], A, B[:1], C[:1], D, state[:1])
    np.testing.assert_allclose(y[0], kept_y[0], rtol=1e-6, atol=1e-6)


def test_the_step_is_the_scans_one_row():
    args = operands(4, 1, 256, 16, seed=2)
    y, h = selective_scan(*args)
    want_y, want_h = row_by_row(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s,d,n", [(12, 128, 8), (16, 96, 8), (4, 128, 8), (16, 128, 4)])
def test_shapes_the_kernel_does_not_take_keep_the_scan(s, d, n):
    assert plan(s, d, n) is None
    args = operands(1, s, d, n, seed=1)
    y, h = selective_scan(*args)
    want_y, want_h = row_by_row(*args)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h, rtol=2e-5, atol=2e-5)
