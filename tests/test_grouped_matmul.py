"""The routed experts' grouped-matmul kernel (ops/grouped_matmul.py) under the
Pallas interpreter, held to ``jax.lax.ragged_dot`` and to a plain loop over
the experts. (That Mosaic takes it at the served shapes is in
tests/test_kernel_lowering.py and tests/test_tpu_program.py; what it costs on
the chip is in PERF.md and docs/performance.md.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from seldon_core_tpu.ops.grouped_matmul import (
    ROW_TILES, SUB_BLOCK, grouped_matmul, make_visits, row_tile, sub_block)


def operands(m, k, n, e, seed=0, int8=True):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.float32).astype(jnp.bfloat16)
    if not int8:
        return lhs, jax.random.normal(keys[1], (e, k, n), jnp.float32), None
    q = jax.random.randint(keys[1], (e, k, n), -127, 128, jnp.int8)
    return lhs, q, jax.random.uniform(keys[2], (e, n), jnp.float32, 0.5, 1.5) / 127


def by_loop(lhs, rhs, scale, sizes):
    """Each expert's rows times that expert's matrix, one expert at a time."""
    out, start = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), 0
    for g, size in enumerate(sizes):
        w = np.asarray(rhs[g].astype(lhs.dtype).astype(jnp.float32))
        rows = np.asarray(lhs[start:start + size].astype(jnp.float32))
        out[start:start + size] = rows @ w * (1.0 if scale is None else np.asarray(scale[g]))
        start += size
    return out


def by_ragged_dot(lhs, rhs, scale, sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    out = jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), sizes,
                             preferred_element_type=jnp.float32)
    row = jnp.arange(lhs.shape[0])
    expert = jnp.minimum(jnp.searchsorted(jnp.cumsum(sizes), row, side="right"), len(sizes) - 1)
    if scale is not None:
        out = out * scale[expert]
    return np.asarray(jnp.where((row < jnp.sum(sizes))[:, None], out, 0.0))


CASES = {
    # name: (m, k, n, group sizes, row tile, int8 stack + per-expert scale)
    "an empty group between two full ones": (64, 128, 128, [32, 0, 32], 32, True),
    "a group that spans three row tiles": (64, 128, 128, [5, 40, 19], 16, True),
    "a row tile shared by four groups": (64, 128, 128, [3, 4, 5, 4, 48], 16, True),
    "rows behind the last group, within a tile and whole tiles": (96, 128, 128, [7, 0, 30], 16, True),
    "no group at all": (32, 128, 128, [0, 0, 0], 16, True),
    "rows that no tile divides": (50, 128, 128, [1, 7, 30], 32, True),
    "a floating stack, no scale": (64, 128, 256, [20, 0, 30, 14], 16, False),
    "widths that are not whole 128-lane tiles": (48, 64, 32, [9, 0, 20, 11], 16, True),
    "a last expert that is empty": (64, 128, 128, [30, 30, 0], 32, True),
    **{f"row tile {tile}": (256, 128, 128, [100, 0, 56, 1, 70], tile, True) for tile in ROW_TILES},
    # the 128-row tile, whose visits multiply the run of 32-row blocks that holds their rows
    "tile 128: a group inside one 64-row half": (128, 128, 128, [40, 0, 88], 128, True),
    "tile 128: a group that straddles the halves": (128, 128, 128, [50, 30, 48], 128, True),
    "tile 128: groups that end exactly on a block's boundary": (256, 128, 128, [32, 32, 64, 96], 128, True),
    "tile 128: four small groups sharing a tile": (128, 128, 128, [10, 20, 30, 40], 128, True),
    "tile 128: a second half owned by nobody": (128, 128, 128, [20, 0, 30], 128, True),
    "tile 128: rows behind the last group, within a tile and whole tiles": (384, 128, 128, [100, 0, 60], 128, True),
    "tile 128: a floating stack, no scale": (256, 128, 256, [20, 0, 70, 100], 128, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_ragged_dot_with_zeros_behind_the_last_group(case):
    m, k, n, sizes, tile, int8 = CASES[case]
    lhs, rhs, scale = operands(m, k, n, len(sizes), int8=int8)
    visits = make_visits(jnp.asarray(sizes, jnp.int32), m, tile)
    got = np.asarray(grouped_matmul(lhs, rhs, visits, scale, interpret=True))
    assert got.shape == (m, n) and got.dtype == np.float32
    assert np.all(got[sum(sizes):] == 0.0), "rows in no group are exactly zero"
    # the same products summed in float32: the order of the sums is the backend's
    np.testing.assert_allclose(got, by_ragged_dot(lhs, rhs, scale, sizes), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, by_loop(lhs, rhs, scale, sizes), rtol=1e-4, atol=1e-4)


def test_a_strip_of_columns_where_an_experts_matrix_is_too_large_for_one_block(monkeypatch):
    """Above WEIGHT_BLOCK_BYTES the weight block is a strip of whole 128-lane
    tiles (no served width: a Mixtral-sized expert would)."""
    import seldon_core_tpu.ops.grouped_matmul as module

    monkeypatch.setattr(module, "WEIGHT_BLOCK_BYTES", 128 * 128)
    sizes = [20, 0, 30, 14]
    lhs, rhs, scale = operands(64, 128, 384, len(sizes))
    visits = make_visits(jnp.asarray(sizes, jnp.int32), 64, 16)
    got = np.asarray(grouped_matmul(lhs, rhs, visits, scale, interpret=True))
    np.testing.assert_allclose(got, by_ragged_dot(lhs, rhs, scale, sizes), rtol=1e-5, atol=1e-5)


def test_the_visit_list():
    """Five groups over four 16-row tiles: a group visits each tile it has a
    row in, an empty one none, and the tile no group reaches gets one visit
    that owns no rows (it writes the zeros) and moves no weights."""
    visits = make_visits(jnp.asarray([10, 0, 12, 20, 3], jnp.int32), 64, 16)
    n = int(visits.count)
    assert n == 7 and visits.rows == 16
    assert np.asarray(visits.tile)[:n].tolist() == [0, 0, 1, 1, 2, 2, 3]
    assert np.asarray(visits.expert)[:n].tolist() == [0, 2, 2, 3, 3, 4, 4]
    assert np.asarray(visits.lo)[:n].tolist() == [0, 10, 10, 22, 22, 42, 0]
    assert np.asarray(visits.hi)[:n].tolist() == [10, 22, 22, 42, 42, 45, 0]
    # the bound the arrays are sized by: tiles + groups
    assert visits.tile.shape == (4 + 5,)


def multiplied_by_hand(sizes, tile, block):
    """Every (group, tile) pair with a row in common: the blocks of ``block``
    rows of the tile, from the one that holds the pair's first row to the one
    that holds its last."""
    rows, start = 0, 0
    for size in sizes:
        for t in range(start // tile, -(-(start + size) // tile) if size else 0):
            r0, r1 = max(start - t * tile, 0), min(start + size - t * tile, tile)
            rows += (-(-r1 // block) - r0 // block) * block
        start += size
    return rows


@pytest.mark.parametrize("sizes,by_hand", [
    # all in tile 0: rows [0, 10) and [10, 22) a block each, [22, 42) two, [42, 45) one; tile 1 nobody's
    ([10, 0, 12, 20, 3], 32 + 32 + 64 + 32),
    # [0, 100) four blocks; [100, 156) the last block of tile 0 and the first of tile 1;
    # [156, 157) one; [157, 227) = rows [29, 99) of tile 1, four
    ([100, 0, 56, 1, 70], 128 + 32 + 32 + 32 + 128),
    ([0, 0, 0, 0, 0], 0),
    ([64, 64, 128, 0, 0], 256)])
def test_the_rows_a_visit_list_multiplies(sizes, by_hand):
    """``Visits.multiplied`` (what ``MoEFFN`` sows as ``tile_rows``): at the
    128-row tile the live visits' runs of 32-row blocks, a visit without rows
    nothing; at tiles of 64 rows or fewer every visit its tile, as before."""
    assert (sub_block(128), SUB_BLOCK) == (32, 32)
    assert multiplied_by_hand(sizes, 128, 32) == by_hand
    assert int(make_visits(jnp.asarray(sizes, jnp.int32), 256, 128).multiplied) == by_hand
    for tile in (16, 32, 64):
        assert sub_block(tile) == tile
        visits = make_visits(jnp.asarray(sizes, jnp.int32), 256, tile)
        assert int(visits.multiplied) == int(visits.count) * tile


def test_the_row_tile_at_the_six_served_shapes():
    """OLMoE (8 of 64 experts a token): a 32-slot step, chunks of 128 and
    256; DeepSeek-V2-Lite (6 of 64): an 8-slot step, chunks of 128 and 256.
    docs/performance.md "The grouped matmul" has the chip's table."""
    served = {(32 * 8, 64): 64, (128 * 8, 64): 64, (256 * 8, 64): 64,
              (8 * 6, 64): 16, (128 * 6, 64): 64, (256 * 6, 64): 64}
    assert {shape: row_tile(*shape) for shape in served} == served
    # a balanced router at a long chunk: groups that fill a larger tile get it
    assert row_tile(1024 * 8, 64) == 128 and row_tile(512 * 8, 64) == 64
    assert all(row_tile(m, e) in ROW_TILES for m in (1, 48, 4096, 1 << 20) for e in (1, 8, 256))


@pytest.mark.parametrize("seq,lens,tile", [(12, [12, 5], 64), (300, [300, 170], 128)])
def test_moeffn_through_the_kernel_is_moeffn_through_ragged_dot(monkeypatch, seq, lens, tile):
    """``MoEFFN`` picks by the lowering platform (the kernel for a TPU,
    ``ragged_dot`` elsewhere). Here the TPU's branch is taken by hand, its
    kernel under the interpreter: the same output as the branch tier-1
    otherwise runs, int8 stacks and dead rows included, and the rows the
    kernel multiplied are sown beside the routing (0 where ragged_dot
    serves): visits x row tile, never under the routed pairs; where the mean
    group is over 64 rows and the tile 128, its visits' runs of 32-row blocks,
    counted by hand from the groups the layer sowed."""
    import seldon_core_tpu.ops.grouped_matmul as module
    from seldon_core_tpu.models.transformer import MoEFFN, TransformerConfig
    from seldon_core_tpu.ops.quantize import dequantize_params, quantize_params

    cfg = TransformerConfig(vocab_size=64, dim=64, n_layers=1, n_heads=2, n_kv_heads=2,
                            ffn_dim=32, max_seq_len=32, n_experts=8, n_experts_per_token=2,
                            router_renormalize=False, dtype=jnp.bfloat16)
    ffn = MoEFFN(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, 64), jnp.float32).astype(jnp.bfloat16)
    valid = jnp.arange(seq)[None, :] < jnp.asarray(lens)[:, None]   # the rest is padding
    params = dequantize_params(quantize_params(ffn.init(jax.random.PRNGKey(1), x)),
                               keep_consumed=True)
    assert params["params"]["w1"].q.dtype == jnp.int8
    want, sown = ffn.apply(params, x, valid, mutable=["moe"])
    assert int(sown["moe"]["tile_rows"][0]) == 0

    kernel = module.grouped_matmul
    monkeypatch.setattr(module, "grouped_matmul",
                        lambda *args, interpret, **kw: kernel(*args, interpret=True, **kw))
    monkeypatch.setattr(jax.lax, "platform_dependent", lambda *args, tpu, default: tpu(*args))
    got, sown = ffn.apply(params, x, valid, mutable=["moe"])
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
    assert np.all(np.asarray(got[1, lens[1]:], np.float32) == 0.0)
    pairs = sum(lens) * 2
    assert row_tile(2 * seq * 2, 8) == tile
    tile_rows = int(sown["moe"]["tile_rows"][0])
    if tile == 128:
        sizes = np.asarray(sown["moe"]["tokens"][0]).sum(axis=0).tolist()
        assert sum(sizes) == pairs
        assert pairs <= tile_rows == multiplied_by_hand(sizes, 128, 32) < (1200 // tile + 8) * tile
    else:
        assert tile_rows % tile == 0 and pairs <= tile_rows <= (48 // tile + 8) * tile


@pytest.mark.parametrize("sizes", [[3, 0, 7, 2], [0, 0, 0, 0], [16, 0, 0, 16], [1, 1, 1, 1]])
def test_the_rows_behind_the_last_group_come_out_zero(sizes):
    """A caller that holds a SHARE of the experts has most of its rows behind
    the last group and does not mask them: every tile wholly behind it gets
    one visit that multiplies nothing and writes zeros, the tail of the last
    group's tile is zero too, and the groups' rows are ``ragged_dot``'s."""
    m, tile = 96, 16
    lhs, rhs, scale = operands(m, 128, 128, len(sizes))
    visits = make_visits(jnp.asarray(sizes, jnp.int32), m, tile)
    total = sum(sizes)
    live = sum(-(-(sum(sizes[:i + 1])) // tile) - sum(sizes[:i]) // tile for i, n in enumerate(sizes) if n)
    assert int(visits.count) == live + m // tile - -(-total // tile)
    got = np.asarray(grouped_matmul(lhs, rhs, visits, scale, interpret=True))
    np.testing.assert_allclose(got[:total], by_ragged_dot(lhs, rhs, scale, sizes)[:total],
                               atol=1e-3, rtol=1e-3)
    assert np.all(got[total:] == 0.0)
