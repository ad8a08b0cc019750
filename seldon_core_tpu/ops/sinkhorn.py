"""Pallas TPU kernel: the Sinkhorn chain of a hyper-connection's residual
matrix, all its iterations in one call.

``HyperConnection`` (models/transformer.py) makes an n x n matrix a token
doubly stochastic by ``hc_sinkhorn_iters`` (20) iterations: rows divided by
their sums, then columns by theirs. The matrices are tiny (n = 4) and the
tokens few (32 rows a decode step, 256 a chunk), so the chain is pure latency,
and as XLA ops it has no good form on a TPU: a reduce over a matrix axis is a
fusion of its own (78 a sub-layer); written entry by entry and unrolled whole
it is four fusions a sub-layer, but each carries all twenty iterations of all
sixteen entries, 4.5 k instructions a sub-layer: 5 of the 7.4 s a layer that the
TPU compiler took over a step program, and as much again of every device
trace, which carries each program's module (PERF.md section 6, PR 31); as a
loop of a few iterations a trip it is nine fusions a trip.

Here the sixteen entries are sixteen [8, 128] tiles of tokens held in
registers through a loop inside one kernel: one op a sub-layer in the
program, a few dozen instructions in its module. The arithmetic and its order
are ``sinkhorn_entrywise``'s (models/transformer.py), which every lowering
that is not for a TPU keeps and tier-1 holds this kernel to under the Pallas
interpreter (tests/test_reference_xing4.py).
"""

from __future__ import annotations

import functools

# the name the device trace shows for the kernel
KERNEL_NAME = "sinkhorn_hc"
SUBLANES, LANES = 8, 128
TILE = SUBLANES * LANES   # tokens in one register of every entry


def entrywise_iteration(rows: list, eps: float) -> list:
    """One iteration on the n x n entries ``rows[i][j]`` (arrays of tokens):
    every row over its sum + eps, then every column over its sum + eps."""
    n = len(rows)
    rows = [[v / (sum(row) + eps) for v in row] for row in rows]
    cols = [sum(rows[i][j] for i in range(n)) + eps for j in range(n)]
    return [[rows[i][j] / cols[j] for j in range(n)] for i in range(n)]


def _kernel(n: int, iters: int, eps: float, m_ref, out_ref):
    import jax

    rows = [[m_ref[i * n + j] for j in range(n)] for i in range(n)]
    rows = jax.lax.fori_loop(0, iters, lambda _, r: entrywise_iteration(r, eps), rows)
    for i in range(n):
        for j in range(n):
            out_ref[i * n + j] = rows[i][j]


def sinkhorn(m, iters: int, eps: float, interpret: bool | None = None):
    """``iters`` Sinkhorn iterations on the n x n matrices ``m`` [n, n, ...]
    float32 (the tokens behind the matrix axes) -> the same shape.
    ``interpret=None`` compiles the kernel on a TPU and interprets it on any
    other backend; pass a bool to force either."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from seldon_core_tpu.ops import pallas_interpret_default

    if interpret is None:
        interpret = pallas_interpret_default()
    n, tokens = m.shape[0], m.shape[2:]
    assert m.shape[1] == n and m.dtype == jnp.float32, (m.shape, m.dtype)
    t = 1
    for size in tokens:
        t *= size
    tiles = max(-(-t // TILE), 1)
    flat = m.reshape(n * n, t)
    if tiles * TILE != t:   # ones: a padded token's matrix stays finite
        flat = jnp.pad(flat, ((0, 0), (0, tiles * TILE - t)), constant_values=1.0)
    block = pl.BlockSpec((n * n, SUBLANES, LANES), lambda tile: (0, tile, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n, iters, eps),
        out_shape=jax.ShapeDtypeStruct((n * n, tiles * SUBLANES, LANES), jnp.float32),
        grid=(tiles,), in_specs=[block], out_specs=block,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        cost_estimate=pl.CostEstimate(
            flops=iters * 4 * n * n * tiles * TILE, transcendentals=0,
            bytes_accessed=2 * n * n * tiles * TILE * 4),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat.reshape(n * n, tiles * SUBLANES, LANES))
    return out.reshape(n * n, tiles * TILE)[:, :t].reshape(m.shape)


__all__ = ["KERNEL_NAME", "entrywise_iteration", "sinkhorn"]
