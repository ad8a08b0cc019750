"""Int8 weight-only post-training quantization for serving.

TPU serving is usually HBM-bandwidth-bound; storing weights as int8 halves
the weight traffic vs bf16 while the MXU still computes in bf16: inside the
jitted forward each quantized leaf is dequantized as ``q.astype(bf16) *
scale``, and weights live in HBM as int8. (The reference's native-performance
path delegates to TensorRT for this role; here it is a first-class transform
on any checkpoint.)

What the v5e's compiler does with that expression depends on the consumer
(read from the compiled step programs, PERF.md section 3 "Reading a program's
ops without a chip"):

- a plain ``x @ W`` (the transformer's ``wo``, ``w1``, ``w3``, ``w2``): the
  convert+multiply fuses INTO the matmul's own fusion, the int8 bytes are read
  once and no floating copy of the weight exists;
- a projection whose output is split into heads (``wq``, ``wk``, ``wv``: the
  matmul fuses with the reshape and the rotary instead): the dequant stays a
  fusion of its own that writes the weight out in bf16, and the convolution
  wants that array with the CONTRACTED dimension minor. Held ``[in, out]`` it
  was therefore also transposed, a second whole-weight pass that computes
  nothing (``copy bf16[4096,4096]`` + 2 x ``copy bf16[1024,4096]``, 1.74 ms
  of an 18.75 ms Mistral step). Such a leaf is held OUTPUT-MAJOR: ``q`` is
  ``[out, in]`` in HBM (``out_major``), the transpose back to the logical
  ``[in, out]`` is a bitcast, and the copy is gone. The standalone dequant
  remains;
- the head (``lm_head``, a float32 matmul) is the first case: its dequant is
  inside the matmul's fusion, which reads the int8 parameter (at xing4's
  width 470 MB in 0.62 ms a step, 92 % of the HBM's peak: PERF.md section 6,
  PR 34). The server hands it to the module int8 and the module dequantizes
  it where it multiplies by it (the same fusion in a step; in a prefill chunk
  that is inside a conditional, and no dequant is moved into a branch);
- a ROW LOOKUP (the embedding table ``tok_embeddings [vocab, dim]``): the
  dequant is NOT pushed through the gather. Dequantized before the lookup, the
  whole table was converted and written out in bf16 (``vocab x dim x 3``
  bytes: 2.4 ms of a 9 ms Xing4.0 step, once in every step and chunk of
  every model; the records booked it as "the head's copy" until PR 34) to
  read 32 rows of it. Such a leaf is marked ``lookup`` and reaches its module
  int8, which gathers the int8 rows and dequantizes those
  (``lookup_rows``): the same two operations an element, on the rows asked for.

The orientation rule: a matrix is held in the order its consumer reads it.
Which leaves those are is read off the module's logical axes (an output axis
of ``heads`` / ``kv_heads``: parallel/sharding.py ``head_split_outputs``; a
first axis of ``vocab``, rows that token ids index: ``row_lookups``), not off
a model's name or an option; the matrix, its scales and every dequantized
value are the same either way.

Scheme: symmetric per-output-channel int8 (scale = max|w| / 127 over all
dims but the last). 1-D leaves (biases, norms) and integer leaves pass
through unquantized — they are tiny and precision-critical.

3-D leaves are STACKS ([e, d, f]: one matrix per expert) and keep one scale
per matrix per channel ([e, f]): experts of different magnitude would
otherwise share the largest one's step. A stack is also not dequantized by
``dequantize_params(keep_consumed=True)`` (nor is a ``lookup`` table: the one
rule, ``QuantizedTensor.consumed_int8``): its consumer (a grouped matmul) takes
the int8 array and applies the scale to the product, so no floating copy of
the stack is ever made. A stack that its module multiplies DENSELY, batched
over its first axis (latent attention's per-head ``W_UK`` [H, nope, latent] and
``W_UV`` [H, latent, v], a megabyte each: models/transformer.py
``_dense_stack``), arrives int8 by the same rule and is dequantized where it is
used; it is held ``[H, K, N]``, the contracted axis in the middle, which is the
order those batched products read.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import numpy as np


@dataclass
class QuantizedTensor:
    """int8 values + per-channel f32 scales (broadcast over the last dim).
    ``orig_dtype`` records the dtype dequantization restores,
    ``out_major`` that ``q`` holds the matrix transposed, ``[C, K]`` for a
    logical ``[K, C]``, and ``lookup`` that the consumer indexes its rows (an
    embedding table) rather than multiplies by it (all static pytree
    metadata, so one compiled program per dtype, orientation and use)."""

    q: Any  # int8 [..., C]; [C, K] when out_major
    scale: Any  # f32 [C]; [E, C] for a stack of matrices [E, ..., C]
    orig_dtype: str = "bfloat16"
    out_major: bool = False
    lookup: bool = False

    @property
    def shape(self):
        """The logical matrix's shape, however ``q`` is held."""
        return self.q.shape[::-1] if self.out_major else self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def stacked(self) -> bool:
        return self.scale.ndim == 2

    @property
    def consumed_int8(self) -> bool:
        """Does the leaf's consumer take the int8 array itself (a grouped or
        batched matmul over a stack, a row lookup)? XLA fuses a dequant into a
        plain dot and into neither of those: dequantized ahead of them the
        whole leaf would be written out in floating point."""
        return self.stacked or self.lookup


def _register_pytree() -> None:
    import jax

    try:
        jax.tree_util.register_pytree_node(
            QuantizedTensor,
            lambda t: ((t.q, t.scale), (t.orig_dtype, t.out_major, t.lookup)),
            lambda aux, children: QuantizedTensor(*children, *aux),
        )
    except ValueError:
        pass  # already registered


def quantize_array(w, bits: int = 8, out_major: bool = False, lookup: bool = False):
    """Symmetric per-last-dim-channel quantization of one float array; a
    3-D array is a stack of matrices and keeps its leading axis in the scale.
    ``out_major`` holds a matrix's int8 values transposed (the same values
    and scales, the byte order its consumer reads); ``lookup`` marks a table
    whose rows are indexed (held as it is: a row is contiguous)."""
    import jax.numpy as jnp

    qmax = 2 ** (bits - 1) - 1
    w = jnp.asarray(w)
    orig_dtype = str(w.dtype)
    stack = w.ndim == 3
    reduce_dims = (1,) if stack else tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_dims, keepdims=stack)
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -qmax - 1, qmax).astype(jnp.int8)
    if (out_major or lookup) and w.ndim != 2:
        raise ValueError(f"out_major and lookup hold a matrix, not shape {w.shape}")
    if out_major and lookup:
        raise ValueError("a lookup table is held row-major: its rows are what is read")
    if out_major:
        q = q.T
    return QuantizedTensor(q=q, scale=scale[:, 0, :] if stack else scale,
                           orig_dtype=orig_dtype, out_major=out_major, lookup=lookup)


def dequantize_array(t: QuantizedTensor, dtype=None):
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype or t.orig_dtype)
    if t.out_major:
        # the logical [K, C] matrix: the transpose of what is held is a
        # bitcast to a consumer that contracts over K
        return (t.q.astype(dtype) * t.scale.astype(dtype)[:, None]).T
    scale = t.scale[:, None, :] if t.stacked else t.scale
    return t.q.astype(dtype) * scale.astype(dtype)


def lookup_rows(table, index, dtype):
    """``table.astype(dtype)[index]`` for a floating table or a ``lookup``
    leaf held int8, to the bit: the int8 rows are gathered first and those
    alone dequantized (int8 -> float is exact and the multiply by the scale is
    per element, so it is the same product on ``index.size`` rows as on all)."""
    if not isinstance(table, QuantizedTensor):
        return table.astype(dtype)[index]
    return dequantize_array(replace(table, q=table.q[index])).astype(dtype)


def _is_quantizable(leaf) -> bool:
    import jax.numpy as jnp

    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        return False
    # jnp.issubdtype, not np: bfloat16 (and float8) are ml_dtypes that numpy
    # classifies as void — np.issubdtype would silently skip bf16 checkpoints
    return jnp.issubdtype(jnp.dtype(str(dtype)), jnp.floating) and getattr(leaf, "ndim", 0) >= 2


def quantize_params(params: Any, bits: int = 8, out_major: Any = None,
                    keep: Any = None, lookup: Any = None) -> Any:
    """Quantize every ≥2-D float leaf of a param pytree; the rest passes
    through. Returns a tree mixing QuantizedTensor and original leaves.
    ``out_major`` is a tree of bools shaped like ``params`` (parallel/
    sharding.py ``head_split_outputs``): the leaves to hold output-major.
    ``keep`` is another (``float32_leaves``): the leaves to leave as they are;
    ``lookup`` a third (``row_lookups``): the tables whose rows are indexed."""
    import jax

    _register_pytree()

    def visit(leaf, transposed=False, kept=False, indexed=False):
        if kept or not _is_quantizable(leaf):
            return leaf
        return quantize_array(leaf, bits, transposed, indexed)

    false = jax.tree.map(lambda _: False, params)
    return jax.tree.map(visit, params, *(false if tree is None else tree
                                         for tree in (out_major, keep, lookup)))


def dequantize_params(params: Any, dtype=None, keep_consumed: bool = False) -> Any:
    """Inverse transform, used INSIDE the jitted forward so XLA fuses the
    dequant into consumers (int8 stays the HBM format). ``keep_consumed``
    leaves the leaves quantized whose consumer takes them as they are
    (``QuantizedTensor.consumed_int8``: the stacks of models/transformer.py
    MoEFFN and LatentAttention, the embedding table): XLA fuses a dequant into
    a plain dot, not into a grouped matmul or a gather, where it would write
    the whole leaf out."""
    import jax

    _register_pytree()

    def visit(leaf):
        if not isinstance(leaf, QuantizedTensor) or (keep_consumed and leaf.consumed_int8):
            return leaf
        return dequantize_array(leaf, dtype)

    return jax.tree.map(visit, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


def quantized_matmul(x, qt: QuantizedTensor, out_dtype=None):
    """Public int8-weight matmul for user components.

    Serving path is the XLA-fused dequant expression on every backend: the
    round-4 decision bench (earlier harness; record removed in PR 21)
    measured the explicit Pallas kernel at 0.55-0.79x the fused XLA
    expression on the decode GEMM shapes — XLA's fusion of
    convert+multiply into the consuming matmul beats the hand-tiled
    schedule here. The kernel stays available as
    ``ops.pallas_int8.int8_dense`` for explicit experiments."""
    out_dtype = out_dtype or qt.orig_dtype
    # dequant in the activation dtype (the compute dtype): XLA fuses the
    # convert+multiply into the matmul, weights stay int8 in HBM
    return (x @ dequantize_array(qt, x.dtype)).astype(out_dtype)


def quantized_bytes(params: Any) -> int:
    """HBM footprint of the (possibly mixed) tree — for reporting.

    Metadata-only on purpose: sizing from shape/dtype never touches the
    buffers, where the old ``np.asarray(leaf)`` pulled the ENTIRE tree
    (gigabytes at 7B) through the host just to read ``.size`` — a
    device->host sync per leaf (graftlint: host-sync-in-hot-path).
    """
    import math

    import jax

    _register_pytree()
    total = 0
    for leaf in jax.tree.leaves(params):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        itemsize = np.dtype(dtype).itemsize if dtype is not None else 8
        total += math.prod(shape) * itemsize
    return total
