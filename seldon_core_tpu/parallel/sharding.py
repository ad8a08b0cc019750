"""Parameter + activation sharding via GSPMD.

Models in seldon_core_tpu.models carry flax *logical* axis names on their
params (param_with_axes). This module maps logical names onto mesh axes with a
rule table and jits the apply function with NamedShardings, letting XLA insert
all_gather/reduce_scatter/psum over ICI — the TPU-native replacement for the
reference's replica-per-pod scaling (SURVEY.md §2 parallelism note).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

# logical axis -> mesh axis (None = replicated). Megatron-style layout:
# hidden/ffn/head dims shard over 'model'; batch over 'data'; sequence over
# 'seq' (long-context); experts over 'expert'.
DEFAULT_LOGICAL_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", "data"),
    ("seq", None),
    ("embed", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("mlp", "model"),
    ("vocab", "model"),
    ("expert", "expert"),
)


def _rules_for_mesh(mesh, rules) -> list:
    """Drop rules whose mesh axis doesn't exist on this mesh."""
    available = set(mesh.axis_names)
    out = []
    for logical, physical in rules:
        out.append((logical, physical if physical in available else None))
    return out


def logical_axis_tree(module, example_input):
    """Abstract-init the module to recover the logical PartitionSpec tree for
    its params (the 'params_axes' collection), without allocating memory."""
    import jax

    def _init():
        x = example_input
        if isinstance(x, jax.ShapeDtypeStruct):
            x = jax.numpy.zeros(x.shape, x.dtype)
        return module.init(jax.random.PRNGKey(0), x)

    return logical_axes_of(jax.eval_shape(_init))


def logical_axes_of(abstract):
    """The logical PartitionSpec tree of an (abstractly) initialized
    module's variables; None where the module names no axes."""
    from flax.linen import partitioning as nn_partitioning

    if "params_axes" not in abstract:
        return None
    return nn_partitioning.get_axis_names(abstract["params_axes"])


# an output axis that the consumer splits into attention heads
HEAD_SPLIT_AXES = ("heads", "kv_heads")
# a first axis whose rows the consumer looks up by index (token ids)
ROW_LOOKUP_AXES = ("vocab",)


def _leaves_whose_spec(params: Any, logical_specs: Any, holds) -> Any:
    """A tree of bools shaped like ``params``: ``holds(spec)`` of each leaf's
    logical spec; False for every leaf where the module names no axes."""
    import jax

    if logical_specs is None:
        return jax.tree.map(lambda _: False, params)
    return jax.tree.map(
        lambda s: s is not None and holds(s), _align_specs(params, logical_specs),
        is_leaf=lambda x: x is None or _is_spec(x))


def head_split_outputs(params: Any, logical_specs: Any):
    """Per leaf of ``params``: is it a matrix whose OUTPUT axis is split into
    attention heads (the q/k/v projections of models/transformer.py
    Attention)? The TPU compiler fuses such a projection with the split and
    the rotary and reads the weight output-major, so that is how its int8
    values are held (ops/quantize.py, "the orientation rule"). No leaf where
    the module names no axes."""
    return _leaves_whose_spec(
        params, logical_specs, lambda s: len(s) == 2 and s[-1] in HEAD_SPLIT_AXES)


def row_lookups(params: Any, logical_specs: Any):
    """Per leaf of ``params``: is it a table whose ROWS are looked up by index
    (models/transformer.py's token embeddings, ``[vocab, embed]``; the head,
    ``[embed, vocab]``, is a matmul and is not)? No compiler pushes a dequant
    through a gather, so such a leaf reaches its module int8 and the rows are
    dequantized after the lookup (ops/quantize.py ``lookup_rows``). No leaf
    where the module names no axes."""
    return _leaves_whose_spec(
        params, logical_specs, lambda s: len(s) == 2 and s[0] in ROW_LOOKUP_AXES)


def float32_leaves(params: Any, logical_specs: Any):
    """Per leaf of ``params``: does it name one of models/leaves.py's
    FLOAT32_AXES (the stream mixing's maps, scalars and biases, the router's
    selection bias, a convolution's taps, a per-head norm's weight, the delta
    rule's A_log / dt_bias, the shared expert's scalar gate)? Such a leaf is
    precision-critical and small: no tree holds it in int8 or casts it to the
    serving dtype."""
    from seldon_core_tpu.models.leaves import FLOAT32_AXES

    return _leaves_whose_spec(
        params, logical_specs, lambda s: any(axis in FLOAT32_AXES for axis in s))


def shard_params(params: Any, mesh, logical_specs: Any, rules=DEFAULT_LOGICAL_RULES):
    """device_put the param pytree with NamedShardings from logical specs.
    Params without a spec (or when logical_specs is None) are replicated.

    Int8-quantized leaves (ops.quantize.QuantizedTensor) shard too: the
    weight's logical spec applies to ``q`` (same shape as the original float
    leaf; reversed with the array for a leaf held ``out_major``), and the
    per-output-channel ``scale`` [C] takes the spec's LAST axis (the channel
    dim it broadcasts over; a stack's [E, C] scale its first and last) — so
    int8 serving composes with tensor parallelism instead of excluding it."""
    import jax
    from flax.linen import partitioning as nn_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seldon_core_tpu.ops.quantize import QuantizedTensor

    rules = _rules_for_mesh(mesh, rules)
    replicated = NamedSharding(mesh, P())

    def is_q(x) -> bool:
        return isinstance(x, QuantizedTensor)

    if logical_specs is None:
        return jax.device_put(params, replicated)

    def to_mesh_spec(spec):
        return nn_partitioning.logical_to_mesh_axes(spec, rules=rules)

    def to_sharding(spec):
        return NamedSharding(mesh, P(*to_mesh_spec(spec)))

    flat_p, treedef_p = jax.tree.flatten(params, is_leaf=is_q)
    specs_for_params = _align_specs(params, logical_specs, extra_leaf=is_q)
    flat_s, _ = jax.tree.flatten(specs_for_params, is_leaf=lambda x: x is None or _is_spec(x))
    if len(flat_s) != len(flat_p):
        logger.warning("param/spec tree mismatch (%d vs %d); replicating params", len(flat_p), len(flat_s))
        return jax.device_put(params, replicated)
    out = []
    for p, s in zip(flat_p, flat_s):
        if is_q(p):
            if s is not None:
                mesh_spec = list(to_mesh_spec(s))
                wsh = NamedSharding(
                    mesh, P(*(mesh_spec[::-1] if p.out_major else mesh_spec)))
                last = mesh_spec[-1] if mesh_spec else None
                # a stack's scale [E, C] keeps the stack axis beside the channel
                ssh = NamedSharding(
                    mesh, P(mesh_spec[0], last) if p.stacked else P(last))
            else:
                wsh = ssh = replicated
            # (replace: the static metadata rides along, whatever it holds)
            out.append(dataclasses.replace(
                p, q=jax.device_put(p.q, wsh), scale=jax.device_put(p.scale, ssh)))
        else:
            out.append(jax.device_put(p, to_sharding(s) if s is not None else replicated))
    return jax.tree.unflatten(treedef_p, out)


def _is_spec(x) -> bool:
    from jax.sharding import PartitionSpec

    return isinstance(x, (tuple, PartitionSpec))


def _align_specs(params: Any, logical_specs: Any, extra_leaf=None):
    """The params tree may contain collections (params/batch_stats) while the
    axes tree covers only 'params'. Walk params and pull matching specs, None
    where absent. ``extra_leaf`` marks additional leaf types (quantized
    tensors) so the walk doesn't descend into them."""
    import jax

    spec_map = {}

    def record(path, leaf):
        spec_map[tuple(str(k) for k in path)] = leaf

    jax.tree_util.tree_map_with_path(record, logical_specs, is_leaf=_is_spec)

    def lookup(path, leaf):
        key = tuple(str(k) for k in path)
        # try suffix match: params tree has a leading collection key
        if key in spec_map:
            return spec_map[key]
        if len(key) > 1 and key[1:] in spec_map:
            return spec_map[key[1:]]
        return None

    return jax.tree_util.tree_map_with_path(lookup, params, is_leaf=extra_leaf)


def sharding_report(params: Any) -> dict:
    """Inspect the actual ``.sharding`` of every array leaf: how many leaves
    are sharded vs replicated, and which mesh axes carry shards. This is the
    guard against the silent full-replication fallback — tests and strict
    callers assert on it rather than trusting that shard_params worked."""
    import jax
    from jax.sharding import NamedSharding

    report = {"sharded": 0, "replicated": 0, "other": 0, "axes": set()}

    def visit(leaf):
        sh = getattr(leaf, "sharding", None)
        if not isinstance(sh, NamedSharding):
            report["other"] += 1
            return
        axes = set()
        for entry in sh.spec:
            if entry is None:
                continue
            axes.update(entry if isinstance(entry, tuple) else (entry,))
        # Axes of size 1 don't partition anything.
        axes = {a for a in axes if sh.mesh.shape[a] > 1}
        if axes:
            report["sharded"] += 1
            report["axes"] |= axes
        else:
            report["replicated"] += 1

    jax.tree.map(visit, params)
    return report


def shard_apply(
    apply_fn: Callable,
    module,
    params: Any,
    mesh,
    rules=None,
    example_input=None,
    batch_axis: str = "data",
    strict: bool = False,
):
    """Return (jitted_apply, sharded_params) for mesh execution.

    - params shard per the module's logical axis names (replicated fallback);
    - inputs/outputs shard their leading batch dim over ``batch_axis``;
    - the mesh is installed as context so flax sharding constraints resolve.
    - ``strict=True`` raises if the mesh has a non-trivial parameter axis
      (any axis other than ``batch_axis`` with size > 1) but no param leaf
      actually sharded over it — i.e. the replication fallback fired on a
      mesh that was supposed to partition the model.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rules = tuple(rules) if rules is not None else DEFAULT_LOGICAL_RULES

    logical_specs = None
    if example_input is not None:
        try:
            logical_specs = logical_axis_tree(module, example_input)
        except Exception as e:
            logger.warning("could not derive logical axes (%s); replicating params", e)
    sharded_params = shard_params(params, mesh, logical_specs, rules)

    param_axes = {a for a in mesh.axis_names if a != batch_axis and mesh.shape[a] > 1}
    if param_axes:
        report = sharding_report(sharded_params)
        if not (report["axes"] & param_axes):
            msg = (
                f"mesh has parameter axes {sorted(param_axes)} but every param "
                f"leaf is replicated (report: sharded={report['sharded']} "
                f"replicated={report['replicated']}) — the logical-axis spec "
                "did not align with the param tree"
            )
            if strict:
                raise ValueError(msg)
            logger.warning(msg)

    batch_sharding = NamedSharding(mesh, P(batch_axis))
    replicated = NamedSharding(mesh, P())

    jitted = jax.jit(
        apply_fn,
        in_shardings=(None, batch_sharding),
        out_shardings=batch_sharding,
    )

    def run(p, x):
        with mesh:
            return jitted(p, x)

    return run, sharded_params
