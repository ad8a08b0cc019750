"""Virtual-mesh conformance harness: the dynamic half of shardlint.

The static rules prove axis names and device worlds are DECLARED; this
harness proves the declarations survive compilation. Every sharded
serving contract — the ``shard_apply`` predict path that JAX_SERVER
jits, and the LLMServer decode scan that the hlolint TP contract pins —
is lowered under three virtual 8-device mesh shapes (data x model =
1x8, 2x4, 4x2) and the COMPILED executable's input/output shardings are
compared leaf-by-leaf against specs computed independently from the
declared sources of truth:

- params: the logical-axis tree (``param_with_axes`` names) mapped
  through DEFAULT_LOGICAL_RULES — recomputed here, NOT read back from
  ``shard_params``'s output, so a drift between the rule table and the
  placement code goes red;
- KV caches: ``LLMServer._cache_shardings`` (the declared decode
  ``in_shardings``), which donation must carry to the outputs — the
  mid-stream-recovery snapshots depend on the compiled cache layout
  matching the declared one;
- activations: batch over the ``data`` axis on both ends of predict.

A mismatch is emitted as a JSON shard-spec diff (``--diff-out``) naming
the shape, cell, leaf path, declared spec, and compiled spec — the
artifact CI uploads when the multi-chip dryrun step fails.

    python -m tools.shardlint.conformance [--shapes 1x8,2x4,4x2]
                                          [--diff-out FILE]
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

# data x model factorization of the 8-device virtual mesh -> model_parallel
SHAPES = {"1x8": 8, "2x4": 4, "4x2": 2}

# decode-contract dims, matching tools/hlolint/contracts.py
PLEN = 16
MAX_LEN = 24
N_STEPS = 7

CONFORMANCE_MODEL = "shardlint-conformance-tiny"


def _ensure_model():
    """Register the conformance transformer: llama-tiny's n_heads=4 /
    n_kv_heads=2 don't divide the 4- and 8-wide model axes, so the
    harness carries its own tiny config whose head counts divide every
    tested shape (8 heads, 8 KV heads, dim 64, ffn 128, vocab 256)."""
    from seldon_core_tpu.models import register_model
    from seldon_core_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )
    import jax.numpy as jnp

    def make(dtype: str = "float32", **kwargs):
        cfg = TransformerConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=8, n_kv_heads=8,
            ffn_dim=128, max_seq_len=128, dtype=jnp.dtype(dtype),
            tie_embeddings=True, **kwargs,
        )
        return Transformer(cfg)

    register_model(CONFORMANCE_MODEL, make)


def _topology():
    from seldon_core_tpu.parallel.topology import Topology

    topo = Topology.detect()
    if topo.device_count != 8:
        raise RuntimeError(
            f"conformance needs the 8-device virtual mesh, got "
            f"{topo.device_count} (ensure_platform() must run before jax "
            "initializes)")
    return topo


def _spec_str(sharding) -> str:
    spec = getattr(sharding, "spec", sharding)
    return str(spec)


def _compare(declared_leaves, compiled_leaves, ndims, sites, shape_name,
             cell, mismatches: List[Dict]):
    """declared None = unconstrained leaf: recorded, never a mismatch."""
    for declared, compiled, ndim, site in zip(
            declared_leaves, compiled_leaves, ndims, sites):
        if declared is None:
            continue
        ok = declared.is_equivalent_to(compiled, ndim)
        if not ok:
            mismatches.append({
                "shape": shape_name,
                "cell": cell,
                "site": site,
                "declared": _spec_str(declared),
                "compiled": _spec_str(compiled),
            })


def _declared_param_shardings(module, mesh):
    """The independently-computed declared placement: logical axis names
    -> mesh axes via the rule table, replicated when unnamed."""
    import jax
    from flax.linen import partitioning as nn_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seldon_core_tpu.parallel.sharding import (
        DEFAULT_LOGICAL_RULES,
        _rules_for_mesh,
        logical_axis_tree,
    )

    logical = logical_axis_tree(
        module, jax.ShapeDtypeStruct((1, 8), jax.numpy.int32))
    rules = _rules_for_mesh(mesh, DEFAULT_LOGICAL_RULES)
    replicated = NamedSharding(mesh, P())

    def to_sharding(spec):
        if spec is None:
            return replicated
        mesh_axes = nn_partitioning.logical_to_mesh_axes(spec, rules=rules)
        return NamedSharding(mesh, P(*mesh_axes))

    return jax.tree.map(
        to_sharding, logical,
        is_leaf=lambda x: x is None or isinstance(x, tuple))


def _leaf_paths(tree):
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(p) for p, _ in flat]


def check_predict_cell(topo, model_parallel: int, shape_name: str,
                       mismatches: List[Dict]) -> int:
    """Cell A: the shard_apply predict path. Params shard by logical
    rules, activations by batch over 'data'; the compiled program must
    agree on every leaf."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.parallel.sharding import shard_apply

    module = get_model(CONFORMANCE_MODEL)
    mesh = topo.mesh({"data": -1, "model": model_parallel})
    params = jax.jit(module.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def apply_fn(p, x):
        out = module.apply(p, x)
        if isinstance(out, tuple):
            out = out[0]
        return out

    # strict=True: the replication fallback firing on a model axis IS a
    # conformance failure, not a warning
    _, sharded = shard_apply(
        apply_fn, module, params, mesh,
        example_input=jax.ShapeDtypeStruct((1, 8), jnp.int32), strict=True)

    batch = NamedSharding(mesh, P("data"))
    jitted = jax.jit(apply_fn, in_shardings=(None, batch),
                     out_shardings=batch)
    x = jax.ShapeDtypeStruct((8, 8), jnp.int32)
    compiled = jitted.lower(sharded, x).compile()

    declared_tree = _declared_param_shardings(module, mesh)
    declared = jax.tree.leaves(declared_tree) + [batch]
    sites = ["params" + s for s in _leaf_paths(declared_tree)] + ["x"]
    arg_leaves = jax.tree.leaves(sharded) + [x]
    ndims = [a.ndim for a in arg_leaves]
    compiled_in = jax.tree.leaves(compiled.input_shardings[0])
    if len(compiled_in) != len(declared):
        raise RuntimeError(
            f"{shape_name}/predict: {len(compiled_in)} compiled input "
            f"leaves vs {len(declared)} declared")
    _compare(declared, compiled_in, ndims, sites, shape_name, "predict",
             mismatches)

    out = jax.tree.leaves(compiled.output_shardings)
    _compare([batch], out[:1], [3], ["logits"], shape_name, "predict",
             mismatches)
    return len(declared) + 1


def check_decode_cell(topo, model_parallel: int, shape_name: str,
                      mismatches: List[Dict]) -> int:
    """Cell B: the LLMServer decode scan (the hlolint TP contract's
    function) with the topology INJECTED — the server must build its
    mesh from the given world view, and the compiled cache shardings
    must match the declared ``_cache_shardings`` on inputs AND outputs
    (donation aliasing: the mid-stream snapshot layout)."""
    import jax

    from seldon_core_tpu.models.cache import init_kv_caches
    from seldon_core_tpu.servers.llmserver import LLMServer

    s = LLMServer(
        model=CONFORMANCE_MODEL, model_kwargs={"dtype": "bfloat16"},
        init_random=True, max_new_tokens=N_STEPS + 1,
        len_buckets=(PLEN,), batch_buckets=(1,), seed=7,
        kv_cache_dtype="int8", tensor_parallel=model_parallel,
        topology=topo,
    )
    s.load()
    assert s.topology is topo, "server must adopt the injected topology"

    fn = s._get_decode(1, MAX_LEN, donate=True)
    caches = jax.eval_shape(
        lambda: init_kv_caches(s._cfg, 1, MAX_LEN, s.kv_cache_dtype))
    sds = jax.ShapeDtypeStruct
    compiled = fn.lower(
        s._params, caches, sds((1,), "int32"), sds((1,), "int32"),
        N_STEPS, sds((2,), "uint32"), sds((), "float32")).compile()

    declared_params_tree = _declared_param_shardings(s._module, s.mesh)
    declared_caches = s._cache_shardings(1, MAX_LEN)
    if declared_caches is None:
        raise RuntimeError(
            f"{shape_name}/decode: _cache_shardings declared nothing — the "
            "conformance model's KV heads must shard on every tested shape")

    p_leaves = jax.tree.leaves(declared_params_tree)
    c_leaves = jax.tree.leaves(declared_caches)
    declared = p_leaves + c_leaves + [None] * 4
    sites = (["params" + s_ for s_ in _leaf_paths(declared_params_tree)]
             + ["caches" + s_ for s_ in _leaf_paths(declared_caches)]
             + ["last_tok", "true_len", "rng", "temperature"])
    arg_leaves = (jax.tree.leaves(s._params) + jax.tree.leaves(caches)
                  + [sds((1,), "int32"), sds((1,), "int32"),
                     sds((2,), "uint32"), sds((), "float32")])
    ndims = [a.ndim for a in arg_leaves]
    compiled_in = jax.tree.leaves(compiled.input_shardings[0])
    if len(compiled_in) != len(declared):
        raise RuntimeError(
            f"{shape_name}/decode: {len(compiled_in)} compiled input "
            f"leaves vs {len(declared)} declared")
    _compare(declared, compiled_in, ndims, sites, shape_name, "decode",
             mismatches)

    # outputs: (tokens [1, n_steps], caches) — donation must carry the
    # declared cache layout through to the aliased outputs
    out_leaves = jax.tree.leaves(compiled.output_shardings)
    cache_out = out_leaves[1:]
    cache_ndims = [a.ndim for a in jax.tree.leaves(caches)]
    if len(cache_out) != len(c_leaves):
        raise RuntimeError(
            f"{shape_name}/decode: {len(cache_out)} compiled cache outputs "
            f"vs {len(c_leaves)} declared")
    _compare(c_leaves, cache_out, cache_ndims,
             ["caches.out" + s_ for s_ in _leaf_paths(declared_caches)],
             shape_name, "decode", mismatches)
    return len(declared) + len(c_leaves)


def run_conformance(shapes=None, cells=("predict", "decode")):
    """Returns (report dict, mismatches list)."""
    from tools.hlolint.contracts import ensure_platform

    ensure_platform()
    _ensure_model()
    topo = _topology()

    mismatches: List[Dict] = []
    report: Dict[str, Dict] = {}
    for name in shapes or sorted(SHAPES):
        tp = SHAPES[name]
        checked: Dict[str, int] = {}
        if "predict" in cells:
            checked["predict"] = check_predict_cell(
                topo, tp, name, mismatches)
        if "decode" in cells:
            checked["decode"] = check_decode_cell(topo, tp, name, mismatches)
        report[name] = {
            "model_parallel": tp,
            "leaves_checked": checked,
            "mismatches": sum(1 for m in mismatches if m["shape"] == name),
        }
    return report, mismatches


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.shardlint.conformance",
        description="virtual-mesh shard-spec conformance "
                    "(docs/static-analysis.md)")
    parser.add_argument("--shapes", default=None,
                        help="comma-separated subset of: "
                             + ", ".join(sorted(SHAPES)))
    parser.add_argument("--cells", default="predict,decode",
                        help="comma-separated subset of: predict, decode")
    parser.add_argument("--diff-out", default=None, metavar="FILE",
                        help="write the shard-spec diff JSON here "
                             "(always written when given; empty diff = "
                             "conformant)")
    args = parser.parse_args(argv)

    shapes = None
    if args.shapes:
        shapes = [s.strip() for s in args.shapes.split(",")]
        unknown = set(shapes) - set(SHAPES)
        if unknown:
            print(f"conformance: unknown shape(s): "
                  f"{', '.join(sorted(unknown))}", file=sys.stderr)
            return 2
    cells = tuple(c.strip() for c in args.cells.split(","))
    unknown = set(cells) - {"predict", "decode"}
    if unknown:
        print(f"conformance: unknown cell(s): {', '.join(sorted(unknown))}",
              file=sys.stderr)
        return 2

    report, mismatches = run_conformance(shapes, cells)

    if args.diff_out:
        with open(args.diff_out, "w") as f:
            json.dump({"report": report, "mismatches": mismatches}, f,
                      indent=2)

    for m in mismatches:
        print(f"{m['shape']}/{m['cell']} {m['site']}: declared "
              f"{m['declared']} but compiled {m['compiled']}")
    for name, r in report.items():
        print(f"conformance {name} (model={r['model_parallel']}): "
              f"{r['leaves_checked']} leaves checked, "
              f"{r['mismatches']} mismatch(es)", file=sys.stderr)
    return 1 if mismatches else 0


if __name__ == "__main__":
    sys.exit(main())
