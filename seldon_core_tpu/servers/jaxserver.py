"""JAX_SERVER: the native TPU prepackaged server.

This is the component that replaces the reference's delegation to external
native inference servers (`integrations/tfserving/TfServingProxy.py:20-125`,
`integrations/nvidia-inference-server/TRTProxy.py:31-81`): instead of proxying
to a C++ process over HTTP, the XLA-compiled model runs in-process on TPU.

Checkpoint layout at ``modelUri``:
    config.json   {"model": "<registry name>", "kwargs": {...},
                   "input_shape": [...], "input_dtype": "float32",
                   "batch_buckets": [1, 8, 64], "apply_kwargs": {...}}
    params/       orbax checkpoint of the param pytree (preferred), or
    params.msgpack  flax serialized params.

Serving path: request ndarray -> device staging with batch bucketing
(codec.staging) -> jitted apply (one compiled program per bucket) -> slice
back to the true batch. Optionally shards params + activations over a device
mesh via parallel.sharding for models larger than one chip.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, Optional, Sequence

import numpy as np

from seldon_core_tpu import storage
from seldon_core_tpu.codec.staging import DEFAULT_BUCKETS, pad_batch
from seldon_core_tpu.components.component import SeldonComponent
from seldon_core_tpu.contracts.payload import SeldonError

logger = logging.getLogger(__name__)


class JAXServer(SeldonComponent):
    def __init__(
        self,
        model_uri: str = "",
        model: Optional[str] = None,
        mesh: Optional[Any] = None,
        topology: Optional[Any] = None,
        param_sharding_rules: Optional[Any] = None,
        batch_buckets: Optional[Sequence[int]] = None,
        strict_sharding: bool = False,
        tensor_parallel: int = 0,
        quantize: str = "",
        param_dtype: str = "",
        **kwargs: Any,
    ):
        super().__init__(**kwargs)
        self.model_uri = model_uri
        self.model_name = model
        self.mesh = mesh
        # Injected device-world view (parallel/topology.py); None = adopt
        # the process topology at load() instead of re-deriving it here.
        self.topology = topology
        self.param_sharding_rules = param_sharding_rules
        self.strict_sharding = strict_sharding
        # Spec-reachable sharding: `tensor_parallel` arrives as a typed unit
        # parameter from the graph spec (the CR analogue of the reference's
        # per-predictor `replicas`, proto/seldon_deployment.proto:57) and
        # builds the standard ('data', 'model') serving mesh at load time.
        self.tensor_parallel = int(tensor_parallel)
        # "int8": weight-only PTQ — weights live in HBM as int8, dequant
        # fuses into the matmuls (ops/quantize.py)
        self.quantize = str(quantize or "")
        # Param-dtype cast at load ("auto" = module compute dtype). Off by
        # default: the on-chip A/B showed pre-cast bf16 params decode SLOWER
        # (XLA hoists the convert; see benchmarks/DECODE_NOTES.md). The knob
        # stays for HBM-residency-bound configs.
        self.param_dtype = param_dtype
        # None = the checkpoint's config.json "batch_buckets", else the
        # codec default ladder (resolved at load())
        self.batch_buckets = tuple(batch_buckets) if batch_buckets else None
        self.ready = False
        self._apply = None
        self._params = None
        self._config: Dict[str, Any] = {}

    # ------------------------------------------------------------------
    def load(self) -> None:
        if self.ready:
            return
        import jax
        import flax

        path = storage.download(self.model_uri)
        cfg_path = os.path.join(path, "config.json")
        if not os.path.exists(cfg_path):
            raise SeldonError(f"JAXServer checkpoint missing config.json at {path}", status_code=500)
        with open(cfg_path) as f:
            self._config = json.load(f)
        if self.batch_buckets is None:
            self.batch_buckets = tuple(
                self._config.get("batch_buckets") or DEFAULT_BUCKETS)

        from seldon_core_tpu.models import get_model

        name = self.model_name or self._config["model"]
        module = get_model(name, **self._config.get("kwargs", {}))
        self._module = module

        from seldon_core_tpu.parallel.topology import get_topology

        # adopted unconditionally, like LLMServer: detection is what puts
        # the platform and device_kind this server runs on into its log
        self.topology = self.topology or get_topology()
        if self.mesh is None and self.tensor_parallel > 1:
            n = self.topology.device_count
            if n % self.tensor_parallel:
                raise SeldonError(
                    f"tensor_parallel={self.tensor_parallel} does not divide "
                    f"{n} available devices",
                    status_code=500,
                )
            self.mesh = self.topology.serving_mesh(
                model_parallel=self.tensor_parallel)

        params = self._load_params(path)
        param_dtype = self._config.get("param_dtype", self.param_dtype)
        module_dtype = getattr(module, "dtype", None)
        if param_dtype and (param_dtype != "auto" or module_dtype is not None):
            # only "auto" needs the module's compute dtype; an explicit
            # param_dtype casts regardless of whether the module exposes one
            from seldon_core_tpu.servers.llmserver import _cast_params

            params = _cast_params(
                params, param_dtype, module_dtype or "float32"
            )
        apply_kwargs = self._config.get("apply_kwargs", {})

        def apply_fn(params, x):
            out = module.apply(params, x, **apply_kwargs)
            if isinstance(out, tuple):
                out = out[0]
            return out

        quantize = self.quantize or self._config.get("quantize", "")
        if quantize:
            if quantize != "int8":
                raise SeldonError(f"unsupported quantize={quantize!r} (int8 only)", status_code=500)
            # Composes with a mesh: shard_params places q under the weight's
            # logical spec and scale under its channel (last) axis, so int8
            # and tensor parallelism are no longer mutually exclusive.
            from seldon_core_tpu.ops.quantize import dequantize_params, quantize_params

            params = quantize_params(params)
            base_apply = apply_fn

            def apply_fn(params, x):  # noqa: F811 — quantized wrapper
                return base_apply(dequantize_params(params), x)

        if self.mesh is not None:
            from seldon_core_tpu.parallel.sharding import shard_apply

            # The jitted program shards the batch dim over the 'data' axis, so
            # every compiled bucket must be a multiple of its size — round the
            # buckets up (padding masks the remainder, sliced off on return).
            dp = dict(self.mesh.shape).get("data", 1)
            if dp > 1:
                self.batch_buckets = tuple(sorted({-(-b // dp) * dp for b in self.batch_buckets}))

            example_input = None
            shape = self._config.get("input_shape")
            if shape is not None:
                example_input = jax.ShapeDtypeStruct(
                    (1, *shape), jax.numpy.dtype(self._config.get("input_dtype", "float32"))
                )
            self._apply, params = shard_apply(
                apply_fn, module, params, self.mesh,
                rules=self.param_sharding_rules, example_input=example_input,
                strict=self.strict_sharding,
            )
        else:
            self._apply = jax.jit(apply_fn)
            # a msgpack restore yields host (numpy) arrays; left there, jit
            # uploads every weight again on every call (seen on the chip in
            # PR 21: 39 MB resident behind a 102 MB ResNet-50)
            params = jax.device_put(params)
        self._params = params
        self.ready = True
        logger.info("JAXServer loaded model %s from %s", name, path)

    def _load_params(self, path: str):
        import jax

        orbax_dir = os.path.join(path, "params")
        msgpack_file = os.path.join(path, "params.msgpack")
        if os.path.isdir(orbax_dir):
            import orbax.checkpoint as ocp

            ckptr = ocp.StandardCheckpointer()
            params = ckptr.restore(os.path.abspath(orbax_dir))
            return params
        if os.path.exists(msgpack_file):
            import flax.serialization

            from seldon_core_tpu.models import get_model

            # Build an abstract target so deserialization restores exact dtypes.
            module = self._module
            shape = self._config.get("input_shape")
            dtype = self._config.get("input_dtype", "float32")
            if shape is None:
                raise SeldonError("config.json needs input_shape to restore msgpack params", status_code=500)
            example = jax.ShapeDtypeStruct((1, *shape), jax.numpy.dtype(dtype))
            target = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jax.numpy.zeros(example.shape, example.dtype)))
            target = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), target)
            with open(msgpack_file, "rb") as f:
                blob = f.read()
            try:
                return flax.serialization.from_bytes(target, blob)
            except ValueError as orig:
                # params-only checkpoint (e.g. converted from HF): retry
                # against the params subtree; surface the original
                # diagnostic if that also fails
                if "params" not in target:
                    raise
                try:
                    return flax.serialization.from_bytes({"params": target["params"]}, blob)
                except ValueError:
                    raise orig
        raise SeldonError(f"No params found under {path} (expected params/ or params.msgpack)", status_code=500)

    # ------------------------------------------------------------------
    def predict(self, X: np.ndarray, names: Sequence[str], meta: Optional[Dict] = None):
        if not self.ready:
            self.load()
        # graftlint: allow-host-sync-in-hot-path(request ingress: X arrives as host payload from the transport, never a device array)
        arr = np.asarray(X)
        dtype = np.dtype(self._config.get("input_dtype", "float32"))
        if arr.dtype != dtype:
            arr = arr.astype(dtype)
        padded, true_n = pad_batch(arr, self.batch_buckets)
        out = self._apply(self._params, padded)
        # graftlint: allow-host-sync-in-hot-path(the sync predict API's one deliberate result sync: the response must carry host bytes; batching above this keeps the chip busy)
        return np.asarray(out)[:true_n]

    def jax_fn(self):
        if not self.ready:
            self.load()
        apply = self._apply

        def fn(params, x):
            return apply(params, x)

        return fn, self._params

    def class_names(self):
        return self._config.get("class_names")

    @property
    def input_dtype(self):
        """Declared request dtype from the checkpoint config."""
        return np.dtype(self._config.get("input_dtype", "float32"))


def export_checkpoint(
    out_dir: str,
    model: str,
    params: Any,
    kwargs: Optional[Dict[str, Any]] = None,
    input_shape: Optional[Sequence[int]] = None,
    input_dtype: str = "float32",
    apply_kwargs: Optional[Dict[str, Any]] = None,
    class_names: Optional[Sequence[str]] = None,
    use_orbax: bool = True,
    batch_buckets: Optional[Sequence[int]] = None,
) -> str:
    """Write a JAXServer-servable checkpoint directory."""
    os.makedirs(out_dir, exist_ok=True)
    cfg = {
        "model": model,
        "kwargs": kwargs or {},
        "input_dtype": input_dtype,
    }
    if input_shape is not None:
        cfg["input_shape"] = list(input_shape)
    if apply_kwargs:
        cfg["apply_kwargs"] = apply_kwargs
    if class_names:
        cfg["class_names"] = list(class_names)
    if batch_buckets:
        cfg["batch_buckets"] = [int(b) for b in batch_buckets]
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    if use_orbax:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckptr.save(os.path.abspath(os.path.join(out_dir, "params")), params)
        ckptr.wait_until_finished()
    else:
        import flax.serialization

        with open(os.path.join(out_dir, "params.msgpack"), "wb") as f:
            f.write(flax.serialization.to_bytes(params))
    return out_dir
