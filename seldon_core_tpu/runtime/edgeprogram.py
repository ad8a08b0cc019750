"""Edge-program compiler: PredictorSpec -> native edge graph program.

The control plane compiles inference graphs whose every unit is a builtin
(the reference's in-engine hardcoded units, `engine/src/main/java/io/seldon/
engine/predictors/PredictorConfigBean.java:77-82`) into a compact JSON
program that the native edge server (native/edge.cc) executes without
touching Python — the compiled-orchestrator hot path that the reference gets
from its Java engine. Graphs with any other unit (JAX models, remote
endpoints, stateful routers) return None and are served by the Python engine
behind the edge's shared-memory-ring fallback.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

from seldon_core_tpu.contracts.graph import (
    PredictiveUnit,
    PredictorSpec,
    UnitImplementation,
)

_NATIVE_KINDS = {
    UnitImplementation.SIMPLE_MODEL: "SIMPLE_MODEL",
    UnitImplementation.SIMPLE_ROUTER: "SIMPLE_ROUTER",
    UnitImplementation.RANDOM_ABTEST: "RANDOM_ABTEST",
    UnitImplementation.AVERAGE_COMBINER: "AVERAGE_COMBINER",
    # Stateful bandits execute natively too (per-edge-process state, the
    # multi-replica model of analytics/routers.py); seeded instances also
    # run native — the edge replays the numpy/CPython streams bit-exactly
    # (np_rng.h: PCG64 + Lemire integers + ziggurat gamma/beta).
    UnitImplementation.EPSILON_GREEDY: "EPSILON_GREEDY",
    UnitImplementation.THOMPSON_SAMPLING: "THOMPSON_SAMPLING",
}

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native"
)
EDGE_BINARY = os.path.join(_NATIVE_DIR, "build", "seldon_edge")
LOADGEN_BINARY = os.path.join(_NATIVE_DIR, "build", "seldon_loadgen")


# Golden draws recorded from numpy 2.0.2 — the version the checked-in
# ziggurat tables (native/ziggurat_tables.h) and np_rng.h replay logic were
# extracted from and verified against. Seeded-native routing is only sound
# when the INSTALLED numpy produces these exact streams: the native edge
# replays numpy draw-for-draw, and the Python engine plane uses the installed
# numpy directly, so any drift would silently desync the two planes
# (ADVICE.md round 5). pyproject pins numpy to a known-good range; this probe
# is the belt-and-braces runtime check before enabling seeded-native compile.
_NUMPY_PARITY_SEED_BETA = 20260803
_NUMPY_PARITY_BETA = (
    ((1.0, 1.0), 0.8861055853627264),
    ((0.5, 0.5), 0.2187824033435847),
    ((2.5, 1.7), 0.6781937015134641),
    ((9.3, 0.2), 0.9919305747956653),
)
_NUMPY_PARITY_SEED_GAMMA = 7
_NUMPY_PARITY_GAMMA = (
    (0.4, 0.309950474806918),
    (1.0, 0.5685486573832514),
    (3.7, 1.982692295846162),
)
_NUMPY_PARITY_SEED_INT = 123
_NUMPY_PARITY_INTEGERS = (15, 682, 592, 53)
_NUMPY_PARITY_UNIFORM = (0.22035987277261138, 0.1843718106986697)

_numpy_parity_cache: Optional[bool] = None


def numpy_stream_parity_ok() -> bool:
    """Cheap startup probe: do the installed numpy's Generator streams
    (beta/gamma ziggurat paths, Lemire integers, uniform doubles) still match
    the numpy 2.0.2 goldens the native replay was extracted from? Bit-exact
    comparison — parity is all-or-nothing. Cached after the first call."""
    global _numpy_parity_cache
    if _numpy_parity_cache is not None:
        return _numpy_parity_cache
    import numpy as np

    ok = True
    try:
        g = np.random.Generator(np.random.PCG64(_NUMPY_PARITY_SEED_BETA))
        ok &= all(g.beta(a, b) == want for (a, b), want in _NUMPY_PARITY_BETA)
        g = np.random.Generator(np.random.PCG64(_NUMPY_PARITY_SEED_GAMMA))
        ok &= all(g.standard_gamma(shape) == want for shape, want in _NUMPY_PARITY_GAMMA)
        g = np.random.Generator(np.random.PCG64(_NUMPY_PARITY_SEED_INT))
        ok &= tuple(g.integers(0, 1000, 4).tolist()) == _NUMPY_PARITY_INTEGERS
        ok &= tuple(g.random(2).tolist()) == _NUMPY_PARITY_UNIFORM
    except Exception:
        ok = False
    if not ok:
        import logging

        logging.getLogger(__name__).warning(
            "installed numpy %s diverges from the 2.0.2 streams the native "
            "tables were extracted from; seeded units stay on the Python "
            "engine (native replay would desync)", np.__version__,
        )
    _numpy_parity_cache = bool(ok)
    return _numpy_parity_cache


def build_edge_binaries() -> bool:
    """Bring the native edge/loadgens up to date (one ``make`` covers every
    target — native/staging.py ``build_native``); False only when this
    machine has no toolchain and nothing built. A compile that fails
    raises with make's stderr."""
    from seldon_core_tpu.native.staging import ToolchainMissing, build_native

    try:
        build_native()
    except ToolchainMissing:
        return False
    return all(os.path.exists(b) for b in
               (EDGE_BINARY, LOADGEN_BINARY, LOADGEN_BINARY + "_grpc"))


def compile_edge_program(
    spec: PredictorSpec,
    deployment: str = "",
    predictor: str = "",
    device_components: Optional[Dict[str, Any]] = None,
) -> Optional[Dict[str, Any]]:
    """Return the native edge program for this graph, or None if any unit
    cannot execute natively (the edge then runs in ring-fallback mode).

    ``device_components`` (unit name -> live SeldonComponent) additionally
    compiles leaf MODEL units backed by real in-process models (JAXServer,
    sklearn, user components) to DEVICE_MODEL nodes: the edge executes the
    graph natively and ships only the packed tensor over the ring to the
    engine process's ModelExecutor (transport/ipc.py kind 2), which owns the
    device and micro-batches concurrent calls. Eligibility per unit: MODEL
    type, no children, a plain ``predict`` (components overriding
    ``predict_raw`` need the full SeldonMessage and fall back)."""
    units: List[Dict[str, Any]] = []
    device_models: List[str] = []

    def _device_eligible(unit: PredictiveUnit, method: str) -> Optional[Any]:
        from seldon_core_tpu.components.component import _has_impl, has_raw

        if not device_components or unit.name not in device_components:
            return None
        component = device_components[unit.name]
        if component is None or not _has_impl(component, method) \
                or has_raw(component, method):
            return None
        if _has_impl(component, "send_feedback") or has_raw(component, "send_feedback"):
            # native feedback handling is bandit-only; a component that
            # learns from feedback must keep the Python engine in the loop
            return None
        if getattr(component, "is_async", False):
            return None
        return component

    def compile_device_unit(unit: PredictiveUnit, transformed: bool) -> Optional[int]:
        from seldon_core_tpu.contracts.graph import UnitType

        if unit.type == UnitType.TRANSFORMER and len(unit.children) == 1:
            # input transformer (e.g. an outlier detector) feeding a device
            # subtree: its transformed output flows to the child as a
            # deferred ring call chain
            component = _device_eligible(unit, "transform_input")
            if component is None:
                return None
            child = compile_unit(unit.children[0], transformed=True)
            if child is None:
                return None
            units.append({
                "name": unit.name,
                "kind": "DEVICE_TRANSFORM",
                "children": [child],
                "modelId": len(device_models),
                "className": type(component).__name__,
            })
            device_models.append(unit.name)
            return len(units) - 1
        if unit.children:
            return None  # a device model's output feeding a chain stays Python
        if unit.type not in (None, UnitType.MODEL):
            return None
        component = _device_eligible(unit, "predict")
        if component is None:
            return None
        units.append({
            "name": unit.name,
            "kind": "DEVICE_MODEL",
            "children": [],
            "modelId": len(device_models),
            "className": type(component).__name__,
        })
        device_models.append(unit.name)
        return len(units) - 1

    def compile_unit(unit: PredictiveUnit, transformed: bool = False) -> Optional[int]:
        kind = _NATIVE_KINDS.get(unit.implementation)
        if kind is None:
            return compile_device_unit(unit, transformed)
        if transformed and kind in ("SIMPLE_MODEL",):
            # a stub consuming a device-transformed value would need the
            # transformed row count at eval time, which isn't known until
            # the ring call completes — keep such graphs on the Python engine
            return None
        params = unit.parameters_dict()
        if str(params.get("python_routing", "")).lower() in ("true", "1"):
            # Seeded determinism scope: each serving PLANE replays its own
            # exact stream from the seed (same per-replica model as
            # multi-worker edges / multi-replica engines). Traffic that
            # splits across planes (e.g. strData riding the ring while
            # tensors run native) therefore interleaves two streams. A
            # deployment that needs ONE globally-deterministic stream sets
            # python_routing=true on the router to pin it to the Python
            # engine — the pre-round-4 behavior.
            return None
        try:
            seed = params.get("seed")
            seed = None if seed is None else int(seed)
            if seed is not None and not 0 <= seed < 2**53:
                # negative (numpy raises) or beyond double precision (the
                # program JSON carries numbers as doubles): Python plane
                return None
        except (TypeError, ValueError):
            return None
        if seed is not None and not numpy_stream_parity_ok():
            # installed numpy drifted from the recorded 2.0.2 streams: the
            # native replay would silently desync from the Python plane, so
            # seeded units fall back to the Python engine
            return None
        if kind in ("EPSILON_GREEDY", "THOMPSON_SAMPLING"):
            # Parameters the Python constructor would reject must surface as
            # its build error, so invalid specs fall back rather than getting
            # a silently different native default. Only the params each kind
            # actually consumes are checked — the components ignore foreign
            # kwargs, and a foreign param must not cost native execution.
            try:
                n_branches = int(params.get("n_branches", 2))
                if n_branches < 1:
                    return None
                if kind == "EPSILON_GREEDY":
                    if not 0.0 <= float(params.get("epsilon", 0.1)) <= 1.0:
                        return None
                    if not 0 <= int(params.get("best_branch", 0)) < n_branches:
                        return None
                else:
                    if float(params.get("alpha", 1.0)) <= 0:
                        return None
                    if float(params.get("beta", 1.0)) <= 0:
                        return None
            except (TypeError, ValueError):
                return None
        children: List[int] = []
        for child in unit.children:
            idx = compile_unit(child, transformed=transformed)
            if idx is None:
                return None
            children.append(idx)
        out: Dict[str, Any] = {"name": unit.name, "kind": kind, "children": children}
        if kind == "RANDOM_ABTEST":
            out["ratioA"] = float(params.get("ratioA", 0.5))
            out["nBranches"] = int(params.get("n_branches", 2))
            if seed is not None:
                out["seed"] = seed
        elif kind == "EPSILON_GREEDY":
            out["nBranches"] = int(params.get("n_branches", 2))
            out["epsilon"] = float(params.get("epsilon", 0.1))
            out["bestBranch"] = int(params.get("best_branch", 0))
            if seed is not None:
                out["seed"] = seed
        elif kind == "THOMPSON_SAMPLING":
            out["nBranches"] = int(params.get("n_branches", 2))
            out["alpha"] = float(params.get("alpha", 1.0))
            out["beta"] = float(params.get("beta", 1.0))
            if seed is not None:
                # the edge replays Generator.beta draw-for-draw
                # (np_rng.h standard_gamma/beta over the extracted
                # ziggurat tables, proven by test_np_rng_gamma_beta_parity)
                out["seed"] = seed
        units.append(out)
        return len(units) - 1

    root = compile_unit(spec.graph)
    if root is None:
        return None
    program = {
        "deployment": deployment,
        "predictor": predictor or spec.name,
        "native": True,
        "units": units,
        "root": root,
    }
    if device_models:
        program["deviceModels"] = device_models
    return program


def fallback_program(spec: PredictorSpec, deployment: str = "", predictor: str = "") -> Dict[str, Any]:
    return {
        "deployment": deployment,
        "predictor": predictor or spec.name,
        "native": False,
    }


def write_program(program: Dict[str, Any], path: str) -> str:
    with open(path, "w") as f:
        json.dump(program, f)
    return path
