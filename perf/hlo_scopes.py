"""Device self time of the ops under a `jax.named_scope`, by jitted program.

    python hlo_scopes.py <trace.xplane.pb> <out.json> <scope names as a JSON list>

A v5e trace's op events carry no scope (perf/scope_times.py), but the trace
itself holds every traced program's HLO: the plane `/host:metadata` has one
event metadata a module, named like the module's events on the device's
`XLA Modules` line (`jit_decode_step(<program id>)`), with the serialized
HloProto as its `Hlo Proto` stat.  An instruction's `metadata.op_name` there
is the path of scopes it was traced under (`jit(decode_step)/.../attn/
attn.latent.read/dot_general`), and its `name` is the op event's
(`%fusion.12 = ...` -> `fusion.12`).  So: op event -> module running then ->
that module's instruction of the same name -> its scope path.  A fusion is
under a scope when its own `op_name` is (the compiler gives a fusion its root's)
or, where it has none, when its fused computation's root is.

An op's time is its own, less the ops inside it (perf/trace.py).  The protos
are read with the tensorflow package's compiled descriptors; where those
cannot be imported, or the trace has no metadata plane, nothing is marked.
Runs as a helper child (the benchmark's parent imports neither JAX nor TF).
"""

from __future__ import annotations

import bisect
import json
import re
import sys

from scope_times import self_seconds

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
METADATA_PLANE = "/host:metadata"
TOP = 12


def instruction_scopes(hlo_bytes: bytes, hlo_pb2) -> dict:
    """{instruction name: scope path} of one module."""
    proto = hlo_pb2.HloProto()
    proto.ParseFromString(hlo_bytes)
    roots, paths, calls = {}, {}, {}
    for comp in proto.hlo_module.computations:
        by_id = {ins.id: ins for ins in comp.instructions}
        root = by_id.get(comp.root_id)
        roots[comp.id] = root.metadata.op_name if root is not None else ""
        for ins in comp.instructions:
            paths[ins.name] = ins.metadata.op_name
            if ins.opcode == "fusion" and ins.called_computation_ids:
                calls[ins.name] = ins.called_computation_ids[0]
    for name, comp_id in calls.items():
        if not paths[name]:
            paths[name] = roots.get(comp_id, "")
    return paths


def op_name(raw: str) -> str:
    """`%fusion.12 = bf16[8,128]{1,0} fusion(...)` -> `fusion.12`."""
    m = re.match(r"%?([\w.\-]+)", raw)
    return m.group(1) if m else raw


def reduce(path: str, scopes: list) -> dict:
    out: dict = {"programs": {}, "modules_with_hlo": 0}
    try:
        from tensorflow.compiler.xla.service import hlo_pb2
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as exc:  # noqa: BLE001 - no descriptors, nothing to read
        out["error"] = f"{type(exc).__name__}: {exc}"
        return out
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    by_module: dict = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat_names.get(stat.metadata_id) == "Hlo Proto" and stat.bytes_value:
                    by_module[meta.name] = instruction_scopes(stat.bytes_value, hlo_pb2)
    out["modules_with_hlo"] = len(by_module)
    devices = [p for p in space.planes if DEVICE_PLANE.match(p.name)]
    if not devices or not by_module:
        return out
    device = devices[0]
    names = {k: v.name for k, v in device.event_metadata.items()}
    modules, ops = [], []
    for line in device.lines:
        if line.name not in ("XLA Modules", "XLA Ops"):
            continue
        t0 = line.timestamp_ns * 1e-9
        for e in line.events:
            start = t0 + e.offset_ps * 1e-12
            event = (start, start + e.duration_ps * 1e-12, names.get(e.metadata_id, ""))
            (modules if line.name == "XLA Modules" else ops).append(event)
    modules.sort()
    starts = [m[0] for m in modules]
    for start, end, raw in modules:
        prog = out["programs"].setdefault(
            re.sub(r"\(\d+\)$", "", raw),
            {"calls": 0, "seconds": 0.0, "scoped_s": 0.0, "ops": {}})
        prog["calls"] += 1
        prog["seconds"] += end - start

    def scoped(start: float, raw: str) -> str:
        """The op's label if it ran under one of the scopes, else ''."""
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= modules[i][1]:
            return ""
        path = by_module.get(modules[i][2], {}).get(op_name(raw), "")
        return raw[:90] if any(scope in path for scope in scopes) else ""

    for start, own, label in self_seconds([(s, e, scoped(s, raw)) for s, e, raw in ops]):
        i = bisect.bisect_right(starts, start) - 1
        if label and i >= 0 and start < modules[i][1]:
            prog = out["programs"][re.sub(r"\(\d+\)$", "", modules[i][2])]
            prog["scoped_s"] += own
            prog["ops"][label] = prog["ops"].get(label, 0.0) + own
    for prog in out["programs"].values():   # the largest scoped ops, for whoever checks the marks
        prog["ops"] = sorted(prog["ops"].items(), key=lambda kv: -kv[1])[:TOP]
    return out


def main() -> None:
    path, out_path, scopes = sys.argv[1], sys.argv[2], json.loads(sys.argv[3])
    with open(out_path, "w") as f:
        json.dump(reduce(path, scopes), f)


if __name__ == "__main__":
    main()
