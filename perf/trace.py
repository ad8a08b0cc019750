"""Reduction of a jax.profiler trace (.xplane.pb) to what the readers use.

Read with `jax.profiler.ProfileData` (start and duration of every event, in
nanoseconds), which benchmarks/xplane_parse.py never did: it summed durations
by name and so could give neither the union of busy intervals nor the gaps.

    busy_s     union of the intervals in which an op ran on a device, averaged
               over the device planes
    window_s   first op start to last op end over the device planes
    programs   per jitted program (the modules line): calls and device seconds
    ops        per op (the ops line, see op_label): self seconds, nesting taken out
    gaps       idle intervals of the first device, each with the name of the
               host event that covers most of it

The parent never imports JAX, so this runs as a helper child on the CPU:

    python trace.py <trace_dir> <out.json>
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_S = 20e-6      # shorter holes between ops are the device's own
TOP = 10


def union(intervals: list) -> tuple:
    """Merged [start, end] intervals, sorted, and their total length."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged, sum(e - s for s, e in merged)


def self_times(events: list) -> dict:
    """Seconds by name with each event's children taken out of it, so that a
    loop op and the ops of its body are not counted twice."""
    out: dict = {}
    stack: list = []   # [name, end, self]

    def close():
        name, _end, own = stack.pop()
        out[name] = out.get(name, 0.0) + own

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        close()
    return out


def op_label(raw: str) -> str:
    """The trace names an op by its whole HLO line.  `%fusion.12 = bf16[8,128]{1,0}
    fusion(...)` -> `fusion bf16[8,128]`: the instruction without its number, and
    the shape it writes, so that the same op of every layer is one row."""
    m = re.match(r"%?([\w.-]+?)(?:\.\d+)? = (\(?[\w]+\[[\d,]*\])?", raw)
    if not m:
        return raw[:80]
    return (m.group(1) + " " + (m.group(2) or "").lstrip("(")).strip()[:80]


def program_name(raw: str) -> str:
    """`jit_decode_step(123456789)` -> `jit_decode_step`."""
    return re.sub(r"\(\d+\)$", "", raw)


def label_gaps(gaps: list, host_events: list) -> list:
    """[label, seconds] for every gap: the host event that covers most of it,
    the shorter one on a tie; `no host span` where none does."""
    host_events = sorted(host_events)
    starts = [e[0] for e in host_events]
    longest = max((e[1] - e[0] for e in host_events), default=0.0)
    out = []
    for g0, g1 in gaps:
        best, best_key = "no host span", (0.0, 0.0)
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        for start, end, name in host_events[lo:hi]:
            overlap = min(end, g1) - max(start, g0)
            key = (overlap, -(end - start))
            if overlap > 0 and key > best_key:
                best, best_key = name, key
        out.append([best, g1 - g0])
    return out


def reduce_planes(planes: list) -> dict:
    """planes: [{name, lines: [{name, events: [(start_s, end_s, name)]}]}]."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    host = [e for p in planes if p["name"] == HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    out = {"devices": len(devices), "busy_s": 0.0, "window_s": 0.0,
           "programs": {}, "ops": [], "gaps": [], "gap_labels": []}
    if not devices:
        return out

    def line(plane, name):
        return [e for ln in plane["lines"] if ln["name"] == name for e in ln["events"]]

    per_device = [line(p, OPS_LINE) for p in devices]
    every = [e for ops in per_device for e in ops]
    if not every:
        return out
    w0, w1 = min(e[0] for e in every), max(e[1] for e in every)
    out["window_s"] = w1 - w0
    busy = [union([(s, e) for s, e, _ in ops]) for ops in per_device]
    out["busy_s"] = sum(total for _m, total in busy) / len(devices)
    merged = busy[0][0]
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] - a[1] >= MIN_GAP_S]
    out["gaps"] = sorted(([g1 - g0 for g0, g1 in gaps]), reverse=True)[:TOP]
    labelled: dict = {}
    for label, seconds in label_gaps(gaps, host):
        labelled[label] = labelled.get(label, 0.0) + seconds
    out["gap_labels"] = sorted(labelled.items(), key=lambda kv: -kv[1])[:TOP]
    ops: dict = {}
    for dev_ops in per_device:
        for name, seconds in self_times(dev_ops).items():
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + seconds / len(devices)
    out["ops"] = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    for start, end, raw in line(devices[0], MODULES_LINE):
        prog = out["programs"].setdefault(program_name(raw), {"calls": 0, "seconds": 0.0})
        prog["calls"] += 1
        prog["seconds"] += end - start
    return out


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = []
        for ln in plane.lines:
            if DEVICE_PLANE.match(plane.name) and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                      for e in ln.events]
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce_file(path: str) -> dict:
    return reduce_planes(read_planes(path))


def find_trace(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def main() -> None:
    trace_dir, out_path = sys.argv[1:3]
    path = find_trace(trace_dir)
    result = reduce_file(path) if path else reduce_planes([])
    result["file"] = path
    with open(out_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
