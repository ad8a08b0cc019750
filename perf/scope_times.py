"""Device self time of the ops that carry a mark, by jitted program.

    python scope_times.py <trace.xplane.pb> <out.json> <marks as a JSON list>

An op is marked when one of the marks is a substring of its event's name or of
any text stat of the event ('-' and '_' taken as the same, case ignored).  On a
TPU v5e the event's name is the op's whole HLO line (its own name and its
operands') and its stats are times alone (my chip run, PR 25): a
`jax.named_scope` is in the HLO's metadata and not in the trace, so a mark has
to be in an op's or an operand's name, as `ragged-dot` is in the grouped-matmul
kernels XLA emits and in the fusions that read them.  Nesting is taken out
as perf/trace.py does: an op's time is its own, less the ops inside it.  Runs
as a helper child with JAX on the CPU (the benchmark's parent never imports it).
"""

from __future__ import annotations

import bisect
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def fold(text: str) -> str:
    return text.lower().replace("_", "-")


def marked(name: str, stats: list, marks: list) -> bool:
    hay = fold(name) + " " + " ".join(fold(v) for v in stats)
    return any(m in hay for m in marks)


def self_seconds(events: list) -> list:
    """[(start, own seconds, is_marked)] from [(start, end, is_marked)]."""
    out, stack = [], []   # stack: [start, end, own, mark]

    def close():
        start, _end, own, mark = stack.pop()
        out.append((start, own, mark))

    for start, end, mark in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([start, end, end - start, mark])
    while stack:
        close()
    return out


def reduce(path: str, marks: list) -> dict:
    from jax.profiler import ProfileData

    marks = [fold(m) for m in marks]
    devices = [p for p in ProfileData.from_file(path).planes if DEVICE_PLANE.match(p.name)]
    out: dict = {"programs": {}}
    if not devices:
        return out
    modules, ops = [], []
    for line in devices[0].lines:
        for e in line.events:
            start, end = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
            if line.name == "XLA Modules":
                modules.append((start, end, re.sub(r"\(\d+\)$", "", e.name)))
            elif line.name == "XLA Ops":
                texts = [value for _key, value in e.stats if isinstance(value, str)]
                ops.append((start, end, marked(e.name, texts, marks)))
    modules.sort()
    starts = [m[0] for m in modules]
    for start, end, name in modules:
        prog = out["programs"].setdefault(name, {"calls": 0, "seconds": 0.0, "marked_s": 0.0})
        prog["calls"] += 1
        prog["seconds"] += end - start
    for start, own, mark in self_seconds(ops):
        i = bisect.bisect_right(starts, start) - 1
        if mark and i >= 0 and start < modules[i][1]:
            out["programs"][modules[i][2]]["marked_s"] += own
    return out


def main() -> None:
    path, out_path, marks = sys.argv[1], sys.argv[2], json.loads(sys.argv[3])
    with open(out_path, "w") as f:
        json.dump(reduce(path, marks), f)


if __name__ == "__main__":
    main()
