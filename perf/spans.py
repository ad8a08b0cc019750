"""The device's idle time, put down to the program's own loop phases.

The serving loop opens a `jax.profiler.TraceAnnotation` named `llm.<phase>`
around every phase of a loop turn, and `llm.turn` around the turn
(seldon_core_tpu/runtime/batcher.py LoopPhases).  The profiler writes them
into the host plane on the device planes' clock, so each idle interval of the
device (the holes of at least trace.MIN_GAP_S between the merged op intervals
of the first device, as in trace.py) can be cut along the phases that cover it:

    python spans.py <file.xplane.pb> <out.json>

    idle_s            all idle seconds of the first device in the traced interval
    by_phase          {phase: idle seconds}; every instant belongs to the
                      innermost `llm.*` span open at it, whatever thread opened
                      it (`llm.turn` itself reads as `hop`: inside a turn,
                      outside every phase), and to OUTSIDE where none is.  A
                      turn that was open when the capture began or ended is
                      not in the trace, so what no phase covers before the
                      first recorded turn and after the last is EDGE
    phase_s           {phase: seconds} the phases themselves took in the interval
    spans             number of `llm.*` events read (0: the program has none)

trace.py labels a gap with the one host event that covers most of it, which is
JAX's name for whatever the host happened to call; this reads the program's.
Like trace.py it runs as a helper child with JAX on the CPU, because the
benchmark's parent never imports JAX.
"""

from __future__ import annotations

import bisect
import importlib.util
import json
import os
import sys

# perf/trace.py shares its name with a module of the standard library, so it
# is loaded by its path
_spec = importlib.util.spec_from_file_location(
    "perf_trace", os.path.join(os.path.dirname(os.path.abspath(__file__)), "trace.py"))
reduction = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reduction)

PREFIX = "llm."
TURN = "turn"
OUTSIDE = "outside llm spans"
EDGE = "edge of the capture"


def innermost(events: list) -> list:
    """[(start, end, name)] spans that nest or are disjoint -> disjoint
    [(start, end, name)] in time order, each instant under the innermost span
    open at it.  A span that outlives its parent (two threads' clocks reads a
    hair apart) is cut at nothing: the later start wins while it is open."""
    out: list = []
    open_: list = []   # [end, name], outermost first
    cursor = 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        if open_ and until > cursor:
            out.append((cursor, until, open_[-1][1]))
        cursor = max(cursor, until)

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ and open_[-1][0] <= start:
            emit(open_[-1][0])
            open_.pop()
        emit(start)
        cursor = max(cursor, start)
        open_.append([end, name])
    while open_:
        emit(open_[-1][0])
        open_.pop()
    return out


def overlap_by_name(intervals: list, segments: list) -> dict:
    """Seconds of `intervals` (disjoint, sorted) under each name of `segments`
    (disjoint, sorted); what no segment covers goes to OUTSIDE."""
    starts = [s[0] for s in segments]
    out: dict = {}
    for a, b in intervals:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(segments) and segments[i][0] < b:
            s0, s1, name = segments[i]
            part = min(b, s1) - max(a, s0)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        if b - a - covered > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (b - a - covered)
    return out


def idle_by_phase(planes: list) -> dict:
    """planes as trace.read_planes gives them."""
    devices = [p for p in planes if reduction.DEVICE_PLANE.match(p["name"])]
    spans = [(s, e, n[len(PREFIX):]) for p in planes if p["name"] == reduction.HOST_PLANE
             for line in p["lines"] for s, e, n in line["events"] if n.startswith(PREFIX)]
    out = {"idle_s": 0.0, "window_s": 0.0, "by_phase": {}, "phase_s": {}, "spans": len(spans)}
    ops = [e for p in devices[:1] for ln in p["lines"]
           if ln["name"] == reduction.OPS_LINE for e in ln["events"]]
    if not ops:
        return out
    merged, _busy = reduction.union([(s, e) for s, e, _ in ops])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= reduction.MIN_GAP_S]
    w0, w1 = merged[0][0], merged[-1][1]
    turns = [(s, e) for s, e, n in spans if n == TURN]
    if turns:
        first, last = min(s for s, _e in turns), max(e for _s, e in turns)
        spans += [(min(w0, first), first, EDGE), (last, max(w1, last), EDGE)]
    segments = [(s, e, "hop" if n == TURN else n) for s, e, n in innermost(spans)]
    out["window_s"] = w1 - w0
    out["idle_s"] = sum(b - a for a, b in gaps)
    out["by_phase"] = overlap_by_name(gaps, segments)
    out["phase_s"] = {k: v for k, v in overlap_by_name(
        [(w0, w1)], segments).items() if k not in (OUTSIDE, EDGE)}
    return out


def main() -> None:
    path, out_path = sys.argv[1:3]
    with open(out_path, "w") as f:
        json.dump(idle_by_phase(reduction.read_planes(path)), f, indent=1)


if __name__ == "__main__":
    main()
