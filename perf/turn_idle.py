"""The device's idle time, put down to the parts of the host's turn, and to what
the transport thread was doing while the loop waited to be woken.

spans.py cuts the idle time along the loop's ten phases.  Since PR 33 the
program names what a phase is made of (`llm.<phase>.<part>`, e.g.
`llm.dispatch.call`), measures `hop` (`llm.hop.loop`: the loop coroutine's own
code; `llm.hop.wake_worker` / `llm.hop.wake_loop`: a thread waiting to be woken,
out to the worker and back), and writes the transport thread's synchronous
stretches into the same trace as `http.<what>` (parse, sse_write, reply,
scrape).  This reads all of them from the same .xplane.pb:

    python turn_idle.py <file.xplane.pb> <out.json>

    idle_s, window_s  as spans.py's
    by_part           {name: idle seconds}; every instant under the innermost
                      `llm.*` span open at it, parts included, the prefix taken
                      off: `dispatch.call`, `dispatch` (the phase outside its
                      parts), `hop.loop`, `hop.wake_worker`, and `hop` for
                      `llm.turn` alone (what no leg and no phase covers: the
                      worker's own Python between two phases)
    part_s            {name: seconds} the spans themselves took in the interval
    idle_http         {name: {what: idle seconds}}: of the idle seconds under
                      `name`, those during which an `http.<what>` span was open
                      on the transport thread (which shares the GIL with the
                      loop and its workers); names with none are left out
    http_s            {what: seconds} the `http.*` spans took in the interval
    spans, http_spans number of `llm.*` / `http.*` events read (0: the program
                      writes none, as the parent of PR 33 does not)
    host_events_per_s all events of the host plane over the interval: what the
                      trace's writing and parsing grow with

A program older than the parts gives spans.py's table under the same keys
(`hop` holds all of it), and an empty `idle_http`.
"""

from __future__ import annotations

import json
import sys

import spans as phases_table

reduction = phases_table.reduction
HTTP = "http."
BARE_TURN = "hop"


def cut(intervals: list, segments: list) -> list:
    """The pieces [(start, end, name)] of `intervals` (disjoint, sorted) that
    lie under `segments` (disjoint, sorted); what no segment covers is dropped."""
    out, i = [], 0
    for a, b in intervals:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            s0, s1, name = segments[j]
            if min(b, s1) > max(a, s0):
                out.append((max(a, s0), min(b, s1), name))
            j += 1
    return out


def idle_by_part(planes: list) -> dict:
    """planes as trace.read_planes gives them."""
    host = [e for p in planes if p["name"] == reduction.HOST_PLANE
            for line in p["lines"] for e in line["events"]]
    llm = [(s, e, n[len(phases_table.PREFIX):]) for s, e, n in host
           if n.startswith(phases_table.PREFIX)]
    http = [(s, e, n[len(HTTP):]) for s, e, n in host if n.startswith(HTTP)]
    out = {"idle_s": 0.0, "window_s": 0.0, "by_part": {}, "part_s": {}, "idle_http": {},
           "http_s": {}, "spans": len(llm), "http_spans": len(http), "host_events_per_s": 0.0}
    devices = [p for p in planes if reduction.DEVICE_PLANE.match(p["name"])]
    ops = [e for p in devices[:1] for ln in p["lines"]
           if ln["name"] == reduction.OPS_LINE for e in ln["events"]]
    if not ops:
        return out
    merged, _busy = reduction.union([(s, e) for s, e, _ in ops])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= reduction.MIN_GAP_S]
    w0, w1 = merged[0][0], merged[-1][1]
    turns = [(s, e) for s, e, n in llm if n == phases_table.TURN]
    if turns:
        first, last = min(s for s, _e in turns), max(e for _s, e in turns)
        llm += [(min(w0, first), first, phases_table.EDGE),
                (last, max(w1, last), phases_table.EDGE)]
    segments = [(s, e, BARE_TURN if n == phases_table.TURN else n)
                for s, e, n in phases_table.innermost(llm)]
    http_segments = phases_table.innermost(http)
    out["window_s"] = w1 - w0
    out["idle_s"] = sum(b - a for a, b in gaps)
    out["host_events_per_s"] = len(host) / (w1 - w0) if w1 > w0 else 0.0
    out["by_part"] = phases_table.overlap_by_name(gaps, segments)
    skip = (phases_table.OUTSIDE, phases_table.EDGE)
    out["part_s"] = {k: v for k, v in phases_table.overlap_by_name(
        [(w0, w1)], segments).items() if k not in skip}
    out["http_s"] = {k: v for k, v in phases_table.overlap_by_name(
        [(w0, w1)], http_segments).items() if k != phases_table.OUTSIDE}
    for a, b, name in cut(gaps, segments):
        under = {k: v for k, v in phases_table.overlap_by_name([(a, b)], http_segments).items()
                 if k != phases_table.OUTSIDE}
        for what, seconds in under.items():
            row = out["idle_http"].setdefault(name, {})
            row[what] = row.get(what, 0.0) + seconds
    return out


def main() -> None:
    path, out_path = sys.argv[1:3]
    with open(out_path, "w") as f:
        json.dump(idle_by_part(reduction.read_planes(path)), f, indent=1)


if __name__ == "__main__":
    main()
