#!/usr/bin/env python3
"""Knee sweep: one server, the cell's traffic at several values of one knob.

    python perf/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --key traffic.arrivals.rate --values 1,2,3,4

Not a cell and not run by the driver: it is how the fixed rate in an open-loop
cell's file (about four fifths of the knee) and the client count of a closed
one were found, once, on the chip.  One row per value: offered and completed
rates, failures, the tails, the share of requests inside the cell's limits, and
the backlog at window close (a backlog that grows with the window means the
rate is above the knee).  The table goes to PERF.md section 4.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import run as harness


def set_key(cell: dict, dotted: str, value: float) -> None:
    *path, last = dotted.split(".")
    node = cell
    for part in path:
        node = node[part]
    node[last] = type(node[last])(value)


async def sweep(run, plane, server, args) -> list:
    rows = []
    await plane.connect()
    try:
        await plane.probe("before", run.rngs["probe"])
        for value in args.values:
            set_key(run.cell, args.key, value)
            measured = await harness.one_window(run, plane, server, args.seconds)
            info = harness.describe(run, measured["records"], measured["in_window"],
                                    measured["window"])
            info.update(value=value, compiles_in_window=measured["compiles"])
            rows.append(info)
            run.note(f"{args.key}={value}: {json.dumps(info)}")
        await plane.probe("after", run.rngs["probe"])
    finally:
        await plane.close()
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--key", required=True, help="dotted path into the cell's file")
    ap.add_argument("--values", required=True, type=lambda s: [float(v) for v in s.split(",")])
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="another dotted key of the cell's file, fixed for the whole sweep "
                         "(a longer drain_s lets a window that tipped finish before the next)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    run = harness.Run(args)
    for pair in args.set:
        key, _, value = pair.partition("=")
        set_key(run.cell, key, float(value))
    server = None
    try:
        plane, server = harness.launch(run)
        rows = asyncio.run(sweep(run, plane, server, args))
        server.stop()
    finally:
        if server is not None:
            server.kill()
    with open(os.path.join(run.out_dir, "sweep.json"), "w") as f:
        json.dump({"key": args.key, "seconds": args.seconds, "rows": rows,
                   "violations": plane.violations}, f, indent=1)
    samples = sorted({k for r in rows for k, v in r.items()
                      if isinstance(v, dict) and "p90" in v and "n" in v})
    print("| " + " | ".join([args.key, "offered/s", "done", "failed", "inside limits",
                             "backlog at close", "late p99 ms"]
                            + [f"{s} p50/p90/p99 ms" for s in samples]) + " |")
    for r in rows:
        cols = [f"{r['value']:g}", f"{r['offered_per_s']:.2f}", str(r["succeeded"]),
                str(r["failed"]), f"{r.get('share_inside_limits', float('nan')):.2f}",
                str(r["backlog_at_close"]), f"{r['lateness_p99_ms'] or 0:.1f}"]
        for s in samples:
            v = r.get(s)
            cols.append("-" if not v else "/".join(f"{v[q] * 1e3:.1f}" for q in ("p50", "p90", "p99")))
        print("| " + " | ".join(cols) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
