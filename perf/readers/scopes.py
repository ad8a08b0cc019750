"""Readers of the device time under a named scope (perf/scope_times.py).

The reduction runs once per set of marks in a helper child on the CPU and is
kept beside the run's trace.json.  Where the trace has no device plane, or the
program has no op with the mark (a program older than the scope), there is
nothing to read: None, and the line leaves the metric out."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import server as srv


def marked(ctx) -> dict | None:
    """{"calls", "seconds", "marked_s"} of the params' program, or None."""
    if not ctx.trace or not ctx.trace.get("devices") or not ctx.trace.get("file"):
        return None
    marks = json.dumps(sorted(ctx.params["marks"]))
    out_path = os.path.join(
        ctx.run.out_dir, f"scopes.{hashlib.sha1(marks.encode()).hexdigest()[:8]}.json")
    if not os.path.exists(out_path):
        proc = subprocess.run(
            [sys.executable, os.path.join(ctx.run.perf_dir, "scope_times.py"),
             ctx.trace["file"], out_path, marks],
            env=srv.child_env("cpu", False, {}), cwd=ctx.run.repo,
            capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(f"scope reduction failed:\n{proc.stderr[-2000:]}")
    with open(out_path) as f:
        programs = json.load(f)["programs"]
    hits = [p for name, p in programs.items() if ctx.params["program"] in name]
    total = {key: sum(p[key] for p in hits) for key in ("calls", "seconds", "marked_s")}
    return total if hits and total["calls"] and total["marked_s"] > 0 else None


def per_call_ms(ctx) -> float | None:
    """params: program, marks."""
    found = marked(ctx)
    return None if found is None else 1e3 * found["marked_s"] / found["calls"]


def roofline(ctx) -> float | None:
    """The marked ops' share of their roofline in one call of the program.
    params: program, marks, work (a function of perf/work/<config's work>.py
    giving {"flops", "bytes"} the marked part of one call needs), bound
    ("mxu", "hbm", or "max": whichever of the two takes longer)."""
    found = marked(ctx)
    need = getattr(ctx.work, ctx.params["work"])(ctx)
    if found is None or need is None:
        return None
    mxu = need["flops"] / ctx.peaks["bf16_flops_per_s"]
    hbm = need["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    least = {"mxu": mxu, "hbm": hbm, "max": max(mxu, hbm)}[ctx.params["bound"]]
    return 100.0 * least / (found["marked_s"] / found["calls"])
