"""Readers of the device's idle time by loop phase (perf/spans.py)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import server as srv


def table(ctx) -> dict | None:
    """The idle-by-phase table of this run's trace, computed once by the
    helper child (JAX on the CPU) and left in the run's out directory.  None
    where there is no trace, or the program wrote no `llm.*` span into it."""
    run = ctx.run
    if not hasattr(run, "idle_by_phase"):
        run.idle_by_phase = None
        path = (ctx.trace or {}).get("file")
        if path and ctx.trace["devices"]:
            out_path = os.path.join(run.out_dir, "idle_by_phase.json")
            proc = subprocess.run(
                [sys.executable, os.path.join(run.perf_dir, "spans.py"), path, out_path],
                env=srv.child_env("cpu", False, {}), cwd=run.repo,
                capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(f"idle by phase failed:\n{proc.stderr[-2000:]}")
            with open(out_path) as f:
                run.idle_by_phase = json.load(f)
            t = run.idle_by_phase
            run.note("idle by phase (ms of %.1f idle in %.3f s): %s; llm spans read: %d" % (
                t["idle_s"] * 1e3, t["window_s"],
                json.dumps({k: round(v * 1e3, 2) for k, v in sorted(
                    t["by_phase"].items(), key=lambda kv: -kv[1])}), t["spans"]))
    return run.idle_by_phase


def idle_in(ctx) -> float | None:
    """100 x the device's idle seconds that fall inside the named phases over
    the traced interval: the part of the device's idle share that those phases
    of the host loop cover.  params: phases."""
    t = table(ctx)
    if not t or not t["spans"] or not t["window_s"]:
        return None
    return 100.0 * sum(t["by_phase"].get(p, 0.0) for p in ctx.params["phases"]) / t["window_s"]
