"""Readers of the device time under a `jax.named_scope`, found through the HLO
the trace carries (perf/hlo_scopes.py).

The reduction runs once per set of scopes in a helper child and is kept
beside the run's trace.json.  Where the trace has no device plane or no HLO,
or the program has no op under the scope (a program older than the scope),
there is nothing to read: None, and the line leaves the metric out."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import server as srv


def scoped(ctx) -> dict | None:
    """{"calls", "seconds", "scoped_s"} of the params' program, or None."""
    if not ctx.trace or not ctx.trace.get("devices") or not ctx.trace.get("file"):
        return None
    scopes = json.dumps(sorted(ctx.params["scopes"]))
    out_path = os.path.join(
        ctx.run.out_dir, f"hlo_scopes.{hashlib.sha1(scopes.encode()).hexdigest()[:8]}.json")
    if not os.path.exists(out_path):
        proc = subprocess.run(
            [sys.executable, os.path.join(ctx.run.perf_dir, "hlo_scopes.py"),
             ctx.trace["file"], out_path, scopes],
            env=srv.child_env("cpu", False, {}), cwd=ctx.run.repo,
            capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(f"scope reduction failed:\n{proc.stderr[-2000:]}")
    with open(out_path) as f:
        programs = json.load(f)["programs"]
    hits = [p for name, p in programs.items() if ctx.params["program"] in name]
    total = {key: sum(p[key] for p in hits) for key in ("calls", "seconds", "scoped_s")}
    return total if hits and total["calls"] and total["scoped_s"] > 0 else None


def per_call_ms(ctx) -> float | None:
    """params: program, scopes."""
    found = scoped(ctx)
    return None if found is None else 1e3 * found["scoped_s"] / found["calls"]


def roofline(ctx) -> float | None:
    """The scoped ops' share of their roofline in one call of the program.
    params: program, scopes, work (a function of perf/work/<config's work>.py
    giving {"flops", "bytes"} the scoped part of one call needs), bound ("mxu",
    "hbm", or "max": whichever of the two takes longer)."""
    found = scoped(ctx)
    need = getattr(ctx.work, ctx.params["work"], lambda _ctx: None)(ctx)
    if found is None or need is None:
        return None
    mxu = need["flops"] / ctx.peaks["bf16_flops_per_s"]
    hbm = need["bytes"] / ctx.peaks["hbm_bytes_per_s"]
    least = {"mxu": mxu, "hbm": hbm, "max": max(mxu, hbm)}[ctx.params["bound"]]
    return 100.0 * least / (found["scoped_s"] / found["calls"])
