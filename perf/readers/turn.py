"""Readers of the host's turn (PR 33): the device's idle time by part of the
loop's phases and under the transport's `http.*` spans (perf/turn_idle.py), and
the counters that say what `hop`, `dispatch` and `emit` are made of
(`seldon_llm_loop_part_seconds_total{part}`, `seldon_http_busy_seconds_total`).
Every reader returns None on a program that has no such span or counter."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import server as srv
from readers import loop

PART_SECONDS = "seldon_llm_loop_part_seconds_total"
HOP_PARTS = ("hop.wake_worker", "hop.wake_loop", "hop.worker", "hop.loop")


def table(ctx) -> dict | None:
    """The idle-by-part table of this run's trace, computed once by the helper
    child (JAX on the CPU) and left in the run's out directory.  None where
    there is no trace, or the program wrote no `llm.*` span into it."""
    run = ctx.run
    if not hasattr(run, "idle_by_part"):
        run.idle_by_part = None
        path = (ctx.trace or {}).get("file")
        if path and ctx.trace["devices"]:
            out_path = os.path.join(run.out_dir, "idle_by_part.json")
            proc = subprocess.run(
                [sys.executable, os.path.join(run.perf_dir, "turn_idle.py"), path, out_path],
                env=srv.child_env("cpu", False, {}), cwd=run.repo,
                capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(f"idle by part failed:\n{proc.stderr[-2000:]}")
            with open(out_path) as f:
                run.idle_by_part = t = json.load(f)

            def ms(row: dict) -> dict:
                return {k: round(v * 1e3, 2) for k, v in sorted(row.items(), key=lambda kv: -kv[1])}

            run.note("idle by part (ms of %.1f idle in %.3f s): %s; with an http span open: %s; "
                     "http busy ms: %s; llm / http spans read: %d / %d; host events a second: %.0f" % (
                         t["idle_s"] * 1e3, t["window_s"], json.dumps(ms(t["by_part"])),
                         json.dumps({k: ms(v) for k, v in t["idle_http"].items()}),
                         json.dumps(ms(t["http_s"])), t["spans"], t["http_spans"],
                         t["host_events_per_s"]))
    return run.idle_by_part


def idle_in(ctx) -> float | None:
    """100 x the device's idle seconds under the named parts over the traced
    interval.  params: parts (keys of the table's by_part); http (optional):
    true counts only the seconds during which an `http.*` span was open."""
    t = table(ctx)
    if not t or not t["spans"] or not t["window_s"]:
        return None
    if ctx.params.get("http"):
        if not t["http_spans"]:
            return None
        seconds = sum(v for p in ctx.params["parts"] for v in t["idle_http"].get(p, {}).values())
    else:
        seconds = sum(t["by_part"].get(p, 0.0) for p in ctx.params["parts"])
    return 100.0 * seconds / t["window_s"]


def summed(pair: tuple, series: list) -> float | None:
    """What [[metric, label], ...] counted together between the end scrapes."""
    parts = [loop.delta(pair, metric, label) for metric, label in series]
    return None if any(v is None for v in parts) else sum(parts)


def hop_identity(ctx, pair: tuple) -> None:
    """Once a run: is any piece of a turn still unnamed?"""
    if getattr(ctx.run, "hop_identity_noted", False):
        return
    ctx.run.hop_identity_noted = True
    parts = summed(pair, [[PART_SECONDS, f'part="{p}"'] for p in HOP_PARTS])
    hop = loop.delta(pair, loop.SECONDS, 'phase="hop"')
    if parts is not None and hop:
        ctx.run.note("hop's four measured parts sum to %.4f s of the phase's %.4f s (%.2f %%): %s" % (
            parts, hop, 100.0 * parts / hop, json.dumps(
                {p: round(loop.delta(pair, PART_SECONDS, f'part="{p}"'), 4) for p in HOP_PARTS})))


def per(ctx) -> float | None:
    """What some counters counted over what others did, between the first and
    the last scrape of the window.  params: over, under ([[metric, label], ...],
    each list summed; `under` may be the string "seconds": the wall between
    the two scrapes), scale."""
    pair = loop.ends(ctx)
    if pair is None:
        return None
    hop_identity(ctx, pair)
    p = ctx.params
    over = summed(pair, p["over"])
    under = pair[2] if p["under"] == "seconds" else summed(pair, p["under"])
    if over is None or not under:
        return None
    return over / under * p.get("scale", 1.0)
