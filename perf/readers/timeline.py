"""Readers of the flight recorder's per-request timelines (/debug/timeline,
on only where TRACING=1).  The program keeps the last 64 completed requests, so
they are collected at every scrape and told apart by trace id."""

from __future__ import annotations

import numpy as np


def timelines(ctx) -> list:
    seen: dict = {}
    for _t, scrape in ctx.scrapes:
        for tl in scrape.get("timelines", []):
            seen[tl["trace_id"]] = tl
    return list(seen.values())


def chunk_events(ctx) -> list:
    """[(offset of the chunk in its prompt, tokens, seconds since the previous
    chunk of the same prompt or None)]."""
    out = []
    for tl in timelines(ctx):
        previous = None
        for ev in tl["events"]:
            if ev["kind"] != "prefill_chunk":
                continue
            out.append((ev["start"], ev["tokens"],
                        None if previous is None else ev["t_s"] - previous))
            previous = ev["t_s"]
    return out


def prefill_chunk_pace(ctx) -> float | None:
    """Median time from one chunk's dispatch to the next of the same prompt, in
    ms, on the host's clock: the chunk itself and whatever decode steps ran
    between.  (The event's own dur_s is the enqueue, not the device.)"""
    paces = [p for _s, _n, p in chunk_events(ctx) if p is not None]
    return float(np.median(paces)) * 1e3 if paces else None
