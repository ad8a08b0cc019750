"""Readers of the batcher loop's time budget: `seldon_llm_loop_seconds_total`
by phase, between the first and the last scrape of the window.  The phases
partition the loop's wall time, so a difference of their sum is wall time."""

from __future__ import annotations

from readers.scrape import total

SECONDS = "seldon_llm_loop_seconds_total"


def ends(ctx) -> tuple | None:
    """(first text, last text, seconds between them) of the window's scrapes."""
    got = [(t, s["metrics"]) for t, s in ctx.scrapes if "metrics" in s]
    return (got[0][1], got[-1][1], got[-1][0] - got[0][0]) if len(got) >= 2 else None


def delta(pair: tuple, metric: str, label: str = "") -> float | None:
    first, last = total(pair[0], metric, label), total(pair[1], metric, label)
    return None if first is None or last is None else last - first


def phase_seconds(pair: tuple, phases: list) -> float | None:
    parts = [delta(pair, SECONDS, f'phase="{p}"') for p in phases]
    return None if any(v is None for v in parts) else sum(parts)


def share(ctx) -> float | None:
    """100 x seconds of the named phases over the seconds of all phases.
    params: phases."""
    pair = ends(ctx)
    if pair is None:
        return None
    part, whole = phase_seconds(pair, ctx.params["phases"]), delta(pair, SECONDS)
    if part is None or not whole:
        return None
    ctx.run.note(f"loop phases sum to {whole:.3f} s of the {pair[2]:.3f} s between the "
                 f"window's end scrapes ({100.0 * whole / pair[2]:.2f} %)")
    return 100.0 * part / whole


def ms_per(ctx) -> float | None:
    """Milliseconds of the named phases per occurrence of a counter.
    params: phases, per (metric, optional label)."""
    pair = ends(ctx)
    if pair is None:
        return None
    per = ctx.params["per"]
    part, n = phase_seconds(pair, ctx.params["phases"]), delta(
        pair, per["metric"], per.get("label", ""))
    return None if part is None or not n else 1e3 * part / n
