"""Readers of the program's start ledger (PR 51, seldon_core_tpu/tracing/start.py):
`seldon_start_stage_seconds{stage}`, the stages of the server's start, and
`seldon_program_build_seconds_total{program,leg,nested}` /
`seldon_program_builds_total{program,cache}`, what every build of a program
cost and whether the persistent compile cache had it.  Set-up ends where the
window's traffic begins, so `stage`, `built` and `hit_share` read the series AS
THEY STAND at the window's first scrape; `in_window` reads what was built
between the window's end scrapes (nothing, on a sound tree).  Every reader
returns None on a program that has no such series.  README.start.md has the
metrics they feed."""

from __future__ import annotations

from readers import loop
from readers.scrape import LINE, total

STAGE = "seldon_start_stage_seconds"
BUILD_SECONDS = "seldon_program_build_seconds_total"
BUILDS = "seldon_program_builds_total"


def series(text: str, name: str) -> list:
    """[(label set as written, value)] of one series."""
    return [(labels or "", float(v)) for n, labels, v in LINE.findall(text) if n == name]


def opening(ctx) -> str | None:
    """The text of the window's first scrape."""
    texts = [s["metrics"] for _t, s in ctx.scrapes if "metrics" in s]
    return texts[0] if texts else None


def stage(ctx) -> float | None:
    """Seconds of the named stages together.  params: stages.  None unless the
    program reports every one of them (a sum of fewer would read as a faster start)."""
    text = opening(ctx)
    if text is None:
        return None
    parts = [total(text, STAGE, f'stage="{s}"') for s in ctx.params["stages"]]
    return None if any(v is None for v in parts) else sum(parts)


def built(ctx) -> float | None:
    """Thread-seconds of the named legs that lay in no other leg (nested="0"),
    over all programs but those in `without`.  params: legs, without (optional)."""
    text = opening(ctx)
    if text is None:
        return None
    rows = series(text, BUILD_SECONDS)
    if not rows:
        return None
    without = [f'program="{p}"' for p in ctx.params.get("without", [])]
    legs = [f'leg="{leg}"' for leg in ctx.params["legs"]]
    return sum(v for labels, v in rows
               if 'nested="0"' in labels and any(leg in labels for leg in legs)
               and not any(p in labels for p in without))


def hit_share(ctx) -> float | None:
    """100 x executables the persistent cache had over those it was asked for
    (hit + miss; one built with the cache off is in neither)."""
    text = opening(ctx)
    if text is None:
        return None
    hit = total(text, BUILDS, 'cache="hit"') or 0.0
    miss = total(text, BUILDS, 'cache="miss"') or 0.0
    return 100.0 * hit / (hit + miss) if hit + miss else None


def in_window(ctx) -> float | None:
    """Executables made or loaded between the window's end scrapes."""
    pair = loop.ends(ctx)
    return None if pair is None else loop.delta(pair, BUILDS)
