"""Readers of series picked by SEVERAL labels (PR 49): the page gauges carry a
`class` label and the attention counters a `kind` beside their `program`, and
the exposition sorts a series' labels by name, so no one substring holds two
of them (readers/scrape.py `total` takes one).  Every reader returns None on a
program whose series have no such labels."""

from __future__ import annotations

from readers import loop
from readers.scrape import LINE


def total(text: str, name: str, parts: list) -> float | None:
    """Sum of one series over the label sets that contain every one of `parts`."""
    found = [float(v) for n, labels, v in LINE.findall(text)
             if n == name and all(part in (labels or "") for part in parts)]
    return sum(found) if found else None


def delta(pair: tuple, metric: str, parts: list) -> float | None:
    """What a counter counted between the window's end scrapes (`loop.ends`)."""
    first, last = total(pair[0], metric, parts), total(pair[1], metric, parts)
    return None if first is None or last is None else last - first


def gauge_share(ctx) -> float | None:
    """Mean over the window's scrapes of one gauge as a share of another.
    params: metric, of, labels (every one must match), scale."""
    p = ctx.params
    texts = [s["metrics"] for _t, s in ctx.scrapes if "metrics" in s]
    pairs = [(total(t, p["metric"], p["labels"]), total(t, p["of"], p["labels"])) for t in texts]
    pairs = [(part, whole) for part, whole in pairs if part is not None and whole]
    if not pairs:
        return None
    return (sum(part for part, _ in pairs) / sum(whole for _, whole in pairs)
            * p.get("scale", 1.0))


def ratio(ctx) -> float | None:
    """What one counter counted over what another did, between the window's end
    scrapes.  params: over, under (counter names), labels, scale."""
    p = ctx.params
    pair = loop.ends(ctx)
    if pair is None:
        return None
    over, under = delta(pair, p["over"], p["labels"]), delta(pair, p["under"], p["labels"])
    return None if over is None or not under else over / under * p.get("scale", 1.0)
