"""Readers of plain Prometheus counters (not histograms): what two of them
counted between the first and the last scrape of the window, as a ratio."""

from __future__ import annotations

from readers import loop


def ratio(ctx) -> float | None:
    """params: over, under (counter names), optional label (a substring of
    the label set, e.g. program="decode"), scale, scale_by (a key of the
    configuration's file whose value multiplies the ratio).  None where the
    program has no such counter, as a program older than the counter has not."""
    p = ctx.params
    pair = loop.ends(ctx)
    if pair is None:
        return None
    over = loop.delta(pair, p["over"], p.get("label", ""))
    under = loop.delta(pair, p["under"], p.get("label", ""))
    if over is None or not under:
        return None
    scale = p.get("scale", 1.0) * (ctx.config[p["scale_by"]] if "scale_by" in p else 1.0)
    return over / under * scale
