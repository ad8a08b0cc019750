"""Readers of the program's Prometheus counters, scraped once a second."""

from __future__ import annotations

import re

LINE = re.compile(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})? (\S+)$", re.M)


def total(text: str, name: str, label: str = "") -> float | None:
    """Sum of one series over its label sets (those containing `label`)."""
    found = [float(v) for n, labels, v in LINE.findall(text)
             if n == name and label in (labels or "")]
    return sum(found) if found else None


def histogram_mean(scrapes: list, metric: str, label: str = "") -> float | None:
    """Exact mean of what a histogram observed between the first and the last
    scrape of the window: the difference of _sum over that of _count."""
    texts = [s["metrics"] for _t, s in scrapes if "metrics" in s]
    if len(texts) < 2:
        return None
    ends = [(total(t, metric + "_sum", label), total(t, metric + "_count", label))
            for t in (texts[0], texts[-1])]
    if any(v is None for pair in ends for v in pair) or ends[1][1] <= ends[0][1]:
        return None
    return (ends[1][0] - ends[0][0]) / (ends[1][1] - ends[0][1])


def gauge_mean(scrapes: list, metric: str) -> float | None:
    values = [total(s["metrics"], metric) for _t, s in scrapes if "metrics" in s]
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def mean(ctx) -> float | None:
    """params: metric, optional label, scale."""
    p = ctx.params
    value = histogram_mean(ctx.scrapes, p["metric"], p.get("label", ""))
    return None if value is None else value * p.get("scale", 1.0)


def gauge_share(ctx) -> float | None:
    """Mean over the window's scrapes of one gauge as a share of another.
    params: metric, of, scale."""
    p = ctx.params
    part, whole = gauge_mean(ctx.scrapes, p["metric"]), gauge_mean(ctx.scrapes, p["of"])
    return None if part is None or not whole else part / whole * p.get("scale", 1.0)


def client_minus_server(ctx) -> float | None:
    """What lies between the client's clock and the server's own: the mean of a
    client sample less the mean the server's histogram saw.  params:
    client_sample (one value a request) or client_list (a list a request, all
    pooled), metric, optional label, scale."""
    p = ctx.params
    if "client_list" in p:
        client = [v for r in ctx.records for v in r.get(p["client_list"], [])]
    else:
        client = [r["samples"][p["client_sample"]] for r in ctx.records
                  if p["client_sample"] in r["samples"]]
    served = histogram_mean(ctx.scrapes, p["metric"], p.get("label", ""))
    if not client or served is None:
        return None
    return (sum(client) / len(client) - served) * p.get("scale", 1.0)
