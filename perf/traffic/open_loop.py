"""Open loop: requests are sent when they are due, whether or not earlier ones
have come back, as independent users send them.  The schedule is drawn whole
before the first request, so nothing is generated inside the window."""

from __future__ import annotations

import asyncio

import numpy as np

from traffic import draw


def build(traffic: dict, make_request, rngs: dict, segments: list) -> list:
    """segments: seconds of ramp, window and tail."""
    per_segment = draw.arrivals(rngs["arrivals"], traffic["arrivals"], segments)
    due = np.concatenate(per_segment)
    sizes = draw.sizes(rngs["sizes"], traffic["request"], [len(d) for d in per_segment])
    return [{"due": float(t), "sizes": s,
             "request": make_request(s, rngs["payload"])}
            for t, s in zip(due, sizes)]


async def run(plan: list, traffic: dict, send, window) -> list:
    """Returns one record per request sent: due, sent, done (seconds on the
    loop's clock), sizes, and whatever the plane's `send` measured."""
    loop = asyncio.get_running_loop()
    records, tasks = [], []

    async def one(item):
        rec = {"due": window.start + item["due"], "sizes": item["sizes"]}
        records.append(rec)
        rec["sent"] = loop.time()
        rec.update(await send(item["request"]))
        rec["done"] = loop.time()

    for item in plan:
        delay = window.start + item["due"] - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(item)))
    await window.drain(tasks)
    return records


def share(rec: dict, window) -> float:
    """A request belongs to the window, whole, if it was due inside it, however
    late it came back."""
    return 1.0 if window.open <= rec["due"] < window.close else 0.0
