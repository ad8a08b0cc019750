"""Closed loop: `clients` callers, each sending its next request when the last
one came back, as batch jobs that wait for replies do.  A slow system gets less
load, so the judged number is work completed, not a tail."""

from __future__ import annotations

import asyncio

from traffic import draw


def build(traffic: dict, make_request, rngs: dict, segments: list) -> list:
    sizes = draw.sizes(rngs["sizes"], traffic["request"], [int(traffic["requests"])])
    return [{"sizes": s, "request": make_request(s, rngs["payload"])}
            for s in sizes]


async def run(plan: list, traffic: dict, send, window) -> list:
    """Requests are handed out in drawn order; a run that wants more than
    `requests` of them starts over at the first."""
    loop = asyncio.get_running_loop()
    records, taken = [], [0]
    stop_at = window.start + window.horizon_s

    async def client():
        while loop.time() < stop_at:
            item = plan[taken[0] % len(plan)]
            taken[0] += 1
            rec = {"sizes": item["sizes"], "due": loop.time()}
            rec["sent"] = rec["due"]
            records.append(rec)
            rec.update(await send(item["request"]))
            rec["done"] = loop.time()

    await asyncio.sleep(max(0.0, window.start - loop.time()))
    tasks = [asyncio.ensure_future(client()) for _ in range(int(traffic["clients"]))]
    await window.drain(tasks)
    return records


def share(rec: dict, window) -> float:
    """The part of a finished request's time that lay inside the window: the
    work the callers got done there.  Counting only whole requests would move
    the rate by one request's worth (some percent) with the luck of the edges."""
    if "done" not in rec:     # never came back: counts, as failed, if it was sent in time
        return 1.0 if rec["sent"] < window.close else 0.0
    inside = min(rec["done"], window.close) - max(rec["sent"], window.open)
    return max(0.0, inside) / max(rec["done"] - rec["sent"], 1e-9)
