"""Everything a run draws, as pure functions of the seed.

A traffic mix is the `traffic` block of a cell's file.  `arrivals` gives the
due times of an open loop, `sizes` the named sizes of each request (prompt and
output tokens, rows ...).  The plane turns sizes into
payload bytes with the generator it is handed, so the same seed gives the same
due times, lengths and bytes.  Streams are spawned from one SeedSequence so
that adding a draw to one of them does not shift the others.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

STREAMS = ("arrivals", "sizes", "payload", "probe")


def streams(seed: int) -> dict:
    children = np.random.SeedSequence(int(seed)).spawn(len(STREAMS))
    return {name: np.random.default_rng(c) for name, c in zip(STREAMS, children)}


def draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """n whole numbers from one distribution spec: its n quantile midpoints
    ((i + 0.5) / n through the inverse distribution function), in seeded random
    order.  Every run of a cell so offers the same multiset of sizes, a fixed
    amount of work; the seed decides which request gets which."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    kind = spec["dist"]
    if kind == "fixed":
        out = np.full(n, spec["value"], dtype=np.float64)
    elif kind == "uniform":
        out = np.floor(spec["min"] + u * (spec["max"] - spec["min"] + 1))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        out = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        out = np.clip(out, spec.get("min", -np.inf), spec.get("max", np.inf))
    return rng.permutation(np.rint(out).astype(np.int64))


def arrivals(rng: np.random.Generator, spec: dict, segments: list) -> list:
    """Due times of an open loop at a fixed mean rate, with a fixed count; one
    array per segment.

    Each segment (ramp, window, tail; seconds) gets exactly round(rate x length)
    arrivals: n + 1 exponential gaps are drawn and scaled so that they fill the
    segment, which is the Poisson process given its count.  A run so offers a
    fixed amount of work, and a tail never lacks its samples by the luck of the
    draw.  `poisson` is the only process a cell uses; the cell that needs
    another (bursts) brings its draw with it.
    """
    rate, process = float(spec["rate"]), spec["process"]
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    out, begin = [], 0.0
    for length in segments:
        n = int(round(rate * length))
        gaps = rng.exponential(1.0, size=n + 1)
        out.append(begin + np.cumsum(gaps)[:n] * (length / gaps.sum()))
        begin += length
    return out


def sizes(rng: np.random.Generator, request: dict, counts: list) -> list:
    """sum(counts) dicts of named sizes; every key of `request` is a
    distribution, drawn apart for each count (ramp, window, tail), so that the
    window alone holds the whole distribution."""
    columns = {name: np.concatenate([draw(rng, spec, n) for n in counts])
               for name, spec in sorted(request.items())}
    return [{name: int(col[i]) for name, col in columns.items()}
            for i in range(sum(counts))]
