"""Percentiles that know when they have too few samples to be believed.

`percentile` is numpy's linear interpolation, as the program's own
`seldon_core_tpu/benchmarks/loadgen.py percentile_stats` uses; copied here so
the yardstick cannot move with the program.  A percentile q of n samples has
n * (1 - q/100) samples beyond it; with fewer than ten it is a maximum in
disguise, and `percentile` refuses it.
"""

from __future__ import annotations

import numpy as np

MIN_BEYOND = 10


class TooFewSamples(ValueError):
    pass


def percentile(samples, q: float, min_beyond: int = MIN_BEYOND) -> float:
    values = np.asarray(samples, dtype=np.float64)
    beyond = values.size * (100.0 - q) / 100.0
    if values.size == 0 or beyond < min_beyond:
        raise TooFewSamples(
            f"p{q:g} of {values.size} samples has {beyond:.1f} beyond it, "
            f"fewer than {min_beyond}")
    return float(np.percentile(values, q))
