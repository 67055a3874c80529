"""The one traffic generator: a mix file's kind gives piecewise-constant
rates, and each piece gets ``round(rate * length)`` arrivals placed
uniformly at random in it.

That is a Poisson process conditioned on its count: arrivals within a
piece are as random as Poisson ones, but every seed offers the same
number of requests in every piece, so the seed changes when requests
come and not how much work a run holds.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench.spec import traffic_kind

ARRIVALS_STREAM = 0
PROMPTS_STREAM = 1
SAMPLE_STREAM = 2


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per use of the seed."""
    return np.random.default_rng([int(seed), stream])


def arrivals(traffic: Dict[str, Any], r: np.random.Generator, t0: float,
             t1: float) -> np.ndarray:
    """Sorted arrival times (seconds) in [t0, t1)."""
    parts = []
    for a, b, rate in traffic_kind(traffic["kind"])(traffic, t0, t1):
        n = int(round(rate * (b - a)))
        parts.append(np.sort(r.uniform(a, b, n)))
    return np.concatenate(parts) if parts else np.zeros(0)


def run_arrivals(traffic: Dict[str, Any], seed: int, seconds: float
                 ) -> np.ndarray:
    """The warm-up segment (``warmup_s``) followed by the window."""
    w0 = float(traffic["warmup_s"])
    r = rng(seed, ARRIVALS_STREAM)
    return np.concatenate([arrivals(traffic, r, 0.0, w0),
                           arrivals(traffic, r, w0, w0 + seconds)])
