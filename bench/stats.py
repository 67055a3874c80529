"""End-to-end arithmetic over every request of the measured window.

Latency runs from a request's nominal (due) time, as the executor
stamps it, to its answer, so a stall charges every request that waited
behind it. Nothing is dropped or trimmed: a request that never got an
answer has infinite latency and pushes the tail.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def in_window(t: np.ndarray, w0: float, w1: float) -> np.ndarray:
    return (t >= w0) & (t < w1)


def latency_ms(arrival: np.ndarray, done: np.ndarray, w0: float,
               w1: float) -> np.ndarray:
    """Latencies (ms) of the requests due in [w0, w1); inf if unanswered."""
    sel = in_window(arrival, w0, w1)
    return (done[sel] - arrival[sel]) * 1e3


def percentile(lat_ms: np.ndarray, q: float) -> float:
    """The q-th percentile (linear interpolation); inf once the rank
    reaches an unanswered request."""
    if lat_ms.size == 0:
        raise ValueError("no request in the window")
    lat = np.sort(lat_ms)
    pos = (lat.size - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(lat[hi]):
        return float("inf")
    return float(lat[lo] + (lat[hi] - lat[lo]) * (pos - lo))


def throughput_rps(done: np.ndarray, w0: float, w1: float) -> float:
    """Requests answered inside the window, whenever they were due,
    over the window's length."""
    return float(np.count_nonzero(in_window(done, w0, w1)) / (w1 - w0))


def end_to_end(arrival: np.ndarray, done: np.ndarray, w0: float,
               w1: float) -> Dict[str, float]:
    lat = latency_ms(arrival, done, w0, w1)
    return {"p50_ms": percentile(lat, 50.0),
            "p95_ms": percentile(lat, 95.0),
            "throughput_rps": throughput_rps(done, w0, w1)}


def attainment_pct(lat_ms: np.ndarray, slo_ms: float) -> float:
    """Share of the window's requests answered within the SLO."""
    return float(100.0 * np.count_nonzero(lat_ms <= slo_ms) / lat_ms.size)
