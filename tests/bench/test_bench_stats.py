"""End-to-end arithmetic over every request of the window."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import stats  # noqa: E402


def _steady(n=1000, rate=100.0, service=0.05):
    arrival = np.arange(n) / rate
    return arrival, arrival + service


def test_steady_numbers():
    arrival, done = _steady()
    e = stats.end_to_end(arrival, done, 1.0, 9.0)
    assert e["p50_ms"] == pytest.approx(50.0)
    assert e["p95_ms"] == pytest.approx(50.0)
    assert e["throughput_rps"] == pytest.approx(100.0)


def test_stall_moves_p95_and_throughput():
    arrival, done = _steady()
    base = stats.end_to_end(arrival, done, 1.0, 9.0)
    # a 1 s stall at t = 5 s: everything due in it waits until 6 s
    stalled = done.copy()
    hit = (arrival >= 5.0) & (arrival < 6.0)
    stalled[hit] = 6.0 + 0.05 + np.arange(hit.sum()) * 0.001
    e = stats.end_to_end(arrival, stalled, 1.0, 9.0)
    assert e["p95_ms"] > 5 * base["p95_ms"]
    assert e["p50_ms"] == pytest.approx(base["p50_ms"])
    # stalled answers land after 6 s, still in the window; a stall at the
    # window's close pushes them out of it
    late = done.copy()
    tail = (arrival >= 8.5) & (arrival < 9.0)
    late[tail] = 9.5
    assert stats.throughput_rps(late, 1.0, 9.0) < base["throughput_rps"]


def test_unanswered_request_is_infinite_tail():
    arrival, done = _steady()
    done[500] = np.inf
    lat = stats.latency_ms(arrival, done, 1.0, 9.0)
    assert stats.percentile(lat, 100.0) == float("inf")
    assert np.isfinite(stats.percentile(lat, 95.0))
    done[100:200] = np.inf
    lat = stats.latency_ms(arrival, done, 1.0, 9.0)
    assert stats.percentile(lat, 95.0) == float("inf")


def test_percentile_matches_numpy_linear():
    lat = np.random.default_rng(0).exponential(10.0, 777)
    for q in (50.0, 95.0, 99.0):
        assert stats.percentile(lat, q) == pytest.approx(np.percentile(lat, q))


def test_window_selects_by_due_time():
    arrival = np.array([0.5, 1.0, 2.0, 3.0])
    done = arrival + 1.0
    assert stats.latency_ms(arrival, done, 1.0, 3.0).size == 2
    assert stats.attainment_pct(np.array([10.0, 20.0, 30.0, 40.0]),
                                25.0) == 50.0
