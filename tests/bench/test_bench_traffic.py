"""The traffic generator and its kinds."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import arrivals as gen  # noqa: E402
from bench.spec import load_traffic  # noqa: E402

POISSON = {"kind": "poisson", "rate_rps": 50.0, "warmup_s": 4.0}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("seconds", [20.0, 41.0])
def test_same_seed_same_arrivals(seconds):
    a = gen.run_arrivals(POISSON, BIG_SEED, seconds)
    b = gen.run_arrivals(POISSON, BIG_SEED, seconds)
    c = gen.run_arrivals(POISSON, BIG_SEED + 1, seconds)
    np.testing.assert_array_equal(a, b)
    assert a.size == c.size            # the same work, at other times
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0.0
    assert a[-1] < 4.0 + seconds


def test_warmup_and_window_hold_their_counts():
    a = gen.run_arrivals(POISSON, 7, 20.0)
    assert (a < 4.0).sum() == 200 and (a >= 4.0).sum() == 1000


def test_poisson_rate_and_spacing():
    a = gen.arrivals(POISSON, gen.rng(3, gen.ARRIVALS_STREAM), 0.0, 100.0)
    assert a.size == 5000
    gaps = np.diff(a)
    # exponential-like spacing: mean 1/rate, coefficient of variation ~1
    assert gaps.mean() == pytest.approx(0.02, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)


@pytest.mark.parametrize("name", ["phi3-overload"])
def test_committed_mixes_generate(name):
    t = load_traffic(name)
    a = gen.run_arrivals(t, BIG_SEED, 10.0)
    assert a.size > 50 and t["slo_ms"] > 0 and t["drain_s"] >= 10
