"""A whole run on the CPU, at a small size, with the timed path sound
and then broken underneath the harness: ``correct`` has to follow.

The look for a chip is skipped; everything else is a benchmark run:
weights from the seed, the runtime, the executor, the window, the
reference and the comparison.
"""

import sys
import time
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from repro.configs import get_smoke  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
from smallcell import small_cell  # noqa: E402

SEED = 2**31 + 99


def altered_token(rt):
    """Each answer's fourth token replaced where it is produced."""
    def fn(payloads):
        out = [o.copy() for o in rt(payloads)]
        for o in out:
            o[3] = (o[3] + 1) % rt.cfg.vocab_size
        return out
    return fn


def half_batch(rt):
    """Only the first half of each batch computed; the rest answered
    with copies of its rows."""
    def fn(payloads):
        h = (len(payloads) + 1) // 2
        out = rt(payloads[:h])
        return [out[i % h] for i in range(len(payloads))]
    return fn


# the number each fault fails: an altered token lies below the
# reference's best; rows left out have no logits of their own
FAILS = {"altered_token": "token_gap", "half_batch": "logit_error"}


def _run(cell, fault=None, trace=False):
    return harness.run_cell(cell, SEED, 2.0, trace, jax.devices()[:1],
                            time.perf_counter(), registry=get_smoke,
                            fault=fault, log=lambda s: None)


@pytest.fixture(scope="module")
def cell():
    # above what the small model serves: batches fill, so a batch-level
    # fault shows
    return small_cell("phi3-mini-3.8b", "phi3-overload", 120.0)


def test_sound_run_is_correct(cell):
    res = _run(cell, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 200
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    assert list(res)[-1] == "checks"
    assert {"busy_s", "window_s"} <= set(res["device"])


@pytest.mark.parametrize("fault", [altered_token, half_batch],
                         ids=["altered_token", "half_batch"])
def test_broken_timed_path_is_not_correct(cell, fault):
    res = _run(cell, fault=fault)
    assert not res["correct"]
    failed = [n for n, c in res["checks"].items() if c["value"] > c["limit"]]
    assert FAILS[fault.__name__] in failed
