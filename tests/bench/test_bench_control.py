"""The controls at a size a test run holds, through the harness's own
comparison. The readings at the cell's own size, which set the limits,
are the chip runs of ``bench/control.py`` that PERF.md records."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import control, harness, spec  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from smallcell import small_cell  # noqa: E402

CONTROLS = spec.family("dense").MODES[1:]


@pytest.fixture(scope="module")
def readings():
    cell = small_cell("phi3-mini-3.8b", "phi3-overload", 20.0)
    return control.readings(cell, 2**31 + 5, 2.0, jax.devices()[:1],
                            registry=get_smoke)


def test_program_is_correct_and_reads_under_the_controls(readings):
    r = readings
    assert r["tokens"] == 8 * r["requests"] and r["requests"] >= 16
    # on the CPU the program computes in float32, as the reference does
    assert r["program.correct"]
    for mode in CONTROLS:
        assert r[f"{mode}.logit_error"] > 3 * r["program.logit_error"]
    assert r["int8.token_gap"] > r["program.token_gap"]


@pytest.mark.parametrize("mode", CONTROLS)
def test_control_is_not_correct(readings, mode):
    assert not readings[f"{mode}.correct"]


def test_judge_reads_each_number():
    cfg = {"correct": {"max_token_gap": 0.1, "max_logit_error": 0.01}}
    ref = np.zeros((1, 2, 4), np.float32)
    ref[0, :, 0] = 1.0
    ref[0, :, 1] = 0.95
    good = harness.judge(cfg, ref, np.array([[0, 1]]), ref, 0)
    assert good["token_gap"]["value"] == pytest.approx(0.05)
    assert good["logit_error"]["value"] == 0.0 and harness.passes(good)
    off = ref.copy()
    off[0, 1, 2] = 0.1                 # 0.1 against a norm of 1.379
    bad = harness.judge(cfg, ref, np.array([[0, 2]]), off, 0)
    assert bad["token_gap"]["value"] == pytest.approx(1.0)
    assert bad["logit_error"]["value"] == pytest.approx(0.1 / np.hypot(1, .95))
    assert not harness.passes(bad)
    assert not harness.passes(harness.judge(cfg, ref, np.array([[0, 1]]),
                                            ref, 1))
    missing = harness.judge(cfg, ref, np.array([[0, 1]]),
                            np.full_like(ref, np.nan), 0)
    assert missing["logit_error"]["value"] == float("inf")
    none = harness.judge(cfg, None, np.zeros((0, 2), np.int32), ref, 0)
    assert not harness.passes(none)
