"""Operation and byte counts, against counts made by hand."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops  # noqa: E402
from bench.families import dense  # noqa: E402
from bench.spec import BENCH_DIR, load_json  # noqa: E402


def _config(name):
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def test_phi3_request_flops_by_hand():
    cfg = _config("phi3-mini-3.8b")
    m = dense.dims(cfg)
    # per layer: q, k, v, o 4 x 3072^2 = 37,748,736; gate/up/down
    # 3 x 3072 x 8192 = 75,497,472
    assert dense.layer_matmul_params(m) == 113_246_208
    matmuls = 2 * 113_246_208 * 16 * (128 + 7)
    head = 2 * 3072 * 32064 * 8
    pairs = 128 * 129 // 2 + sum(range(129, 136))   # 8256 + 924
    attn = 4 * 32 * 96 * pairs * 16
    assert dense.request_flops(cfg, 128, 8) == pytest.approx(
        matmuls + head + attn)
    # about 3.65 GFLOP for each of the 135 tokens a request runs
    assert 3.6e9 < dense.request_flops(cfg, 128, 8) / 135 < 3.7e9


def test_kernel_calls_by_hand():
    cfg = _config("phi3-mini-3.8b")
    m = dense.dims(cfg)
    f, b = dense.flash_call(m, 16, 128)
    assert f == 4 * 16 * 32 * 96 * 128 * 129 / 2
    assert b == 4 * 16 * 128 * 96 * (2 * 32 + 2 * 32)     # q, o, k, v in f32
    f, b = dense.decode_call(m, 16, 130)
    assert f == 4 * 16 * 32 * 96 * 130
    assert b == 4 * 16 * 96 * (2 * 32 + 2 * 32 * 130)
    calls = list(dense.kernel_calls(cfg, 16, 128, 8))
    assert sum(c[0] == "flash_attention" for c in calls) == 16
    assert sum(c[0] == "decode_attention" for c in calls) == 16 * 7


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000.0, 1.0, peak) == 10.0     # compute bound
    assert flops.roofline_s(1.0, 1000.0, peak) == 100.0    # memory bound
