"""The four-chip cell's run on four CPU devices, at a small size, with
the timed path sound and then broken underneath the harness: each
replica serves from its own device, and ``correct`` has to follow. The
family's controls, read over the same four replicas, are not correct.

The run needs four devices, which the CPU backend gives only to a
process that asks before JAX starts, so the test runs this file as a
script in a child process of its own.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SEED = 2**31 + 11


def main() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(HERE)]
    import jax
    from bench import control, harness
    from repro.configs import get_smoke
    from smallcell import small_cell
    from test_bench_faults import FAILS, altered_token, half_batch

    # above what four replicas of the small model serve on the CPU:
    # batches hold more than one row, so a batch-level fault shows
    cell = dataclasses.replace(
        small_cell("phi3-mini-3.8b", "phi3-overload", 400.0), chips=4)
    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    used = set()

    def sound(rt):
        def fn(payloads):
            out = rt(payloads)
            used.add(rt._local.device)
            return out
        return fn

    out = {}
    for fault in (sound, altered_token, half_batch):
        res = harness.run_cell(cell, SEED, 2.0, False, devices,
                               time.perf_counter(), registry=get_smoke,
                               fault=fault, log=lambda s: None)
        out[fault.__name__] = {
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": [n for n, c in res["checks"].items()
                       if c["value"] > c["limit"]]}
    out["devices_used"] = sorted(used)
    out["fails"] = FAILS
    out["controls"] = control.readings(cell, SEED + 1, 2.0, devices,
                                       registry=get_smoke)
    print(json.dumps(out))


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_replicas_sound_run_is_correct(runs):
    assert runs["sound"]["correct"], runs["sound"]
    assert runs["sound"]["attempted"] >= 800
    assert runs["devices_used"] == [0, 1, 2, 3]


@pytest.mark.parametrize("fault", ["altered_token", "half_batch"])
def test_four_replicas_broken_timed_path_is_not_correct(runs, fault):
    assert not runs[fault]["correct"]
    assert runs["fails"][fault] in runs[fault]["failed"]


@pytest.mark.parametrize("mode", ["bfloat16", "int8"])
def test_four_replicas_control_is_not_correct(runs, mode):
    r = runs["controls"]
    assert r["program.correct"], r
    assert r["requests"] >= 16
    assert not r[f"{mode}.correct"]


if __name__ == "__main__":
    main()
