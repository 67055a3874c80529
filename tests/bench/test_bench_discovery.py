"""The harness finds every configuration, mix and metric by name."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import harness, spec, trace as tr  # noqa: E402
from bench.record import Call, Run  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from smallcell import small_cell  # noqa: E402

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] == c.config_name
    assert c.chips in (1, 4)
    assert any(m.name == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert spec.traffic_kind(c.traffic["kind"])
    fam = spec.family(c.config["family"])
    assert c.family.__file__ == fam.__file__
    assert fam.MODES[0] == "float32" and len(fam.MODES) >= 2
    assert callable(fam.init_rule)
    for m in c.per_layer:
        assert callable(spec.metric_reader(m.name))


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    names = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in names
        for w in m.get("workloads", []):
            assert w in spec.load_cell(w).name
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).resolve().is_relative_to(ROOT / "bench")


def _fake_root(tmp_path: Path) -> Path:
    """A copy of the benchmark with one new mix kind, mix, config, metric
    and cell added as new files and entries; no existing file edited."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "traffic" / "kinds" / "burst2.py").write_text(
        "def segments(p, t0, t1):\n    return [(t0, t1, p['r'])]\n")
    (root / "bench" / "traffic" / "mix-new.json").write_text(json.dumps(
        {"kind": "burst2", "r": 3.0, "warmup_s": 1.0}))
    (root / "bench" / "configs" / "model-new.json").write_text(json.dumps(
        {"name": "model-new", "family": "dense"}))
    (root / "bench" / "metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "model-new", "source": "x",
                             "file": "bench/configs/model-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "model-new",
                               "traffic": "mix-new", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric.x", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "x", "moves": "setup_s",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_files_are_found_by_name(tmp_path):
    root = _fake_root(tmp_path)
    cell = spec.load_cell("new-cell", root)
    assert cell.config == {"name": "model-new", "family": "dense"}
    assert [m.name for m in cell.end_to_end] == ["setup_s"]
    assert [m.name for m in cell.per_layer] == ["new_metric.x"]
    assert spec.metric_reader("new_metric.x", root)(None) == 42.0
    seg = spec.traffic_kind(cell.traffic["kind"], root)
    assert seg(cell.traffic, 0.0, 2.0) == [(0.0, 2.0, 3.0)]


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(ValueError):
        spec.traffic_kind("../escape")


PROBE = '''"""The dense family, with every call recorded."""
from bench.families import dense

CALLS = []
MODES = dense.MODES


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(dense, name)(*args, **kwargs)
    return call


build_arch = _recorded("build_arch")
init_rule = _recorded("init_rule")
logits = _recorded("logits")
request_flops = _recorded("request_flops")
kernel_calls = _recorded("kernel_calls")
'''


def _add_config(root: Path, name: str, **keys) -> None:
    """A configuration file `name` (phi3-mini-3.8b's, with `keys` set or,
    where None, left out) and a cell `<name>-cell` serving it."""
    cfg = spec.load_json(ROOT / "bench" / "configs" / "phi3-mini-3.8b.json")
    cfg.update(keys, name=name)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "x",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": f"{name}-cell", "config": name,
                               "traffic": "phi3-overload", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_family_is_found_by_name(tmp_path):
    """A family added as one file, named by a configuration added as
    another, serves a whole run: every call that depends on the model's
    shape goes to it."""
    root = _fake_root(tmp_path)
    (root / "bench" / "families" / "probe.py").write_text(PROBE)
    _add_config(root, "probe-model", family="probe")
    cell = spec.load_cell("probe-model-cell", root)
    assert Path(cell.family.__file__) == root / "bench" / "families" / \
        "probe.py"
    small = small_cell("probe-model", "phi3-overload", 20.0, root)
    probe = small.family
    res = harness.run_cell(small, 2**31 + 7, 2.0, False, jax.devices()[:1],
                           time.perf_counter(), registry=get_smoke,
                           log=lambda s: None)
    assert res["correct"], res["checks"]
    # the served weights are drawn by the probe's rule, leaf by leaf
    rules = probe.CALLS.count("init_rule")
    assert rules >= 10
    assert probe.CALLS == ["build_arch"] + ["init_rule"] * rules + ["logits"]
    del probe.CALLS[1:1 + rules]
    # the operation counts of a traced run: one stage call of 2 rows
    ms = 1_000_000
    trace = tr.Trace({0: [("flash_attention", 2 * ms, 3 * ms),
                          ("decode_attention", 3 * ms, 4 * ms)]},
                     {0: [("jit_generate", 2 * ms, 4 * ms)]},
                     {0: (ms, 5 * ms)})
    run = Run(config=small.config, family=probe,
              peak=harness.load_peak("TPU v5 lite"), prompt=128, gen=8,
              buckets=(1, 2), arrival=np.zeros(2), started=np.zeros(2),
              done=np.zeros(2), w0=0.0, w1=0.01,
              calls=[Call(0, 0, 0.0, 0.004, 2)], trace=trace)
    run.offset_ns = tr.clock_offset_ns(trace, {0: 0.0})
    for name in ("step_mfu_pct", "flash_attention_roofline",
                 "decode_attention_roofline"):
        assert spec.metric_reader(name)(run) > 0.0
    assert probe.CALLS[2:] == ["request_flops", "kernel_calls",
                               "kernel_calls"]


def test_unknown_or_missing_family_is_an_error(tmp_path):
    root = _fake_root(tmp_path)
    (root / "bench" / "families" / "half.py").write_text(
        "MODES = ('float32',)\n\ndef logits(*a):\n    pass\n")
    _add_config(root, "no-family", family=None)
    _add_config(root, "unknown-family", family="no_such_family")
    _add_config(root, "bad-family", family="../dense")
    _add_config(root, "half-family", family="half")
    with pytest.raises(KeyError, match="'family'"):
        spec.load_cell("no-family-cell", root)
    with pytest.raises(FileNotFoundError, match="no_such_family"):
        spec.load_cell("unknown-family-cell", root)
    with pytest.raises(ValueError, match="bad name"):
        spec.load_cell("bad-family-cell", root)
    with pytest.raises(ValueError, match="build_arch"):
        spec.load_cell("half-family-cell", root)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


ARGS = ["--workload", "phi3-overload", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_run_fails_without_a_chip():
    p = _run(ARGS, ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_fails_with_the_benchmark_files_alone(tmp_path):
    root = tmp_path / "alone"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    p = _run(ARGS, root)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert "{" not in p.stdout
