"""The harness finds every configuration, mix and metric by name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] == c.config_name
    assert c.chips in (1, 4)
    assert any(m.name == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert spec.traffic_kind(c.traffic["kind"])
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
        {"name": "model-new"}))
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
    assert cell.config == {"name": "model-new"}
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
