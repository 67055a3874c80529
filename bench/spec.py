"""Find a cell's configuration, model family, traffic mix and metrics by
name.

Nothing here knows a cell, configuration, mix, model family or metric
by name: each is a file under ``bench/`` that ``BENCHMARK.json`` or a
configuration file points to, so a later change adds a file and an entry
and edits nothing that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# what a family file (bench/families/<family>.py) gives the harness
FAMILY_API = ("build_arch", "init_rule", "logits", "MODES",
              "request_flops", "kernel_calls")


def _check_name(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]]      # None: every cell that reports `moves`
    moves: Optional[str] = None         # per-layer metrics only


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    family: ModuleType          # bench/families/<config["family"]>.py
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _metric(m: Dict[str, Any]) -> Metric:
    return Metric(_check_name(m["name"]), m["unit"], m.get("workloads"),
                  m.get("moves"))


def reports(metric: Metric, cell: str, e2e_names: List[str]) -> bool:
    """Whether `cell` reports `metric` (per-layer: only where the
    end-to-end metric it moves is reported too)."""
    if metric.workloads is not None:
        return cell in metric.workloads
    return metric.moves is None or metric.moves in e2e_names


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    path = root / configs[w["config"]]["file"]
    config = load_json(path)
    if "family" not in config:
        raise KeyError(f"{path}: no 'family' key; a configuration file "
                       f"names its model family, a file under "
                       f"bench/families/")
    traffic = load_traffic(_check_name(w["traffic"]), root)
    e2e = [m for m in map(_metric, bench["end_to_end"])
           if reports(m, workload, [])]
    names = [m.name for m in e2e]
    per_layer = [m for m in map(_metric, bench["per_layer"])
                 if reports(m, workload, names)]
    return Cell(workload, w["config"], w["traffic"], int(w["chips"]),
                config, family(config["family"], root), traffic, e2e,
                per_layer)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str, root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "bench" / "traffic" / f"{_check_name(name)}.json")


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod      # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str, root: Path = ROOT) -> Callable:
    """The ``segments(params, t0, t1)`` function of one generator kind."""
    path = root / "bench" / "traffic" / "kinds" / f"{_check_name(kind)}.py"
    return _load_module(path, f"bench_traffic_kind_{kind}").segments


def family(name: str, root: Path = ROOT) -> ModuleType:
    """The module of one model family: everything that depends on the
    model's shape.

    * ``build_arch(config, registry)``: the registry's model cut as the
      configuration file says, checked against the file;
    * ``init_rule(name, shape)``: how the seeded weights draw the leaf
      `name` of one layer's `shape` (:mod:`bench.weights`); raises for
      a leaf the family does not know;
    * ``logits(config, key, seqs, last, mode="float32", rows=64)``: the
      plain reference's logits at the last `last` positions, computed in
      blocks of `rows` sequences from the seed's weights, importing
      nothing of the program; ``MODES[0]`` is ``"float32"``, the
      reference, and the rest are its controls, the nearest first;
    * ``request_flops(config, prompt, gen)``: operations one request
      needs;
    * ``kernel_calls(config, bucket, prompt, gen)``: ``(kernel, flops,
      bytes)`` of every kernel call of one generate.
    """
    path = root / "bench" / "families" / f"{_check_name(name)}.py"
    mod = _load_module(path, "bench_family_" + re.sub(r"[.\-]", "_", name))
    missing = [a for a in FAMILY_API if not hasattr(mod, a)]
    if missing:
        raise ValueError(f"{path} does not define {missing}")
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` function of one per-layer metric."""
    path = root / "bench" / "metrics" / f"{_check_name(name)}.py"
    return _load_module(path, "bench_metric_" + name.replace(".", "_")).read
