"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- configuration ``<c>``: ``bench/configs/<c>.json`` (sizes, serving
  settings, correctness limit) and ``bench/configs/<c>.py`` (the plain
  reference, the weights, the operation and byte counts);
- traffic ``<t>``: ``bench/traffic/<t>.json``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py``, or, when that file is
  absent, ``bench/metrics/<part before the first dot>.py``, so that
  ``step_ms.chat`` and ``step_ms.backlog`` share one reader.

Adding a configuration, a traffic mix or a metric is adding files and
entries; no code here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

from . import traffic as traffic_lib

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Config:
    name: str
    data: dict
    code: ModuleType


@dataclass
class Cell:
    name: str
    chips: int
    config: Config
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]      # metric entries this cell reports
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> Config:
    data = json.loads((bench_dir / "configs" / f"{name}.json").read_text())
    code = _module(bench_dir / "configs" / f"{name}.py",
                   f"bench_config_{name.replace('-', '_').replace('.', '_')}")
    return Config(name, data, code)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    base = bench_dir / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return _module(path, "bench_metric_" + stem.replace(".", "_"))
    raise FileNotFoundError(f"no reader for metric {name!r} under {base}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark(root)
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    bench_dir = root / "bench"
    tr = traffic_lib.load(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), load_config(w["config"], bench_dir),
                w["traffic"], tr, e2e, layer)
