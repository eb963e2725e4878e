"""Find a cell and everything it names, by name, from the data files.

``BENCHMARK.json`` lists the cells (``workloads``), the configurations and
the metrics.  Each cell also has ``bench/cells/<cell>.json`` (driver and
its parameters), each traffic mix ``bench/traffic/<mix>.json`` and each
configuration the ``file`` that ``BENCHMARK.json`` gives it.  A driver
is the module ``bench/harness/<driver>.py``, with ``run(cell, ...)``; a
per-layer metric is read by ``bench/layers/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    driver: str
    params: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path


def _load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              spec: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics.

    ``bench_dir`` is the benchmark's directory; ``BENCHMARK.json`` sits
    in its parent, unless ``spec`` gives its content.  Raises
    ``KeyError`` for an unknown name and ``ValueError`` where the cell
    file and ``BENCHMARK.json`` disagree."""
    root = bench_dir.parent
    if spec is None:
        spec = _load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(by_name)}")
    entry = by_name[name]
    cell_file = _load_json(bench_dir / "cells" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if cell_file.get(key) != entry[key]:
            raise ValueError(f"cell {name}: {key} is {cell_file.get(key)!r} "
                             f"in its file, {entry[key]!r} in BENCHMARK.json")
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[entry["config"]]["file"])
    traffic = _load_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    return Cell(
        name=name, chips=int(entry["chips"]), driver=cell_file["driver"],
        params=dict(cell_file.get("params", {})),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if reports(m, name)],
        per_layer=per_layer_for(spec, name),
        bench_dir=bench_dir)


def reports(metric: Dict[str, Any], cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (no ``workloads``: every cell)."""
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_for(spec: Dict[str, Any], cell: str) -> List[Dict[str, Any]]:
    """Per-layer metrics read in ``cell``: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in spec["end_to_end"] if reports(m, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_driver(name: str):
    """The driver module ``bench.harness.<name>``."""
    if not name.isidentifier():
        raise ValueError(f"driver {name!r} is not a module name")
    return importlib.import_module(f"bench.harness.{name}")


def load_reader(metric: str, bench_dir: Path = BENCH_DIR
                ) -> Callable[[Any], Optional[float]]:
    """``read(window)`` from ``bench/layers/<metric>.py``."""
    path = bench_dir / "layers" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_layer_{metric.replace('.', '_').replace('-', '_')}", path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
