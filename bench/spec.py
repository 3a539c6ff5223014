"""Find a cell's configuration, traffic mix and metric readers by name."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = ["BENCH", "ROOT", "load_benchmark", "find_cell", "load_json",
           "load_module", "metrics_for"]


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find_cell(bm: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bm["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bm['workloads']]}")


def load_json(kind: str, name: str) -> Dict[str, Any]:
    """``bench/<kind>/<name>.json``."""
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py``, imported from its path (metric names
    hold dots, so they are no importable module names)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bm: Dict[str, Any], cell: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The cell's ``per_layer`` metrics when tracing, else its
    ``end_to_end`` ones: those whose ``workloads`` list names the cell,
    or that have no such list."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
