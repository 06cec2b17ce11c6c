"""Everything a cell is made of, found by name: ``BENCHMARK.json`` at the
checkout root, and under ``bench/`` one file for each workload, each
configuration, each traffic mix and each metric.  A new cell, mix,
configuration or metric is a new file; nothing here changes for it."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _read(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark() -> Dict[str, Any]:
    return _read(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict[str, Any]:
    return _read(BENCH / "workloads" / f"{name}.json")


def config(name: str) -> Dict[str, Any]:
    return _read(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _read(BENCH / "traffic" / f"{name}.json")


def metric_reader(name: str) -> Callable:
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"missing {path.relative_to(ROOT)}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: Dict[str, Any], cell: str, group: str) -> List[dict]:
    """The metrics of ``group`` ("end_to_end" | "per_layer") that ``cell``
    reports: those naming it under ``workloads``, and those with no such
    key, which every cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def resolve(cell: str) -> Dict[str, Any]:
    """The cell's workload entry with its configuration and traffic."""
    wl = workload(cell)
    return {"name": cell, "workload": wl, "config": config(wl["config"]),
            "traffic": traffic(wl["traffic"])}
