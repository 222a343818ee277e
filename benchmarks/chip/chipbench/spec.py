"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``.  Its configuration is the file that
the ``configs`` entry names, with its plain reference beside it (the
``reference`` key names ``<reference>.py`` in the same directory); its
traffic mix is ``traffic/<traffic>.json``; its limits are
``limits/<cell>.json``; each per-layer metric is read by
``metrics/<metric>.py``, a module with ``read(run) -> float | None``.  New
cells, mixes, configurations and metrics are new files: nothing here names
one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

#: the benchmark's own directory (holds this package)
BENCH_DIR = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


@dataclass
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None      # per-layer reader


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    reference: ModuleType
    traffic_name: str
    traffic: dict
    limits: Optional[dict]
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"{path} is missing") from e


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    if str(path.parents[1]) not in sys.path:
        sys.path.insert(0, str(path.parents[1]))    # for `import chipbench`
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, its files read from
    ``bench_dir``."""
    spec = _json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = Path(root) / configs[w["config"]]["file"]
    config = _json(cfg_file)
    ref = _module(cfg_file.parent / f"{config['reference']}.py",
                  f"chipbench_ref_{config['reference']}")
    limits_file = bench_dir / "limits" / f"{name}.json"
    e2e = [Metric(m["name"], m["unit"])
           for m in spec["end_to_end"] if _applies(m, name)]
    per_layer = [
        Metric(m["name"], m["unit"],
               _module(bench_dir / "metrics" / f"{m['name']}.py",
                       f"chipbench_metric_{m['name']}").read)
        for m in spec["per_layer"] if _applies(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, reference=ref, traffic_name=w["traffic"],
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_json(limits_file) if limits_file.is_file() else None,
        end_to_end=e2e, per_layer=per_layer)


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    """The peaks row of device kind ``kind``; an unknown kind raises."""
    table = _json(bench_dir / "peaks.json")
    if kind not in table:
        raise UnknownDevice(f"device kind {kind!r} has no peaks in "
                            f"{bench_dir / 'peaks.json'} (known: "
                            f"{sorted(table)})")
    return table[kind]
