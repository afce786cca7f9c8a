"""Find a cell and everything that belongs to it by name.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Its parts are
files of their own, found by the names the entry gives:

  configs/<config>.json   the model configuration as it is run; it names
                          the modules of this directory that count a
                          step's work (``work``) and follow it in plain
                          float32 (``reference``)
  traffic/<traffic>.json  the traffic mix: groups, speed curve, windows
  limits/<cell>.json      the limits of the comparison that decides
                          ``correct``, with the readings they came from
  metrics/<metric>.py     one reader per metric (see ``metrics/``)
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench if bench is not None else _read(BENCHMARK)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    body = _read(ROOT / config["file"])
    if "work" not in body:
        raise ValueError(f"{config['file']} names no work module ('work'): "
                         f"the FLOPs and bytes of its step are uncounted")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=body,
        traffic=_read(HERE / "traffic" / f"{entry['traffic']}.json"),
        limits=_read(HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``, loaded from
    its path (a metric name may hold dots)."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def module(name: str):
    """A module of this directory named by a configuration file."""
    return importlib.import_module(f"chipbench.{name}")
