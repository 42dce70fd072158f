"""Resolve a cell of ``BENCHMARK.json`` to the files that define it, by name.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Everything else is found from those names:

- ``configs[].file``: the configuration's sizes, generator, solver
  options and correctness limits (JSON);
- ``bench/inputs/<generator>.py``: the LP class that the configuration's
  ``generator`` key names, which makes the inputs from the seed
  (``bench/lpgen.py``);
- ``bench/traffic/<traffic>.json``: the traffic mix, whose ``loop`` key
  names the general driver in ``bench/loops/`` that reads it;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric, for
  every per-layer metric whose ``workloads`` list names the cell (or that
  moves an end-to-end metric the cell reports, where it has no list).

So a later change adds a cell, a mix, an LP class or a metric by adding
files and entries, and edits no file that is already there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

from bench import lpgen


@dataclasses.dataclass
class Cell:
    """One workload with its resolved files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]
    inputs: lpgen.LPClass


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reader(path: Path) -> Callable:
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load(root: Path, name: str) -> Cell:
    """Resolve workload ``name`` of ``root/BENCHMARK.json``.

    Raises ``KeyError`` for an unknown workload or configuration and
    ``FileNotFoundError`` for a file that a name points to and that is
    missing.
    """
    root = Path(root)
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names unknown configuration {w['config']!r}")
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    readers = {m["name"]: _reader(root / "bench" / "metrics" / f"{m['name']}.py") for m in layer}
    inputs = lpgen.load(root, config["generator"])
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer, readers, inputs)
