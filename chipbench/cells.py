"""From a cell's name to the files that define it.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them sits in a file found by that name:

  * ``configs/<config>.json``  - the sizes as run, source, ``reduced``,
    ``assumed`` (optimizer, dtype policy);
  * ``traffic/<traffic>.json`` - ``kind`` (the driver in ``drivers/<kind>.py``)
    and its parameters;
  * ``metrics/<metric>.json``  - ``reader`` (``readers/<reader>.py``) and the
    reader's arguments.

A new cell, configuration, mix or per-layer metric is a new file plus an
entry in ``BENCHMARK.json``; no file that is there needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]   # BENCHMARK.json entries of this cell
    per_layer: List[Dict[str, Any]]


def _reported_in(metric: Dict[str, Any], cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def resolve(bench: Dict[str, Any], name: str, here: Path = HERE) -> Cell:
    """The cell ``name`` of ``bench`` with its files read. Raises
    ``KeyError`` for a name ``BENCHMARK.json`` does not list."""
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        known = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    declared = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(here.parent / declared["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reported_in(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reported_in(m, name)],
    )


def load_driver(kind: str):
    """``drivers/<kind>.py``: ``run(cell, seed, seconds, trace, t0) -> Result``."""
    return importlib.import_module(f"chipbench.drivers.{kind}")


def load_reader(metric_name: str, here: Path = HERE):
    """``(read, args)`` of a per-layer metric: the function ``read`` of
    ``readers/<reader>.py`` and the arguments its metric file gives it."""
    spec = load_json(here / "metrics" / f"{metric_name}.json")
    module = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    return module.read, spec.get("args", {})
