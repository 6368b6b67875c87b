"""What a cell is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each lives in a file of
its own: ``configs/<config>.json`` (the file ``BENCHMARK.json`` gives),
``traffic/<traffic>.json`` and ``limits/<cell>.json`` (the limits that
decide ``correct``).  The traffic's ``kind`` names the module that runs it,
``kinds/<kind>.py``; each per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a mix or a metric is adding
files and entries: nothing here names one.

Importing this module touches no accelerator.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic file
    limits: dict          # number compared -> its limit
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(path: Path = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, cell_e2e: set[str]) -> bool:
    """Does ``cell`` report ``metric``?  A metric with ``workloads`` is
    reported where it lists; a per-layer one without, wherever the
    end-to-end metric it moves is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in cell_e2e
    return True


def resolve(workload: str) -> Cell:
    """The cell named ``workload``, with its files read."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(ROOT / configs[w["config"]]["file"])
    traffic = _read_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(HERE / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits["limits"],
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path, name: str) -> ModuleType:
    """Import the file at ``path`` as a module called ``name`` (metric
    files carry dots in their names, so they are loaded by path)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    if name in sys.modules:
        return sys.modules[name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str) -> ModuleType:
    return load_module(HERE / "kinds" / f"{kind}.py",
                       f"chipbench_kind_{kind}")


def metric_reader(name: str):
    """``read`` of ``metrics/<name>.py``: run record -> number or None."""
    mod = load_module(HERE / "metrics" / f"{name}.py",
                      "chipbench_metric_" + name.replace(".", "_"))
    return mod.read
