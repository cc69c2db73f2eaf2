"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names every cell. Each piece of
a cell is a file of its own, found from its name alone:

* configuration: the ``file`` its ``configs`` entry names
  (``bench/configs/<config>.json``);
* traffic mix: ``bench/traffic/<traffic>.json``;
* limits of the comparison that decides ``correct``:
  ``bench/limits/<workload>.json``;
* per-layer metric: ``bench/metrics/<metric>.py``, a module with
  ``read(ctx) -> float | None``;
* model (the configuration's ``model``): ``bench/models/<model>.py``, from
  the configuration's sizes its parameter tree and whole layer stack for
  the plain reference, and its SpMM widths and dense maps for the work
  counts.

A new cell, mix or metric is new files plus new entries in
``BENCHMARK.json``; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def _load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    here = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=_load_json(here / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _module(kind: str, name: str, root: Path):
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, root).read


def model_module(name: str, root: Path = ROOT):
    """``bench/models/<name>.py``."""
    return _module("models", name, root)
