"""Find a cell and everything it names, by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file names its graph recipe and its model; the
traffic, the graph recipe, the model, the correctness limits and each
per-layer metric's reader are files of their own under ``bench/``:

  bench/traffic/<traffic>.json    sync interval, store precision, pull mode
  bench/graphs/<graph>.json       generator parameters, counts, source
  bench/models/<model>.py         the reference's ``layer`` and ``pull``,
                                  the epoch's ``work``, the program's
                                  pulled halo tables (``slab``)
  bench/limits/<cell>.json        limit of each number the comparison checks
  bench/metrics/<metric>.py       ``read(run) -> float | None``

So a cell, a graph, a metric or a model is added by adding files and
entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    model: object         # the model file (load_model)
    traffic: dict
    graph: dict
    limits: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def bench_dir(root: Path) -> Path:
    return Path(root) / "bench"


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files.
    Raises ``KeyError`` for a cell that is not there and
    ``FileNotFoundError`` for a file it names that is missing, the
    configuration's model file among them."""
    root = Path(root)
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(root / configs[entry["config"]]["file"])
    here = bench_dir(root)
    graph = read_json(here / "graphs" / f"{config['graph']}.json")
    if config["num_nodes"] != graph["num_nodes"]:
        raise ValueError(f"{config['name']}: num_nodes {config['num_nodes']}"
                         f" but graph {graph['name']} has "
                         f"{graph['num_nodes']}")
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and _reports(m, name)]
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                model=load_model(root, config["model"]),
                traffic=read_json(here / "traffic"
                                  / f"{entry['traffic']}.json"),
                graph=graph,
                limits=read_json(here / "limits" / f"{name}.json"),
                end_to_end=end_to_end, per_layer=per_layer)


def _load(path: Path, module: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _load(bench_dir(root) / "metrics" / f"{metric}.py",
                 "bench_metric_" + re.sub(r"\W", "_", metric)).read


def load_model(root: Path, model: str):
    """The model file ``bench/models/<model>.py``: its ``layer`` and
    ``pull`` (``reference.py``), ``work`` (``counts.epoch``) and ``slab``
    (``cell.outputs``).  A model with no file is refused."""
    return _load(bench_dir(root) / "models" / f"{model}.py",
                 "bench_model_" + re.sub(r"\W", "_", model))
