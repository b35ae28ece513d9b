"""The benchmark as data: ``BENCHMARK.json`` and the files it names.

Every cell, configuration, traffic mix and per-layer metric is found by its
name alone, so a later cell, configuration or metric is new files plus new
entries in ``BENCHMARK.json``, never an edit of this harness:

- a configuration: ``BENCHMARK.json``'s ``file`` for it (a JSON object whose
  ``generator`` names ``data/<generator>.py``);
- a traffic mix: ``workloads/<traffic>.json``, whose ``driver`` names
  ``drivers/<driver>.py`` (how the clients drive the cluster) and whose
  ``op`` names ``ops/<op>.py`` (the plain reference of what a shuffle
  delivers);
- a metric: ``metrics/<metric name>.py``, with ``read(ctx)``.  A metric
  split by cell groups (``front_end_ms.part``) reads with its own file where
  there is one, else with the file of the name before its first dot.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, resolved."""

    name: str
    chips: int
    config: dict        # the configuration's file, as run
    traffic: dict       # workloads/<name>.json
    end_to_end: list    # the end-to-end metric entries this cell reports
    per_layer: list     # the per-layer metric entries this cell reports
    here: str = HERE    # the benchmark directory its files were found in


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, cell_name: str, root: str = ROOT,
            here: str = HERE) -> Cell:
    """The cell ``cell_name`` of ``bench``; KeyError names an unknown one."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r}; cells: {sorted(cells)}")
    w = cells[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    end_to_end = [m for m in bench["end_to_end"] if _reports(m, cell_name)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _reports(m, cell_name)]
    return Cell(
        name=cell_name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, conf["file"])),
        traffic=_load_json(os.path.join(here, "workloads", w["traffic"] + ".json")),
        end_to_end=end_to_end, per_layer=per_layer, here=here)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(config: dict, here: str = HERE):
    """``data/<config["generator"]>.py``: makes the configuration's tables."""
    name = config["generator"]
    return _module(os.path.join(here, "data", name + ".py"),
                   f"chipbench_data_{name}")


def driver(name: str, here: str = HERE):
    """``drivers/<name>.py``: its ``Driver(session)`` sets up the clients and
    warms the cell's shapes; ``.window(seconds, max_calls)`` measures."""
    return _module(os.path.join(here, "drivers", name + ".py"),
                   f"chipbench_driver_{name}")


def op(name: str, here: str = HERE):
    """``ops/<name>.py``: ``expected(keys, vals)``, the reference's rows,
    and ``lowered(keys, vals)``, the same one precision lower (the
    control)."""
    return _module(os.path.join(here, "ops", name + ".py"), f"chipbench_op_{name}")


def metric_reader(name: str, here: str = HERE):
    """``metrics/<name>.py``'s ``read(ctx) -> float | None``, or that of
    the name before its first dot."""
    path = os.path.join(here, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(here, "metrics", name.split(".")[0] + ".py")
    return _module(path, f"chipbench_metric_{name.replace('.', '_')}").read
