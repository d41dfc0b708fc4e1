"""Find a cell's configuration, traffic mix and metrics by name.

Everything is data under the benchmark's root: ``BENCHMARK.json`` names
the cells; ``kbench/configs/<config>.json`` and
``kbench/traffic/<traffic>.json`` hold a cell's deployment and mix; and
``kbench/metrics/<metric>.py`` is the reader of one metric, with a
``read(ctx)`` that returns a number, or ``None`` where the run has nothing
for it to read. A cell is added by adding files and entries, never by
editing code.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, List, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]     # the end-to-end metrics this cell reports
    per_layer: List[dict]      # the per-layer metrics this cell reports


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _named(kind: str, name: str, root: str, ext: str) -> str:
    path = os.path.join(root, "kbench", kind, name + ext)
    if not os.path.isfile(path):
        raise KeyError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                       f"named {name!r} ({path} is missing)")
    return path


def traffic(name: str, root: str = ROOT) -> dict:
    return _load_json(_named("traffic", name, root, ".json"))


def reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read`` function of ``kbench/metrics/<metric>.py``."""
    path = _named("metrics", metric, root, ".py")
    spec = importlib.util.spec_from_file_location(
        "kbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``; an unknown name is an
    error."""
    bench = benchmark(root)
    for w in bench["workloads"]:
        if w["name"] == name:
            cfgs = {c["name"]: c for c in bench["configs"]}
            if w["config"] not in cfgs:
                raise KeyError(f"cell {name!r} names config {w['config']!r}, "
                               "which BENCHMARK.json does not list")
            cfg = _load_json(os.path.join(root, cfgs[w["config"]]["file"]))
            return Cell(
                name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic(w["traffic"], root),
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])
    raise KeyError(f"no workload named {name!r}; BENCHMARK.json has "
                   f"{[w['name'] for w in bench['workloads']]}")
