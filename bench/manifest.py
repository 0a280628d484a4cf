"""``BENCHMARK.json`` and the files it names, found by name.

A cell ``<config>.<traffic>`` needs nothing but data: its configuration is
the file the manifest's ``configs`` entry names, its traffic mix is
``traffic/<traffic>.json`` beside this module, and every metric it reports
is read by ``metrics/<metric name>.py``, a module with one function
``read(ctx) -> float | None``.  Adding a cell, a mix or a metric adds files
and manifest entries and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # manifest metric entries this cell reports
    per_layer: list


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(root: str, name: str, bench_dir: str = HERE) -> Cell:
    """The cell ``name`` with its configuration, traffic mix and metrics."""
    man = load(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in man["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in man["per_layer"] if _reports(m, name)])


def reader(metric: str, bench_dir: str = HERE):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list, ctx: dict, bench_dir: str = HERE) -> dict:
    """``{name: {"value", "unit"}}`` for each entry whose reader finds
    something to read; a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for m in entries:
        v = reader(m["name"], bench_dir)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
