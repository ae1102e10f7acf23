"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by name:

* ``<bench>/configs/<config>.json``: the deployment (the ``file`` of the
  configuration's entry);
* ``<bench>/traffic/<mix>.json``: the traffic mix; its ``kind`` names the
  generator module ``<bench>/traffic/<kind>.py`` that reads it;
* ``<bench>/metrics/<metric>.py``, else ``<bench>/metrics/<prefix>.py``
  for a metric named ``<prefix>.<suffix>``: the metric's reader, a module
  with ``read(ctx) -> float | None`` (``None``: nothing to read, and the
  metric is left out of the result line). End-to-end metrics have readers
  too.

A new cell, configuration, mix or metric is therefore a new file and an
entry in ``BENCHMARK.json``, never an edit of a file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: ModuleType
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict[str, ModuleType]


def load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def metric_reader_path(bench_dir: str, metric: str) -> str:
    exact = os.path.join(bench_dir, "metrics", metric + ".py")
    if os.path.exists(exact):
        return exact
    prefix = os.path.join(bench_dir, "metrics", metric.split(".")[0] + ".py")
    if os.path.exists(prefix):
        return prefix
    raise FileNotFoundError(f"no reader for metric {metric!r} under "
                            f"{os.path.join(bench_dir, 'metrics')}")


def resolve(root: str, cell_name: str, bench_dir: str | None = None) -> Cell:
    """The cell ``cell_name`` of ``<root>/BENCHMARK.json``, with its
    configuration, mix, generator and metric readers loaded from files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[w["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    bench = bench_dir or os.path.join(root, os.path.dirname(entry["file"]),
                                      os.pardir)
    bench = os.path.normpath(bench)
    with open(os.path.join(bench, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    generator = load_module(os.path.join(bench, "traffic",
                                         traffic["kind"] + ".py"),
                            f"bench_traffic_{traffic['kind']}")
    e2e = [m for m in spec["end_to_end"] if _applies(m, cell_name)]
    layer = [m for m in spec["per_layer"] if _applies(m, cell_name)]
    readers = {m["name"]: load_module(metric_reader_path(bench, m["name"]),
                                      "bench_metric_" + m["name"].replace(
                                          ".", "_"))
               for m in e2e + layer}
    return Cell(name=cell_name, chips=int(w["chips"]),
                config=config, traffic=traffic,
                generator=generator, end_to_end=e2e, per_layer=layer,
                readers=readers)
