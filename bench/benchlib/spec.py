"""``BENCHMARK.json`` and the files it names, found by name.

Layout under the benchmark directory (``bench/``):

- ``configs/<name>.json``: a configuration (the ``file`` of its entry);
- ``generators/<generator>.py``: makes a configuration's matrices from a
  seed (``make(config, nb, seed)``), named by the configuration's
  ``generator`` key;
- ``traffic/<name>.json``: a traffic mix, parameters only; its ``op`` key
  names the operation;
- ``ops/<op>.py``: drives the program under test for one operation;
- ``reference/``: the plain references, which import nothing of the
  program;
- ``cells/<workload>.json``: the correctness limits of one cell;
- ``metrics/<name>.py``: the reader of one metric (``read(record)``).

Adding a configuration, a traffic mix, a cell or a metric is adding
files and ``BENCHMARK.json`` entries; no file that is already there
changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` under the module name ``name``
    (metric files carry dots in their names, so they are loaded by path)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1
    bench_dir: str

    @property
    def block_rows(self) -> int:
        """Block rows of the configuration as cut for this cell's chips."""
        return int(self.config["block_rows"][str(self.chips)])

    def op(self):
        return load_module(os.path.join(self.bench_dir, "ops",
                                        f"{self.traffic['op']}.py"),
                           f"bench_op_{self.traffic['op']}")

    def generator(self):
        gen = self.config["generator"]
        return load_module(os.path.join(self.bench_dir, "generators",
                                        f"{gen}.py"), f"bench_gen_{gen}")

    def metric(self, name: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        f"{name}.py"),
                           "bench_metric_" + name.replace(".", "_"))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, spec["paths"][0])
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; one of "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(bench_dir, "cells", f"{workload}.json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, workload) and m["moves"] in reported]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits["limits"], end_to_end=e2e,
                per_layer=per_layer, bench_dir=bench_dir)
