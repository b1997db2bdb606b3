"""Fixtures of the benchmark's tests: the benchmark directory on the path,
and a scratch checkout (``BENCHMARK.json`` plus a copy of ``bench/``)
whose configurations are cut to a size the CPU runs in seconds."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# block rows of each configuration in the scratch checkout, per chip count
TINY_ROWS = {"h2o_dft_ls": {"1": 16, "4": 16}, "dense": {"1": 8}}


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def read_json(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark with tiny configurations."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, rows in TINY_ROWS.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = read_json(path)
        cfg["block_rows"] = rows
        write_json(path, cfg)
    return root


def run(root, workload, *, seed=5, seconds=0.3, trace=0, control=False,
        monkeypatch=None):
    """One run of ``workload`` in the scratch checkout ``root`` on the CPU,
    past the harness's look for a chip.  Returns the result line."""
    from benchlib import harness
    from benchlib.spec import load_cell

    if monkeypatch is not None:
        # the persistent compile cache is global to the process
        monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "")
    cell = load_cell(str(root), workload)
    rec, checks = harness.run_cell(
        cell, seed=seed, seconds=seconds, trace=bool(trace), root=str(root),
        t0=time.perf_counter(), require_tpu=False, control=control)
    devices = harness.pick_devices(cell.chips, False)
    return harness.result_line(cell, rec, checks, bool(trace), devices)


@pytest.fixture
def bench_run(checkout, monkeypatch):
    return SimpleNamespace(
        root=checkout,
        run=lambda workload, **kw: run(checkout, workload,
                                       monkeypatch=monkeypatch, **kw))
