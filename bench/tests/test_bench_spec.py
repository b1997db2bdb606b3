"""A configuration, a traffic mix, a cell and a per-layer metric are added
as new files plus new ``BENCHMARK.json`` entries, and the harness finds
and runs them with no file that was there changed."""
import hashlib

from conftest import read_json, run, write_json

METRIC = '''"""Most sweeps of any purification in the window."""


def read(rec):
    s = rec.counters.get("sweeps")
    return max(s) if s else None
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and p.name != "BENCHMARK.json"}


def test_new_files_are_found_by_name(checkout, monkeypatch):
    bench = checkout / "bench"
    before = _digests(checkout)

    cfg = read_json(bench / "configs" / "h2o_dft_ls.json")
    cfg.update(name="h2o_small", block_rows={"1": 12})
    write_json(bench / "configs" / "h2o_small.json", cfg)
    traffic = read_json(bench / "traffic" / "purify.json")
    traffic["sync_every"] = 2
    write_json(bench / "traffic" / "purify_sync2.json", traffic)
    (bench / "metrics" / "sweeps_max.py").write_text(METRIC)
    write_json(bench / "cells" / "h2o_small.sync2.json",
               read_json(bench / "cells" / "h2o_purify_1chip.json"))
    spec = read_json(checkout / "BENCHMARK.json")
    spec["configs"].append({
        "name": "h2o_small", "source": "https://arxiv.org/abs/1705.10218",
        "file": "bench/configs/h2o_small.json", "reduced": ["block_rows"],
        "why": "a test configuration"})
    spec["workloads"].append({
        "name": "h2o_small.sync2", "config": "h2o_small",
        "traffic": "purify_sync2", "chips": 1, "why": "a test cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "h2o_purify_1chip" in m.get("workloads", ()):
            m["workloads"].append("h2o_small.sync2")
    spec["per_layer"].append({
        "name": "sweeps_max", "unit": "sweeps", "better": "lower",
        "source": "program_counter", "layer": "sign iteration",
        "moves": "purify_s", "workloads": ["h2o_small.sync2"]})
    write_json(checkout / "BENCHMARK.json", spec)

    after = _digests(checkout)
    assert all(after[p] == d for p, d in before.items())
    assert set(after) - set(before) == {
        p.relative_to(checkout) for p in (
            bench / "configs" / "h2o_small.json",
            bench / "traffic" / "purify_sync2.json",
            bench / "metrics" / "sweeps_max.py",
            bench / "cells" / "h2o_small.sync2.json")}

    line = run(checkout, "h2o_small.sync2", trace=1, monkeypatch=monkeypatch)
    assert line["correct"], line["checks"]
    sweeps = line["metrics"]["sweeps_max"]["value"]
    assert sweeps >= 2 and sweeps % 2 == 0  # synced every 2 sweeps
    assert "sweeps_per_purify" in line["metrics"]
    # the new metric is not reported by the cells that do not list it
    line = run(checkout, "h2o_purify_1chip", trace=1, monkeypatch=monkeypatch)
    assert "sweeps_max" not in line["metrics"]


def test_every_entry_has_its_files():
    import os

    from benchlib.spec import load_cell
    from conftest import ROOT

    spec = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell = load_cell(ROOT, w["name"])
        assert cell.op().Op and cell.generator().make
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.metric(m["name"]).read)
        assert str(cell.chips) in cell.config["block_rows"]


def test_benchmark_json_shape():
    import os
    import re

    from conftest import ROOT

    spec = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source",
                           "workloads"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    for section, allowed in keys.items():
        names = [e["name"] for e in spec[section]]
        assert len(names) == len(set(names))
        for e in spec[section]:
            assert set(e) <= allowed, e
            assert name.match(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text]
            if "unit" in e:
                assert unit.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    cells = {w["name"] for w in spec["workloads"]}
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(cells) // 2)
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
