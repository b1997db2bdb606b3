"""Runs the four-chip purification cell on four CPU devices at a tiny size,
sound and with the exchange between devices left out (every
``lax.ppermute`` returns its own shard), and prints the two results as
one JSON object.  ``XLA_FLAGS`` must give four host devices before JAX is
imported, so the test starts this file as a process:

    python bench/tests/four_device_run.py <scratch dir>
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

import conftest  # noqa: E402

WORKLOAD = "h2o_purify_4chip"


class _Patch:
    """Just enough of pytest's monkeypatch for ``conftest.run``."""

    def __init__(self):
        self.undo = []

    def setattr(self, obj, name, value):
        self.undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def restore(self):
        for obj, name, value in reversed(self.undo):
            setattr(obj, name, value)


def main(scratch: str) -> int:
    import pathlib
    import shutil

    from repro.core import plan

    root = pathlib.Path(scratch) / "checkout"
    root.mkdir(parents=True)
    shutil.copy(os.path.join(conftest.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(conftest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    path = root / "bench" / "configs" / "h2o_dft_ls.json"
    cfg = conftest.read_json(path)
    cfg["block_rows"] = conftest.TINY_ROWS["h2o_dft_ls"]
    conftest.write_json(path, cfg)
    # the four-chip cell is not in BENCHMARK.json (not yet proven on the
    # chip); its entries are added here, beside its limits file
    spec = conftest.read_json(root / "BENCHMARK.json")
    if WORKLOAD not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append({
            "name": WORKLOAD, "config": "h2o_dft_ls", "traffic": "purify",
            "chips": 4, "why": "four-device test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "h2o_purify_1chip" in m.get("workloads", ()):
                m["workloads"].append(WORKLOAD)
        conftest.write_json(root / "BENCHMARK.json", spec)

    out = {"devices": len(jax.devices())}
    patch = _Patch()
    out["sound"] = conftest.run(root, WORKLOAD, monkeypatch=patch)
    plan.clear_cache()
    patch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)
    out["no_exchange"] = conftest.run(root, WORKLOAD, monkeypatch=patch)
    patch.restore()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
