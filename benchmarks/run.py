"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip-measured]
    PYTHONPATH=src python -m benchmarks.run --summary-only

Prints ``name,value,notes`` CSV.  Each module's ``check()`` asserts the
paper-claim validation (Table 2 within 2x on all 39 cells, Fig. 2/3/4
scaling laws, Fig. 1 bounds); ``run()`` emits the numbers.

The run ends with an aggregate of every ``BENCH_*.json`` series the CI
benchmarks emit (local_mm, signiter, tuner, plan_cache, ...): one flat
``file:path,value`` table, so the perf trajectory of any metric is
greppable across PRs from one place.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

from benchmarks import (
    bench_serving,
    fig1_speedups,
    fig2_message_sizes,
    fig3_comm_ratios,
    fig4_weak_scaling,
    moe_spgemm,
    roofline_report,
    table1_matrices,
    table2_strong_scaling,
)

MODULES = [
    ("table1", table1_matrices, False),
    ("table2", table2_strong_scaling, True),
    ("fig1", fig1_speedups, True),
    ("fig2", fig2_message_sizes, True),
    ("fig3", fig3_comm_ratios, True),
    ("fig4", fig4_weak_scaling, True),
    ("moe_spgemm", moe_spgemm, True),
    ("serving", bench_serving, True),
    ("roofline", roofline_report, False),
]


def _flatten(prefix: str, obj, out: list[tuple[str, object]]) -> None:
    """Flatten a BENCH json into (dotted.path, scalar) rows."""
    if isinstance(obj, dict):
        for k in sorted(obj):
            _flatten(f"{prefix}.{k}" if prefix else str(k), obj[k], out)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _flatten(f"{prefix}[{i}]", v, out)
    elif isinstance(obj, (int, float, str, bool)) or obj is None:
        out.append((prefix, obj))


def summarize_bench_json(paths: list[str] | None = None) -> int:
    """One flat, greppable summary table of every BENCH_*.json series."""
    if paths is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # realpath-dedup: running from the repo root must not list each
        # file twice (absolute via root + relative via cwd)
        paths = sorted(
            {os.path.realpath(p)
             for p in glob.glob(os.path.join(root, "BENCH_*.json"))
             + glob.glob("BENCH_*.json")}
        )
    if not paths:
        return 0
    print("\n# BENCH summary (file:path,value)")
    n = 0
    for path in paths:
        tag = os.path.basename(path).removesuffix(".json")
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            print(f"{tag}:LOAD_FAILED,{e!r}")
            continue
        rows: list[tuple[str, object]] = []
        _flatten("", data, rows)
        for key, val in rows:
            if isinstance(val, float):
                val = f"{val:.6g}"
            print(f"{tag}:{key},{val}")
            n += 1
    return n


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-measured", action="store_true",
                    help="skip the 64-fake-device HLO measurement subprocess "
                    "(it runs on the CPU: JAX_PLATFORMS=cpu)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--summary-only", action="store_true",
                    help="only aggregate existing BENCH_*.json files")
    args = ap.parse_args()

    if args.summary_only:
        summarize_bench_json()
        return

    failures = []
    for name, mod, has_check in MODULES:
        if args.only and name not in args.only:
            continue
        try:
            if has_check:
                mod.check()
            for row_name, val, note in mod.run():
                print(f"{row_name},{val},{note}")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name}/CHECK_FAILED,-1,{e!r}", flush=True)

    if not args.skip_measured and (not args.only or "measured" in args.only):
        # HLO-measured engine collective bytes: a check of the compiled
        # HLO on 64 fake CPU devices, so a subprocess (the flag must precede
        # its jax import) pinned to the CPU with JAX_PLATFORMS=cpu — this
        # process has imported jax and may hold the accelerator, which a
        # child must never contend for
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "benchmarks", "measure_comm.py")],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            failures.append(("measured", proc.stderr[-500:]))
            print(f"measured/CHECK_FAILED,-1,{proc.stderr[-200:]!r}")
        else:
            sys.stdout.write(proc.stdout)

    summarize_bench_json()

    if failures:
        print(f"\n{len(failures)} benchmark module(s) FAILED", file=sys.stderr)
        for n, e in failures:
            print(f"  {n}: {e}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
