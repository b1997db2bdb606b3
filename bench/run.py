#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload h2o_purify_1chip --seed 7 \
        --seconds 20 --trace 0

Runs on a TPU only, with at least the chips the cell asks for; anywhere
else it exits non-zero and prints no result.  Set-up (making the inputs
from the seed, compiling or loading the programs from the compile cache
in ``.jax_cache/``, one warm-up of each) counts from the start of this
process.  The window then runs whole units of work back to back for
``--seconds`` and closes at the end of the first unit that ends after it.
``--trace 1`` traces the window with the profiler and reports the
per-layer metrics instead of the end-to-end ones.  After the window the
output sampled from it is compared with a plain reference; the numbers
compared, each beside its limit, are the last lines of standard error,
and the last line of standard output is the result as JSON.

``--control 1`` puts the reference, computed one precision lower, in the
program's place: a check that has to come out not correct.  The timed
runs never use it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, HERE)
    from benchlib.harness import main as run

    return run(args, root=ROOT, t0=T0)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
