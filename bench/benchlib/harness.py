"""One run of one cell: set-up, the measured window, the correctness
check and the result line.

The operation of the cell's traffic mix (``ops/<op>.py``) provides a
class ``Op`` with

- ``Op(cell, seed, devices, control=False)``: make the inputs from the
  seed and hand them to the program (set-up);
- ``warm()``: run every program the window will run, once (set-up);
- ``step(k)``: the k-th unit of closed-loop work (a purification, a
  multiply), ended on the host with ``block_until_ready``;
- ``counters()``: what the program counted in the window;
- ``release()``: free the program's state, keeping the sampled output;
- ``check()``: the numbers compared, ``[(name, value, limit)]``, against
  the plain reference; ``value <= limit`` passes.

With ``control=True`` the op puts the reference, computed one precision
lower, in the program's place; its run has to come out not correct.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

CACHE_DIR = ".jax_cache"  # in the checkout: the path is part of the key
TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    pass


@dataclass
class RunRecord:
    """What a run measured; the metric readers read it."""

    workload: str
    chips: int
    seed: int
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    memory_peak_bytes: int = 0
    compiles_in_window: int = 0
    counters: dict = field(default_factory=dict)
    trace: object = None  # benchlib.trace.TraceSummary with --trace 1
    peaks: object = None  # benchlib.peaks.DevicePeaks


def enable_compile_cache(root: str) -> str:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = os.path.join(root, CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    return path


def pick_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, root: str,
             t0: float, require_tpu: bool = True, control: bool = False):
    """Set up, measure and check one run.  Returns ``(record, checks)``."""
    import jax

    from benchlib.clock import CompileClock
    from benchlib.peaks import peaks_for

    devices = pick_devices(cell.chips, require_tpu)
    enable_compile_cache(root)
    clock = CompileClock()
    rec = RunRecord(workload=cell.name, chips=cell.chips, seed=seed)
    if devices[0].platform == "tpu":
        rec.peaks = peaks_for(devices[0].device_kind)

    op = cell.op().Op(cell, seed, devices, control=control)
    op.warm()
    rec.setup_s = time.perf_counter() - t0

    trace_dir = os.path.join(root, TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    n0 = clock.count
    with jax.profiler.TraceAnnotation("bench.window"):
        w0 = time.perf_counter()
        k = 0
        while True:
            op.step(k)
            k += 1
            if time.perf_counter() - w0 >= seconds:
                break
        rec.window_s = time.perf_counter() - w0
    rec.steps = k
    rec.compiles_in_window = clock.count - n0
    clock.close()
    if trace:
        jax.profiler.stop_trace()
        from benchlib.trace import find_xplane, reduce_xplane

        rec.trace = reduce_xplane(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec.counters = op.counters()
    rec.memory_peak_bytes = memory_peak_bytes(devices)
    op.release()
    checks = op.check()
    print(f"bench: {cell.name} seed {seed}: {rec.steps} steps in "
          f"{rec.window_s:.3f} s, set-up {rec.setup_s:.3f} s", file=sys.stderr)
    return rec, checks


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python events
    opts.host_tracer_level = 2
    return opts


def metric_values(cell, rec, trace: bool) -> dict:
    """``{name: {"value", "unit"}}`` of the cell's end-to-end metrics
    (``trace`` False) or per-layer metrics (``trace`` True).  A reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.metric(m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, rec, checks, trace: bool, devices) -> dict:
    correct = all(_passes(v, lim) for _, v, lim in checks)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": rec.memory_peak_bytes}
    line = {
        "correct": correct,
        "attempted": rec.steps,
        "failed": int(rec.counters.get("failed", 0)),
        "metrics": metric_values(cell, rec, trace),
        "device": dev,
    }
    if trace and rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s()
        dev["window_s"] = rec.trace.window_s
        line["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                             "idle_gaps": rec.trace.idle_gaps(10)}
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    return line


def _passes(value, limit) -> bool:
    return value is not None and not math.isnan(value) and value <= limit


def format_checks(checks) -> list[str]:
    return [f"check {name}: {v!r} (limit {lim!r}) "
            f"{'ok' if _passes(v, lim) else 'FAIL'}"
            for name, v, lim in checks]


def main(args, *, root: str, t0: float) -> int:
    """The command line's run: prints the result as the last line of
    standard output, and each number compared, beside its limit, as the
    last lines of standard error."""
    from benchlib.spec import load_cell

    cell = load_cell(root, args.workload)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is not in this checkout ({src}/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    try:
        devices = pick_devices(cell.chips, require_tpu=True)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    rec, checks = run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), root=root, t0=t0,
                           control=bool(args.control))
    line = result_line(cell, rec, checks, bool(args.trace), devices)
    sys.stdout.flush()
    for text in format_checks(checks):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
