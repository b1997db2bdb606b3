#!/usr/bin/env python3
"""Smoke run of the purification driver and the SpGEMM engines on a TPU.

    python3 chip_smoke.py             # one chip, three phases
    python3 chip_smoke.py --chips 4   # the multi-chip engines, four chips

Everything runs in this one process, on the TPU only: without one, or
with a device count other than ``--chips`` (default 1), the script exits
non-zero before it computes anything.  Each phase prints one
JSON line (its numbers, each error beside its tolerance); the last line
of standard output is ``{"ok": true, "device": {...}}`` and appears only
when every phase passed.

One chip, at the H2O-DFT-LS shape of the paper's Table 1 (block size 23,
~10% block occupancy, decay pattern; ``configs/dbcsr_benchmarks.py``),
cut in rows only:

1. ``purify``: ``repro.launch.purify`` at nb 64 (against the
   eigendecomposition projector of the same H) and at nb 512 (N = 11,776).
   Each P is checked for idempotency with a plain dense product.
2. ``multiply_xla``: ``engine.multiply(backend="auto")`` at block size 23,
   at nb 512 and at nb 192.  On a TPU the compacted pick at bs 23 is the
   XLA ``stacks`` path, whose f32 stacks are padded to (8, 128) tiles: at
   nb 512 the list does not fit the chip, so ``auto`` takes the dense
   einsum; at nb 192 it fits and ``auto`` takes ``stacks``.
3. ``multiply_pallas``: the Pallas kernel at block size 128 over a product
   list longer than one kernel launch, in f32 and bf16 storage, with the
   kernel checked to be compiled Mosaic (``tpu_custom_call``).

Four chips: the purification through each static engine on a 2x2 mesh,
the 2.5D pull engine on the 1x4 grid, and ``engine="auto"`` with a fresh
tuning database, at nb 128 (against the eigendecomposition projector)
and at nb 512 (against a dense Newton-Schulz purification), with every
operand's shards checked to be partitioned over four distinct devices.

JAX's compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where set, else
``.jax_cache/`` in the checkout; each phase reports its backend-compile
seconds apart from its run seconds.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

H2O_ROWS = 158_976  # Table 1 rows of H2O-DFT-LS, for the printed cut
PURIFY_NB = 512
PURIFY_EIG_NB = 64
XLA_NB = (PURIFY_NB, 192)
PALLAS_NB, PALLAS_BS, PALLAS_OCC = 128, 128, 0.20
FOUR_CHIP_EIG_NB = 128

# tolerances: relative Frobenius errors
TOL_IDEMPOTENCY = 1e-4  # ||P^2 - P|| / ||P|| of a converged f32 purification
TOL_PROJECTOR = 1e-3  # ||P - P_ref|| / ||P_ref||, eigh or dense reference
TOL_F32 = 1e-5  # one f32 multiply against a HIGHEST-precision dense product
TOL_BF16 = 1e-2  # bf16 storage: one rounding of the output


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit counts
    only its retrieval)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.seconds += duration


def _rel(x, ref) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))


def _dense_mm(x, y):
    import jax.numpy as jnp

    return jnp.matmul(x, y, precision="highest")


def _check(name: str, err: float, tol: float) -> dict:
    if not err <= tol:
        raise AssertionError(f"{name}: error {err!r} above tolerance {tol!r}")
    return {f"{name}_err": err, f"{name}_tol": tol}


def _projector(h_dense, mu: float = 0.0):
    """Density matrix of the eigendecomposition: the projector onto the
    eigenvectors of H below mu, in f32.  It is computed on JAX's host CPU
    backend (LAPACK), whose eigh compiles in a second where the TPU's
    takes minutes; only where JAX has no CPU backend does it run on the
    chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    try:
        h_dense = jax.device_put(h_dense, jax.devices("cpu")[0])
    except RuntimeError:
        pass
    w, v = jnp.linalg.eigh(h_dense)
    occ = v * (w < mu)[None, :].astype(v.dtype)
    return np.asarray(_dense_mm(occ, v.T)), int(jnp.sum(w < mu))


def _dense_purification(h_dense, mu: float = 0.0, tol: float = 1e-6,
                        max_iter: int = 100):
    """Density matrix of a plain dense purification: the Newton-Schulz
    sign iteration of H - mu with HIGHEST-precision matmuls and no
    filtering, P = (I - sign(H - mu)) / 2, stopped where a sweep changes
    X by less than ``tol`` relative.  Returns P and its sweeps."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sweep(x):
        eye = jnp.eye(x.shape[0], dtype=x.dtype)
        new = 0.5 * _dense_mm(x, 3.0 * eye - _dense_mm(x, x))
        return new, jnp.linalg.norm(new - x) / jnp.linalg.norm(new)

    x = h_dense - mu * jnp.eye(h_dense.shape[0], dtype=h_dense.dtype)
    x = x / jnp.max(jnp.sum(jnp.abs(x), axis=1))  # Gershgorin: |lambda| <= 1
    for it in range(1, max_iter + 1):
        x, res = sweep(x)
        if float(res) < tol:
            eye = jnp.eye(x.shape[0], dtype=x.dtype)
            return 0.5 * (eye - x), it
    raise AssertionError(f"dense purification: residual {float(res)} after "
                         f"{max_iter} sweeps")


def _dense(m):
    """Dense f32 copy of a (possibly sharded) block-sparse matrix."""
    import jax.numpy as jnp

    from repro.core import bsm as B

    return B.unshard_bsm(m).to_dense().astype(jnp.float32)


def _purify_args(nb: int, *extra: str) -> list[str]:
    from repro.configs.dbcsr_benchmarks import BENCHMARKS

    h2o = BENCHMARKS["h2o_dft_ls"]
    return [
        "--nb", str(nb), "--bs", str(h2o.block_size),
        "--occupancy", str(h2o.occupancy),
        "--threshold", str(h2o.filter_eps),
        "--filter-eps", str(h2o.filter_eps),
        "--repeats", "2", *extra,
    ]


def _check_purification(run, *, eig: bool) -> dict:
    """Convergence, idempotency and (``eig``) the eigendecomposition
    projector of one ``purify.run`` result."""
    from repro.core.signiter import trace

    if not all(s.converged for s in run.stats):
        raise AssertionError(
            f"purification did not converge: "
            f"{[(s.iterations, s.residual) for s in run.stats]}")
    p = _dense(run.p)
    out = {
        "converged": True,
        "sweeps": [s.iterations for s in run.stats],
        "repeat_s": run.seconds,
        "trace_p": float(trace(run.p)),
    }
    out.update(_check("idempotency", _rel(_dense_mm(p, p), p),
                      TOL_IDEMPOTENCY))
    if eig:
        p_ref, n_occ = _projector(_dense(run.h))
        out["occupied_states"] = n_occ
        out.update(_check("projector", _rel(p, p_ref), TOL_PROJECTOR))
        if abs(out["trace_p"] - n_occ) > 0.5:
            raise AssertionError(f"trace(P) {out['trace_p']} != {n_occ}")
    return out


def _shards_on_distinct_devices(x, n: int) -> None:
    """``x`` is partitioned (not replicated) over ``n`` distinct devices."""
    devs = {s.device for s in x.addressable_shards}
    if len(devs) != n:
        raise AssertionError(f"shards on {len(devs)} devices, expected {n}")
    if x.sharding.is_fully_replicated or any(
            s.data.shape == x.shape for s in x.addressable_shards):
        raise AssertionError(f"{x.shape} is replicated, not partitioned")


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_purify(clock, nb_list=(PURIFY_EIG_NB, PURIFY_NB)) -> dict:
    from repro.launch import purify

    out = {}
    for nb in nb_list:
        c0, t0 = clock.seconds, time.perf_counter()
        run = purify.run(_purify_args(nb, "--p", "1"))
        res = _check_purification(run, eig=nb == PURIFY_EIG_NB)
        n = run.p.shape[0]
        res.update({
            "rows": n, "cut_vs_table1": n / H2O_ROWS,
            "compile_s": clock.seconds - c0,
            "wall_s": time.perf_counter() - t0,
        })
        out[f"nb{nb}"] = res
    return out


def phase_multiply_xla(clock, nb_list=XLA_NB) -> dict:
    import jax

    from repro.configs.dbcsr_benchmarks import BENCHMARKS
    from repro.core import bsm as B
    from repro.core.engine import choose_backend, multiply
    from repro.core.local_mm import device_memory_budget, stacks_memory_bytes
    from repro.kernels.stacks import bucket_capacity, pair_cube

    h2o = BENCHMARKS["h2o_dft_ls"]
    bs = h2o.block_size
    out = {}
    for nb in nb_list:
        a, b = (B.random_bsm(jax.random.key(s), nb=nb, bs=bs,
                             occupancy=h2o.occupancy, pattern=h2o.pattern)
                for s in (1, 2))
        cap = bucket_capacity(int(pair_cube(a.mask, b.mask).sum()))
        stacks_bytes = stacks_memory_bytes(nb, nb, nb, bs, bs, bs, cap)
        picked = choose_backend(a, b, 0.0)
        fits = stacks_bytes <= device_memory_budget()
        if picked != ("stacks" if fits else "jnp"):
            raise AssertionError(
                f"nb {nb}: auto picked {picked!r} with a stacks footprint "
                f"of {stacks_bytes:.3e} B")
        c0 = clock.seconds
        c = multiply(a, b, backend="auto", threshold=0.0)
        jax.block_until_ready(c.blocks)
        compile_s = clock.seconds - c0
        t0 = time.perf_counter()
        jax.block_until_ready(
            multiply(a, b, backend="auto", threshold=0.0).blocks)
        run_s = time.perf_counter() - t0
        ref = _dense_mm(a.to_dense(), b.to_dense())
        res = {"rows": a.shape[0], "cut_vs_table1": a.shape[0] / H2O_ROWS,
               "backend": picked, "stacks_capacity": cap,
               "stacks_bytes": stacks_bytes,
               "budget_bytes": device_memory_budget(),
               "compile_s": compile_s, "run_s": run_s}
        res.update(_check("f32", _rel(c.to_dense(), ref), TOL_F32))
        out[f"nb{nb}"] = res
    return out


def phase_multiply_pallas(clock) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply
    from repro.kernels.block_spgemm import MAX_PREFETCH_PRODUCTS
    from repro.kernels.stacks import pair_cube

    a, b = (B.random_bsm(jax.random.key(s), nb=PALLAS_NB, bs=PALLAS_BS,
                         occupancy=PALLAS_OCC, pattern="decay")
            for s in (3, 4))
    ok = pair_cube(a.mask, b.mask)
    stacks, n_products = plan_mod.get_product_stacks(ok)
    cap = stacks.capacity
    if cap <= MAX_PREFETCH_PRODUCTS:
        raise AssertionError(f"capacity {cap} fits one launch")
    out = {"nb": PALLAS_NB, "bs": PALLAS_BS, "products": n_products,
           "capacity": cap}
    for dtype, tol in ((jnp.float32, TOL_F32), (jnp.bfloat16, TOL_BF16)):
        name = jnp.dtype(dtype).name
        a_s, b_s = a.astype(dtype), b.astype(dtype)
        c0 = clock.seconds
        c = multiply(a_s, b_s, backend="pallas", threshold=0.0)
        jax.block_until_ready(c.blocks)
        compile_s = clock.seconds - c0
        t0 = time.perf_counter()
        jax.block_until_ready(
            multiply(a_s, b_s, backend="pallas", threshold=0.0).blocks)
        run_s = time.perf_counter() - t0
        ref = _dense_mm(a_s.to_dense().astype(jnp.float32),
                        b_s.to_dense().astype(jnp.float32))
        out.update({f"{name}_compile_s": compile_s, f"{name}_run_s": run_s})
        out.update(_check(name, _rel(c.to_dense().astype(jnp.float32), ref),
                          tol))
        prog = plan_mod.get_local_compiled(
            PALLAS_NB, PALLAS_NB, PALLAS_NB, PALLAS_BS, PALLAS_BS, PALLAS_BS,
            dtype, backend="pallas", capacity=cap)
        text = prog.lower(a_s.blocks, b_s.blocks, stacks).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name}: no tpu_custom_call in the program")
        out[f"{name}_tpu_custom_call"] = True
    return out


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def phase_four_chip_engines(clock) -> dict:
    """Every engine at nb 128 against the eigendecomposition projector,
    and at nb 512 against a dense purification."""
    return {f"nb{nb}": _four_chip_engines(clock, nb, eig=eig)
            for nb, eig in ((FOUR_CHIP_EIG_NB, True), (PURIFY_NB, False))}


def _four_chip_engines(clock, nb: int, *, eig: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.dbcsr_benchmarks import BENCHMARKS
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.signiter import density_matrix
    from repro.core.topology import validate_l
    from repro.launch import purify
    from repro.launch.mesh import make_spgemm_mesh

    h2o = BENCHMARKS["h2o_dft_ls"]
    out = {"rows": nb * h2o.block_size}
    p_ref = None

    def against_reference(p) -> dict:
        return _check("projector" if eig else "dense_purification",
                      _rel(_dense(p), p_ref), TOL_PROJECTOR)

    for engine in ("cannon", "onesided", "gather", "twofive"):
        c0 = clock.seconds
        run = purify.run(_purify_args(nb, "--p", "2", "--engine", engine))
        for x in (run.h.blocks, run.p.blocks):
            _shards_on_distinct_devices(x, 4)
        if p_ref is None:
            t0 = time.perf_counter()
            if eig:
                p_ref, out["occupied_states"] = _projector(_dense(run.h))
            else:
                p_ref, out["reference_sweeps"] = _dense_purification(
                    _dense(run.h))
            out["reference_s"] = time.perf_counter() - t0
            out["reference_trace"] = float(jnp.trace(p_ref))
        res = _check_purification(run, eig=False)
        if abs(res["trace_p"] - out["reference_trace"]) > 0.5:
            raise AssertionError(
                f"{engine}: trace(P) {res['trace_p']} != reference "
                f"{out['reference_trace']}")
        res.update(against_reference(run.p))
        res.update({"mesh": dict(run.mesh.shape),
                    "compile_s": clock.seconds - c0})
        out[engine] = res

    # the 2.5D pull engine on the non-square 1x4 grid: the paper's rule
    # (validate_l) admits L = max/min only where max <= min^2
    mesh14 = make_spgemm_mesh(p_r=1, p_c=4)
    depth = plan_mod.plan_multiply(mesh14, "twofive").topo.l
    h = B.random_bsm(jax.random.key(0), nb=nb, bs=h2o.block_size,
                     occupancy=h2o.occupancy, pattern="decay",
                     symmetric=True)
    h14 = B.shard_bsm(h, mesh14)
    _shards_on_distinct_devices(h14.blocks, 4)
    c0, t0 = clock.seconds, time.perf_counter()
    p14, stats = density_matrix(h14, 0.0, engine="twofive",
                                threshold=h2o.filter_eps,
                                filter_eps=h2o.filter_eps,
                                max_iter=100, sync_every=4)
    jax.block_until_ready(p14.blocks)
    wall_s = time.perf_counter() - t0
    _shards_on_distinct_devices(p14.blocks, 4)
    if not stats.converged:
        raise AssertionError(f"1x4 twofive did not converge: {stats}")
    res = {"mesh": dict(mesh14.shape), "L": depth,
           "L4_admitted": validate_l(1, 4, 4),
           "why": "validate_l(1, 4, 4) is False: a non-square grid takes "
                  "L = max/min only where max <= min^2 (4 > 1), so the "
                  "pull engine runs at L = 1",
           "sweeps": stats.iterations, "compile_s": clock.seconds - c0,
           "wall_s": wall_s}
    res.update(against_reference(p14))
    out["twofive_1x4"] = res

    # engine="auto" with a fresh tuning database: every trial runs on the
    # chip, and a failing one raises (tuner.measure)
    db_dir = os.path.join(ROOT, ".chip_smoke")
    shutil.rmtree(db_dir, ignore_errors=True)
    os.makedirs(db_dir)
    c0 = clock.seconds
    run = purify.run(_purify_args(
        nb, "--p", "2", "--engine", "auto",
        "--tuning-db", os.path.join(db_dir, "tuning_db.json")))
    for x in (run.h.blocks, run.p.blocks):
        _shards_on_distinct_devices(x, 4)
    res = _check_purification(run, eig=False)
    res.update(against_reference(run.p))
    res["compile_s"] = clock.seconds - c0
    out["auto"] = res
    return out


PHASES = {
    1: (phase_purify, phase_multiply_xla, phase_multiply_pallas),
    4: (phase_four_chip_engines,),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} runs on exactly "
              f"{args.chips} TPU device(s), found {len(devices)}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache": cache_dir, "kind": dev.device_kind,
                      "devices": len(devices)}), flush=True)
    clock = CompileClock()
    failed = []
    for phase in PHASES[args.chips]:
        name = phase.__name__.removeprefix("phase_")
        t0 = time.perf_counter()
        try:
            res = phase(clock)
        except Exception:  # noqa: BLE001 - report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            print(json.dumps({"phase": name, "ok": False}), flush=True)
            continue
        print(json.dumps({"phase": name, "ok": True,
                          "seconds": time.perf_counter() - t0, **res}),
              flush=True)
    print(json.dumps({"compile_s_total": clock.seconds}), flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
