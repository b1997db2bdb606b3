"""Purification driver: distributed density-matrix purification as a
long-running service loop.

    PYTHONPATH=src python -m repro.launch.purify --nb 16 --bs 8 \
        --repeats 3 --sync-every 4 --tuning-db tuning_db.json

The production rendering of the paper's driving workload: build a sparse
model Hamiltonian, shard it ONCE onto the SpGEMM mesh, and run repeated
purifications (an SCF-like outer loop re-purifies a slowly-changing H)
entirely device-resident — the fused sign-iteration engine of
``core/signiter.py`` (DESIGN.md §5).  After the first purification every
later one is pure cache: the chain-step program, the multiply plan and
the jit executable are all reused (``plan.cache_stats()`` is printed per
repeat; ``builds`` must stay flat).  Each repeat also prints the share of
the block products the local stage multiplied whose A and B blocks were
both present, and the host seconds the chain spent enqueueing sweeps
(``signiter.dispatch``) and waiting for their residuals
(``signiter.sync``), read from the program's spans (``repro.obs``).

Engine selection is autotuned (DESIGN.md §6): with ``--tuning-db`` the
driver runs ``engine="auto"`` — the pattern-aware tuner picks (engine, L)
for H's sparsity pattern, measuring short trials on a cold database and
resolving *measurement-free* on a warm one; winners persist to the DB
file for the next launch.  Without a tuning DB the driver falls back to
the static ``--engine`` choice (default twofive) — a production loop
should not silently re-measure on every start.

The driver runs on the platform's real devices, and the (r, c) grid
defaults to the largest square the device count holds (``--p 1`` on one
chip).  ``--fake-devices N`` instead fakes N CPU host devices, for
multi-device runs on a CPU; it takes effect only in a process that has
not initialised jax yet.

``run`` returns what the purifications produced, for callers that check
it (``chip_smoke.py``); ``main`` is the command line.
"""
from __future__ import annotations

import argparse
import math
import os
import sys
from dataclasses import dataclass


@dataclass
class PurifyRun:
    """What :func:`run` produced: the Hamiltonian of the last repeat and
    its density matrix (both in the mesh layout), each repeat's
    ``SignIterStats`` and wall seconds (repeat 0 includes compiling)."""

    h: object
    p: object
    stats: list
    seconds: list[float]
    mesh: object


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nb", type=int, default=16, help="block-grid side")
    ap.add_argument("--bs", type=int, default=8, help="atomic block size")
    ap.add_argument("--p", type=int, default=None,
                    help="(r, c) grid side (default: the largest square "
                    "the devices hold)")
    ap.add_argument("--l", type=int, default=1, help="2.5D depth (l axis)")
    ap.add_argument("--engine", default="auto",
                    choices=("auto", "cannon", "onesided", "gather",
                             "twofive"))
    ap.add_argument("--tuning-db", default=None,
                    help="tuning-database JSON path: enables engine "
                    "autotuning (warm-started when the file exists, "
                    "created/updated after measuring)")
    ap.add_argument("--occupancy", type=float, default=0.10)
    ap.add_argument("--threshold", type=float, default=1e-9)
    ap.add_argument("--filter-eps", type=float, default=1e-8)
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--max-iter", type=int, default=100)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--repeats", type=int, default=3,
                    help="purifications of the (perturbed) Hamiltonian")
    ap.add_argument("--fake-devices", type=int, default=None,
                    help="fake this many CPU host devices instead of "
                    "using the platform's devices")
    return ap


def main(argv=None) -> int:
    run(argv)
    return 0


def run(argv=None) -> PurifyRun:
    args = _parser().parse_args(argv)
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import time

    import jax

    from repro import obs, tuner
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.signiter import density_matrix, trace
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_spgemm_mesh

    enable_compile_cache()
    p_side = args.p or math.isqrt(len(jax.devices()) // args.l)
    mesh = make_spgemm_mesh(p=p_side, l=args.l)
    engine = args.engine
    h = B.random_bsm(
        jax.random.key(0), nb=args.nb, bs=args.bs,
        occupancy=args.occupancy, pattern="decay", symmetric=True,
    )
    mu = 0.0
    plan_mod.clear_cache()
    if engine == "auto":
        if args.tuning_db:
            tuner.set_default_db(args.tuning_db)  # after clear_cache: it
            # resets the tuner binding along with every other cache level
        else:
            # no DB to consult or persist to: static fallback — a service
            # loop must not re-measure on every launch
            engine = "twofive"

    print(f"purify: H {h.shape[0]}x{h.shape[0]} "
          f"({float(h.occupancy()):.1%} blocks), mesh {dict(mesh.shape)}, "
          f"engine {engine}"
          + (f" (db {args.tuning_db})" if engine == "auto" else "")
          + f", sync_every {args.sync_every}")
    h_dev = B.shard_bsm(h, mesh)  # the one chain-boundary scatter
    all_stats, seconds = [], []
    for rep in range(args.repeats):
        if rep:
            # SCF-like drift: perturb H on-device and re-purify (same
            # pattern -> every cache level hits; the chain program is
            # reused as-is)
            h_dev = h_dev.scale(1.0 + 1e-3 * rep)
        t0 = time.perf_counter()
        p, stats = density_matrix(
            h_dev, mu, engine=engine,
            threshold=args.threshold, filter_eps=args.filter_eps,
            max_iter=args.max_iter, tol=args.tol,
            mode="fused", sync_every=args.sync_every,
        )
        jax.block_until_ready(p.blocks)
        dt = time.perf_counter() - t0
        all_stats.append(stats)
        seconds.append(dt)
        cache = plan_mod.cache_stats()
        sweeps_s = stats.iterations / dt if dt > 0 else float("inf")
        print(f"  repeat {rep}: {stats.iterations} sweeps "
              f"({stats.host_syncs} syncs) in {dt:.2f}s "
              f"[{sweeps_s:.1f} sweeps/s], converged={stats.converged}, "
              f"trace(P)={float(trace(p)):.2f}, "
              f"cache builds={cache['builds']} "
              f"chain {cache['chain_hits']}h/{cache['chain_misses']}m "
              f"tuner {cache['tuner_hits']}h/{cache['tuner_misses']}m/"
              f"{cache['tuner_trials']}t")
        host = _chain_host_seconds(obs.records())
        computed = 2 * stats.iterations * stats.products_computed
        useful = sum(map(sum, stats.products_present)) / max(computed, 1)
        print(f"    useful products {useful:.2%}, host "
              f"dispatch {host['signiter.dispatch']:.4f}s "
              f"sync {host['signiter.sync']:.4f}s")
    final = plan_mod.cache_stats()
    # the chain program is compiled exactly once; program builds beyond it
    # can only come from the tuner's measured trials (cold DB), never from
    # the purification loop itself
    assert final["chain_misses"] == 1, final
    assert final["builds"] <= 1 + final["tuner_trials"], final
    assert final["tuner_misses"] <= 1, final  # one decision per pattern
    print(f"purify OK: one compiled chain step served "
          f"{final['chain_hits'] + 1} sweeps across {args.repeats} "
          f"purifications (builds={final['builds']}, "
          f"trials={final['tuner_trials']})")
    db = tuner.get_default_db()
    if db is not None and db.path:
        print(f"tuning db: {len(db)} record(s) at {db.path}")
    return PurifyRun(h=h_dev, p=p, stats=all_stats, seconds=seconds,
                     mesh=mesh)


def _chain_host_seconds(records) -> dict:
    """Seconds in ``signiter.dispatch`` and ``signiter.sync`` spans of
    the last ``signiter.chain`` span among ``records``."""
    chain = next(r for r in reversed(records) if r.name == "signiter.chain")
    out = {"signiter.dispatch": 0.0, "signiter.sync": 0.0}
    for r in records:
        if r.parent == chain.id and r.name in out:
            out[r.name] += r.seconds
    return out


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
