"""Multi-device checks, run in a subprocess so the fake-device XLA flag never
leaks into the main pytest process (smoke tests must see 1 device).

Usage:  python -m tests._dist <check> [<check> ...]
Each check raises on failure; exit code 0 == all passed.
"""
from __future__ import annotations

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=64 " + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.launch.mesh import make_mesh  # noqa: E402


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def check_engines():
    """All distributed engines == single-device filtered oracle."""
    from repro.core import bsm as B
    from repro.core.engine import multiply, multiply_reference
    from repro.launch.mesh import make_spgemm_mesh

    key = jax.random.key(0)
    a = B.random_bsm(key, nb=8, bs=8, occupancy=0.4, pattern="decay")
    b = B.random_bsm(jax.random.key(1), nb=8, bs=8, occupancy=0.4, pattern="decay")

    for threshold in (0.0, 0.35):
        ref = multiply_reference(a, b, threshold=threshold)
        rd = np.asarray(ref.to_dense())
        mesh2 = make_spgemm_mesh(p=2)
        for eng in ("cannon", "onesided", "gather"):
            c = multiply(a, b, mesh2, engine=eng, threshold=threshold)
            np.testing.assert_allclose(
                np.asarray(c.to_dense()), rd, rtol=1e-5, atol=1e-5,
                err_msg=f"{eng} t={threshold}")
            np.testing.assert_array_equal(
                np.asarray(c.mask), np.asarray(ref.mask), err_msg=eng)
        for l in (2,):
            mesh3 = make_spgemm_mesh(p=2, l=l)
            for layout in ("2d", "scatter"):
                c = multiply(a, b, mesh3, engine="twofive",
                             threshold=threshold, c_layout=layout)
                np.testing.assert_allclose(
                    np.asarray(c.to_dense()), rd, rtol=1e-5, atol=1e-5,
                    err_msg=f"twofive {layout} t={threshold}")
    # pallas backend through the distributed gather engine
    mesh2 = make_spgemm_mesh(p=2)
    ref = multiply_reference(a, b)
    c = multiply(a, b, mesh2, engine="gather", backend="pallas")
    np.testing.assert_allclose(
        np.asarray(c.to_dense()), np.asarray(ref.to_dense()), rtol=1e-4, atol=1e-4)
    print("engines OK")


def check_stacks_backends():
    """Compacted backends distributed: the auto-derived per-device stack
    capacity (plan.get_device_capacity) must never drop products — checked
    with a *skewed* pattern where one device's panel dominates, across all
    engines, both compacted backends, and a non-square grid."""
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply, multiply_reference
    from repro.launch.mesh import make_spgemm_mesh

    a = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=0.15)
    b = B.random_bsm(jax.random.key(1), nb=8, bs=8, occupancy=0.15)
    # skew: one quadrant fully occupied WITH data (fresh blocks — the
    # blocks random_bsm masked out are zero, and zero-norm products would
    # be filtered right back out) — the max-device capacity bound must
    # come from the dense quadrant, not the average
    mask = np.asarray(a.mask).copy()
    mask[:4, :4] = True
    blocks = jax.random.normal(jax.random.key(2), a.blocks.shape) / np.sqrt(8)
    a = B.make_bsm(blocks, jnp.asarray(mask))

    thr = 1e-3
    ref = np.asarray(multiply_reference(a, b, threshold=thr).to_dense())
    mesh2 = make_spgemm_mesh(p=2)
    for eng in ("cannon", "onesided", "gather", "twofive"):
        for be in ("stacks", "pallas"):
            c = multiply(a, b, mesh2, engine=eng, threshold=thr, backend=be)
            np.testing.assert_allclose(
                np.asarray(c.to_dense()), ref, rtol=1e-5, atol=1e-5,
                err_msg=f"{eng}/{be}")
    # non-square pull grid (forced virtual L) + stacked (l, r, c) mesh

    devs = np.array(jax.devices()[:8])
    mesh24 = make_mesh((2, 4), ("r", "c"), devices=devs)
    for eng in ("onesided", "twofive"):
        c = multiply(a, b, mesh24, engine=eng, threshold=thr, backend="stacks")
        np.testing.assert_allclose(
            np.asarray(c.to_dense()), ref, rtol=1e-5, atol=1e-5,
            err_msg=f"{eng}/stacks 2x4")
    mesh3 = make_spgemm_mesh(p=2, l=2)
    c = multiply(a, b, mesh3, engine="twofive", threshold=thr, backend="stacks")
    np.testing.assert_allclose(
        np.asarray(c.to_dense()), ref, rtol=1e-5, atol=1e-5,
        err_msg="twofive stacked/stacks")
    # repeated pattern: bound + product list re-derivations are cache hits
    s1 = plan_mod.cache_stats()
    multiply(a, b, mesh2, engine="gather", threshold=thr, backend="stacks")
    s2 = plan_mod.cache_stats()
    assert s2["pattern_hits"] > s1["pattern_hits"], (s1, s2)
    assert s2["builds"] == s1["builds"], (s1, s2)
    print("stacks_backends OK")


def check_engines_rectangular():
    """gather/onesided engines on non-square grids (non-ideal topologies)."""
    from repro.core import bsm as B
    from repro.core.engine import multiply, multiply_reference

    a = B.random_bsm(jax.random.key(2), nb=8, bs=4, occupancy=0.5)
    b = B.random_bsm(jax.random.key(3), nb=8, bs=4, occupancy=0.5)
    ref = np.asarray(multiply_reference(a, b).to_dense())
    for shape in ((2, 4), (4, 2), (1, 8)):
        mesh = make_mesh(shape, ("r", "c"))
        for eng in ("gather", "onesided"):
            c = multiply(a, b, mesh, engine=eng)
            np.testing.assert_allclose(
                np.asarray(c.to_dense()), ref, rtol=1e-5, atol=1e-5,
                err_msg=f"{eng} {shape}")
    print("engines_rectangular OK")


def check_plan_rectangular():
    """The 2.5D engine on non-square grids (virtual depth L = max/min) and
    on a square grid with L = 4: equals both the single-device reference
    and the paper-fidelity numpy oracle ``simulate_algorithm2``."""
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply, multiply_reference
    from repro.core.topology import simulate_algorithm2
    from repro.launch.mesh import make_spgemm_mesh

    a = B.random_bsm(jax.random.key(4), nb=8, bs=4, occupancy=0.5,
                     pattern="decay")
    b = B.random_bsm(jax.random.key(5), nb=8, bs=4, occupancy=0.5,
                     pattern="decay")
    ref = np.asarray(multiply_reference(a, b).to_dense())
    ad, bd = np.asarray(a.to_dense()), np.asarray(b.to_dense())

    for p_r, p_c, l in ((2, 4, None), (4, 2, None), (2, 2, 4)):
        mesh = make_spgemm_mesh(p_r=p_r, p_c=p_c)
        c = multiply(a, b, mesh, engine="twofive", l=l)
        plan = plan_mod.plan_multiply(mesh, "twofive", l)
        want_l = l if l is not None else max(p_r, p_c) // min(p_r, p_c)
        assert plan.topo.l == want_l, (p_r, p_c, plan.topo.l)
        sim = simulate_algorithm2(ad, bd, p_r, p_c, plan.topo.l)
        cd = np.asarray(c.to_dense())
        np.testing.assert_allclose(cd, ref, rtol=1e-5, atol=1e-5,
                                   err_msg=f"{p_r}x{p_c} L={plan.topo.l} ref")
        np.testing.assert_allclose(cd, sim, rtol=1e-5, atol=1e-5,
                                   err_msg=f"{p_r}x{p_c} L={plan.topo.l} sim")
        np.testing.assert_allclose(sim, ad @ bd, rtol=1e-5, atol=1e-5)

    # stacked mesh with uneven L (L does not divide the grid side)
    mesh = make_spgemm_mesh(p=2, l=4)
    for layout in ("2d", "scatter"):
        c = multiply(a, b, mesh, engine="twofive", c_layout=layout)
        np.testing.assert_allclose(np.asarray(c.to_dense()), ref,
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"stacked uneven {layout}")
    print("plan_rectangular OK")


def check_tensor():
    """Distributed blocked tensor contraction (DESIGN.md §10): the
    matricized ``contract("ijk,kl->ijl")`` of the three_center corpus
    entry equals the dense np.einsum oracle for every engine on the
    square 2x2 grid, the rectangular 2x4 grid, and the stacked
    uneven-L mesh; a sharded chain stays device-resident between
    contractions; and non-identity block→device assignments on the
    rectangular matricized product are rejected loudly."""

    from repro.core import tensor as T
    from repro.core.engine import multiply
    from repro.launch.mesh import make_spgemm_mesh
    from repro.tuner.corpus import corpus

    entry = [e for e in corpus(smoke=True) if e.kind == "three_center"][0]
    t, bm = entry.build_tensor()  # (4,4,4) blocks of 8^3 vs (4,4) of 8^2
    b2 = T.make_tensor(bm.blocks, bm.mask)  # the (k, l) operand as a tensor
    ref = T.contract_reference("ijk,kl->ijl", t, b2)

    meshes = {
        "2x2": (make_spgemm_mesh(p=2),
                ("cannon", "onesided", "gather", "twofive")),
        "2x4": (make_mesh((2, 4), ("r", "c"), devices=jax.devices()[:8]),
                ("onesided", "gather", "twofive")),
        "stacked": (make_spgemm_mesh(p=2, l=4), ("twofive",)),
    }
    for name, (mesh, engines) in meshes.items():
        for eng in engines:
            out = T.contract("ijk,kl->ijl", t, b2, mesh=mesh, engine=eng,
                             threshold=entry.threshold)
            assert out.nbs == t.nbs and out.bss == t.bss, (name, eng)
            np.testing.assert_allclose(
                np.asarray(out.to_dense()), ref, rtol=1e-4, atol=1e-4,
                err_msg=f"{name}/{eng}")

    # engine="auto": the tuner owns the choice end to end
    mesh24 = meshes["2x4"][0]
    out = T.contract("ijk,kl->ijl", t, b2, mesh=mesh24, engine="auto",
                     threshold=entry.threshold)
    np.testing.assert_allclose(np.asarray(out.to_dense()), ref,
                               rtol=1e-4, atol=1e-4, err_msg="auto")

    # sharded chain: shard once, contract twice, gather once — the
    # intermediate never leaves the devices and its split lines up with
    # the next contraction's needs
    mesh = meshes["2x2"][0]
    b3 = T.random_tensor(jax.random.key(33), (4, 4), 8, occupancy=0.6)
    st_ = T.shard_tensor(t, mesh, (0, 1), (2,))
    sb2 = T.shard_tensor(b2, mesh, (0,), (1,))
    sb3 = T.shard_tensor(b3, mesh, (0,), (1,))
    mid = T.contract("ijk,kl->ijl", st_, sb2, mesh=mesh, engine="gather")
    assert isinstance(mid, T.MatricizedTensor) and mid.sharded, mid
    fin = T.contract("ijl,lm->ijm", mid, sb3, mesh=mesh, engine="gather")
    assert isinstance(fin, T.MatricizedTensor) and fin.sharded, fin
    chain_ref = T.contract_reference("ijk,kl,lm->ijm", t, b2, b3)
    np.testing.assert_allclose(
        np.asarray(fin.to_tensor().to_dense()), chain_ref,
        rtol=1e-4, atol=1e-4, err_msg="sharded chain")

    # a sharded intermediate whose split does NOT line up must refuse the
    # implicit global redistribution, not silently gather
    try:
        T.contract("ijl,jm->ilm", mid, sb3, mesh=mesh, engine="gather")
        raise AssertionError("expected split-mismatch ValueError")
    except ValueError as e:
        assert "redistribution" in str(e), e

    # satellite: non-identity assignments have no symmetric layout on the
    # rectangular matricized product — loud rejection at both entry points
    ma = T.matricize(t, (0, 1), (2,))
    try:
        multiply(ma, bm, mesh, engine="gather", assignment="nnz_greedy")
        raise AssertionError("expected non-square assignment ValueError")
    except ValueError as e:
        assert "square" in str(e), e
    print("tensor OK")


def check_plan_cache():
    """Repeated multiplies reuse one compiled program: the second call hits
    the plan cache (no re-build / re-lower) and dispatches much faster."""
    import time

    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply
    from repro.core.signiter import sign_iteration
    from repro.launch.mesh import make_spgemm_mesh

    mesh = make_spgemm_mesh(p=2, l=2)
    a = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=0.5,
                     pattern="decay", symmetric=True)
    b = B.random_bsm(jax.random.key(1), nb=8, bs=8, occupancy=0.5)

    plan_mod.clear_cache()
    t0 = time.perf_counter()
    multiply(a, b, mesh, engine="twofive").blocks.block_until_ready()
    first = time.perf_counter() - t0
    s1 = plan_mod.cache_stats()
    assert s1["misses"] == 1 and s1["builds"] == 1, s1

    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        multiply(a, b, mesh, engine="twofive").blocks.block_until_ready()
        times.append(time.perf_counter() - t0)
    s2 = plan_mod.cache_stats()
    assert s2["builds"] == 1, s2  # no re-lowering on cache hits
    assert s2["hits"] == s1["hits"] + 5, (s1, s2)
    steady = sorted(times)[len(times) // 2]
    assert steady < first, (first, steady)

    # the driving hot path, legacy per-op loop: every multiply re-enters
    # the plan cache and shares one program
    plan_mod.clear_cache()
    _, st = sign_iteration(a, mesh=mesh, engine="twofive", max_iter=4,
                           mode="legacy")
    s3 = plan_mod.cache_stats()
    assert s3["builds"] == 1 and s3["hits"] == st.multiplications - 1, s3
    # fused mode: the whole sweep is ONE chain program, fetched per sweep
    plan_mod.clear_cache()
    _, st = sign_iteration(a, mesh=mesh, engine="twofive", max_iter=4)
    s4 = plan_mod.cache_stats()
    assert s4["builds"] == 1, s4  # one multiply body for both multiplies
    assert s4["chain_misses"] == 1, s4
    assert s4["chain_hits"] == st.iterations - 1, (s4, st.iterations)
    print(f"plan_cache OK first={first:.3f}s steady={steady:.4f}s")


def check_signiter_sharded():
    """The device-resident purification chain on a distributed mesh:

    * fused sweep == legacy per-op loop (residual trace, occupancy trace,
      converged X to 1e-5) across engines / thresholds / backends;
    * a 10-sweep iteration compiles AT MOST ONE program per distinct
      multiply shape (plan.cache_stats: builds == 1, one chain miss,
      sweeps-1 chain hits);
    * the fused step's compiled HLO performs no global gather — X enters
      and leaves in the 2D home layout (onesided/twofive: zero all-gather
      ops; the collectives are the engine's ppermutes and the scalar
      residual all-reduce);
    * ShardedBSM stays sharded end-to-end (C in the home layout) and
      density_matrix on a ShardedBSM H returns a ShardedBSM P.
    """
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.signiter import (
        density_matrix,
        lower_sweep,
        sign_iteration,
        sign_iteration_legacy,
        trace,
    )
    from repro.launch.mesh import make_spgemm_mesh

    x = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=0.6,
                     pattern="banded", symmetric=True)
    mesh2 = make_spgemm_mesh(p=2)
    mesh3 = make_spgemm_mesh(p=2, l=2)

    for thr, eps in ((0.0, 0.0), (1e-7, 1e-6)):
        ref, st_ref = sign_iteration_legacy(
            x, mesh=mesh2, engine="onesided", threshold=thr,
            filter_eps=eps, max_iter=60, tol=1e-6)
        assert st_ref.converged
        rd = np.asarray(ref.to_dense())
        for engine, mesh, backend in (
            ("onesided", mesh2, "jnp"),
            ("gather", mesh2, "jnp"),
            ("cannon", mesh2, "jnp"),
            ("twofive", mesh3, "jnp"),
            ("onesided", mesh2, "stacks"),
        ):
            s, st = sign_iteration(
                x, mesh=mesh, engine=engine, threshold=thr, filter_eps=eps,
                max_iter=60, tol=1e-6, mode="fused", backend=backend)
            tag = f"{engine}/{backend} t={thr}"
            assert st.converged, tag
            assert st.iterations == st_ref.iterations, tag
            np.testing.assert_allclose(
                st.residual_trace, st_ref.residual_trace,
                rtol=1e-4, atol=1e-7, err_msg=tag)
            np.testing.assert_allclose(
                st.occupancy_trace, st_ref.occupancy_trace,
                atol=1e-7, err_msg=tag)
            np.testing.assert_allclose(
                np.asarray(s.to_dense()), rd, rtol=1e-5, atol=1e-5,
                err_msg=tag)

    # --- cache: 10 sweeps, at most one program per distinct multiply shape
    plan_mod.clear_cache()
    _, st = sign_iteration(x, mesh=mesh2, engine="onesided",
                           threshold=1e-7, filter_eps=1e-6,
                           max_iter=10, tol=0.0, sync_every=5)
    stats = plan_mod.cache_stats()
    assert st.iterations == 10 and st.host_syncs == 2, st
    assert stats["builds"] == 1, stats
    assert stats["chain_misses"] == 1, stats
    assert stats["chain_hits"] == 9, stats
    assert st.retraces == 1, st  # the whole chain traced ONE program
    # second chain on the same key: pure chain-cache hits, no new build
    _, st_warm = sign_iteration(x, mesh=mesh2, engine="onesided",
                                threshold=1e-7, filter_eps=1e-6,
                                max_iter=5, tol=0.0)
    s2 = plan_mod.cache_stats()
    assert s2["builds"] == 1 and s2["chain_misses"] == 1, s2
    assert st_warm.retraces == 0, st_warm  # warm chain: zero retraces

    # --- no global gather in the fused step (jaxpr/HLO of one sweep)
    for engine, mesh in (("onesided", mesh2), ("twofive", mesh3)):
        hlo = lower_sweep(mesh, 8, 8, engine=engine, threshold=1e-7,
                          filter_eps=1e-6).compile().as_text()
        n_ag = sum("all-gather" in ln for ln in hlo.splitlines())
        assert n_ag == 0, (engine, n_ag)

    # --- ShardedBSM end-to-end: sharded in, sharded out, home layout
    from jax.sharding import PartitionSpec as P

    hx = B.shard_bsm(x, mesh2)
    s, st = sign_iteration(hx, engine="onesided", threshold=1e-7,
                           filter_eps=1e-6, max_iter=60, tol=1e-6)
    assert isinstance(s, B.ShardedBSM)
    assert s.blocks.sharding.spec == P("r", "c", None, None), (
        s.blocks.sharding)
    assert s.mask.sharding.spec == P("r", "c"), s.mask.sharding
    ref, _ = sign_iteration_legacy(x, mesh=mesh2, engine="onesided",
                                   threshold=1e-7, filter_eps=1e-6,
                                   max_iter=60, tol=1e-6)
    np.testing.assert_allclose(np.asarray(s.to_dense()),
                               np.asarray(ref.to_dense()),
                               rtol=1e-5, atol=1e-5)
    p, stp = density_matrix(hx, 0.0, engine="onesided", threshold=1e-9,
                            filter_eps=1e-8, max_iter=80, tol=1e-6)
    assert isinstance(p, B.ShardedBSM) and stp.converged
    dense = np.asarray(x.to_dense(), np.float64)
    w = np.linalg.eigvalsh(dense)
    assert abs(float(trace(p)) - int((w < 0.0).sum())) < 0.05
    print("signiter_sharded OK")


def check_envelope_sharded():
    """Pattern-envelope chains on distributed meshes (DESIGN.md §7):

    * a 10-sweep drifting-pattern purification compiled against the
      forecast envelope runs builds == 1 / chain_misses == 1 /
      st.retraces == 1, with compacted capacities derived from the
      envelope's union cube — and matches the plain chain-safe fused
      chain BIT-EXACT (same engine/backend: identical contraction
      order, the envelope only pads the compacted product list with
      zero-contribution slots);
    * the envelope lifts the chain-safety pins: compressed panel
      transport inside a fused chain, previously a hard error, now
      packs against the envelope's operand-mask unions;
    * warm path: a second chain over the same operand re-hits the
      envelope cache (envelope_hits) and the chain program — zero
      retraces, zero new builds;
    * engine="auto" under an envelope ranks the full candidate space
      and still keys ONE chain program.
    """
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.signiter import sign_iteration
    from repro.launch.mesh import make_spgemm_mesh

    mesh2 = make_spgemm_mesh(p=2)
    mesh3 = make_spgemm_mesh(p=2, l=2)
    x0 = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=0.3,
                      pattern="decay", symmetric=True)
    # pre-scale on the host so envelope and baseline chains see the SAME
    # input bits (scale_input=False: ShardedBSM.frobenius_norm reduces
    # in psum order, which may differ by a ULP between programs)
    x = B.scale(x0, float(1.0 / max(float(x0.frobenius_norm()), 1e-30)))
    kw = dict(threshold=1e-7, filter_eps=1e-6, max_iter=10, tol=0.0,
              scale_input=False, backend="stacks")

    for engine, mesh, l in (("onesided", mesh2, None),
                            ("twofive", mesh3, 2)):
        plan_mod.clear_cache()
        want, _ = sign_iteration(x, mesh=mesh, engine=engine, l=l, **kw)
        plan_mod.clear_cache()
        got, st = sign_iteration(x, mesh=mesh, engine=engine, l=l,
                                 envelope="auto", **kw)
        s = plan_mod.cache_stats()
        assert st.envelope and st.retraces == 1, (engine, st)
        assert s["builds"] == 1 and s["chain_misses"] == 1, (engine, s)
        assert s["chain_hits"] == st.iterations - 1, (engine, s)
        assert s["envelope_misses"] == 1 and s["drift_retunes"] == 0, (
            engine, s)
        np.testing.assert_array_equal(np.asarray(got.mask),
                                      np.asarray(want.mask), err_msg=engine)
        assert np.array_equal(np.asarray(got.blocks),
                              np.asarray(want.blocks)), engine
        # warm: same operand -> envelope cache hit, zero retraces
        _, st2 = sign_iteration(x, mesh=mesh, engine=engine, l=l,
                                envelope="auto", **kw)
        s2 = plan_mod.cache_stats()
        assert st2.retraces == 0, (engine, st2)
        assert s2["builds"] == 1 and s2["envelope_hits"] == 1, (engine, s2)

    # compressed transport inside a fused chain — envelope-only territory
    plan_mod.clear_cache()
    want, _ = sign_iteration(x, mesh=mesh2, engine="onesided", **kw)
    plan_mod.clear_cache()
    got, st = sign_iteration(x, mesh=mesh2, engine="onesided",
                             envelope="auto", transport="compressed", **kw)
    s = plan_mod.cache_stats()
    assert st.retraces == 1 and s["builds"] == 1, (st, s)
    assert s["transport_compressed"] >= 1, s
    assert np.array_equal(np.asarray(got.blocks),
                          np.asarray(want.blocks)), "compressed chain"
    # without an envelope the same request is a hard error (chain safety)
    try:
        sign_iteration(x, mesh=mesh2, engine="onesided",
                       transport="compressed", **kw)
    except ValueError:
        pass
    else:
        raise AssertionError(
            "compressed chain transport without an envelope must raise")

    # engine="auto" with an envelope: full candidate space, one chain
    plan_mod.clear_cache()
    got, st = sign_iteration(x, mesh=mesh2, engine="auto",
                             envelope="auto", **kw)
    s = plan_mod.cache_stats()
    assert s["chain_misses"] == 1 and st.retraces == 1, (s, st)
    np.testing.assert_allclose(np.asarray(got.to_dense()),
                               np.asarray(want.to_dense()),
                               rtol=1e-5, atol=1e-6)
    print("envelope_sharded OK")


def check_transport():
    """Compressed transport == dense transport BIT-EXACT for every
    engine, across occupancy in {0, low, medium, full}, thresholds,
    rectangular meshes (forced virtual L) and uneven-L stacked meshes —
    plus: the auto mode resolves compressed at low fill and dense at
    high fill, capacities are served from the signature cache on
    repeats, and the REPRO_TRANSPORT env override forces the mode."""

    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply, multiply_reference

    from repro.launch.mesh import make_spgemm_mesh

    mesh2 = make_spgemm_mesh(p=2)
    mesh24 = make_mesh((2, 4), ("r", "c"), devices=jax.devices()[:8])
    mesh42 = make_mesh((4, 2), ("r", "c"), devices=jax.devices()[:8])
    mesh_uneven = make_spgemm_mesh(p=2, l=4)  # L does not divide the side
    grids = (
        (mesh2, ("cannon", "onesided", "gather", "twofive")),
        (mesh24, ("onesided", "gather", "twofive")),  # forced virtual L=2
        (mesh42, ("onesided", "gather", "twofive")),
        (mesh_uneven, ("twofive",)),  # stacked, uneven chunks
    )
    for occ in (0.0, 0.1, 0.5, 1.0):
        a = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=occ,
                         pattern="decay")
        b = B.random_bsm(jax.random.key(1), nb=8, bs=8, occupancy=occ)
        for thr in (0.0, 1e-3):
            ref = np.asarray(
                multiply_reference(a, b, threshold=thr).to_dense())
            for mesh, engines in grids:
                for eng in engines:
                    tag = f"{eng}/{dict(mesh.shape)} occ={occ} t={thr}"
                    cd = multiply(a, b, mesh, engine=eng, threshold=thr,
                                  transport="dense")
                    cc = multiply(a, b, mesh, engine=eng, threshold=thr,
                                  transport="compressed")
                    np.testing.assert_array_equal(
                        np.asarray(cc.blocks), np.asarray(cd.blocks),
                        err_msg=tag)
                    np.testing.assert_array_equal(
                        np.asarray(cc.mask), np.asarray(cd.mask),
                        err_msg=tag)
                    np.testing.assert_allclose(
                        np.asarray(cd.to_dense()), ref,
                        rtol=1e-5, atol=1e-5, err_msg=tag)

    # auto crossover: sparse pattern -> compressed, full pattern -> dense
    # (nb=16 so a shard holds 64 blocks — auto never compresses panels
    # small enough for the bucket floor to dominate)
    plan_mod.clear_cache()
    ii, jj = np.meshgrid(np.arange(16), np.arange(16), indexing="ij")
    sparse_mask = (ii % 4 == 0) & (jj % 4 == 0)  # 4 blocks per 8x8 shard
    sparse = B.make_bsm(
        jax.random.normal(jax.random.key(2), (16, 16, 8, 8)),
        jnp.asarray(sparse_mask),
    )
    full = B.random_bsm(jax.random.key(3), nb=16, bs=8, occupancy=1.0)
    multiply(sparse, sparse, mesh2, engine="onesided", transport="auto")
    s = plan_mod.cache_stats()
    assert s["transport_compressed"] == 1, s
    multiply(full, full, mesh2, engine="onesided", transport="auto")
    s = plan_mod.cache_stats()
    assert s["transport_dense"] == 1, s
    # repeated pattern: resolution served from the signature cache
    multiply(sparse, sparse, mesh2, engine="onesided", transport="auto")
    s2 = plan_mod.cache_stats()
    assert s2["transport_hits"] >= 1, s2
    assert s2["transport_misses"] == s["transport_misses"], (s, s2)

    # REPRO_TRANSPORT forces the default mode (plumbed like
    # REPRO_PALLAS_INTERPRET)
    plan_mod.clear_cache()
    os.environ["REPRO_TRANSPORT"] = "dense"
    try:
        multiply(sparse, sparse, mesh2, engine="onesided")
        s = plan_mod.cache_stats()
        assert s["transport_misses"] == 0, s  # dense: no resolution walk
        os.environ["REPRO_TRANSPORT"] = "compressed"
        multiply(sparse, sparse, mesh2, engine="onesided")
        s = plan_mod.cache_stats()
        assert s["transport_compressed"] == 1, s
    finally:
        del os.environ["REPRO_TRANSPORT"]
    print("transport OK")


def check_tuner_auto():
    """engine="auto" on real multi-device meshes (DESIGN.md §6):

    * the tuned multiply equals the single-device filtered oracle on
      square, rectangular and stacked meshes (replicated AND sharded
      operands);
    * the decision is Eq. (6)-feasible and, on a rectangular grid, never
      an engine the topology forbids (cannon);
    * a warm tuning DB resolves with ZERO timed trials — the production
      property the persisted database exists for;
    * sign_iteration(engine="auto") matches the static legacy loop and
      keys ONE chain program (the tuner resolves before the chain key).
    """
    import tempfile

    from repro import tuner
    from repro.core import bsm as B
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply, multiply_reference
    from repro.core.signiter import sign_iteration, sign_iteration_legacy
    from repro.launch.mesh import make_spgemm_mesh

    a = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=0.3,
                     pattern="decay", symmetric=True)
    b = B.random_bsm(jax.random.key(1), nb=8, bs=8, occupancy=0.3,
                     pattern="decay")
    thr = 1e-6
    ref = np.asarray(multiply_reference(a, b, threshold=thr).to_dense())

    meshes = [
        make_spgemm_mesh(p=2),
        make_mesh((2, 4), ("r", "c"), devices=jax.devices()[:8]),
        make_spgemm_mesh(p=2, l=2),
    ]
    for mesh in meshes:
        plan_mod.clear_cache()
        c = multiply(a, b, mesh, engine="auto", threshold=thr)
        np.testing.assert_allclose(np.asarray(c.to_dense()), ref,
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=str(dict(mesh.shape)))
        s = plan_mod.cache_stats()
        assert s["tuner_misses"] == 1 and s["tuner_trials"] >= 1, s

    # rectangular grid: the decision can never be cannon (square-only)
    mesh24 = meshes[1]
    dec = tuner.autotune(a, b, mesh24, threshold=thr)
    assert dec.engine != "cannon", dec

    # sharded operands stay sharded through the tuned path
    mesh2 = meshes[0]
    plan_mod.clear_cache()
    c = multiply(B.shard_bsm(a, mesh2), B.shard_bsm(b, mesh2),
                 engine="auto", threshold=thr)
    assert isinstance(c, B.ShardedBSM)
    np.testing.assert_allclose(np.asarray(c.to_dense()), ref,
                               rtol=1e-5, atol=1e-5)

    # warm DB: zero timed trials in a "fresh process"
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "db.json")
        plan_mod.clear_cache()
        tuner.set_default_db(path)
        multiply(a, b, mesh2, engine="auto", threshold=thr)
        assert plan_mod.cache_stats()["tuner_trials"] >= 1
        plan_mod.clear_cache()
        tuner.set_default_db(path)
        multiply(a, b, mesh2, engine="auto", threshold=thr)
        s = plan_mod.cache_stats()
        assert s["tuner_trials"] == 0 and s["tuner_hits"] == 1, s

    # autotuned purification == static legacy loop; one chain program
    plan_mod.clear_cache()
    x = a
    want, st_ref = sign_iteration_legacy(
        x, mesh=mesh2, engine="onesided", threshold=1e-7, filter_eps=1e-6,
        max_iter=60, tol=1e-6)
    plan_mod.clear_cache()
    got, st = sign_iteration(x, mesh=mesh2, engine="auto", threshold=1e-7,
                             filter_eps=1e-6, max_iter=60, tol=1e-6)
    assert st.converged and st.iterations == st_ref.iterations
    np.testing.assert_allclose(np.asarray(got.to_dense()),
                               np.asarray(want.to_dense()),
                               rtol=1e-5, atol=1e-5)
    s = plan_mod.cache_stats()
    assert s["chain_misses"] == 1, s  # tuner resolved BEFORE the chain key
    assert s["builds"] <= 1 + s["tuner_trials"], s
    print("tuner_auto OK")


def check_assignment():
    """The block→device assignment layer (core.distribute) end-to-end:

    * distribute → shard_bsm → unshard → undistribute round-trips
      BIT-EXACT for every mode on square, rectangular and uneven-L
      stacked meshes (pure reindexing + data movement, no arithmetic);
    * replicated multiply under every assignment mode returns results in
      ORIGINAL block coordinates matching the identity-layout multiply,
      for every engine x mesh x backend (the permutation is wrapped
      inside the compiled program);
    * sharded execution: operands sharded under one assignment multiply
      in-layout, the result carries the assignment, and unshard restores
      original coordinates; mixing layouts raises;
    * the fused purification chain under one pinned assignment matches
      the identity-layout chain trace-for-trace;
    * balancing pays: on the hub-skewed zipf pattern the nnz_greedy
      layout yields a strictly smaller compacted stack capacity.
    """

    from repro.core import bsm as B
    from repro.core import distribute as D
    from repro.core import plan as plan_mod
    from repro.core.engine import multiply, multiply_reference
    from repro.launch.mesh import make_spgemm_mesh
    from repro.tuner.corpus import CorpusEntry

    # hub-skewed operands: the workload assignments exist for
    z = CorpusEntry("zipf_hub", "zipf", 8, 8, occupancy=0.3,
                    zipf_alpha=1.4, seed=15)
    a, b = z.build()
    mesh2 = make_spgemm_mesh(p=2)
    mesh24 = make_mesh((2, 4), ("r", "c"), devices=jax.devices()[:8])
    mesh_uneven = make_spgemm_mesh(p=2, l=4)  # L does not divide the side
    # (mesh, engines, backends): compacted backends ride along where the
    # transport/stacks checks already cover that mesh class
    grids = (
        (mesh2, ("cannon", "onesided", "gather", "twofive"),
         ("jnp", "stacks")),
        (mesh24, ("onesided", "gather", "twofive"), ("jnp",)),  # virtual L
        (mesh_uneven, ("twofive",), ("jnp", "stacks")),  # stacked, uneven
    )

    # --- shard/unshard round-trip: bit-exact per mode and mesh
    for mesh, _, _ in grids:
        for mode in ("randomized", "nnz_greedy"):
            hm = B.shard_bsm(a, mesh, assignment=mode)
            assert hm.assignment is not None and not hm.assignment.is_identity
            back = hm.unshard()
            tag = f"{mode}/{dict(mesh.shape)}"
            np.testing.assert_array_equal(
                np.asarray(back.blocks), np.asarray(a.blocks), err_msg=tag)
            np.testing.assert_array_equal(
                np.asarray(back.mask), np.asarray(a.mask), err_msg=tag)
            np.testing.assert_array_equal(
                np.asarray(back.norms), np.asarray(a.norms), err_msg=tag)
        # identity spec collapses to the plain layout
        assert B.shard_bsm(a, mesh, assignment="identity").assignment is None

    # --- replicated multiply: every mode == identity layout, original
    #     coordinates (allclose: the permutation regroups the k-sum)
    thr = 1e-6
    ref = np.asarray(multiply_reference(a, b, threshold=thr).to_dense())
    for mesh, engines, backends in grids:
        for eng in engines:
            for spec in ("randomized", "nnz_greedy"):
                for backend in backends:
                    tag = f"{eng}/{backend}/{spec}/{dict(mesh.shape)}"
                    c = multiply(a, b, mesh, engine=eng, threshold=thr,
                                 backend=backend, assignment=spec)
                    np.testing.assert_allclose(
                        np.asarray(c.to_dense()), ref, rtol=1e-5, atol=1e-5,
                        err_msg=tag)
                    np.testing.assert_array_equal(
                        np.asarray(c.mask),
                        np.asarray(multiply_reference(
                            a, b, threshold=thr).mask), err_msg=tag)

    # an explicit Assignment object is honored as-is
    counts = D.product_counts(np.asarray(a.mask), np.asarray(b.mask))
    asg = D.assignment_for("nnz_greedy", counts, (2, 2))
    c = multiply(a, b, mesh2, engine="onesided", threshold=thr,
                 assignment=asg)
    np.testing.assert_allclose(np.asarray(c.to_dense()), ref,
                               rtol=1e-5, atol=1e-5)

    # --- sharded path: multiply in-layout, result carries the assignment.
    # A mode STRING derives the perm from each operand's own mask, so an
    # A@B pair shards under one explicit Assignment from the pair's
    # product counts (mode strings remain the convenience for the
    # symmetric H@H chain, where both operands share the mask).
    for spec in ("randomized", "nnz_greedy"):
        pair_asg = D.compute_assignment(spec, np.asarray(a.mask),
                                        np.asarray(b.mask), mesh2)
        ha = B.shard_bsm(a, mesh2, assignment=pair_asg)
        hb = B.shard_bsm(b, mesh2, assignment=pair_asg)
        hc = multiply(ha, hb, None, engine="onesided", threshold=thr)
        assert isinstance(hc, B.ShardedBSM)
        assert hc.assignment == ha.assignment
        np.testing.assert_allclose(np.asarray(hc.to_dense()), ref,
                                   rtol=1e-5, atol=1e-5, err_msg=spec)
    # mixing layouts is an error, not a silent wrong answer
    ha = B.shard_bsm(a, mesh2, assignment="nnz_greedy")
    hb = B.shard_bsm(b, mesh2)
    try:
        multiply(ha, hb, None, engine="onesided")
    except ValueError as e:
        assert "assignment" in str(e)
    else:
        raise AssertionError("mixed-layout multiply must raise")

    # --- fused chain under one pinned assignment == identity-layout chain
    from repro.core.signiter import sign_iteration

    x = B.random_bsm(jax.random.key(0), nb=8, bs=8, occupancy=0.6,
                     pattern="banded", symmetric=True)
    want, st_ref = sign_iteration(x, mesh=mesh2, engine="onesided",
                                  threshold=1e-7, filter_eps=1e-6,
                                  max_iter=60, tol=1e-6)
    for spec in ("randomized", "nnz_greedy"):
        got, st = sign_iteration(x, mesh=mesh2, engine="onesided",
                                 threshold=1e-7, filter_eps=1e-6,
                                 max_iter=60, tol=1e-6, assignment=spec)
        assert st.iterations == st_ref.iterations, spec
        np.testing.assert_allclose(st.residual_trace, st_ref.residual_trace,
                                   rtol=1e-4, atol=1e-7, err_msg=spec)
        np.testing.assert_allclose(np.asarray(got.to_dense()),
                                   np.asarray(want.to_dense()),
                                   rtol=1e-5, atol=1e-5, err_msg=spec)
    # sharded-in chain keeps its layout end-to-end
    hx = B.shard_bsm(x, mesh2, assignment="nnz_greedy")
    s, _ = sign_iteration(hx, engine="onesided", threshold=1e-7,
                          filter_eps=1e-6, max_iter=60, tol=1e-6)
    assert isinstance(s, B.ShardedBSM) and s.assignment == hx.assignment
    np.testing.assert_allclose(np.asarray(s.to_dense()),
                               np.asarray(want.to_dense()),
                               rtol=1e-5, atol=1e-5)

    # --- the win: balancing shrinks the max-device compacted capacity
    zz = CorpusEntry("zipf_hub", "zipf", 32, 4, occupancy=0.15,
                     zipf_alpha=1.4, seed=15)
    za, zb = zz.build()
    ok = np.asarray(za.mask)[:, :, None] & np.asarray(zb.mask)[None, :, :]
    mesh44 = make_mesh((4, 4), ("r", "c"), devices=jax.devices()[:16])
    zasg = D.assignment_for(
        "nnz_greedy", D.product_counts(np.asarray(za.mask),
                                       np.asarray(zb.mask)), (4, 4))
    cap_id = plan_mod.get_device_capacity(ok, mesh44, "onesided")
    cap_gr = plan_mod.get_device_capacity(D.permute_cube(ok, zasg.perm),
                                          mesh44, "onesided")
    assert cap_gr < cap_id, (cap_id, cap_gr)
    print("assignment OK "
          f"cap identity={cap_id} nnz_greedy={cap_gr}")


def check_comm_volume():
    """Measured HLO collective bytes track the paper's volume model:

    * cannon and onesided (PTP vs OS1) move identical A/B volume (Table 2);
    * the 2.5D engine's A/B traffic drops ~L-fold in tick count (the mesh
      formulation's Eq. (7) analogue) while adding the (L-1)/L C reduction.
    """
    from repro.core.engine import lower_multiply
    from repro.launch.mesh import make_spgemm_mesh
    from repro.roofline.hlo_cost import analyze_hlo

    nb, bs = 16, 8

    def coll(mesh, engine, **kw):
        lowered = lower_multiply(mesh, nb, bs, engine=engine, **kw)
        txt = lowered.compile().as_text()
        return analyze_hlo(txt, default_group=mesh.size)

    mesh2 = make_spgemm_mesh(p=4)
    r_cannon = coll(mesh2, "cannon")
    r_onesided = coll(mesh2, "onesided")
    r_gather = coll(mesh2, "gather")

    # PTP == OS1 volume up to the pre-shift (a small constant)
    ratio = r_onesided.collective_wire_bytes / r_cannon.collective_wire_bytes
    assert 0.7 < ratio <= 1.01, ratio
    # gather moves the same panel volume as the streaming engines (+-20%)
    ratio_g = r_gather.collective_wire_bytes / r_onesided.collective_wire_bytes
    assert 0.5 < ratio_g < 1.5, ratio_g

    mesh25_l1 = make_spgemm_mesh(p=4)  # L=1 == onesided ticks
    mesh25_l4 = make_spgemm_mesh(p=4, l=4)
    r_l1 = coll(mesh25_l1, "onesided")
    r_l4 = coll(mesh25_l4, "twofive", c_layout="scatter")
    # per-device A/B traffic: 4 ticks -> 1 tick; plus the C reduce-scatter.
    # net must be well below L=1 (the communication reduction of the paper)
    assert r_l4.collective_wire_bytes < 0.7 * r_l1.collective_wire_bytes, (
        r_l4.collective_wire_bytes, r_l1.collective_wire_bytes)
    print("comm_volume OK:",
          f"cannon={r_cannon.collective_wire_bytes:.3g}",
          f"os1={r_onesided.collective_wire_bytes:.3g}",
          f"l4={r_l4.collective_wire_bytes:.3g}")


def check_train_steps():
    """build_train_step executes on a (2,2) mesh: loss finite + decreasing,
    donated buffers update, gradient compression preserves learning."""
    from repro.configs import get_arch
    from repro.data.pipeline import DataConfig, SyntheticLMData, make_global_batch
    from repro.launch.steps import StepOptions, build_train_step
    from repro.optim import AdamWConfig
    from repro.config import ShapeConfig
    from repro.models import transformer as T
    from repro.parallel.sharding import batch_spec

    cfg = get_arch("olmo_1b").reduced()
    mesh = make_mesh((2, 2), ("data", "model"))
    shape = ShapeConfig("t", seq_len=64, global_batch=8, kind="train")

    for compress in (False, True):
        options = StepOptions(remat="full", compress_grads=compress, loss_chunk=64)
        step, (p_sds, o_sds, b_sds) = build_train_step(
            cfg, mesh, shape, opt=AdamWConfig(lr=5e-3, weight_decay=0.0),
            options=options)

        params = jax.jit(
            lambda k: T.init_params(cfg, k),
            out_shardings=jax.tree.map(lambda s: s.sharding, p_sds),
        )(jax.random.key(0))
        opt_state = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype,
                                device=s.sharding), o_sds)

        data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=64,
                                          global_batch=8))
        spec = batch_spec(mesh, 8, 64)
        losses = []
        for i in range(5):
            batch = make_global_batch(data, i, mesh, spec)
            params, opt_state, metrics = step(params, opt_state, batch)
            loss = float(metrics["loss"])
            assert np.isfinite(loss), (compress, i)
            losses.append(loss)
        assert losses[-1] < losses[0], (compress, losses)
        print(f"train_steps compress={compress} OK {losses[0]:.3f}->{losses[-1]:.3f}")


def check_serve_steps():
    """build_serve_step + build_prefill_step execute on a (2,2) mesh and
    match the single-device decode."""
    from repro.configs import get_arch
    from repro.config import ShapeConfig
    from repro.launch.steps import StepOptions, build_prefill_step, build_serve_step
    from repro.models import transformer as T

    cfg = get_arch("olmo_1b").reduced()
    mesh = make_mesh((2, 2), ("data", "model"))
    b, s = 4, 32
    shape_d = ShapeConfig("d", seq_len=s, global_batch=b, kind="decode")
    shape_p = ShapeConfig("p", seq_len=s, global_batch=b, kind="prefill")

    params = T.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab)

    # single-device oracle
    cache0 = T.init_cache(cfg, b, s)
    logits_ref, cache_ref = T.prefill(cfg, params, toks, cache0)
    tok = jnp.argmax(logits_ref[:, -1], -1)[:, None].astype(jnp.int32)
    logits_ref2, _ = T.decode_step(cfg, params, tok, cache_ref,
                                   jnp.asarray(s, jnp.int32))

    pstep, (p_sds, c_sds, b_sds) = build_prefill_step(cfg, mesh, shape_p,
                                                      options=StepOptions())
    put = lambda tree, sds: jax.tree.map(
        lambda x, s_: jax.device_put(x, s_.sharding), tree, sds)
    params_sh = put(params, p_sds)
    cache_sh = put(T.init_cache(cfg, b, s), c_sds)
    logits, cache_sh = pstep(params_sh, cache_sh, {"tokens": jax.device_put(
        toks, b_sds["tokens"].sharding)})
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(logits_ref, np.float32),
                               rtol=2e-2, atol=2e-2)

    dstep, (p_sds2, c_sds2, b_sds2) = build_serve_step(cfg, mesh, shape_d,
                                                       options=StepOptions())
    logits2, _ = dstep(put(params, p_sds2),
                       jax.tree.map(lambda x, s_: jax.device_put(
                           np.asarray(x), s_.sharding), cache_sh, c_sds2),
                       jax.device_put(tok, b_sds2["tokens"].sharding),
                       jnp.asarray(s, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits2, np.float32),
                               np.asarray(logits_ref2, np.float32),
                               rtol=2e-2, atol=2e-2)
    print("serve_steps OK")


def check_checkpoint_cross_mesh():
    """Save sharded on (4,1), restore onto (2,2) — the elastic path."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    import tempfile

    mesh_a = make_mesh((4, 1), ("data", "model"))
    mesh_b = make_mesh((2, 2), ("data", "model"))
    x = jnp.arange(64.0).reshape(8, 8)
    xa = jax.device_put(x, NamedSharding(mesh_a, P("data", None)))
    tree = {"w": xa, "step": jnp.asarray(3)}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, tree, mesh=mesh_a)
        shardings = {
            "w": NamedSharding(mesh_b, P("data", "model")),
            "step": NamedSharding(mesh_b, P()),
        }
        r = restore_checkpoint(d, 1, jax.eval_shape(lambda: tree),
                               shardings=shardings)
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(x))
        assert r["w"].sharding.spec == P("data", "model")
    print("checkpoint_cross_mesh OK")


def check_data_global_batch():
    from repro.data.pipeline import DataConfig, SyntheticLMData, make_global_batch
    from repro.parallel.sharding import batch_spec

    mesh = make_mesh((4, 2), ("data", "model"))
    d = SyntheticLMData(DataConfig(vocab=64, seq_len=16, global_batch=8))
    spec = batch_spec(mesh, 8, 16)
    gb = make_global_batch(d, 2, mesh, spec)
    want = d.batch_numpy(2)
    np.testing.assert_array_equal(np.asarray(gb["tokens"]), want["tokens"])
    np.testing.assert_array_equal(np.asarray(gb["targets"]), want["targets"])
    assert gb["tokens"].sharding.spec[0] == "data"
    print("data_global_batch OK")


def check_matmul_2p5d():
    """The paper's 2.5D schedule on the LM-head matmul: exact vs x @ w."""
    from repro.parallel.matmul_2p5d import matmul_2p5d_shardmap, plan_2p5d

    mesh = make_mesh((2, 2, 4), ("pod", "data", "model"))
    t, dm, v = 16, 32, 64
    x = jax.random.normal(jax.random.key(0), (t, dm))
    w = jax.random.normal(jax.random.key(1), (dm, v))
    want = np.asarray(x @ w)
    for reduce in ("scatter", "psum"):
        fn = matmul_2p5d_shardmap(mesh, reduce=reduce)
        out = fn(x, w)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4,
                                   err_msg=reduce)
    plan = plan_2p5d(tokens=2048, d_model=4096, vocab=128256, l=2, tp=16)
    assert plan.bytes_2p5d > 0 and plan.bytes_baseline > 0
    print("matmul_2p5d OK")


def check_compressed_allreduce():
    from repro.optim.compress import (
        compressed_allreduce_shardmap,
        init_compress_state,
    )

    mesh = make_mesh((4,), ("data",))
    fn = compressed_allreduce_shardmap(mesh, axis="data")
    g = jax.random.normal(jax.random.key(0), (4, 64)) * 1e-2
    r0 = jnp.zeros((4, 64), jnp.float32)
    synced, resid = fn({"w": g}, {"w": r0})
    want = np.asarray(jnp.mean(g.astype(jnp.bfloat16).astype(jnp.float32), 0))
    for row in np.asarray(synced["w"]):
        np.testing.assert_allclose(row, want, rtol=2e-2, atol=1e-4)
    # residual carries the quantization error exactly
    np.testing.assert_allclose(
        np.asarray(resid["w"]),
        np.asarray(g, np.float32)
        - np.asarray(g.astype(jnp.bfloat16), np.float32),
        atol=1e-7,
    )
    print("compressed_allreduce OK")


def check_spgemm_scaling():
    """Comm-volume scaling over mesh sizes: measured bytes per device drop
    as the grid grows (O(1/sqrt(P)) of Eq. (7) with fixed matrix)."""
    from repro.core.engine import lower_multiply
    from repro.launch.mesh import make_spgemm_mesh
    from repro.roofline.hlo_cost import analyze_hlo

    nb, bs = 16, 8
    got = {}
    for p in (2, 4):
        lowered = lower_multiply(make_spgemm_mesh(p=p), nb, bs, engine="onesided")
        got[p] = analyze_hlo(lowered.compile().as_text(),
                             default_group=p * p).collective_wire_bytes
    # panel size shrinks 4x (p doubles both dims), ticks double -> net ~1/2
    ratio = got[4] / got[2]
    assert 0.3 < ratio < 0.75, (got, ratio)
    print("spgemm_scaling OK", got)


def check_microbatch_equivalence():
    """Gradient accumulation (microbatch=k) == single-batch step, and the
    ZeRO-1 layout produces the same update."""
    from repro.configs import get_arch
    from repro.config import ShapeConfig
    from repro.launch.steps import StepOptions, build_train_step
    from repro.optim import AdamWConfig
    from repro.models import transformer as T

    cfg = get_arch("olmo_1b").reduced()
    mesh = make_mesh((2, 2), ("data", "model"))
    shape = ShapeConfig("t", 64, 8, "train")
    opt = AdamWConfig(lr=1e-3, weight_decay=0.0)
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab),
        "targets": jax.random.randint(jax.random.key(2), (8, 64), 0, cfg.vocab),
    }
    results = {}
    for name, opts in {
        "mb1": StepOptions(remat="full", loss_chunk=64),
        "mb4": StepOptions(remat="full", loss_chunk=64, microbatch=4),
        "mb4z": StepOptions(remat="full", loss_chunk=64, microbatch=4, zero1=True),
    }.items():
        step, (p_sds, o_sds, _) = build_train_step(cfg, mesh, shape, opt=opt,
                                                   options=opts)
        sh = lambda t: jax.tree.map(lambda x: x.sharding, t)
        params = jax.jit(lambda k: T.init_params(cfg, k),
                         out_shardings=sh(p_sds))(jax.random.key(0))
        opt_state = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding), o_sds)
        p2, _, m = step(params, opt_state, batch)
        results[name] = (float(m["loss"]), p2)
    base_loss, base_p = results["mb1"]
    for name in ("mb4", "mb4z"):
        loss, p = results[name]
        assert abs(loss - base_loss) < 1e-2, (name, loss, base_loss)
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(base_p), jax.tree.leaves(p)))
        assert d < 1e-4, (name, d)
    print("microbatch_equivalence OK")


def check_pipeline():
    """GPipe schedule over a 4-stage axis == sequential composition."""
    from repro.parallel.pipeline import pipeline_shardmap, split_microbatches

    mesh = make_mesh((4,), ("pod",))
    d = 16
    ws = jax.random.normal(jax.random.key(0), (4, d, d)) * (d**-0.5)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    fn = pipeline_shardmap(mesh, stage_fn, axis="pod")
    x = jax.random.normal(jax.random.key(1), (8, 2, d))  # 8 microbatches
    out = fn(ws, x)

    want = x
    for i in range(4):
        want = jnp.tanh(want @ ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    print("pipeline OK")


def check_product_counts():
    """The fused sweep's block-product counts on several devices equal
    the count from the operand masks for every multiply, and repeat
    exactly: twofive and cannon on a 2x2 mesh, cannon's scanned ring on
    4x4, twofive on a stacked 2x2x2 mesh and on an uneven 2x3x3 one.
    Summed over the mesh, each engine's local stage multiplies the whole
    cube once per multiply (jnp backend), plus the cubes of the uneven
    mesh's masked ticks; the compiled sweep's permutes are named
    ``spgemm.transport``."""
    from repro.core.signiter import lower_sweep, sign_iteration
    from repro.launch.mesh import make_spgemm_mesh
    from tests.test_signiter import banded_hamiltonian, mask_chain_products

    nb = 12
    h, mask = banded_hamiltonian(nb=nb)
    want = mask_chain_products(mask, 5)
    for engine, kw, cubes in (("twofive", {"p": 2}, 1), ("cannon", {"p": 2}, 1),
                              ("cannon", {"p": 4}, 1), ("twofive", {"p": 2, "l": 2}, 1),
                              ("twofive", {"p": 3, "l": 2}, 4 / 3)):
        mesh = make_spgemm_mesh(**kw)
        runs = [sign_iteration(h, mesh=mesh, engine=engine, max_iter=5,
                               tol=0.0, sync_every=2)[1] for _ in range(2)]
        for st in runs:
            assert st.products_present == want, (engine, kw, st.products_present)
            assert st.products_computed == cubes * nb ** 3, (
                engine, kw, st.products_computed)
            assert st.host_syncs == 3
        hlo = lower_sweep(mesh, nb, 4, engine=engine).compile().as_text()
        permutes = [ln for ln in hlo.splitlines()
                    if "collective-permute" in ln and "op_name=" in ln]
        assert permutes and all("spgemm.transport" in ln for ln in permutes), engine
    print("product_counts OK")


CHECKS = {
    "product_counts": check_product_counts,
    "engines": check_engines,
    "transport": check_transport,
    "stacks_backends": check_stacks_backends,
    "microbatch": check_microbatch_equivalence,
    "pipeline": check_pipeline,
    "engines_rectangular": check_engines_rectangular,
    "plan_rectangular": check_plan_rectangular,
    "plan_cache": check_plan_cache,
    "signiter_sharded": check_signiter_sharded,
    "envelope_sharded": check_envelope_sharded,
    "tuner_auto": check_tuner_auto,
    "comm_volume": check_comm_volume,
    "train_steps": check_train_steps,
    "serve_steps": check_serve_steps,
    "checkpoint_cross_mesh": check_checkpoint_cross_mesh,
    "data_global_batch": check_data_global_batch,
    "matmul_2p5d": check_matmul_2p5d,
    "compressed_allreduce": check_compressed_allreduce,
    "spgemm_scaling": check_spgemm_scaling,
    "assignment": check_assignment,
    "tensor": check_tensor,
}


def main(argv: list[str]) -> int:
    names = argv or list(CHECKS)
    for name in names:
        CHECKS[name]()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
