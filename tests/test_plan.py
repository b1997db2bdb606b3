"""Plan layer: schedule compilation, table validity, and the program cache.

The pull schedule is validated two ways without any devices:
  * structurally — every round is a valid partial permutation, every active
    process receives exactly the panels of ``group_products``;
  * numerically — a pure-numpy interpreter of the plan tables (mimicking
    ppermute semantics: listed pairs deliver, everyone else receives zeros)
    reproduces A @ B exactly for square, non-square, and deep topologies.

Multi-device execution of the same plans is covered by
tests/test_distributed.py::test_plan_rectangular_grids / test_plan_cache.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.plan import _partition_rounds, _pull_schedule, _resolve_l
from repro.core.topology import (
    Topology,
    coords3d,
    group_products,
    make_topology,
)
from repro.launch.mesh import make_mesh


# ---- round partitioning ----------------------------------------------------


def test_partition_rounds_splits_multicasts():
    pairs = [(0, 1), (0, 2), (0, 3), (1, 4)]
    rounds = _partition_rounds(pairs)
    assert len(rounds) == 3  # source 0 serialized over 3 rounds
    for r in rounds:
        srcs = [s for s, _ in r]
        dsts = [d for _, d in r]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
    assert sorted(p for r in rounds for p in r) == sorted(pairs)


@pytest.mark.parametrize(
    "pr,pc,l",
    [(2, 2, 1), (4, 4, 1), (2, 4, 2), (4, 2, 2), (2, 2, 4), (4, 4, 4),
     (6, 6, 9), (3, 9, 3), (6, 2, 3)],
)
def test_pull_rounds_are_partial_permutations(pr, pc, l):
    topo = make_topology(pr, pc, l)
    a_ticks, b_ticks, c_rounds, ca, cb = _pull_schedule(topo)
    for ticks in (a_ticks, b_ticks):
        for rounds in ticks:
            for rd in rounds:
                srcs = [s for s, _ in rd.pairs]
                dsts = [d for _, d in rd.pairs]
                assert len(set(srcs)) == len(srcs), (pr, pc, l)
                assert len(set(dsts)) == len(dsts), (pr, pc, l)
    n = pr * pc
    for perm in c_rounds:
        assert sorted(s for s, _ in perm) == list(range(n))
        assert sorted(d for _, d in perm) == list(range(n))


@pytest.mark.parametrize("pr,pc,l", [(2, 4, 2), (4, 2, 2), (4, 4, 4)])
def test_pull_schedule_delivers_group_products(pr, pc, l):
    """Per tick, each active process receives exactly the virtual panels of
    ``group_products`` — the plan is faithful to Algorithm 2."""
    topo = make_topology(pr, pc, l)
    a_ticks, b_ticks, _, ca, cb = _pull_schedule(topo)
    s = topo.side3d
    for g in range(topo.ticks):
        got_a: dict[int, set] = {}
        got_b: dict[int, set] = {}
        for rd in a_ticks[g]:
            for src, dst in rd.pairs:
                m, jc = divmod(src, topo.p_c)
                got_a.setdefault(dst, set()).add((m, jc * ca + rd.q))
        for rd in b_ticks[g]:
            for src, dst in rd.pairs:
                ir, n = divmod(src, topo.p_c)
                got_b.setdefault(dst, set()).add((ir * cb + rd.q, n))
        for i in range(pr):
            for j in range(pc):
                _, _, lay = coords3d(topo, i, j)
                f = i * pc + j
                if g >= topo.layer_groups(lay):
                    assert f not in got_a and f not in got_b
                    continue
                prods = group_products(topo, i, j, g)
                assert got_a[f] == {(m, k) for m, k, _ in prods}
                assert got_b[f] == {(k, n) for _, k, n in prods}


# ---- numpy interpretation of the plan tables == A @ B ----------------------


def _execute_pull_plan(topo: Topology, a: np.ndarray, b: np.ndarray):
    """Interpret the pull schedule with numpy ppermute semantics."""
    a_ticks, b_ticks, c_rounds, ca, cb = _pull_schedule(topo)
    p_r, p_c, depth, s = topo.p_r, topo.p_c, topo.l, topo.side3d
    n = a.shape[0]
    hr, hc, hv = n // p_r, n // p_c, n // topo.v
    nproc = p_r * p_c

    def a_shard(f):
        i, j = divmod(f, p_c)
        return a[i * hr : (i + 1) * hr, j * hc : (j + 1) * hc]

    def b_shard(f):
        i, j = divmod(f, p_c)
        return b[i * hr : (i + 1) * hr, j * hc : (j + 1) * hc]

    parts = [np.zeros((depth, hr, hc)) for _ in range(nproc)]
    for g in range(topo.ticks):
        pan_a = [np.zeros((topo.l_r, hr, hv)) for _ in range(nproc)]
        pan_b = [np.zeros((topo.l_c, hv, hc)) for _ in range(nproc)]
        for rd in a_ticks[g]:
            for src, dst in rd.pairs:
                pan_a[dst][rd.slot] += a_shard(src)[
                    :, rd.q * hv : (rd.q + 1) * hv
                ]
        for rd in b_ticks[g]:
            for src, dst in rd.pairs:
                pan_b[dst][rd.slot] += b_shard(src)[
                    rd.q * hv : (rd.q + 1) * hv, :
                ]
        for f in range(nproc):
            for i3 in range(topo.l_r):
                for j3 in range(topo.l_c):
                    t = j3 * topo.l_r + i3
                    parts[f][t] += pan_a[f][i3] @ pan_b[f][j3]

    def layer_of(f):
        i, j = divmod(f, p_c)
        return (j // s) * topo.l_r + (i // s)

    totals = [parts[f][layer_of(f)].copy() for f in range(nproc)]
    for d, perm in enumerate(c_rounds, start=1):
        for src, dst in perm:
            totals[dst] += parts[src][(layer_of(src) + d) % depth]

    c = np.zeros((n, n))
    for f in range(nproc):
        i, j = divmod(f, p_c)
        c[i * hr : (i + 1) * hr, j * hc : (j + 1) * hc] = totals[f]
    return c


@pytest.mark.parametrize(
    "pr,pc,l",
    [(2, 2, 1), (2, 4, 2), (4, 2, 2), (2, 2, 4), (4, 4, 4), (4, 4, 16),
     (3, 9, 3), (6, 2, 3), (6, 6, 9)],
)
def test_pull_plan_numpy_execution_exact(pr, pc, l):
    # invalid L falls back to 1 (Algorithm 2's rule), e.g. (6, 2): 6 > 2^2
    topo = make_topology(pr, pc, l)
    import math

    n = math.lcm(topo.v, pr, pc) * 2
    rng = np.random.default_rng(pr * 100 + pc * 10 + l)
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, n))
    c = _execute_pull_plan(topo, a, b)
    np.testing.assert_allclose(c, a @ b, rtol=1e-10, atol=1e-10)


# ---- depth resolution & validation -----------------------------------------


def test_resolve_l_rules():
    assert _resolve_l(2, 4, None) == 2  # forced mx/mn
    assert _resolve_l(4, 2, None) == 2
    assert _resolve_l(2, 8, None) == 1  # mx > mn^2 -> fallback
    assert _resolve_l(4, 4, None) == 1  # square default
    assert _resolve_l(4, 4, 4) == 4  # explicit override


def test_stacked_chunks_partition_virtual_range():
    """Uneven L: the per-layer chunks must still partition [0, V)."""
    for p, l in ((2, 4), (3, 2), (6, 4)):
        topo = Topology(p_r=p, p_c=p, l=l, l_r=1, l_c=l, side3d=p,
                        v=p, nbuffers_a=2, nbuffers_b=2)
        flat = []
        for li in range(l):
            lo, hi = topo.chunk(li)
            flat.extend(range(lo, hi))
        assert sorted(flat) == list(range(p))
        assert max(topo.layer_groups(li) for li in range(l)) == topo.ticks


def test_validate_blocks_errors():
    topo = make_topology(2, 4, 2)
    plan = plan_mod.MultiplyPlan(
        engine="twofive", kind="pull", mesh=None, axes=("r", "c"),
        p_r=2, p_c=4, topo=topo, ticks=topo.ticks,
    )
    plan.validate_blocks(8, 8)
    with pytest.raises(ValueError):
        plan.validate_blocks(6, 6)  # 6 % 4 != 0
    with pytest.raises(ValueError):
        plan.validate_blocks(10, 10)  # divides p_r but not V=4


def test_explicit_l_rejected_when_not_honored():
    """Engines with fixed depth (cannon/onesided/gather) and stacked meshes
    with a conflicting depth must reject an explicit ``l`` rather than
    silently ignoring it."""
    import jax

    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh2d = make_mesh((1, 1), ("r", "c"))
    for engine in ("cannon", "onesided", "gather"):
        with pytest.raises(ValueError, match="no depth parameter"):
            plan_mod.plan_multiply(mesh2d, engine, 2)
    mesh3d = make_mesh((1, 1, 1), ("l", "r", "c"))
    with pytest.raises(ValueError, match="conflicts with the mesh"):
        plan_mod.plan_multiply(mesh3d, "twofive", 4)


def test_scatter_layout_needs_stacked_mesh():
    topo = make_topology(2, 2, 1)
    plan = plan_mod.MultiplyPlan(
        engine="onesided", kind="pull", mesh=None, axes=("r", "c"),
        p_r=2, p_c=2, topo=topo, ticks=topo.ticks,
    )
    with pytest.raises(ValueError, match="stacked"):
        plan_mod.build_program(
            plan, threshold=0.0, backend="jnp", c_layout="scatter"
        )


# ---- program cache (single-device mesh: runs in the main test process) -----


def test_program_cache_hits_and_reuse():
    import jax

    from repro.core import bsm as B
    from repro.core.engine import multiply, multiply_reference

    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    a = B.random_bsm(jax.random.key(0), nb=4, bs=4, occupancy=0.6)
    b = B.random_bsm(jax.random.key(1), nb=4, bs=4, occupancy=0.6)
    ref = np.asarray(multiply_reference(a, b).to_dense())

    plan_mod.clear_cache()
    c1 = multiply(a, b, mesh, engine="twofive")
    s1 = plan_mod.cache_stats()
    c2 = multiply(a, b, mesh, engine="twofive")
    s2 = plan_mod.cache_stats()
    np.testing.assert_allclose(np.asarray(c1.to_dense()), ref, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(c2.to_dense()), ref, rtol=1e-5,
                               atol=1e-5)
    assert s1["misses"] == 1 and s1["builds"] == 1
    assert s2["builds"] == s1["builds"]  # second call: no re-build/lower
    assert s2["hits"] == s1["hits"] + 1
    # a different key (threshold) builds a distinct program
    multiply(a, b, mesh, engine="twofive", threshold=0.1)
    s3 = plan_mod.cache_stats()
    assert s3["builds"] == s2["builds"] + 1


# ---- transport in the program-cache key ------------------------------------


def test_get_compiled_requires_resolved_transport():
    """Mode strings must be resolved (plan.resolve_transport) BEFORE the
    program-cache key is formed — an auto decision baked into a key
    would alias distinct programs."""
    import jax

    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    with pytest.raises(TypeError, match="resolved PanelTransport"):
        plan_mod.get_compiled(mesh, "onesided", 4, 4, "float32",
                              transport="auto")


def test_build_shard_body_defaults_dense_transport():
    """Chain bodies (signiter) build with dense transport unless told
    otherwise — compressed capacities from an initial pattern are not
    chain-safe (the pattern evolves under the traced sweep)."""
    import jax

    from repro.core import transport as T

    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    plan = plan_mod.plan_multiply(mesh, "onesided")
    # None -> DENSE inside build_shard_body; an explicit PanelTransport
    # is honored (both bodies construct without error)
    plan_mod.build_shard_body(plan, threshold=0.0, backend="jnp")
    plan_mod.build_shard_body(
        plan, threshold=0.0, backend="jnp",
        transport=T.PanelTransport("compressed", 8, 8),
    )
