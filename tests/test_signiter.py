"""Matrix-sign iteration — the paper's driving application (Eqs. (1)-(3))."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bsm as B
from repro.core.signiter import density_matrix, sign_iteration, trace


def _sym_bsm(key, nb=4, bs=8, occupancy=0.6):
    return B.random_bsm(key, nb=nb, bs=bs, occupancy=occupancy,
                        pattern="banded", symmetric=True)


def test_sign_converges_and_is_involutory():
    m = _sym_bsm(jax.random.key(0))
    s, stats = sign_iteration(m, max_iter=80, tol=1e-6)
    assert stats.converged, stats
    dense = np.asarray(s.to_dense(), np.float64)
    # sign(A)^2 == I
    np.testing.assert_allclose(dense @ dense, np.eye(dense.shape[0]), atol=5e-4)


def test_sign_matches_eigendecomposition():
    m = _sym_bsm(jax.random.key(1))
    dense = np.asarray(m.to_dense(), np.float64)
    w, v = np.linalg.eigh(dense)
    want = v @ np.diag(np.sign(w)) @ v.T
    s, stats = sign_iteration(m, max_iter=100, tol=1e-6)
    assert stats.converged
    np.testing.assert_allclose(np.asarray(s.to_dense(), np.float64), want, atol=1e-3)


def test_density_matrix_counts_states():
    """trace(P) == number of eigenvalues below mu (paper Eq. (1) observable)."""
    m = _sym_bsm(jax.random.key(2), nb=4, bs=6)
    dense = np.asarray(m.to_dense(), np.float64)
    w = np.linalg.eigvalsh(dense)
    mu = float(np.median(w)) + 1e-3
    p, stats = density_matrix(m, mu, max_iter=100, tol=1e-6)
    assert stats.converged
    n_occ = int((w < mu).sum())
    assert float(trace(p)) == pytest.approx(n_occ, abs=1e-2)
    # P idempotent (a projector)
    pd = np.asarray(p.to_dense(), np.float64)
    np.testing.assert_allclose(pd @ pd, pd, atol=1e-3)


def test_filtering_keeps_convergence():
    """With on-the-fly + post filtering the iteration still converges and
    the result stays close to the unfiltered one (the paper's premise that
    filtered SpGEMM preserves the physics)."""
    m = _sym_bsm(jax.random.key(3), nb=6, bs=6, occupancy=0.4)
    s_exact, st_exact = sign_iteration(m, max_iter=100, tol=1e-6)
    s_filt, st_filt = sign_iteration(
        m, threshold=1e-7, filter_eps=1e-6, max_iter=100, tol=1e-6
    )
    assert st_exact.converged and st_filt.converged
    err = np.abs(
        np.asarray(s_exact.to_dense(), np.float64)
        - np.asarray(s_filt.to_dense(), np.float64)
    ).max()
    assert err < 1e-3
    # filtering keeps occupancy at or below the unfiltered trajectory end
    assert st_filt.occupancy_trace[-1] <= 1.0


def test_two_multiplications_per_iteration():
    """Paper: 'two multiplications per iteration' (Eq. (3))."""
    m = _sym_bsm(jax.random.key(4))
    _, stats = sign_iteration(m, max_iter=7, tol=0.0)
    assert stats.multiplications == 2 * stats.iterations


# ---------------------------------------------------------------------------
# fused device-resident sweep vs the legacy per-op loop (DESIGN.md §5)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "stacks"])
@pytest.mark.parametrize("thr,eps", [(0.0, 0.0), (1e-7, 1e-6), (1e-4, 1e-4)])
def test_fused_matches_legacy(backend, thr, eps):
    """Same residual trace, occupancy trace and converged X to 1e-5."""
    m = _sym_bsm(jax.random.key(5), nb=4, bs=6, occupancy=0.5)
    s_leg, st_leg = sign_iteration(
        m, threshold=thr, filter_eps=eps, max_iter=80, tol=1e-6,
        mode="legacy")
    s_fus, st_fus = sign_iteration(
        m, threshold=thr, filter_eps=eps, max_iter=80, tol=1e-6,
        mode="fused", backend=backend)
    assert st_leg.converged and st_fus.converged
    assert st_fus.iterations == st_leg.iterations
    assert st_fus.multiplications == st_leg.multiplications
    np.testing.assert_allclose(
        st_fus.residual_trace, st_leg.residual_trace, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(
        st_fus.occupancy_trace, st_leg.occupancy_trace, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(s_fus.to_dense()), np.asarray(s_leg.to_dense()),
        rtol=1e-5, atol=1e-5)


def test_sync_every_converges_to_same_sign():
    """sync_every > 1 trades host syncs for (at most k-1) extra polishing
    sweeps; the converged sign matrix is unchanged."""
    m = _sym_bsm(jax.random.key(6))
    s1, st1 = sign_iteration(m, max_iter=80, tol=1e-6, sync_every=1)
    s5, st5 = sign_iteration(m, max_iter=80, tol=1e-6, sync_every=5)
    assert st1.converged and st5.converged
    assert st1.iterations <= st5.iterations <= st1.iterations + 4
    assert st5.host_syncs <= -(-st5.iterations // 5) + 1
    assert st5.host_syncs < st5.iterations
    # traces are complete despite the batched syncs
    assert len(st5.residual_trace) == st5.iterations
    np.testing.assert_allclose(
        np.asarray(s5.to_dense()), np.asarray(s1.to_dense()), atol=1e-5)


def test_fused_density_matrix_counts_states():
    m = _sym_bsm(jax.random.key(7), nb=4, bs=6)
    dense = np.asarray(m.to_dense(), np.float64)
    w = np.linalg.eigvalsh(dense)
    mu = float(np.median(w)) + 1e-3
    p, stats = density_matrix(m, mu, max_iter=100, tol=1e-6,
                              mode="fused", sync_every=3)
    assert stats.converged and stats.mode == "fused"
    assert float(trace(p)) == pytest.approx(int((w < mu).sum()), abs=1e-2)


def test_pattern_cache_rehits_on_evolving_x():
    """Per-chain pattern counters: the legacy/compacted path walks X's
    concrete pattern every multiply; as the iteration's sparsity structure
    stabilizes, the walks become pattern-cache re-hits (and the capacity
    buckets keep the compiled-program count far below the multiply
    count)."""
    from repro.core import plan as plan_mod

    m = _sym_bsm(jax.random.key(9), nb=4, bs=6, occupancy=0.5)
    plan_mod.clear_cache()
    _, st = sign_iteration(m, threshold=1e-6, filter_eps=1e-6, max_iter=80,
                           tol=1e-6, mode="legacy", backend="stacks")
    stats = plan_mod.cache_stats()
    assert st.converged
    # every multiply compacted a pattern; most were repeats of an earlier
    # sweep's structure
    walks = stats["pattern_hits"] + stats["pattern_misses"]
    assert walks >= st.multiplications, (stats, st.multiplications)
    assert stats["pattern_hits"] > st.multiplications // 2, (
        stats, st.multiplications)
    # capacity bucketing: far fewer compiled local programs than multiplies
    assert stats["builds"] < st.multiplications // 2, stats


def test_fused_rejects_bad_args():
    m = _sym_bsm(jax.random.key(8))
    with pytest.raises(ValueError):
        sign_iteration(m, mode="turbo")
    with pytest.raises(ValueError):
        sign_iteration(m, sync_every=0)


def test_sign_iteration_storage_dtype_matrix():
    """The CI dtype matrix leg (REPRO_STORAGE_DTYPE): purification runs
    end-to-end at the configured storage dtype and lands within that
    dtype's documented tolerance of the f32 oracle (DESIGN.md §2 —
    bf16 blocks, f32 accumulation, norms recalibrated after the cast)."""
    from repro.config import storage_dtype

    dt = storage_dtype()
    m = _sym_bsm(jax.random.key(4))
    s32, _ = sign_iteration(m, max_iter=80, tol=1e-6)
    tol = {"float32": 1e-6, "bfloat16": 1e-2}[dt]
    s, st = sign_iteration(m, storage_dtype=dt, max_iter=80, tol=max(tol, 1e-6))
    assert st.converged, st
    assert s.blocks.dtype == jnp.dtype(dt)
    err = np.abs(np.asarray(s.to_dense(), np.float64)
                 - np.asarray(s32.to_dense(), np.float64)).max()
    assert err <= {"float32": 1e-5, "bfloat16": 7e-2}[dt], (dt, err)


# ---------------------------------------------------------------------------
# block-product counts of the fused sweep
# ---------------------------------------------------------------------------


def banded_hamiltonian(nb: int = 16, bs: int = 4, seed: int = 0):
    """A symmetric block-tridiagonal H: X fills in by one band per
    multiply, so a short chain stays sparse."""
    rng = np.random.default_rng(seed)
    idx = np.arange(nb)
    mask = np.abs(idx[:, None] - idx[None, :]) <= 1
    dense = rng.standard_normal((nb * bs, nb * bs)).astype(np.float32)
    dense = dense + dense.T
    blocks = dense.reshape(nb, bs, nb, bs).transpose(0, 2, 1, 3)
    return B.make_bsm(jnp.asarray(blocks), jnp.asarray(mask)), mask


def mask_chain_products(mask, sweeps: int) -> list[tuple[int, int]]:
    """(X.X, X.Y) block products with both blocks present, per sweep, of
    an unfiltered chain from X's mask alone (``benchlib.work``'s count:
    sum_k colcount_A(k) * rowcount_B(k))."""
    def present(a, b):
        return int(a.sum(axis=0, dtype=np.int64) @ b.sum(axis=1,
                                                       dtype=np.int64))

    x = np.asarray(mask, bool)
    eye = np.eye(x.shape[0], dtype=bool)
    out = []
    for _ in range(sweeps):
        x2 = (x.astype(np.int64) @ x.astype(np.int64)) > 0
        y = eye | x2
        out.append((present(x, x), present(x, y)))
        x = (x.astype(np.int64) @ y.astype(np.int64)) > 0
    return out


def test_sweep_counts_products_present_exactly():
    """The fused sweep's ``products_present`` equals the count from the
    operand masks for every multiply, repeats exactly, and takes no host
    sync of its own; ``products_computed`` is the jnp backend's cube."""
    h, mask = banded_hamiltonian()
    runs = [sign_iteration(h, max_iter=5, tol=0.0, sync_every=2)[1]
            for _ in range(2)]
    st = runs[0]
    assert st.products_present == mask_chain_products(mask, 5)
    assert runs[1].products_present == st.products_present
    assert st.products_computed == 16 ** 3
    assert st.host_syncs == 3  # sweeps 2, 4 and the last: unchanged
    assert len(st.residual_trace) == len(st.products_present) == 5


def test_chain_span_carries_the_counts():
    from repro import obs

    h, mask = banded_hamiltonian()
    _, st = sign_iteration(h, max_iter=3, tol=0.0, sync_every=2)
    chain = [r for r in obs.records() if r.name == "signiter.chain"][-1]
    kids = [r for r in obs.records() if r.parent == chain.id]
    assert chain.counts == {
        "sweeps": 3, "host_syncs": 2,
        "products_present": sum(map(sum, mask_chain_products(mask, 3))),
        "products_computed": 2 * 3 * 16 ** 3, "block_flops": 2 * 4 ** 3,
    }
    assert [r.name for r in kids].count("signiter.dispatch") == 3
    assert [r.name for r in kids].count("signiter.sync") == 2


def test_sweep_hlo_names_its_layers():
    """The compiled sweep's op metadata names the layer of each op: the
    local stage's dot under ``spgemm.local``, the engine's panel permutes
    under ``spgemm.transport``."""
    from repro.core.signiter import lower_sweep
    from repro.launch.mesh import make_spgemm_mesh

    hlo = lower_sweep(make_spgemm_mesh(p=1), 8, 4).compile().as_text()
    lines = hlo.splitlines()
    dots = [ln for ln in lines if " dot(" in ln]
    permutes = [ln for ln in lines if "collective-permute" in ln
                and "op_name=" in ln]
    assert dots and all("spgemm.local" in ln for ln in dots)
    assert permutes and all("spgemm.transport" in ln for ln in permutes)
    assert "spgemm.engine" in hlo
    assert "signiter.residual" in hlo
