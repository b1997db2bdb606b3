"""Block-sparse matrix format: invariants and semantics (paper section 2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bsm as B
from repro.launch.mesh import make_mesh


def test_to_dense_roundtrip():
    key = jax.random.key(0)
    m = B.random_bsm(key, nb=6, bs=4, occupancy=0.5)
    d = m.to_dense()
    m2 = B.from_dense(d, bs=4)
    np.testing.assert_allclose(np.asarray(m2.to_dense()), np.asarray(d), rtol=1e-6)


def test_from_dense_shape_check():
    with pytest.raises(ValueError):
        B.from_dense(jnp.zeros((10, 10)), bs=4)


def test_mask_zeroes_blocks():
    key = jax.random.key(1)
    blocks = jax.random.normal(key, (4, 4, 3, 3))
    mask = jnp.zeros((4, 4), bool).at[0, 0].set(True)
    m = B.make_bsm(blocks, mask)
    # masked-out blocks must be exactly zero (consistency of the triple)
    dense = np.asarray(m.to_dense())
    assert np.all(dense[3:, :] == 0)
    assert np.any(dense[:3, :3] != 0)
    assert float(m.occupancy()) == pytest.approx(1 / 16)


def test_norms_consistent_with_blocks():
    key = jax.random.key(2)
    m = B.random_bsm(key, nb=5, bs=4, occupancy=0.4)
    ref = np.linalg.norm(
        np.asarray(m.blocks, np.float32), axis=(2, 3)
    )
    np.testing.assert_allclose(np.asarray(m.norms), ref, rtol=1e-5, atol=1e-6)


def test_filter_bsm_drops_small_blocks():
    key = jax.random.key(3)
    m = B.random_bsm(key, nb=6, bs=4, occupancy=1.0, pattern="dense")
    scaled = B.BlockSparseMatrix(
        blocks=m.blocks.at[0, 1].mul(1e-8),
        mask=m.mask,
        norms=B.block_norms(m.blocks.at[0, 1].mul(1e-8)),
    )
    f = B.filter_bsm(scaled, threshold=1e-4)
    assert not bool(f.mask[0, 1])
    assert bool(f.mask[0, 0])
    # filtered block data is zeroed, not just masked
    assert float(jnp.abs(f.blocks[0, 1]).max()) == 0.0


def test_identity_multiplicative():
    from repro.core.engine import multiply_reference

    key = jax.random.key(4)
    m = B.random_bsm(key, nb=4, bs=8, occupancy=0.5)
    eye = B.identity(4, 8)
    out = multiply_reference(m, eye)
    np.testing.assert_allclose(
        np.asarray(out.to_dense()), np.asarray(m.to_dense()), rtol=1e-5, atol=1e-5
    )


def test_add_scale():
    key = jax.random.key(5)
    a = B.random_bsm(key, nb=4, bs=4, occupancy=0.4)
    b = B.random_bsm(jax.random.key(6), nb=4, bs=4, occupancy=0.4)
    s = B.add(B.scale(a, 2.0), b)
    np.testing.assert_allclose(
        np.asarray(s.to_dense()),
        2.0 * np.asarray(a.to_dense()) + np.asarray(b.to_dense()),
        rtol=1e-5,
        atol=1e-5,
    )


def test_permutation_preserves_content():
    key = jax.random.key(7)
    m = B.random_bsm(key, nb=6, bs=4, occupancy=0.5)
    perm = B.random_load_balance_permutation(jax.random.key(8), 6)
    p = B.permute(m, perm, perm)
    # permuting block rows/cols == permuting dense rows/cols blockwise
    dense = np.asarray(m.to_dense()).reshape(6, 4, 6, 4)
    expect = dense[perm][:, :, perm].reshape(24, 24)
    np.testing.assert_allclose(np.asarray(p.to_dense()), expect, rtol=1e-6)


def test_grid_block_loads_balance():
    """The paper's randomized permutation evens out per-panel block loads."""
    rng = np.random.default_rng(0)
    nb = 64
    # adversarial pattern: all blocks in the top rows
    mask = np.zeros((nb, nb), bool)
    mask[:16, :] = True
    loads_before = B.grid_block_loads(mask, 4, 4)
    perm = rng.permutation(nb)
    loads_after = B.grid_block_loads(mask[perm][:, perm], 4, 4)
    assert loads_before.max() - loads_before.min() == 256  # fully unbalanced
    assert loads_after.std() < loads_before.std()


# ---------------------------------------------------------------------------
# rectangular atomic blocks in from_dense / identity (PR 2 made them
# first-class in the engines; the constructors must accept them too)
# ---------------------------------------------------------------------------


def test_from_dense_rectangular_blocks():
    dense = jnp.asarray(np.arange(8 * 6, dtype=np.float32).reshape(8, 6))
    m = B.from_dense(dense, (4, 2))
    assert (m.nb_r, m.nb_c, m.bs_r, m.bs_c) == (2, 3, 4, 2)
    np.testing.assert_allclose(np.asarray(m.to_dense()), np.asarray(dense))
    # int spec still means square
    m2 = B.from_dense(jnp.zeros((8, 8)), 4)
    assert (m2.bs_r, m2.bs_c) == (4, 4)
    with pytest.raises(ValueError):
        B.from_dense(dense, (4, 4))  # 6 % 4 != 0


def test_identity_rectangular_blocks():
    i = B.identity(3, (4, 2))
    assert i.blocks.shape == (3, 6, 4, 2)
    np.testing.assert_allclose(np.asarray(i.to_dense()), np.eye(12))
    # tuple spec with equal sides == the square fast path
    np.testing.assert_allclose(
        np.asarray(B.identity(3, (4, 4)).to_dense()),
        np.asarray(B.identity(3, 4).to_dense()),
    )
    with pytest.raises(ValueError):
        B.identity(3, (4, 5))  # 12 % 5 != 0


def test_identity_rectangular_multiplicative():
    from repro.core.engine import multiply_reference

    key = jax.random.key(9)
    blocks = jax.random.normal(key, (3, 3, 2, 4))  # rectangular A blocks
    a = B.make_bsm(blocks, jnp.ones((3, 3), bool))
    eye = B.identity(3, (4, 4))
    out = multiply_reference(a, eye)
    np.testing.assert_allclose(
        np.asarray(out.to_dense()), np.asarray(a.to_dense()),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# derived norms: filter / scale / add never go through make_bsm recompute
# ---------------------------------------------------------------------------


def test_derived_norms_match_make_bsm():
    m = B.random_bsm(jax.random.key(10), nb=6, bs=4, occupancy=0.6)
    thr = float(np.median(np.asarray(m.norms)[np.asarray(m.mask)]))
    for got, want in (
        (B.filter_bsm(m, thr), B.make_bsm(m.blocks, m.mask & (m.norms > thr))),
        (B.scale(m, -2.5), B.make_bsm(m.blocks * -2.5, m.mask)),
    ):
        np.testing.assert_array_equal(np.asarray(got.mask), np.asarray(want.mask))
        np.testing.assert_allclose(np.asarray(got.norms), np.asarray(want.norms),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(got.blocks), np.asarray(want.blocks),
                                   rtol=1e-6, atol=1e-7)
    # axpy == scale + add
    y = B.random_bsm(jax.random.key(11), nb=6, bs=4, occupancy=0.3)
    got = B.axpy(3.0, m, y)
    want = B.add(B.scale(m, 3.0), y)
    np.testing.assert_allclose(np.asarray(got.to_dense()),
                               np.asarray(want.to_dense()), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.norms), np.asarray(want.norms),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# ShardedBSM: device-resident container + shard-local algebra
# ---------------------------------------------------------------------------


def _mesh11():
    return make_mesh((1, 1), ("r", "c"))


def test_sharded_bsm_roundtrip_and_algebra():
    mesh = _mesh11()
    a = B.random_bsm(jax.random.key(12), nb=4, bs=4, occupancy=0.5)
    b = B.random_bsm(jax.random.key(13), nb=4, bs=4, occupancy=0.5)
    sa, sb = B.shard_bsm(a, mesh), B.shard_bsm(b, mesh)
    # round trip
    np.testing.assert_allclose(np.asarray(B.unshard_bsm(sa).to_dense()),
                               np.asarray(a.to_dense()))
    assert B.shard_bsm(sa, mesh) is sa  # idempotent
    # algebra parity with the replicated ops, including derived norms
    pairs = [
        (sa.add(sb), B.add(a, b)),
        (sa.scale(-0.5), B.scale(a, -0.5)),
        (sa.axpy(2.0, sb), B.axpy(2.0, a, b)),
    ]
    thr = float(np.median(np.asarray(a.norms)[np.asarray(a.mask)]))
    pairs.append((sa.filter(thr), B.filter_bsm(a, thr)))
    for got, want in pairs:
        assert isinstance(got, B.ShardedBSM)
        np.testing.assert_allclose(np.asarray(got.to_dense()),
                                   np.asarray(want.to_dense()),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(got.unshard().norms),
                                   np.asarray(want.norms),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(sa.frobenius_norm()),
                               float(a.frobenius_norm()), rtol=1e-6)
    from repro.core.signiter import trace

    np.testing.assert_allclose(float(sa.trace()), float(trace(a)), rtol=1e-5)
    assert float(sa.occupancy()) == pytest.approx(float(a.occupancy()))


def test_sharded_bsm_identity_and_errors():
    mesh = _mesh11()
    i = B.sharded_identity(4, 4, mesh)
    assert isinstance(i, B.ShardedBSM)
    np.testing.assert_allclose(np.asarray(i.to_dense()), np.eye(16))
    m = B.random_bsm(jax.random.key(14), nb=5, bs=2, occupancy=0.5)
    with pytest.raises(ValueError):
        B.shard_bsm(m, make_mesh((1,), ("r",)))  # no 'c' axis


def test_sharded_multiply_reference_parity():
    from repro.core.engine import multiply, multiply_reference

    mesh = _mesh11()
    a = B.random_bsm(jax.random.key(15), nb=4, bs=4, occupancy=0.5)
    b = B.random_bsm(jax.random.key(16), nb=4, bs=4, occupancy=0.5)
    ref = multiply_reference(a, b, threshold=1e-3)
    c = multiply(B.shard_bsm(a, mesh), B.shard_bsm(b, mesh),
                 engine="onesided", threshold=1e-3, filter_eps=1e-3)
    assert isinstance(c, B.ShardedBSM)
    want = B.filter_bsm(ref, 1e-3)
    np.testing.assert_allclose(np.asarray(c.to_dense()),
                               np.asarray(want.to_dense()),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(TypeError):
        multiply(B.shard_bsm(a, mesh), b)  # mixed operands


@settings(max_examples=20, deadline=None)
@given(
    nb=st.integers(2, 8),
    bs=st.sampled_from([1, 2, 4]),
    occ=st.floats(0.05, 1.0),
)
def test_property_occupancy_and_diag(nb, bs, occ):
    m = B.random_bsm(jax.random.key(42), nb=nb, bs=bs, occupancy=occ)
    # diagonal always occupied (operators have dominant diagonal)
    assert bool(jnp.all(jnp.diag(m.mask)))
    assert 0.0 < float(m.occupancy()) <= 1.0
    # norms zero exactly where mask is False
    off = np.asarray(m.norms)[~np.asarray(m.mask)]
    assert np.all(off == 0.0)
