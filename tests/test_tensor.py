"""Blocked sparse tensor layer: matricization round-trips and the
einsum-style ``contract`` driver (DESIGN.md §10).

The load-bearing invariant is losslessness: ``unmatricize`` must invert
``matricize`` BIT-EXACTLY — blocks, mask and norms — for every ordered
index split, rectangular atomic blocks included, because the contraction
driver leans on the index map being a pure relabeling (no arithmetic, no
tolerance).  Semantics (does the matricized SpGEMM compute the einsum?)
are pinned against ``np.einsum`` on densified operands.

Multi-device coverage (all four engines, rectangular and uneven-L
meshes, sharded chaining) lives in ``tests/_dist.py::check_tensor``.
"""
from __future__ import annotations

from itertools import permutations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tensor as T
from repro.core.bsm import block_norms
from repro.launch.mesh import make_mesh


def _bit_equal(t1: T.BlockSparseTensor, t2: T.BlockSparseTensor) -> None:
    assert t1.blocks.shape == t2.blocks.shape
    assert np.array_equal(np.asarray(t1.blocks), np.asarray(t2.blocks))
    assert np.array_equal(np.asarray(t1.mask), np.asarray(t2.mask))
    assert np.array_equal(np.asarray(t1.norms), np.asarray(t2.norms))


# ---------------------------------------------------------------------------
# container
# ---------------------------------------------------------------------------


def test_make_tensor_zeroes_masked_blocks():
    key = jax.random.key(0)
    blocks = jax.random.normal(key, (2, 3, 2, 4, 5, 3))
    mask = np.zeros((2, 3, 2), bool)
    mask[0, 1, 1] = True
    t = T.make_tensor(blocks, jnp.asarray(mask))
    assert float(jnp.abs(t.blocks[1]).max()) == 0.0
    assert float(jnp.abs(t.blocks[0, 1, 1]).max()) > 0.0
    # norms recomputed from the zeroed data, f32
    ref = np.sqrt((np.asarray(t.blocks, np.float32) ** 2).sum(axis=(3, 4, 5)))
    np.testing.assert_allclose(np.asarray(t.norms), ref, rtol=1e-5, atol=1e-6)


def test_make_tensor_rank_check():
    with pytest.raises(ValueError, match="2x the mask's rank"):
        T.make_tensor(jnp.zeros((2, 2, 4, 4)), jnp.ones((2, 2, 2), bool))


def test_dense_roundtrip_rectangular_blocks():
    key = jax.random.key(1)
    dense = jax.random.normal(key, (6, 8, 10))
    t = T.from_dense_tensor(dense, (3, 2, 5))
    assert t.nbs == (2, 4, 2) and t.bss == (3, 2, 5)
    np.testing.assert_allclose(
        np.asarray(t.to_dense()), np.asarray(dense), rtol=1e-6
    )


def test_from_dense_shape_check():
    with pytest.raises(ValueError, match="not divisible"):
        T.from_dense_tensor(jnp.zeros((6, 7)), (3, 3))


def test_random_tensor_decay_keeps_diagonal():
    t = T.random_tensor(jax.random.key(2), (5, 5, 5), 4, occupancy=0.05)
    m = np.asarray(t.mask)
    assert m[np.arange(5), np.arange(5), np.arange(5)].all()
    assert 0.0 < m.mean() < 1.0


# ---------------------------------------------------------------------------
# matricization round-trips: bit-exact for EVERY ordered split
# ---------------------------------------------------------------------------


def test_matricize_roundtrip_all_ordered_splits_3d():
    t = T.random_tensor(jax.random.key(3), (2, 3, 4), (3, 2, 4),
                        occupancy=0.4)
    for perm in permutations(range(3)):
        for cut in (1, 2):
            rows, cols = perm[:cut], perm[cut:]
            m = T.matricize(t, rows, cols)
            assert m.blocks.shape == (
                int(np.prod([t.nbs[a] for a in rows])),
                int(np.prod([t.nbs[a] for a in cols])),
                int(np.prod([t.bss[a] for a in rows])),
                int(np.prod([t.bss[a] for a in cols])),
            )
            _bit_equal(t, T.unmatricize(m, rows, cols, t.nbs, t.bss))


def test_matricize_carries_mask_and_norms_exactly():
    t = T.random_tensor(jax.random.key(4), (3, 2, 2), (2, 5, 3),
                        occupancy=0.3)
    m = T.matricize(t, (2, 0), (1,))
    # occupancy is preserved (pure relabeling, no fill-in, no drops)
    assert int(np.asarray(m.mask).sum()) == int(np.asarray(t.mask).sum())
    # the carried norms ARE the Frobenius norms of the flattened blocks:
    # a reshape does not change a 2-norm
    np.testing.assert_allclose(
        np.asarray(m.norms), np.asarray(block_norms(m.blocks)),
        rtol=1e-5, atol=1e-6,
    )


def test_unmatricize_shape_mismatch_is_loud():
    t = T.random_tensor(jax.random.key(5), (2, 2, 2), 3, occupancy=0.5)
    m = T.matricize(t, (0, 1), (2,))
    with pytest.raises(ValueError, match="do not fold"):
        T.unmatricize(m, (0,), (1, 2), t.nbs, t.bss)


def test_matricize_split_validation():
    t = T.random_tensor(jax.random.key(6), (2, 2), 2, occupancy=1.0)
    with pytest.raises(ValueError, match="at least one index"):
        T.matricize(t, (0, 1), ())
    with pytest.raises(ValueError, match="partition"):
        T.matricize(t, (0,), (0,))


NBS_POOL = (2, 3, 4, 2)
BSS_RECT = (3, 2, 4, 5)


@settings(deadline=None, max_examples=40)
@given(
    ndim=st.integers(min_value=2, max_value=4),
    cut=st.integers(min_value=1, max_value=3),
    reverse=st.booleans(),
    occupancy=st.floats(min_value=0.0, max_value=1.0),
    rect=st.booleans(),
    seed=st.integers(min_value=0, max_value=7),
)
def test_matricize_roundtrip_property(ndim, cut, reverse, occupancy,
                                      rect, seed):
    """matricize ∘ unmatricize == id, bit-exact: every rank 2..4, every
    cut point, reversed (non-natural) axis orders, rectangular atomic
    blocks, and the occupancy extremes (all-empty / all-full included)."""
    cut = min(cut, ndim - 1)
    nbs = NBS_POOL[:ndim]
    bss = BSS_RECT[:ndim] if rect else (3,) * ndim
    t = T.random_tensor(jax.random.key(seed), nbs, bss,
                        occupancy=occupancy)
    axes = tuple(range(ndim))
    if reverse:
        axes = axes[::-1]
    rows, cols = axes[:cut], axes[cut:]
    m = T.matricize(t, rows, cols)
    _bit_equal(t, T.unmatricize(m, rows, cols, t.nbs, t.bss))


# ---------------------------------------------------------------------------
# contract: semantics vs np.einsum (single device, mesh=None)
# ---------------------------------------------------------------------------


def _pair(seed: int = 7, nb: int = 3, bs: int = 4):
    t = T.random_tensor(jax.random.key(seed), (nb, nb, nb), bs,
                        occupancy=0.3)
    m = T.random_tensor(jax.random.key(seed + 1), (nb, nb), bs,
                        occupancy=0.6)
    return t, m


def _check_contract(spec: str, *ops, **kw):
    got = T.contract(spec, *ops, **kw)
    ref = T.contract_reference(spec, *ops)
    np.testing.assert_allclose(
        np.asarray(got.to_dense()), ref, rtol=1e-4, atol=1e-4
    )
    return got


def test_contract_three_center_single_device():
    t, m = _pair()
    out = _check_contract("ijk,kl->ijl", t, m)
    assert out.nbs == (3, 3, 3) and out.bss == (4, 4, 4)


def test_contract_permuted_output():
    # non-natural output order: replicated path transposes after folding
    t, m = _pair(seed=9)
    _check_contract("ijk,kl->lij", t, m)


def test_contract_multi_index_contraction():
    # two indices contracted at once: (ij|k) with itself over (j, k)
    t, _ = _pair(seed=11)
    t2 = T.random_tensor(jax.random.key(20), (3, 3, 3), 4, occupancy=0.3)
    _check_contract("ijk,mjk->im", t, t2)


def test_contract_rectangular_blocks():
    t = T.random_tensor(jax.random.key(12), (2, 3, 4), (3, 2, 4),
                        occupancy=0.5)
    m = T.random_tensor(jax.random.key(13), (4, 3), (4, 5), occupancy=0.7)
    out = _check_contract("ijk,kl->ijl", t, m)
    assert out.bss == (3, 2, 5)


def test_contract_chain_three_operands():
    t, m = _pair(seed=15)
    m2 = T.random_tensor(jax.random.key(16), (3, 3), 4, occupancy=0.6)
    _check_contract("ijk,kl,lm->ijm", t, m, m2)


def test_contract_threshold_filters():
    t, m = _pair(seed=17)
    exact = T.contract("ijk,kl->ijl", t, m)
    loose = T.contract("ijk,kl->ijl", t, m, threshold=1e6)
    assert int(np.asarray(loose.mask).sum()) < int(np.asarray(exact.mask).sum())


# ---------------------------------------------------------------------------
# loud rejections: everything outside the matricized-SpGEMM model
# ---------------------------------------------------------------------------


def test_contract_requires_explicit_output():
    t, m = _pair()
    with pytest.raises(ValueError, match="->"):
        T.contract("ijk,kl", t, m)


def test_contract_rejects_traces():
    t, m = _pair()
    with pytest.raises(ValueError, match="trace"):
        T.contract("iik,kl->il", t, m)


def test_contract_rejects_batch_dims():
    t, m = _pair()
    with pytest.raises(NotImplementedError, match="batch"):
        T.contract("ijk,kl->ijkl", t, m)


def test_contract_rejects_outer_products():
    a = T.random_tensor(jax.random.key(21), (2, 2), 3, occupancy=1.0)
    b = T.random_tensor(jax.random.key(22), (2, 2), 3, occupancy=1.0)
    with pytest.raises(ValueError, match="outer"):
        T.contract("ij,kl->ijkl", a, b)


def test_contract_rejects_full_inner_products():
    a = T.random_tensor(jax.random.key(23), (2, 2), 3, occupancy=1.0)
    b = T.random_tensor(jax.random.key(24), (2, 2), 3, occupancy=1.0)
    with pytest.raises(ValueError, match="no free index"):
        T.contract("ij,ij->", a, b)


def test_contract_rejects_stray_output_index():
    t, m = _pair()
    with pytest.raises(ValueError, match="appears in no operand"):
        T.contract("ijk,kl->ijz", t, m)


def test_contract_rejects_contracted_dim_mismatch():
    t = T.random_tensor(jax.random.key(25), (2, 2, 3), 4, occupancy=1.0)
    m = T.random_tensor(jax.random.key(26), (2, 2), 4, occupancy=1.0)
    with pytest.raises(ValueError, match="disagrees"):
        T.contract("ijk,kl->ijl", t, m)


def test_contract_needs_two_operands():
    t, _ = _pair()
    with pytest.raises(ValueError):
        T.contract("ijk->ijk", t)


def test_contract_rejects_foreign_operands():
    t, m = _pair()
    with pytest.raises(TypeError, match="BlockSparseTensor"):
        T.contract("ijk,kl->ijl", t, np.zeros((12, 12)))


def test_rectangular_product_rejects_assignment():
    """Satellite of the non-square plumbing: symmetric block→device
    permutations have no meaning on a rectangular block grid, so the
    plan layer must refuse them LOUDLY (never silently corrupt)."""
    from repro.core import plan as plan_mod
    from repro.core.distribute import Assignment

    mesh = make_mesh((1, 1), ("r", "c"), devices=jax.devices()[:1])
    asg = Assignment("nnz_greedy", perm=(1, 0))
    with pytest.raises(ValueError, match="symmetric"):
        plan_mod.get_compiled(
            mesh, "gather", 2, 4, jnp.float32,
            assignment=asg, nb_k=4, nb_c=2, bs_k=4, bs_c=4,
        )
