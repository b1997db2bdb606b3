"""Local-stage backends: jnp dense vs stacks vs pallas (interpret).

Property tests (hypothesis; conftest fallback shim when absent) assert all
backends agree with the dense reference across occupancy, threshold and
dtype — including the empty-product-list edge case and rectangular atomic
blocks — plus the acceptance checks of the compaction PR: measured
surviving-product FLOPs and pattern-signature cache hits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import plan as plan_mod
from repro.core.bsm import random_bsm
from repro.core.engine import (
    AUTO_DENSE_FILL,
    choose_backend,
    multiply_reference,
)
from repro.core.local_mm import local_filtered_mm, pair_filter, stacks_mm
from repro.kernels.stacks import (
    bucket_capacity,
    compact_pair_mask,
    pattern_signature,
    product_count,
)
from repro.roofline.hlo_cost import (
    spgemm_stacks_flops,
    xla_cost_analysis,
)

BACKENDS = ("jnp", "stacks", "pallas")


def _mats(key, ni, nk, nj, bs_r, bs_k, bs_c, occupancy, dtype):
    k1, k2, k3, k4 = jax.random.split(jax.random.key(key), 4)
    # divide before the cast: a NumPy f64 scalar would silently promote
    # bf16 operands back to f32 under JAX's promotion rules
    ab = (jax.random.normal(k1, (ni, nk, bs_r, bs_k))
          / np.sqrt(bs_k)).astype(dtype)
    bb = (jax.random.normal(k2, (nk, nj, bs_k, bs_c))
          / np.sqrt(bs_k)).astype(dtype)
    am = jax.random.bernoulli(k3, occupancy, (ni, nk))
    bm = jax.random.bernoulli(k4, occupancy, (nk, nj))
    ab = ab * am[:, :, None, None].astype(dtype)
    bb = bb * bm[:, :, None, None].astype(dtype)
    an = jnp.sqrt(jnp.sum(jnp.square(ab.astype(jnp.float32)), axis=(2, 3)))
    bn = jnp.sqrt(jnp.sum(jnp.square(bb.astype(jnp.float32)), axis=(2, 3)))
    return ab, am, an, bb, bm, bn


@settings(max_examples=12, deadline=None)
@given(
    occupancy=st.sampled_from([0.0, 0.05, 0.3, 1.0]),
    threshold=st.sampled_from([0.0, 0.05]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_backends_agree_with_dense_reference(occupancy, threshold, dtype):
    dt = jnp.dtype(dtype)
    args = _mats(42, 5, 6, 4, 8, 8, 8, occupancy, dt)
    want, want_m = local_filtered_mm(*args, threshold=threshold, backend="jnp")
    tol = 1e-5 if dt == jnp.float32 else 3e-2
    for backend in ("stacks", "pallas"):
        got, got_m = local_filtered_mm(
            *args, threshold=threshold, backend=backend
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(want, np.float32),
            rtol=tol,
            atol=tol,
        )
        assert bool(jnp.all(got_m == want_m))


@settings(max_examples=6, deadline=None)
@given(capacity=st.sampled_from([8, 64, 1024]))
def test_tight_capacity_matches(capacity):
    """An exact (or generous) static capacity changes nothing numerically."""
    args = _mats(7, 4, 4, 4, 8, 8, 8, 0.3, jnp.float32)
    ok = pair_filter(args[1], args[2], args[4], args[5], 0.0)
    n = int(np.asarray(ok).sum())
    cap = max(capacity, bucket_capacity(n))  # sound: never below the count
    want, _ = local_filtered_mm(*args, backend="jnp")
    for backend in ("stacks", "pallas"):
        got, _ = local_filtered_mm(*args, backend=backend, stack_capacity=cap)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )


def test_empty_product_list():
    """occupancy 0 -> zero capacity, zero C, empty mask, on every backend."""
    args = _mats(3, 3, 4, 2, 8, 8, 8, 0.0, jnp.float32)
    for backend in BACKENDS:
        cb, cm = local_filtered_mm(*args, backend=backend)
        assert float(jnp.abs(cb).max()) == 0.0
        assert not bool(jnp.any(cm))
    # compacted with explicit capacity 0
    cb, cm = local_filtered_mm(*args, backend="stacks", stack_capacity=0)
    assert float(jnp.abs(cb).max()) == 0.0
    # threshold filters *everything* out despite full occupancy
    full = _mats(4, 3, 3, 3, 8, 8, 8, 1.0, jnp.float32)
    for backend in BACKENDS:
        cb, cm = local_filtered_mm(*full, threshold=1e9, backend=backend)
        assert float(jnp.abs(cb).max()) == 0.0
        assert not bool(jnp.any(cm))


@settings(max_examples=8, deadline=None)
@given(
    bs_r=st.sampled_from([4, 8]),
    bs_k=st.sampled_from([8, 16]),
    bs_c=st.sampled_from([4, 16]),
)
def test_rectangular_atomic_blocks(bs_r, bs_k, bs_c):
    """bs_r != bs_k != bs_c end-to-end through every backend."""
    args = _mats(11, 3, 5, 2, bs_r, bs_k, bs_c, 0.4, jnp.float32)
    want, want_m = local_filtered_mm(*args, threshold=0.01, backend="jnp")
    assert want.shape == (3, 2, bs_r, bs_c)
    for backend in ("stacks", "pallas"):
        got, got_m = local_filtered_mm(*args, threshold=0.01, backend=backend)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )
        assert bool(jnp.all(got_m == want_m))


# ---------------------------------------------------------------------------
# mixed precision (satellite: backend x dtype x occupancy x block shape
# against the kernels.ref mixed-precision oracle)
# ---------------------------------------------------------------------------


from repro.kernels import ref as kref  # noqa: E402

# documented tolerances vs the f32-accumulating oracle (see the
# ``kernels.ref.block_spgemm_ref`` docstring): all backends accumulate in
# f32, so the error is operand + output rounding at the storage width
_DTYPE_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


@settings(max_examples=16, deadline=None)
@given(
    occupancy=st.sampled_from([0.0, 0.2, 0.7]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    shape=st.sampled_from([(8, 8, 8), (4, 16, 8), (8, 16, 4)]),
    backend=st.sampled_from(["jnp", "stacks", "pallas"]),
)
def test_mixed_precision_matches_ref_oracle(occupancy, dtype, shape, backend):
    """Every backend, at every storage dtype, over rectangular blocks and
    the occupancy range, lands within the documented tolerance of the
    mixed-precision oracle (quantized operands, f32 HIGHEST einsum)."""
    bs_r, bs_k, bs_c = shape
    args = _mats(17, 3, 4, 3, bs_r, bs_k, bs_c, occupancy, jnp.dtype(dtype))
    ab, am, an, bb, bm, bn = args
    got, got_m = local_filtered_mm(*args, backend=backend)
    assert got.dtype == jnp.dtype(dtype)  # storage dtype round-trips
    ok = pair_filter(am, an, bm, bn, 0.0)
    want = kref.block_spgemm_ref(ab, bb, ok)
    tol = _DTYPE_TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_f32_accumulation_beats_storage_precision():
    """The reduced-precision path accumulates in f32: a long k-sum of
    same-sign terms matches the f32 result to input-rounding error, far
    tighter than bf16 accumulation (which loses ~1 ulp per term) would."""
    nk, bs = 8, 16
    ab = jnp.full((1, nk, bs, bs), 1.0 + 1 / 256, jnp.bfloat16)
    bb = jnp.full((nk, 1, bs, bs), 1.0 - 1 / 256, jnp.bfloat16)
    m_a = jnp.ones((1, nk), bool)
    m_b = jnp.ones((nk, 1), bool)
    n_a = jnp.sqrt(jnp.sum(jnp.square(ab.astype(jnp.float32)), axis=(2, 3)))
    n_b = jnp.sqrt(jnp.sum(jnp.square(bb.astype(jnp.float32)), axis=(2, 3)))
    exact = float(nk * bs * (1.0 + 1 / 256) * (1.0 - 1 / 256))
    for backend in BACKENDS:
        got, _ = local_filtered_mm(ab, m_a, n_a, bb, m_b, n_b,
                                   backend=backend)
        rel = abs(float(jnp.asarray(got, jnp.float32)[0, 0, 0, 0]) - exact)
        rel /= exact
        # bf16 has ~3 decimal digits; f32 accumulation keeps the 128-term
        # sum within one bf16 output rounding (~0.4%), not ~n ulps
        assert rel < 5e-3, (backend, rel)


@settings(max_examples=8, deadline=None)
@given(tile=st.sampled_from([None, (8, 8, 8), (8, 16, 8), (16, 8, 16)]))
def test_pallas_tile_param_matches_dense(tile):
    """The tile override changes scheduling, never numerics."""
    args = _mats(23, 3, 3, 3, 16, 16, 16, 0.5, jnp.float32)
    want, want_m = local_filtered_mm(*args, backend="jnp")
    got, got_m = local_filtered_mm(*args, backend="pallas", tile=tile)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    assert bool(jnp.all(got_m == want_m))


# ---------------------------------------------------------------------------
# the jnp backend's two forms: separable at threshold 0, masked above it
# ---------------------------------------------------------------------------


def _dense_product(ab, am, bb, bm):
    """float64 (mA ⊙ A)(mB ⊙ B) as dense matrices."""
    def dense(blocks, mask):
        x = np.asarray(blocks, np.float64) * np.asarray(mask)[:, :, None, None]
        r, c, br, bc = x.shape
        return x.transpose(0, 2, 1, 3).reshape(r * br, c * bc)

    return dense(ab, am) @ dense(bb, bm)


def _separable_case(case):
    shape = (8, 4, 8) if case == "rectangular" else (8, 8, 8)
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    ab, am, an, bb, bm, bn = _mats(31, 5, 6, 4, *shape, 0.6, dtype)
    # an empty block row of A, an empty block column of B and an empty k
    am = am.at[1].set(False).at[:, 2].set(False)
    bm = bm.at[:, 3].set(False).at[2].set(False)
    if case == "junk_under_mask":
        # nonzero data (and norms) under every false mask entry
        k1, k2 = jax.random.split(jax.random.key(32))
        ab = jnp.where(am[:, :, None, None], ab,
                       jax.random.normal(k1, ab.shape).astype(dtype))
        bb = jnp.where(bm[:, :, None, None], bb,
                       jax.random.normal(k2, bb.shape).astype(dtype))
        an, bn = an + 1.0, bn + 1.0
    return ab, am, an, bb, bm, bn


@pytest.mark.parametrize(
    "case", ["empty_rows_cols", "junk_under_mask", "rectangular", "bf16"])
def test_separable_form_matches_masked_oracle(case):
    """At threshold 0 the jnp backend contracts (mA ⊙ A)(mB ⊙ B) without
    the filter cube: it equals the cube-weighted einsum and the dense
    product, ignores data under false mask entries, and its C mask is
    exactly any_k of the filter cube."""
    args = _separable_case(case)
    ab, am, an, bb, bm, bn = args
    fn = jax.jit(lambda *xs: local_filtered_mm(*xs, backend="jnp"))
    assert "spgemm.local/separable" in fn.lower(*args).as_text(
        debug_info=True)
    got, got_m = fn(*args)
    ok = pair_filter(am, an, bm, bn, 0.0)
    assert got.dtype == ab.dtype
    np.testing.assert_array_equal(np.asarray(got_m),
                                  np.asarray(jnp.any(ok, axis=1)))
    tol = _DTYPE_TOL[jnp.dtype(ab.dtype).name]
    want = kref.block_spgemm_ref(ab, bb, ok)  # the cube-weighted einsum
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    ni, nj, bs_r, bs_c = got.shape
    dense = np.asarray(got, np.float64).transpose(0, 2, 1, 3).reshape(
        ni * bs_r, nj * bs_c)
    np.testing.assert_allclose(dense, _dense_product(ab, am, bb, bm),
                               rtol=tol, atol=tol)
    # empty rows and columns of C come out zero, not merely unmasked
    assert float(jnp.abs(got[1]).max()) == 0.0
    assert float(jnp.abs(got[:, 3]).max()) == 0.0


def test_positive_threshold_still_filters_per_triple():
    """Above 0 the filter couples i, k and j: the one product whose norm
    product falls just under the threshold is dropped, while the same A
    block times another B block, and another A block times the same B
    block, are kept."""
    ab, am, _, bb, bm, _ = _mats(33, 2, 2, 2, 4, 4, 4, 1.0, jnp.float32)
    am, bm = jnp.ones((2, 2), bool), jnp.ones((2, 2), bool)
    thr = 1.0
    an = jnp.array([[2.0, 0.5], [2.0, 2.0]])
    bn = jnp.array([[1.0, 1.0], [1.99, 4.0]])  # 0.5 * 1.99 = 0.995 < 1
    fn = jax.jit(lambda *xs: local_filtered_mm(*xs, threshold=thr,
                                               backend="jnp"))
    lowered = fn.lower(ab, am, an, bb, bm, bn)
    assert "spgemm.local/separable" not in lowered.as_text(debug_info=True)
    got, got_m = fn(ab, am, an, bb, bm, bn)
    ok = np.ones((2, 2, 2), bool)
    ok[0, 1, 0] = False
    np.testing.assert_array_equal(
        np.asarray(pair_filter(am, an, bm, bn, thr)), ok)
    want = np.einsum("ikj,ikab,kjbc->ijac", ok, np.asarray(ab, np.float64),
                     np.asarray(bb, np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(got_m))
    # the dropped product is not a rounding-level term
    kept = np.einsum("ab,bc->ac", np.asarray(ab[0, 1]), np.asarray(bb[1, 0]))
    assert np.abs(kept).max() > 0.1


# ---------------------------------------------------------------------------
# compaction machinery
# ---------------------------------------------------------------------------


def test_compact_pair_mask_structure():
    ok = jnp.asarray(
        np.array(
            [  # (ni=2, nk=2, nj=2)
                [[True, False], [True, True]],
                [[False, False], [False, True]],
            ]
        )
    )
    st_ = compact_pair_mask(ok, capacity=8)
    n = int(np.asarray(ok).sum())  # 4
    v = np.asarray(st_.valid)
    assert v.sum() == n and v[:n].all()
    # sorted by (i, j) with k-runs contiguous; padding repeats last triple
    tiles = np.asarray(st_.tile)
    assert (np.diff(tiles) >= 0).all()
    triples = list(
        zip(np.asarray(st_.ia)[:n], np.asarray(st_.ik)[:n], np.asarray(st_.ij)[:n])
    )
    assert triples == [(0, 0, 0), (0, 1, 0), (0, 1, 1), (1, 1, 1)]
    assert (np.asarray(st_.ia)[n:] == 1).all()  # padding = last triple
    # one first per distinct tile, one write per distinct tile boundary
    firsts = np.asarray(st_.first)
    writes = np.asarray(st_.write)
    assert firsts.sum() == len(set(tiles[:n].tolist()))
    assert writes[-1] == 1


def test_bucket_capacity():
    assert bucket_capacity(0) == 0
    assert bucket_capacity(1) == 8
    assert bucket_capacity(8) == 8
    assert bucket_capacity(9) == 16
    assert bucket_capacity(1000) == 1024


def test_pattern_signature_distinguishes():
    a = np.zeros((2, 2, 2), bool)
    b = a.copy()
    b[0, 0, 0] = True
    assert pattern_signature(a) != pattern_signature(b)
    assert pattern_signature(a) == pattern_signature(a.copy())
    assert pattern_signature(a) != pattern_signature(a.reshape(2, 1, 4))


# ---------------------------------------------------------------------------
# acceptance: surviving-product FLOPs + pattern-cache behaviour
# ---------------------------------------------------------------------------


def test_stacks_flops_fraction_at_low_occupancy():
    """At 10% block occupancy with filtering on, the compacted backend's
    measured FLOPs are <= 20% of the dense einsum's (acceptance)."""
    nb, bs = 16, 16
    a = random_bsm(jax.random.key(0), nb, bs, occupancy=0.1)
    b = random_bsm(jax.random.key(1), nb, bs, occupancy=0.1)
    thr = 1e-3
    args = (a.blocks, a.mask, a.norms, b.blocks, b.mask, b.norms)

    dense = jax.jit(
        lambda *xs: local_filtered_mm(*xs, threshold=thr, backend="jnp")
    )
    dense_flops = xla_cost_analysis(dense.lower(*args).compile())["flops"]

    ok = np.asarray(pair_filter(a.mask, a.norms, b.mask, b.norms, thr))
    stacks, n = plan_mod.get_product_stacks(ok)
    assert 0 < n <= stacks.capacity
    fn = plan_mod.get_local_compiled(
        nb, nb, nb, bs, bs, bs, jnp.float32,
        backend="stacks", capacity=stacks.capacity,
    )
    comp = fn.lower(a.blocks, b.blocks, stacks).compile()
    stacks_flops = xla_cost_analysis(comp)["flops"]

    assert stacks_flops <= 0.20 * dense_flops, (stacks_flops, dense_flops)
    # and the measured number is the surviving-product model, not the cube
    assert stacks_flops == pytest.approx(
        spgemm_stacks_flops(stacks.capacity, bs, bs, bs), rel=0.10
    )
    # numerics still match the dense reference to 1e-5
    want = multiply_reference(a, b, threshold=thr, backend="jnp")
    for backend in ("stacks", "pallas"):
        got = multiply_reference(a, b, threshold=thr, backend=backend)
        np.testing.assert_allclose(
            np.asarray(got.to_dense()),
            np.asarray(want.to_dense()),
            rtol=1e-5,
            atol=1e-5,
        )


def test_repeated_pattern_is_cache_hit_no_recompile():
    """Same sparsity pattern again -> pattern-cache hit, zero new builds."""
    plan_mod.clear_cache()
    a = random_bsm(jax.random.key(5), 8, 8, occupancy=0.2)
    b = random_bsm(jax.random.key(6), 8, 8, occupancy=0.2)
    c1 = multiply_reference(a, b, threshold=1e-3, backend="stacks")
    s1 = plan_mod.cache_stats()
    assert s1["pattern_misses"] >= 1 and s1["builds"] >= 1
    # the same multiply again — the sign-iteration / serving hot path
    c2 = multiply_reference(a, b, threshold=1e-3, backend="stacks")
    s2 = plan_mod.cache_stats()
    assert s2["pattern_hits"] == s1["pattern_hits"] + 1
    assert s2["builds"] == s1["builds"]  # no recompile
    assert s2["hits"] == s1["hits"] + 1  # compiled program reused
    np.testing.assert_allclose(
        np.asarray(c1.to_dense()), np.asarray(c2.to_dense()), rtol=1e-6
    )
    # a *different* pattern in the same capacity bucket still reuses the
    # compiled program (key is the bucket, not the pattern)
    a3 = random_bsm(jax.random.key(7), 8, 8, occupancy=0.2)
    multiply_reference(a3, b, threshold=1e-3, backend="stacks")
    s3 = plan_mod.cache_stats()
    assert s3["pattern_misses"] == s2["pattern_misses"] + 1
    ok3 = np.asarray(
        pair_filter(a3.mask, a3.norms, b.mask, b.norms, 1e-3)
    )
    ok1 = np.asarray(pair_filter(a.mask, a.norms, b.mask, b.norms, 1e-3))
    if bucket_capacity(int(ok3.sum())) == bucket_capacity(int(ok1.sum())):
        assert s3["builds"] == s2["builds"]


def test_auto_backend_heuristic():
    lo_a = random_bsm(jax.random.key(8), 8, 8, occupancy=0.05)
    lo_b = random_bsm(jax.random.key(9), 8, 8, occupancy=0.05)
    hi_a = random_bsm(jax.random.key(10), 8, 8, occupancy=1.0, pattern="dense")
    hi_b = random_bsm(jax.random.key(11), 8, 8, occupancy=1.0, pattern="dense")
    lo = choose_backend(lo_a, lo_b)
    hi = choose_backend(hi_a, hi_b)
    assert lo in ("stacks", "pallas")
    assert hi == "jnp"
    ok = np.asarray(pair_filter(hi_a.mask, hi_a.norms, hi_b.mask, hi_b.norms, 0.0))
    assert ok.mean() > AUTO_DENSE_FILL
    # auto end-to-end matches the dense reference
    want = multiply_reference(lo_a, lo_b, backend="jnp")
    got = multiply_reference(lo_a, lo_b, backend="auto")
    np.testing.assert_allclose(
        np.asarray(got.to_dense()), np.asarray(want.to_dense()),
        rtol=1e-5, atol=1e-5,
    )


def test_stacks_mm_direct_vs_einsum():
    """stacks_mm over an exact host-compacted list == masked einsum."""
    args = _mats(21, 4, 3, 5, 8, 16, 4, 0.5, jnp.float32)
    ab, am, an, bb, bm, bn = args
    ok = pair_filter(am, an, bm, bn, 0.0)
    n = product_count(np.asarray(ok))
    st_ = compact_pair_mask(ok, capacity=bucket_capacity(n))
    got = stacks_mm(ab, bb, st_, ni=4, nj=5)
    want, _ = local_filtered_mm(*args, backend="jnp")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize(
    "shape,dtype,want",
    [
        ((128, 128, 128), jnp.float32, "pallas"),
        ((256, 256, 256), jnp.float32, "pallas"),
        ((128, 128, 128), jnp.bfloat16, "pallas"),
        ((23, 23, 23), jnp.float32, "stacks"),
        ((6, 6, 6), jnp.float32, "stacks"),
        ((32, 32, 32), jnp.float32, "stacks"),
        ((8, 128, 64), jnp.float32, "stacks"),
    ],
)
def test_compacted_backend_pallas_only_for_lane_aligned_blocks(
    monkeypatch, shape, dtype, want
):
    """On a TPU the Pallas kernel is chosen only where the block shape has
    a tile compiled Mosaic accepts; everywhere else, and off the TPU, the
    XLA stacks path."""
    from repro.core.local_mm import compacted_backend
    from repro.tuner.model import choose_local_backend

    assert compacted_backend(*shape, dtype) == "stacks"  # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compacted_backend(*shape, dtype) == want
    # the tuner's dense/compacted choice defers to the same rule
    assert choose_local_backend(16, 16, 16, *shape, fill=0.01,
                                dtype=dtype) == want


def test_auto_backend_on_tpu_keeps_atomic_blocks_off_pallas(monkeypatch):
    """engine.choose_backend and the tuner's candidate space, steered to a
    TPU: bs 23 at low fill goes to stacks, and no pallas candidate of an
    uncompilable block shape is enumerated."""
    from repro.launch.mesh import make_mesh
    from repro.tuner.features import featurize
    from repro.tuner.model import enumerate_candidates

    a = random_bsm(jax.random.key(12), 16, 23, occupancy=0.05)
    b = random_bsm(jax.random.key(13), 16, 23, occupancy=0.05)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert choose_backend(a, b) == "stacks"
    mesh = make_mesh((1, 1), ("r", "c"))
    f = featurize(a, b, 0.0)
    ok = np.asarray(a.mask)[:, :, None] & np.asarray(b.mask)[None, :, :]
    cands = enumerate_candidates(mesh, f, ok=ok, engines=("gather",),
                                 transports=("dense",))
    assert {c.backend for c in cands} == {"jnp", "stacks"}
    pinned = enumerate_candidates(mesh, f, ok=ok, engines=("gather",),
                                  backends=("pallas",), transports=("dense",))
    assert pinned == []


def test_stacks_memory_priced_with_tpu_tile_padding(monkeypatch):
    """On a TPU a (capacity, 23, 23) f32 stack is laid out in (8, 128)
    tiles.  The auto choice and the tuner's Eq. 6 prune count that
    padding, and a ``stacks`` list that cannot fit the device goes to
    ``jnp``."""
    from repro.core import commvolume
    from repro.core import plan as plan_mod
    from repro.core.local_mm import choose_local_backend, stack_entry_bytes
    from repro.kernels.stacks import ProductStacks
    from repro.launch.mesh import make_mesh

    n_idx = len(ProductStacks._fields)
    plan = plan_mod.plan_multiply(make_mesh((1, 1), ("r", "c")), "gather")
    cpu = commvolume.device_memory_bytes(plan, 64, 23, stack_capacity=1024)
    assert stack_entry_bytes(23, 23, 23) == 4.0 * (3 * 23 * 23 + n_idx)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert stack_entry_bytes(23, 23, 23) == 4.0 * (3 * 24 * 128 + n_idx)
    assert stack_entry_bytes(128, 128, 128) == 4.0 * (3 * 128 * 128 + n_idx)
    tpu = commvolume.device_memory_bytes(plan, 64, 23, stack_capacity=1024)
    assert tpu - cpu == 1024 * 4.0 * 3 * (24 * 128 - 23 * 23)
    # H2O-DFT-LS shape at nb 512: 2^20 padded entries need ~39 GB
    dims = (512, 512, 512, 23, 23, 23)
    assert choose_local_backend(*dims, fill=0.005, capacity=2**20) == "jnp"
    assert choose_local_backend(*dims, fill=0.005, capacity=2**17) == "stacks"
    monkeypatch.setenv("REPRO_DEVICE_MEMORY_BYTES", "1e9")
    assert choose_local_backend(*dims, fill=0.005, capacity=2**17) == "jnp"


def test_choose_backend_on_tpu_takes_jnp_where_stacks_cannot_fit(monkeypatch):
    """engine.choose_backend prices the exact bucketed list: under a
    budget the padded list exceeds, ``multiply(backend="auto")`` takes the
    dense einsum."""
    a = random_bsm(jax.random.key(14), 16, 23, occupancy=0.05)
    b = random_bsm(jax.random.key(15), 16, 23, occupancy=0.05)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert choose_backend(a, b) == "stacks"
    monkeypatch.setenv("REPRO_DEVICE_MEMORY_BYTES", "1e5")
    assert choose_backend(a, b) == "jnp"
