"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per instructions: sweep shapes/dtypes for each kernel and assert_allclose
against the ref.py oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.block_spgemm import block_spgemm
from repro.kernels.flash_attention import flash_attention_single


# ---------------------------------------------------------------------------
# block_spgemm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bs", [8, 16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_spgemm_shapes_dtypes(bs, dtype):
    ni, nk, nj = 3, 4, 2
    key = jax.random.key(0)
    a = jax.random.normal(key, (ni, nk, bs, bs), dtype)
    b = jax.random.normal(jax.random.key(1), (nk, nj, bs, bs), dtype)
    ok = jax.random.bernoulli(jax.random.key(2), 0.6, (ni, nk, nj))
    out = block_spgemm(a, b, ok, interpret=True)
    want = ref.block_spgemm_ref(a, b, ok)
    assert out.shape == (ni, nj, bs, bs)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2  # f32: 512-term k-sums
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_block_spgemm_filter_actually_skips():
    """A filtered-out (i,k,j) product must not contribute, even if data huge."""
    bs = 8
    a = jnp.ones((1, 2, bs, bs)) * 1e6
    b = jnp.ones((2, 1, bs, bs))
    ok = jnp.asarray([[[True], [False]]])  # only k=0 allowed
    out = block_spgemm(a, b, ok, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 1e6 * bs, rtol=1e-6)


def test_block_spgemm_all_filtered_is_zero():
    bs = 8
    a = jnp.ones((2, 2, bs, bs))
    b = jnp.ones((2, 2, bs, bs))
    ok = jnp.zeros((2, 2, 2), bool)
    out = block_spgemm(a, b, ok, interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


@settings(max_examples=10, deadline=None)
@given(
    ni=st.integers(1, 4),
    nk=st.integers(1, 4),
    nj=st.integers(1, 4),
    bs=st.sampled_from([4, 8]),
    p=st.floats(0.0, 1.0),
)
def test_block_spgemm_property(ni, nk, nj, bs, p):
    a = jax.random.normal(jax.random.key(10), (ni, nk, bs, bs))
    b = jax.random.normal(jax.random.key(11), (nk, nj, bs, bs))
    ok = jax.random.bernoulli(jax.random.key(12), p, (ni, nk, nj))
    out = block_spgemm(a, b, ok, interpret=True)
    want = ref.block_spgemm_ref(a, b, ok)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ops_wrapper_defaults_interpret_on_cpu():
    a = jnp.ones((1, 1, 8, 8))
    b = jnp.ones((1, 1, 8, 8))
    ok = jnp.ones((1, 1, 1), bool)
    out = ops.block_spgemm(a, b, ok)  # interpret auto-detected (CPU)
    np.testing.assert_allclose(np.asarray(out), 8.0)


def test_interpret_env_override(monkeypatch):
    from repro.kernels.ops import _default_interpret

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert _default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert _default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "auto")
    assert _default_interpret() is (jax.default_backend() != "tpu")


def test_interpreter_on_tpu_only_when_passed_explicitly(monkeypatch):
    """On a TPU the platform default is compiled Mosaic, and an
    environment that forces the interpreter there is refused."""
    from repro.kernels.ops import _default_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert _default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert _default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="interpret=True explicitly"):
        _default_interpret()
    a = jnp.ones((1, 1, 8, 8))
    with pytest.raises(ValueError, match="interpret=True explicitly"):
        ops.block_spgemm(a, a, jnp.ones((1, 1, 1), bool))
    # an explicit interpret=True never consults the environment
    out = ops.block_spgemm(a, a, jnp.ones((1, 1, 1), bool), interpret=True)
    np.testing.assert_allclose(np.asarray(out), 8.0)


def test_block_spgemm_rectangular_blocks():
    """bs_r != bs_k != bs_c through the scalar-prefetch kernel."""
    ni, nk, nj, bs_r, bs_k, bs_c = 2, 3, 4, 8, 16, 4
    a = jax.random.normal(jax.random.key(20), (ni, nk, bs_r, bs_k))
    b = jax.random.normal(jax.random.key(21), (nk, nj, bs_k, bs_c))
    ok = jax.random.bernoulli(jax.random.key(22), 0.5, (ni, nk, nj))
    out = block_spgemm(a, b, ok, interpret=True)
    want = ref.block_spgemm_ref(a, b, ok)
    assert out.shape == (ni, nj, bs_r, bs_c)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_block_spgemm_compacted_capacity():
    """A tight static capacity (the whole point of the compaction) is
    numerically identical to the full-cube grid."""
    ni, nk, nj, bs = 4, 4, 4, 8
    a = jax.random.normal(jax.random.key(30), (ni, nk, bs, bs))
    b = jax.random.normal(jax.random.key(31), (nk, nj, bs, bs))
    ok = jax.random.bernoulli(jax.random.key(32), 0.1, (ni, nk, nj))
    n = int(ok.sum())
    from repro.kernels.stacks import bucket_capacity

    out = block_spgemm(
        a, b, ok, capacity=bucket_capacity(n), interpret=True
    )
    want = ref.block_spgemm_ref(a, b, ok)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_block_spgemm_stacks_grid_is_capacity():
    """The scalar-prefetch grid issues exactly `capacity` steps — the
    kernel's work scales with survivors, not the (ni, nj, nk) cube."""
    from repro.kernels.block_spgemm import block_spgemm_stacks
    from repro.kernels.stacks import compact_pair_mask

    ni, nk, nj, bs = 4, 4, 4, 8
    a = jax.random.normal(jax.random.key(40), (ni, nk, bs, bs))
    b = jax.random.normal(jax.random.key(41), (nk, nj, bs, bs))
    ok = jnp.zeros((ni, nk, nj), bool).at[1, 2, 3].set(True).at[1, 3, 3].set(True)
    stacks = compact_pair_mask(ok, capacity=8)
    out = block_spgemm_stacks(a, b, stacks, ni=ni, nj=nj, interpret=True)
    want = ref.block_spgemm_ref(a, b, ok)
    # only the visited tile is defined; compare it (the two-product k-run)
    np.testing.assert_allclose(
        np.asarray(out[1, 3]), np.asarray(want[1, 3]), rtol=1e-5, atol=1e-5
    )
    # and the pallas grid really is (capacity,), not the (ni*nj*nk) cube
    jpr = jax.make_jaxpr(
        lambda aa, bb, ss: block_spgemm_stacks(
            aa, bb, ss, ni=ni, nj=nj, interpret=True
        )
    )(a, b, stacks)
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "pallas" in str(eqn.primitive):
                grids.append(eqn.params["grid_mapping"].grid)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)

    walk(jpr.jaxpr)
    # grid = (n_tm, n_tn, capacity, n_tk): whole-block default tile at
    # bs=8 puts all the tiling dims at 1 — work still scales with capacity
    assert grids == [(1, 1, 8, 1)], grids


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [3, 5, 8])
def test_block_spgemm_chunked_launches_straddle_k_runs(chunk, dtype):
    """A product list longer than one launch runs as several launches; a
    k-run cut by a launch boundary (runs of length nk=4 against chunks of
    3, 5 and 8 over a ragged capacity) accumulates exactly as in one."""
    from repro.kernels.block_spgemm import block_spgemm_stacks
    from repro.kernels.stacks import compact_pair_mask

    ni, nk, nj, bs = 3, 4, 2, 8
    a = jax.random.normal(jax.random.key(70), (ni, nk, bs, bs), dtype)
    b = jax.random.normal(jax.random.key(71), (nk, nj, bs, bs), dtype)
    ok = jnp.ones((ni, nk, nj), bool).at[2, 1, 0].set(False)
    stacks = compact_pair_mask(ok, capacity=27)  # 23 products + 4 padding
    out = block_spgemm_stacks(a, b, stacks, ni=ni, nj=nj, interpret=True,
                              chunk=chunk)
    want = ref.block_spgemm_ref(a, b, ok)
    one = block_spgemm_stacks(a, b, stacks, ni=ni, nj=nj, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(one, np.float32), rtol=tol, atol=tol)
    # and through the public entry point, with the unvisited-tile mask
    ok2 = ok.at[1].set(False)
    out2 = block_spgemm(a, b, ok2, capacity=16, chunk=chunk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out2, np.float32),
        np.asarray(ref.block_spgemm_ref(a, b, ok2), np.float32),
        rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# MXU tiling
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tile", [(8, 16, 4), (4, 8, 4), (8, 8, 2)])
def test_block_spgemm_explicit_tile_matches_oracle(tile):
    """Blocks spanning several tiles (incl. rectangular tiles) accumulate
    across the k-tile grid dim exactly like the whole-block kernel."""
    ni, nk, nj, bs_r, bs_k, bs_c = 2, 3, 2, 8, 16, 4
    a = jax.random.normal(jax.random.key(50), (ni, nk, bs_r, bs_k))
    b = jax.random.normal(jax.random.key(51), (nk, nj, bs_k, bs_c))
    ok = jax.random.bernoulli(jax.random.key(52), 0.5, (ni, nk, nj))
    out = block_spgemm(a, b, ok, tile=tile, interpret=True)
    want = ref.block_spgemm_ref(a, b, ok)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_block_spgemm_tile_grid_shape():
    """An explicit sub-block tile multiplies the grid dims accordingly."""
    from repro.kernels.block_spgemm import block_spgemm_stacks
    from repro.kernels.stacks import compact_pair_mask

    ni, nk, nj, bs = 2, 2, 2, 16
    a = jax.random.normal(jax.random.key(60), (ni, nk, bs, bs))
    b = jax.random.normal(jax.random.key(61), (nk, nj, bs, bs))
    ok = jnp.ones((ni, nk, nj), bool)
    stacks = compact_pair_mask(ok, capacity=8)
    jpr = jax.make_jaxpr(
        lambda aa, bb, ss: block_spgemm_stacks(
            aa, bb, ss, ni=ni, nj=nj, tile=(8, 8, 8), interpret=True
        )
    )(a, b, stacks)
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "pallas" in str(eqn.primitive):
                grids.append(eqn.params["grid_mapping"].grid)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)

    walk(jpr.jaxpr)
    assert grids == [(2, 2, 8, 2)], grids
    # and the tiled program still matches the oracle
    out = block_spgemm_stacks(a, b, stacks, ni=ni, nj=nj, tile=(8, 8, 8),
                              interpret=True)
    want = ref.block_spgemm_ref(a, b, ok)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_tile_validation_up_front():
    """Satellite: bad tiles fail fast in block_spgemm_stacks with a clear
    ValueError, not a Mosaic lowering error."""
    from repro.kernels.block_spgemm import (
        block_spgemm_stacks,
        validate_tile,
    )
    from repro.kernels.stacks import compact_pair_mask

    ni = nj = 2
    bs = 16
    a = jnp.ones((ni, 2, bs, bs))
    b = jnp.ones((2, nj, bs, bs))
    stacks = compact_pair_mask(jnp.ones((ni, 2, nj), bool), capacity=8)
    with pytest.raises(ValueError, match="does not divide block dim"):
        block_spgemm_stacks(a, b, stacks, ni=ni, nj=nj, tile=(5, 8, 8),
                            interpret=True)
    with pytest.raises(ValueError, match="must be positive"):
        validate_tile(bs, bs, bs, (0, 8, 8), interpret=True)
    with pytest.raises(ValueError, match="integer triple"):
        validate_tile(bs, bs, bs, "big", interpret=True)
    # compiled mode demands lane alignment of the minor dims
    with pytest.raises(ValueError, match="lane-aligned"):
        validate_tile(256, 256, 256, (8, 64, 64), interpret=False)
    # interpret mode only needs divisibility
    assert validate_tile(16, 16, 16, (8, 8, 8), interpret=True) == (8, 8, 8)


def test_default_tile_and_candidates():
    from repro.kernels.block_spgemm import (
        MAX_TILE,
        default_tile,
        tile_candidates,
        tile_working_set_bytes,
        validate_tile,
    )

    # small blocks stay whole-block
    assert default_tile(16, 16, 16) == (16, 16, 16)
    # oversized dims split to the largest aligned divisor <= MAX_TILE
    dt = default_tile(512, 512, 512)
    assert all(t <= MAX_TILE and 512 % t == 0 for t in dt)
    # the candidate list leads with None (= default) and every explicit
    # entry validates for the shape it was generated for
    cands = tile_candidates(512, 512, 512)
    assert cands[0] is None
    for t in cands[1:]:
        assert validate_tile(512, 512, 512, t) == t
    # bf16 working set is half the f32 one at the same tile (+ f32 acc)
    f32 = tile_working_set_bytes(128, 128, 128, (128, 128, 128), jnp.float32)
    bf16 = tile_working_set_bytes(128, 128, 128, (128, 128, 128), jnp.bfloat16)
    assert bf16 < f32


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sq,skv,d", [(128, 128, 64), (256, 128, 32), (128, 256, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(sq, skv, d, causal):
    if causal and sq > skv:
        pytest.skip("causal needs sq <= skv alignment here")
    q = jax.random.normal(jax.random.key(0), (sq, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (skv, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (skv, d), jnp.float32)
    out = flash_attention_single(q, k, v, causal=causal, bq=64, bkv=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    sq = 256
    q = jax.random.normal(jax.random.key(3), (sq, 64))
    k = jax.random.normal(jax.random.key(4), (sq, 64))
    v = jax.random.normal(jax.random.key(5), (sq, 64))
    out = flash_attention_single(
        q, k, v, causal=True, window=window, bq=64, bkv=64, interpret=True
    )
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_attention_softcap():
    """gemma2-style tanh logit capping."""
    q = jax.random.normal(jax.random.key(6), (128, 64)) * 4
    k = jax.random.normal(jax.random.key(7), (128, 64)) * 4
    v = jax.random.normal(jax.random.key(8), (128, 64))
    out = flash_attention_single(
        q, k, v, causal=True, softcap=50.0, bq=64, bkv=64, interpret=True
    )
    want = ref.attention_ref(q, k, v, causal=True, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(jax.random.key(9), (128, 64), dtype)
    k = jax.random.normal(jax.random.key(10), (128, 64), dtype)
    v = jax.random.normal(jax.random.key(11), (128, 64), dtype)
    out = flash_attention_single(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_gqa_batched():
    """ops.flash_attention: GQA head replication + batch/head vmap."""
    b, h, hkv, s, d = 2, 8, 2, 128, 32
    q = jax.random.normal(jax.random.key(12), (b, h, s, d))
    k = jax.random.normal(jax.random.key(13), (b, hkv, s, d))
    v = jax.random.normal(jax.random.key(14), (b, hkv, s, d))
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    assert out.shape == (b, h, s, d)
    rep = h // hkv
    for bi in range(b):
        for hi in range(h):
            want = ref.attention_ref(q[bi, hi], k[bi, hi // rep], v[bi, hi // rep])
            np.testing.assert_allclose(
                np.asarray(out[bi, hi]), np.asarray(want), rtol=2e-4, atol=2e-4
            )
