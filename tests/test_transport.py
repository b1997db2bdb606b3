"""Transport layer: packing format, capacity bounds, mode resolution.

The distributed bit-exactness sweep (compressed == dense for every
engine across occupancy in {0, low, medium, full}, rectangular meshes
and uneven L) runs multi-device in tests/_dist.py::check_transport;
this module pins the layer's building blocks single-process:

* pack/unpack is an exact roundtrip whenever capacity bounds the
  occupied count (hypothesis over random patterns and capacities);
* the wire format is partial-permutation safe (all-zero wire state
  decodes as an empty panel, never as block (0, 0));
* ``panel_nnz_bound`` is sound for every partition cell (hypothesis);
* the auto mode crossover and the ``REPRO_TRANSPORT`` override;
* transport mode + capacities key the compiled-program cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import plan as plan_mod
from repro.core import transport as T
from repro.launch.mesh import make_mesh


def _random_panel(seed: int, nr: int, nc: int, occ: float, bs: int = 4):
    rng = np.random.default_rng(seed)
    mask = rng.random((nr, nc)) < occ
    blocks = rng.standard_normal((nr, nc, bs, bs)).astype(np.float32)
    blocks = blocks * mask[:, :, None, None]
    return jnp.asarray(blocks), jnp.asarray(mask)


# ---- packing format --------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    nr=st.integers(1, 6),
    nc=st.integers(1, 6),
    occ=st.sampled_from([0.0, 0.1, 0.5, 1.0]),
    slack=st.integers(0, 5),
)
def test_pack_unpack_roundtrip_exact(seed, nr, nc, occ, slack):
    """unpack(pack(panel)) == panel bitwise for any capacity >= nnz."""
    blocks, mask = _random_panel(seed, nr, nc, occ)
    cap = int(np.asarray(mask).sum()) + slack
    if cap == 0:
        cap = 1  # capacity must stay positive for a wire buffer to exist
    packed, idx1 = T.pack_panel(blocks, mask, cap)
    assert packed.shape == (cap,) + blocks.shape[2:]
    assert idx1.shape == (cap,)
    ub, um = T.unpack_panel(packed, idx1, nr, nc)
    np.testing.assert_array_equal(np.asarray(ub), np.asarray(blocks))
    np.testing.assert_array_equal(np.asarray(um), np.asarray(mask))


def test_unpack_of_zero_wire_state_is_empty():
    """A device a partial permutation does not address receives zeros —
    they must decode as an empty panel (the one-based index encoding)."""
    bs = 4
    ub, um = T.unpack_panel(
        jnp.zeros((8, bs, bs), jnp.float32), jnp.zeros((8,), jnp.int32), 3, 5
    )
    assert not bool(np.asarray(um).any())
    assert not bool(np.asarray(ub).any())


def test_pack_drops_excess_beyond_capacity():
    """Under-capacity packing silently truncates — the reason the plan
    layer must derive sound bounds (and the bound test below exists)."""
    blocks, mask = _random_panel(0, 4, 4, 1.0)
    packed, idx1 = T.pack_panel(blocks, mask, 8)  # 16 occupied, cap 8
    _, um = T.unpack_panel(packed, idx1, 4, 4)
    assert int(np.asarray(um).sum()) == 8


def test_panel_norms_matches_block_norms_and_skips_when_unfiltered():
    from repro.core.bsm import block_norms

    blocks, _ = _random_panel(1, 3, 3, 0.5)
    np.testing.assert_array_equal(
        np.asarray(T.panel_norms(blocks, 0.5)),
        np.asarray(block_norms(blocks)),
    )
    assert not bool(np.asarray(T.panel_norms(blocks, 0.0)).any())


# ---- capacity bounds -------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    rp=st.sampled_from([1, 2, 4]),
    cp=st.sampled_from([1, 2, 4]),
    mult=st.integers(1, 3),
    occ=st.floats(0.0, 1.0),
)
def test_panel_nnz_bound_sound_for_every_cell(seed, rp, cp, mult, occ):
    """The derived capacity covers EVERY panel of the partition — the
    transport analogue of the distributed stack-bound soundness."""
    nr, nc = rp * mult, cp * mult * 2
    rng = np.random.default_rng(seed)
    mask = rng.random((nr, nc)) < occ
    bound = T.panel_nnz_bound(mask, rp, cp)
    hr, hc = nr // rp, nc // cp
    for i in range(rp):
        for j in range(cp):
            cell = mask[i * hr:(i + 1) * hr, j * hc:(j + 1) * hc]
            assert int(cell.sum()) <= bound


def test_panel_nnz_bound_rejects_non_dividing_partition():
    with pytest.raises(ValueError, match="does not divide"):
        T.panel_nnz_bound(np.ones((6, 6), bool), 4, 2)


def test_plan_panel_parts_pull_vs_shard():
    """Pull plans ship virtual-grid subpanels; everything else ships
    whole home shards."""
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    for engine in ("gather", "cannon"):
        plan = plan_mod.plan_multiply(mesh, engine)
        assert T.plan_panel_parts(plan) == ((1, 1), (1, 1))
    pull = plan_mod.plan_multiply(mesh, "onesided")
    (ar, ac), (br, bc) = T.plan_panel_parts(pull)
    assert (ar, ac) == (1, pull.ca) and (br, bc) == (pull.cb, 1)


# ---- mode resolution -------------------------------------------------------


def test_resolve_mode_crossover():
    # low bucketed fill -> compressed; high fill / tiny panels -> dense
    assert T.resolve_mode("auto", 8, 8, 64, 64) == "compressed"
    assert T.resolve_mode("auto", 32, 8, 64, 64) == "dense"
    assert T.resolve_mode("auto", 8, 8, 16, 16) == "dense"
    # explicit modes pass through untouched
    assert T.resolve_mode("dense", 8, 8, 1024, 1024) == "dense"
    assert T.resolve_mode("compressed", 64, 64, 64, 64) == "compressed"


def test_panel_transport_validation():
    with pytest.raises(ValueError, match="unknown transport mode"):
        T.PanelTransport("zstd")
    with pytest.raises(ValueError, match="positive panel capacities"):
        T.PanelTransport("compressed", 0, 8)
    tr = T.PanelTransport("compressed", 8, 16)
    assert tr.key == ("compressed", 8, 16)
    assert T.DENSE.key == ("dense", 0, 0)


def test_transport_mode_env_override(monkeypatch):
    from repro.config import transport_mode

    monkeypatch.delenv("REPRO_TRANSPORT", raising=False)
    assert transport_mode() == "auto"
    for raw, want in (("dense", "dense"), ("COMPRESSED", "compressed"),
                      ("auto", "auto"), ("", "auto")):
        monkeypatch.setenv("REPRO_TRANSPORT", raw)
        assert transport_mode() == want
    monkeypatch.setenv("REPRO_TRANSPORT", "gzip")
    with pytest.raises(ValueError, match="REPRO_TRANSPORT"):
        transport_mode()


# ---- plan-layer resolution + program-cache keying --------------------------


def test_get_transport_caps_and_counters():
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    plan_mod.clear_cache()
    mask = np.zeros((8, 8), bool)
    mask[0, :3] = True  # 3 occupied blocks in the single shard
    tr = plan_mod.get_transport(mask, mask, mesh, "gather",
                                mode="compressed")
    assert tr.mode == "compressed"
    assert tr.cap_a == tr.cap_b == T.MIN_CAPACITY  # 3 bucketed up to 8
    s1 = plan_mod.cache_stats()
    assert s1["transport_misses"] == 1 and s1["transport_compressed"] == 1
    # repeat: served from the signature cache
    tr2 = plan_mod.get_transport(mask, mask, mesh, "gather",
                                 mode="compressed")
    assert tr2 is tr
    s2 = plan_mod.cache_stats()
    assert s2["transport_hits"] == 1 and s2["transport_misses"] == 1
    # high fill under auto -> dense
    dense_tr = plan_mod.get_transport(
        np.ones((8, 8), bool), np.ones((8, 8), bool), mesh, "gather",
        mode="auto")
    assert dense_tr.mode == "dense"
    s3 = plan_mod.cache_stats()
    assert s3["transport_dense"] == 1
    # clear_cache drops the resolution cache and zeroes the counters
    plan_mod.clear_cache()
    s4 = plan_mod.cache_stats()
    assert s4["transport_hits"] == s4["transport_misses"] == 0
    assert s4["transport_dense"] == s4["transport_compressed"] == 0


def test_transport_keys_program_cache():
    """Dense and compressed transport compile distinct programs; the
    same resolved transport re-hits one program."""
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    from repro.core import bsm as B
    from repro.core.engine import multiply, multiply_reference

    mesh = make_mesh((1, 1), ("r", "c"))
    a = B.random_bsm(jax.random.key(0), nb=4, bs=4, occupancy=0.3)
    b = B.random_bsm(jax.random.key(1), nb=4, bs=4, occupancy=0.3)
    ref = np.asarray(multiply_reference(a, b).to_dense())

    plan_mod.clear_cache()
    c1 = multiply(a, b, mesh, engine="onesided", transport="dense")
    s1 = plan_mod.cache_stats()
    c2 = multiply(a, b, mesh, engine="onesided", transport="compressed")
    s2 = plan_mod.cache_stats()
    assert s2["builds"] == s1["builds"] + 1  # distinct program per mode
    c3 = multiply(a, b, mesh, engine="onesided", transport="compressed")
    s3 = plan_mod.cache_stats()
    assert s3["builds"] == s2["builds"]  # same resolved transport: a hit
    assert s3["hits"] == s2["hits"] + 1
    for c in (c1, c2, c3):
        np.testing.assert_allclose(np.asarray(c.to_dense()), ref,
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c2.to_dense()),
                                  np.asarray(c3.to_dense()))


def test_under_capacity_transport_rejected():
    """An explicit PanelTransport whose capacities under-cover this
    engine's panels must be rejected at resolution — pack_panel
    truncates silently, so a mismatched transport (e.g. capacities
    derived for a different plan kind) would yield a wrong C."""
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    from repro.core import bsm as B
    from repro.core.engine import multiply

    mesh = make_mesh((1, 1), ("r", "c"))
    a = B.random_bsm(jax.random.key(0), nb=8, bs=4, occupancy=1.0)
    with pytest.raises(ValueError, match="under-cover"):
        multiply(a, a, mesh, engine="cannon",
                 transport=T.PanelTransport("compressed", 8, 8))
    # sufficient (>= bound) capacities pass through untouched
    big = T.PanelTransport("compressed", 64, 64)
    c = multiply(a, a, mesh, engine="cannon", transport=big)
    d = multiply(a, a, mesh, engine="cannon", transport="dense")
    np.testing.assert_array_equal(np.asarray(c.to_dense()),
                                  np.asarray(d.to_dense()))


def test_forced_compressed_on_traced_operands_raises():
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    from repro.core import bsm as B
    from repro.core.engine import multiply

    mesh = make_mesh((1, 1), ("r", "c"))
    a = B.random_bsm(jax.random.key(0), nb=4, bs=4, occupancy=0.5)

    @jax.jit
    def traced(x, y):
        return multiply(x, y, mesh, engine="onesided",
                        transport="compressed")

    with pytest.raises(ValueError, match="concrete operand patterns"):
        traced(a, a)


# ---- reduced-precision wire -------------------------------------------------


def test_wire_validation_and_key_back_compat():
    with pytest.raises(ValueError, match="unknown wire"):
        T.PanelTransport("dense", wire="float16x")
    # native wire keeps the historical 3-element key: a program cached
    # before the wire field must keep hitting
    assert T.PanelTransport("compressed", 8, 16).key == ("compressed", 8, 16)
    assert T.DENSE.key == ("dense", 0, 0)
    tr = T.PanelTransport("dense", wire="bfloat16")
    assert tr.key == ("dense", 0, 0, "bfloat16")
    assert tr.wire_itemsize(4.0) == 2.0
    assert T.DENSE.wire_itemsize(4.0) == 4.0
    assert T.DENSE.wire_dtype is None


def test_wire_cast_dense_roundtrip():
    """Dense transport at bf16 wire: ingest casts, dense_view widens back
    to the compute dtype; values land within bf16 rounding."""
    blocks, mask = _random_panel(5, 3, 4, 0.6)
    tr = T.PanelTransport("dense", wire="bfloat16")
    state = T.ingest(tr, tr.cap_a, blocks, mask)
    wb, _ = state
    assert wb.dtype == jnp.bfloat16
    vb, vm = T.dense_view(tr, state, 3, 4, dtype=jnp.float32)
    assert vb.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(mask))
    np.testing.assert_allclose(
        np.asarray(vb), np.asarray(blocks), rtol=1e-2, atol=1e-2
    )


def test_wire_cast_compressed_roundtrip():
    blocks, mask = _random_panel(6, 4, 4, 0.5)
    cap = max(int(np.asarray(mask).sum()), 1)
    tr = T.PanelTransport("compressed", cap, cap, wire="bfloat16")
    state = T.ingest(tr, cap, blocks, mask)
    packed, idx1 = state
    assert packed.dtype == jnp.bfloat16
    assert idx1.dtype == jnp.int32  # indices never quantize
    vb, vm = T.dense_view(tr, state, 4, 4, dtype=jnp.float32)
    assert vb.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(mask))
    np.testing.assert_allclose(
        np.asarray(vb), np.asarray(blocks), rtol=1e-2, atol=1e-2
    )


def test_bf16_storage_native_wire_is_lossless():
    """The headline path: bf16 *storage* rides the native wire with no
    further cast — bitwise identical blocks, half the f32 bytes."""
    blocks, mask = _random_panel(7, 3, 3, 0.7)
    blocks = blocks.astype(jnp.bfloat16)
    state = T.ingest(T.DENSE, T.DENSE.cap_a, blocks, mask)
    vb, _ = T.dense_view(T.DENSE, state, 3, 3, dtype=jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(vb, np.float32), np.asarray(blocks, np.float32)
    )


def test_plan_volume_models_wire_width_exactly():
    """Eq. (7) at wire width: A/B hop bytes scale by wire/storage
    itemsize; partial-C/psum traffic stays at storage width."""
    from jax.sharding import AbstractMesh

    from repro.core import commvolume as CV

    mesh = AbstractMesh((2, 2), ("r", "c"))
    for engine in ("cannon", "gather", "onesided"):
        plan = plan_mod.plan_multiply(mesh, engine)
        v32 = CV.plan_volume(plan, 4, 8, itemsize=4.0)
        vw = CV.plan_volume(
            plan, 4, 8, itemsize=4.0,
            transport=T.PanelTransport("dense", wire="bfloat16"),
        )
        assert vw.c_volume == v32.c_volume  # C never rides the wire cast
        # A/B bytes: blocks halve, the 1-byte mask sidecar does not
        bs, nb = 8, 4
        blk32 = 4.0 * bs * bs
        blk16 = 2.0 * bs * bs
        n_blocks = v32.ab_volume / (blk32 + 1.0)
        assert vw.ab_volume == pytest.approx(n_blocks * (blk16 + 1.0))
    # the stacked twofive plan: same halving on its gather legs
    mesh3 = AbstractMesh((2, 2, 2), ("l", "r", "c"))
    plan = plan_mod.plan_multiply(mesh3, "twofive")
    v32 = CV.plan_volume(plan, 4, 8, itemsize=4.0)
    vw = CV.plan_volume(
        plan, 4, 8, itemsize=4.0,
        transport=T.PanelTransport("dense", wire="bfloat16"),
    )
    assert vw.ab_volume < v32.ab_volume
    assert vw.c_volume == v32.c_volume
