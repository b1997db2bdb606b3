"""Trip-count-aware HLO cost model: validation against XLA cost_analysis."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analyze, model_flops, TARGET_PEAKS
from repro.roofline.hlo_cost import (
    analyze_hlo,
    parse_module,
    shape_elems_bytes,
    xla_cost_analysis,
)


def test_shape_bytes():
    assert shape_elems_bytes("f32[2,3]{1,0}") == (6, 24)
    assert shape_elems_bytes("bf16[128]") == (128, 256)
    assert shape_elems_bytes("pred[]") == (1, 1)
    # tuples sum; layout/tiling annotations ignored
    assert shape_elems_bytes("(s32[], f32[4,4]{1,0:T(8,128)})") == (17, 68)
    # /*index=N*/ comments inside big tuples must not break parsing
    e, b = shape_elems_bytes("(s32[], f32[8]{0}, /*index=5*/bf16[2,2])")
    assert (e, b) == (13, 44)


def test_matches_cost_analysis_loop_free():
    @jax.jit
    def f(x, w):
        return jnp.tanh(x @ w) @ w

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = f.lower(x, w).compile()
    r = analyze_hlo(c.as_text())
    xla = xla_cost_analysis(c)
    assert r.flops == pytest.approx(xla["flops"], rel=0.01)


def test_scan_flops_scale_with_trip_count():
    """The whole reason this module exists: XLA counts while bodies once."""

    def make(n):
        def g(x, w):
            def body(cr, _):
                return jnp.tanh(cr @ w), None

            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        return jax.jit(g)

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    per = 2 * 128**3
    for n in (2, 16):
        c = make(n).lower(x, w).compile()
        r = analyze_hlo(c.as_text())
        assert r.flops == pytest.approx(n * per, rel=0.01)
        assert r.unknown_trip_loops == 0
        # XLA's aggregate number stays flat — document the discrepancy
        assert xla_cost_analysis(c)["flops"] == pytest.approx(per, rel=0.01)


def test_nested_scan_multiplies():
    def g(x, w):
        def outer(c0, _):
            def inner(c1, _):
                return c1 @ w, None

            y, _ = jax.lax.scan(inner, c0, None, length=3)
            return y, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(g).lower(x, w).compile()
    r = analyze_hlo(c.as_text())
    assert r.flops == pytest.approx(15 * 2 * 64**3, rel=0.01)


def test_parse_module_entry_and_computations():
    @jax.jit
    def f(x):
        return jnp.sum(x * 2.0)

    c = f.lower(jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    comps = parse_module(c.as_text())
    entries = [k for k, v in comps.items() if v.is_entry]
    assert len(entries) == 1


def test_collective_wire_formulas():
    from repro.roofline.hlo_cost import _collective_wire

    n = 8
    assert _collective_wire("all-gather", 800, n) == pytest.approx(700)
    assert _collective_wire("all-reduce", 800, n) == pytest.approx(1400)
    assert _collective_wire("reduce-scatter", 100, n) == pytest.approx(700)
    assert _collective_wire("all-to-all", 800, n) == pytest.approx(700)
    assert _collective_wire("collective-permute", 800, n) == 800


def test_model_flops_kinds():
    from repro.config import SHAPES
    from repro.configs import get_arch

    cfg = get_arch("olmo_1b")
    n = cfg.active_param_count()
    assert model_flops(cfg, SHAPES["train_4k"]) == pytest.approx(
        6.0 * n * 256 * 4096
    )
    assert model_flops(cfg, SHAPES["prefill_32k"]) == pytest.approx(
        2.0 * n * 32 * 32768
    )
    assert model_flops(cfg, SHAPES["decode_32k"]) == pytest.approx(2.0 * n * 128)


def test_moe_active_params_below_total():
    from repro.configs import get_arch

    cfg = get_arch("llama4_maverick_400b_a17b")
    assert cfg.active_param_count() < 0.2 * cfg.param_count()
    dense = get_arch("qwen2_72b")
    assert dense.active_param_count() == dense.param_count()


def test_analyze_end_to_end_single_device():
    """analyze() on a tiny single-device jit — terms positive & coherent."""

    @jax.jit
    def f(x, w):
        return jnp.tanh(x @ w)

    x = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    c = f.lower(x, w).compile()
    rep = analyze(c, n_chips=1, model_flops_total=2 * 512**3)
    assert rep.flops_per_device >= 2 * 512**3
    assert rep.compute_s == pytest.approx(rep.flops_per_device / TARGET_PEAKS.flops)
    assert rep.dominant in ("compute", "memory", "collective")
    assert 0.0 < rep.useful_flops_ratio <= 1.2


def test_dus_fusion_memory_not_full_buffer():
    """A scan that dynamic-update-slices a big carried buffer must be
    charged the update region, not the whole buffer, per iteration."""

    def g(xs):
        buf = jnp.zeros((64, 128, 128), jnp.float32)  # 8 MB carried

        def body(b, i):
            return jax.lax.dynamic_update_slice(
                b, jnp.ones((1, 128, 128)), (i, 0, 0)
            ), None

        buf, _ = jax.lax.scan(body, buf, jnp.arange(64))
        return buf

    c = jax.jit(g).lower(jax.ShapeDtypeStruct((1,), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())
    full = 64 * (64 * 128 * 128 * 4)  # whole buffer every iteration
    # must be well below the naive full-buffer accounting
    assert r.hbm_bytes < 0.5 * full, (r.hbm_bytes, full)


def test_dynamic_slice_memory_is_slice_sized():
    def g(x):
        def body(acc, i):
            sl = jax.lax.dynamic_slice(x, (i, 0), (1, 512))
            return acc + jnp.sum(sl), None

        out, _ = jax.lax.scan(body, jnp.zeros(()), jnp.arange(256))
        return out

    c = jax.jit(g).lower(jax.ShapeDtypeStruct((256, 512), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())
    full = 256 * (256 * 512 * 4)  # whole operand per iteration
    assert r.hbm_bytes < 0.2 * full, (r.hbm_bytes, full)


def test_attn_tile_signature_accumulates():
    def g(q, k):
        def body(acc, i):
            s = q @ k.T  # (512, 1024) "attention tile"
            return acc + jnp.sum(s), None

        out, _ = jax.lax.scan(body, jnp.zeros(()), jnp.arange(7))
        return out

    q = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
    c = jax.jit(g).lower(q, k).compile()
    r = analyze_hlo(c.as_text(), attn_tile_signature=(512, 1024))
    assert r.attn_tile_bytes > 0
    assert r.attn_tile_bytes <= r.hbm_bytes


def test_spgemm_stacks_flops_match_cost_analysis():
    """Filtered-product accounting: the compacted local stage must be
    priced by surviving products, not the dense cube — predicted vs
    cost_analysis within tolerance (satellite of the compaction PR)."""
    from repro.core import plan as plan_mod
    from repro.core.bsm import random_bsm
    from repro.core.local_mm import local_filtered_mm, pair_filter
    from repro.roofline import spgemm_dense_flops, spgemm_stacks_flops

    nb, bs = 12, 8
    a = random_bsm(jax.random.key(50), nb, bs, occupancy=0.15)
    b = random_bsm(jax.random.key(51), nb, bs, occupancy=0.15)
    thr = 1e-3

    # dense jnp backend: cost_analysis prices the full cube
    dense = jax.jit(
        lambda *xs: local_filtered_mm(*xs, threshold=thr, backend="jnp")
    )
    args = (a.blocks, a.mask, a.norms, b.blocks, b.mask, b.norms)
    measured_dense = xla_cost_analysis(dense.lower(*args).compile())["flops"]
    assert measured_dense >= spgemm_dense_flops(nb, nb, nb, bs, bs, bs)
    assert measured_dense == pytest.approx(
        spgemm_dense_flops(nb, nb, nb, bs, bs, bs), rel=0.25
    )

    # stacks backend: cost_analysis prices the padded product list
    ok = np.asarray(pair_filter(a.mask, a.norms, b.mask, b.norms, thr))
    stacks, n = plan_mod.get_product_stacks(ok)
    fn = plan_mod.get_local_compiled(
        nb, nb, nb, bs, bs, bs, jnp.float32,
        backend="stacks", capacity=stacks.capacity,
    )
    measured = xla_cost_analysis(
        fn.lower(a.blocks, b.blocks, stacks).compile()
    )["flops"]
    predicted = spgemm_stacks_flops(stacks.capacity, bs, bs, bs)
    assert measured == pytest.approx(predicted, rel=0.15)
    assert measured < 0.5 * measured_dense


def test_separable_local_stage_prices_the_full_cube():
    """At threshold 0 the jnp backend contracts the masked blocks without
    the filter cube, and still multiplies every block of it: cost_analysis
    prices the full cube, the yardstick of ``local_mm_roofline``."""
    from repro.core.bsm import random_bsm
    from repro.core.local_mm import local_filtered_mm
    from repro.roofline import spgemm_dense_flops

    nb, bs = 12, 8
    a = random_bsm(jax.random.key(52), nb, bs, occupancy=0.15)
    b = random_bsm(jax.random.key(53), nb, bs, occupancy=0.15)
    fn = jax.jit(lambda *xs: local_filtered_mm(*xs, backend="jnp"))
    lowered = fn.lower(a.blocks, a.mask, a.norms, b.blocks, b.mask, b.norms)
    assert "spgemm.local/separable" in lowered.as_text(debug_info=True)
    measured = xla_cost_analysis(lowered.compile())["flops"]
    dense = spgemm_dense_flops(nb, nb, nb, bs, bs, bs)
    assert measured >= dense
    assert measured == pytest.approx(dense, rel=0.25)


def test_local_stage_cost_dtype_and_tile_aware():
    """Satellite: the dtype/tile-aware local cost model vs cost_analysis.

    ``LocalCost.flops`` is the *logical* MAC count — what XLA's
    cost_analysis reports regardless of storage dtype (the contraction
    accumulates in f32 either way) — while ``hbm_bytes`` tracks the
    storage width and ``effective`` the MXU dtype throughput and tile
    VMEM pressure."""
    from repro.core.local_mm import local_filtered_mm, local_stage_cost
    from repro.kernels.block_spgemm import VMEM_BUDGET_BYTES

    nb, bs = 6, 16

    def mk(dtype):
        k1, k2 = jax.random.split(jax.random.key(60))
        ab = jax.random.normal(k1, (nb, nb, bs, bs)).astype(dtype)
        bb = jax.random.normal(k2, (nb, nb, bs, bs)).astype(dtype)
        m = jnp.ones((nb, nb), bool)
        n = jnp.sqrt(jnp.sum(jnp.square(ab.astype(jnp.float32)), (2, 3)))
        return ab, m, n, bb, m, n

    fn = jax.jit(lambda *xs: local_filtered_mm(*xs, backend="jnp"))
    measured = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        c = fn.lower(*mk(dtype)).compile()
        measured[dtype] = xla_cost_analysis(c)["flops"]
    lc32 = local_stage_cost(nb, nb, nb, bs, bs, bs, fill=1.0,
                            backend="jnp", dtype=jnp.float32)
    lc16 = local_stage_cost(nb, nb, nb, bs, bs, bs, fill=1.0,
                            backend="jnp", dtype=jnp.bfloat16)
    # logical flops: dtype-independent, matches cost_analysis both ways
    assert lc32.flops == lc16.flops
    assert measured[jnp.float32] == pytest.approx(lc32.flops, rel=0.25)
    assert measured[jnp.bfloat16] == pytest.approx(lc16.flops, rel=0.25)
    # storage traffic halves with the itemsize; effective cost follows the
    # doubled MXU throughput
    assert lc16.hbm_bytes == pytest.approx(lc32.hbm_bytes / 2)
    assert lc16.effective == pytest.approx(lc32.effective / 2)

    # tile awareness (pallas): sub-block tiles re-stream operands
    # (hbm grows with the tile-grid dims) at identical logical flops
    whole = local_stage_cost(1, 1, 1, 256, 256, 256, fill=1.0,
                             backend="pallas", capacity=1)
    split = local_stage_cost(1, 1, 1, 256, 256, 256, fill=1.0,
                             backend="pallas", capacity=1,
                             tile=(128, 128, 128))
    assert split.flops == whole.flops
    assert split.hbm_bytes > whole.hbm_bytes
    # a tile whose working set cannot fit VMEM is infeasible outright
    big = local_stage_cost(1, 1, 1, 1024, 1024, 1024, fill=1.0,
                           backend="pallas", capacity=1)
    assert not big.feasible and big.effective == float("inf")
    assert (
        2 * 3 * 1024 * 1024 * 4 + 1024 * 1024 * 4 > VMEM_BUDGET_BYTES
    )  # the shape above really is over budget, not a model quirk


def test_device_peaks_keyed_by_device_kind():
    """v5e resolves to its published peaks, any non-TPU device to the v5e
    modelling target, and an unknown TPU kind raises."""
    from types import SimpleNamespace

    from repro.roofline import DEVICE_PEAKS, TARGET_PEAKS, device_peaks

    v5e = device_peaks(SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite"))
    assert v5e is DEVICE_PEAKS["TPU v5 lite"]
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "Google Cloud" in v5e.source
    assert device_peaks(SimpleNamespace(platform="cpu",
                                        device_kind="cpu")) is TARGET_PEAKS
    assert device_peaks() is TARGET_PEAKS  # this process runs on the CPU
    with pytest.raises(KeyError, match="TPU v99"):
        device_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v99"))
