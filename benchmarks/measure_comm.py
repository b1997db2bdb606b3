"""Measured collective bytes of the actual TPU engines (lowered HLO).

A check of the compiled HLO on 64 fake CPU host devices, not a chip
measurement.  Standalone (sets the fake-device flag before importing jax —
run as ``JAX_PLATFORMS=cpu python benchmarks/measure_comm.py``, or via
benchmarks.run, which spawns it as a subprocess with ``JAX_PLATFORMS=cpu``
so that it never contends for an accelerator its parent holds).

Measures, per engine x mesh, the per-device collective wire bytes of one
block-sparse multiplication, and validates the paper's claims on the real
compiled programs:
  * PTP (cannon) == OS1 (onesided) A/B volume          [Table 2]
  * 2.5D volume drops vs L=1 and obeys Eq. (7)         [Fig. 3]
  * the plan-layer volume model predicts the measured bytes of every
    engine, including non-square (P_R != P_C) grids    [plan_volume]
  * compressed transport cuts a 10%-occupancy multiply's bytes-on-wire
    to <= 35% of the dense-transport bytes, and the sparsity-aware
    volume model (Eq. (7) scaled by panel occupancy, exact bucketed
    capacities) predicts the measured compressed HLO bytes too
    [plan_volume(transport=...), DESIGN.md §3]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=64 " + os.environ.get("XLA_FLAGS", "")
)

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from repro.core import plan as plan_mod  # noqa: E402
from repro.core.commvolume import plan_volume  # noqa: E402
from repro.core.engine import lower_multiply  # noqa: E402
from repro.launch.mesh import make_spgemm_mesh  # noqa: E402
from repro.roofline.hlo_cost import analyze_hlo  # noqa: E402

NB, BS = 16, 8
NB_SPARSE = 32  # the 10%-occupancy compressed-transport scenario


def measure(mesh, engine, nb=NB, **kw) -> float:
    lowered = lower_multiply(mesh, nb, BS, engine=engine, **kw)
    rep = analyze_hlo(lowered.compile().as_text(), default_group=mesh.size)
    return rep.collective_wire_bytes


def modeled(mesh, engine, nb=NB, c_layout="2d", transport=None,
            itemsize=4.0) -> float:
    plan = plan_mod.plan_multiply(mesh, engine)
    return plan_volume(plan, nb, BS, itemsize=itemsize, c_layout=c_layout,
                       transport=transport).total


def sparse_mask(nb: int) -> np.ndarray:
    """Deterministic ~10%-occupancy banded mask ((i + j) % 10 == 0)."""
    i = np.arange(nb)[:, None]
    j = np.arange(nb)[None, :]
    return np.asarray((i + j) % 10 == 0)


def compressed_rows(rows) -> None:
    """Compressed vs dense transport on the 10%-occupancy pattern: the
    wire-byte ratio and the sparsity-aware model's fidelity (the
    acceptance gates of the transport layer)."""
    mask = sparse_mask(NB_SPARSE)
    occ = float(mask.mean())
    for engine, p in (("onesided", 4), ("cannon", 4), ("gather", 4)):
        mesh = make_spgemm_mesh(p=p)
        tr = plan_mod.get_transport(mask, mask, mesh, engine,
                                    mode="compressed")
        dense = measure(mesh, engine, nb=NB_SPARSE)
        comp = measure(mesh, engine, nb=NB_SPARSE, transport=tr)
        m = modeled(mesh, engine, nb=NB_SPARSE, transport=tr)
        ratio = comp / dense
        rows.append(
            (f"measured/{engine}+ct/p{p}/bytes_per_dev", round(comp),
             f"occ {occ:.2f}: x{ratio:.2f} of dense {dense:.0f}; "
             f"model {m:.0f}: x{comp / m:.2f}")
        )
        assert ratio <= 0.35, (engine, ratio, comp, dense)
        assert 0.8 < comp / m < 1.25, (engine, comp, m)


def reduced_wire_rows(rows) -> None:
    """Reduced-precision transport on the compiled programs.

    The claim: bf16 *storage* rides the native wire at half the f32
    bytes (losslessly — nothing re-cast), and an explicit narrow *wire*
    on f32 storage cuts every A/B hop the same way, with
    ``plan_volume(itemsize=..., transport=...)`` modeling the width
    exactly.

    Platform caveat, verified empirically here: XLA:CPU's bf16
    legalization (FloatNormalization) rewrites bf16 collectives as
    ``all-gather(convert<f32>(x))`` + a semantic bf16 round-trip after —
    so on the host platform the bf16 wire measures at f32 width, a
    measurement artifact of the emulation backend (an optimization
    barrier cannot suppress it; it is type legalization, not code
    motion).  bf16 is native on TPU, where the wire stays narrow and the
    strict halving is asserted.  The f8 wire IS measurably narrower on
    CPU (legalized to f16, not f32): it demonstrates on every platform
    that the transport layer's wire cast reaches the compiled collective
    and bytes-on-wire scale with the wire element width."""
    import jax
    import jax.numpy as jnp

    from repro.core import transport as T

    on_tpu = jax.default_backend() == "tpu"
    for engine, p in (("gather", 4), ("cannon", 4), ("onesided", 4)):
        mesh = make_spgemm_mesh(p=p)
        f32 = measure(mesh, engine)
        bf = measure(mesh, engine, dtype=jnp.bfloat16)
        m = modeled(mesh, engine, itemsize=2.0)
        ratio = bf / f32
        rows.append(
            (f"measured/{engine}_bf16/p{p}/bytes_per_dev", round(bf),
             f"x{ratio:.2f} of f32 {f32:.0f}; model {m:.0f}: x{bf / m:.2f}")
        )
        if on_tpu:  # native bf16 collectives: the halving is on the wire
            assert 0.4 <= ratio <= 0.6, (engine, ratio, bf, f32)
            assert 0.8 < bf / m < 1.25, (engine, bf, m)
        else:  # XLA:CPU legalizes bf16 collectives back to f32 width
            assert ratio <= 1.01, (engine, ratio, bf, f32)
            assert 0.8 < bf / (2.0 * m) < 1.25, (engine, bf, m)

    # f8 wire on f32 storage: A/B hops narrow, measurably on EVERY
    # platform (CPU legalizes f8 collectives to f16 = still 2x under
    # f32; TPU ships 1-byte elements = 4x)
    tr = T.PanelTransport("dense", wire="float8_e4m3fn")
    mesh = make_spgemm_mesh(p=4)
    for engine in ("gather", "cannon"):
        f32 = measure(mesh, engine)
        w = measure(mesh, engine, transport=tr)
        m = modeled(mesh, engine, transport=tr)
        rows.append(
            (f"measured/{engine}_f8wire/p4/bytes_per_dev", round(w),
             f"x{w / f32:.2f} of dense {f32:.0f}; model {m:.0f}: "
             f"x{w / m:.2f}")
        )
        assert w / f32 <= 0.6, (engine, w, f32)
        if on_tpu:  # model fidelity at the un-legalized 1-byte wire
            assert 0.8 < w / m < 1.25, (engine, w, m)
        else:  # CPU ships the f8 panels at f16 width — byte-identical
            # to a 2-byte wire, which the model prices as wire=bf16
            m2 = modeled(mesh, engine,
                         transport=T.PanelTransport("dense",
                                                    wire="bfloat16"))
            assert 0.8 < w / m2 < 1.25, (engine, w, m2)


def main() -> None:
    rows = []
    for p in (2, 4):
        mesh = make_spgemm_mesh(p=p)
        vols = {e: measure(mesh, e) for e in ("cannon", "onesided", "gather")}
        for e, v in vols.items():
            m = modeled(mesh, e)
            rows.append(
                (f"measured/{e}/p{p}/bytes_per_dev", round(v),
                 f"model {m:.0f}: x{v / m:.2f}")
            )
            assert 0.8 < v / m < 1.25, (e, p, v, m)
        assert 0.7 < vols["onesided"] / vols["cannon"] <= 1.01, vols

    base = measure(make_spgemm_mesh(p=4), "onesided")
    for l in (2, 4):
        mesh = make_spgemm_mesh(p=4, l=l)
        v = measure(mesh, "twofive", c_layout="scatter")
        m = modeled(mesh, "twofive", c_layout="scatter")
        rows.append(
            (
                f"measured/twofive_L{l}/p4/bytes_per_dev",
                round(v),
                f"vs L=1 {base:.0f}: x{v / base:.2f}; model {m:.0f}",
            )
        )
        assert v < base, (l, v, base)
        assert 0.8 < v / m < 1.25, (l, v, m)

    # non-square grids: the pull engine's virtual depth (L = max/min)
    for p_r, p_c in ((2, 4), (4, 2)):
        mesh = make_spgemm_mesh(p_r=p_r, p_c=p_c)
        v1 = measure(mesh, "onesided")
        vl = measure(mesh, "twofive")
        m1 = modeled(mesh, "onesided")
        ml = modeled(mesh, "twofive")
        rows.append(
            (f"measured/onesided/p{p_r}x{p_c}/bytes_per_dev", round(v1),
             f"model {m1:.0f}: x{v1 / m1:.2f}")
        )
        rows.append(
            (f"measured/twofive_virtL/p{p_r}x{p_c}/bytes_per_dev", round(vl),
             f"vs L=1 {v1:.0f}: x{vl / v1:.2f}; model {ml:.0f}")
        )
        assert 0.8 < v1 / m1 < 1.25, (p_r, p_c, v1, m1)
        assert 0.8 < vl / ml < 1.25, (p_r, p_c, vl, ml)
        assert vl < v1, (p_r, p_c, vl, v1)  # 2.5D wins on non-square too

    compressed_rows(rows)
    reduced_wire_rows(rows)

    for name, val, note in rows:
        print(f"{name},{val},{note}")


if __name__ == "__main__":
    main()
