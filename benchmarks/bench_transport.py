"""Transport-layer benchmark: compressed vs dense panels across an
occupancy sweep (bytes on the wire + wall time), on the application-
pattern corpus (``repro.tuner.corpus``).

Per (corpus entry, engine) the sweep measures:

  * **wire bytes** — per-device collective bytes of the compiled HLO,
    dense vs compressed transport (the same measurement
    ``benchmarks/measure_comm.py`` asserts): compressed must reach
    <= 35% of dense on at least one low-occupancy entry — the
    load-balanced uniform family; distance-correlated families
    (banded/decay) concentrate occupied blocks in diagonal panels, so
    their per-panel capacity is the densest panel's count and their
    ratio is reported, not gated;
  * **host wall time** — min-of-reps multiply wall time on the fake-
    device CPU mesh, both modes.  Reported for the trajectory, NOT
    asserted: XLA's host "collectives" are intra-process memcpys, so
    byte savings do not convert to wall time here the way they do on a
    real interconnect (the pack/unpack scatter work is all the host
    sees);
  * **projected interconnect-bound wall time** — the measured HLO bytes
    fed through the same roofline cost model the tuner ranks with
    (bytes / ICI bandwidth + per-tick dispatch + local FLOPs at the compacted
    backend's occupancy): the transport PR's headline — >= 1.3x over
    the dense path on at least one low-occupancy corpus entry — is
    asserted on this projection, with the measured byte ratio as its
    load-bearing input.

Also re-checks bit-exactness (compressed == dense results) on every
entry it times — never report numbers off a wrong result.

Results go to BENCH_transport.json (CI perf-trajectory series;
``--smoke`` shrinks the sweep).

    python benchmarks/bench_transport.py [--smoke] [--out BENCH_transport.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=16 " + os.environ.get("XLA_FLAGS", "")
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import plan as plan_mod  # noqa: E402
from repro.core.commvolume import plan_volume  # noqa: E402
from repro.core.engine import lower_multiply, multiply  # noqa: E402
from repro.core.local_mm import backend_local_cost  # noqa: E402
from repro.launch.mesh import make_spgemm_mesh  # noqa: E402
from repro.roofline import TARGET_PEAKS  # noqa: E402
from repro.roofline.hlo_cost import analyze_hlo  # noqa: E402
from repro.tuner.corpus import CorpusEntry  # noqa: E402
from repro.tuner.features import featurize  # noqa: E402

THRESHOLD = 1e-6
LOW_OCC = 0.12  # entries at or below this block occupancy are "low"


def entries(smoke: bool) -> list[CorpusEntry]:
    # shards must hold enough blocks that the packing-bucket floor does
    # not dominate (nb=32 on the 4x4 mesh -> 64-block shards).  The
    # uniform (load-balanced) family is where per-panel capacities track
    # global occupancy; the distance-correlated families show the
    # diagonal-concentration effect (capacity = the densest panel).
    nb, bs = (32, 8) if smoke else (32, 16)
    out = [
        CorpusEntry("uniform_sparse", "uniform", nb, bs,
                    occupancy=0.05, seed=17),
        CorpusEntry("exp_decay_sparse", "exp_decay", nb, bs,
                    occupancy=0.05, seed=13),
        CorpusEntry("exp_decay_mid", "exp_decay", nb, bs,
                    occupancy=0.2, seed=14),
    ]
    if not smoke:
        out.append(CorpusEntry("uniform_10", "uniform", nb, bs,
                               occupancy=0.1, seed=18))
        out.append(CorpusEntry("dft_chain_narrow", "dft_chain", nb, bs,
                               bandwidth=max(1, nb // 16), seed=11))
        out.append(CorpusEntry("exp_decay_filled", "exp_decay", nb, bs,
                               occupancy=0.45, seed=15))
        out.append(CorpusEntry("zipf_hub", "zipf", nb, bs,
                               occupancy=0.1, zipf_alpha=1.4, seed=16))
    return out


def wire_bytes(mesh, nb: int, bs: int, engine: str, transport) -> float:
    lowered = lower_multiply(mesh, nb, bs, engine=engine,
                             threshold=THRESHOLD, transport=transport)
    rep = analyze_hlo(lowered.compile().as_text(), default_group=mesh.size)
    return rep.collective_wire_bytes


def walltime(run, reps: int) -> float:
    out = run()
    jax.block_until_ready((out.blocks, out.mask, out.norms))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run()
        jax.block_until_ready((out.blocks, out.mask, out.norms))
        best = min(best, time.perf_counter() - t0)
    return best


def projected_s(bytes_on_wire: float, plan, feats, ndev: int) -> float:
    """Interconnect-bound roofline projection: measured wire bytes at
    ICI rate + compacted-backend local FLOPs (identical in both modes —
    only the bytes differ).  The tuner's per-tick dispatch term is
    deliberately excluded: it is identical in both modes AND the
    double-buffered schedule exists precisely to hide it behind the
    local GEMM, so the non-overlappable cost is bytes + FLOPs."""
    local = backend_local_cost(
        feats.nb_r, feats.nb_k, feats.nb_c,
        feats.bs_r, feats.bs_k, feats.bs_c,
        fill=feats.product_fill, backend="stacks",
    )
    return (bytes_on_wire / TARGET_PEAKS.ici_bw
            + local / ndev / TARGET_PEAKS.flops)


def bench_entry(entry: CorpusEntry, mesh, engine: str, reps: int) -> dict:
    a, b = entry.build()
    feats = featurize(a, b, THRESHOLD)
    mask_a = np.asarray(a.mask, bool)
    mask_b = np.asarray(b.mask, bool)
    tr = plan_mod.get_transport(mask_a, mask_b, mesh, engine,
                                mode="compressed")
    plan = plan_mod.plan_multiply(mesh, engine)

    by_dense = wire_bytes(mesh, entry.nb, entry.bs, engine, None)
    by_comp = wire_bytes(mesh, entry.nb, entry.bs, engine, tr)
    model_comp = plan_volume(plan, entry.nb, entry.bs,
                             transport=tr).total

    def run(transport):
        return multiply(a, b, mesh, engine=engine, threshold=THRESHOLD,
                        backend="stacks", transport=transport)

    # correctness first: compressed must equal dense bitwise
    cd, cc = run("dense"), run(tr)
    np.testing.assert_array_equal(np.asarray(cc.blocks),
                                  np.asarray(cd.blocks))
    np.testing.assert_array_equal(np.asarray(cc.mask), np.asarray(cd.mask))

    wt_dense = walltime(lambda: run("dense"), reps)
    wt_comp = walltime(lambda: run(tr), reps)
    ndev = mesh.size
    proj_dense = projected_s(by_dense, plan, feats, ndev)
    proj_comp = projected_s(by_comp, plan, feats, ndev)
    return {
        "entry": entry.name,
        "engine": engine,
        "nb": entry.nb,
        "bs": entry.bs,
        "occupancy": feats.occ_a,
        "cap_a": tr.cap_a,
        "cap_b": tr.cap_b,
        "bytes_dense": by_dense,
        "bytes_compressed": by_comp,
        "bytes_ratio": by_comp / by_dense,
        "model_bytes_compressed": model_comp,
        "host_ms_dense": wt_dense * 1e3,
        "host_ms_compressed": wt_comp * 1e3,
        "host_speedup": wt_dense / wt_comp,
        "projected_us_dense": proj_dense * 1e6,
        "projected_us_compressed": proj_comp * 1e6,
        "projected_speedup": proj_dense / proj_comp,
    }


def run_bench(smoke: bool) -> dict:
    mesh = make_spgemm_mesh(p=4)
    reps = 2 if smoke else 4
    engines = ("onesided",) if smoke else ("onesided", "gather")
    rows = []
    for entry in entries(smoke):
        for engine in engines:
            rows.append(bench_entry(entry, mesh, engine, reps))
    return {"smoke": smoke, "mesh": "4x4", "threshold": THRESHOLD,
            "rows": rows}


def check(result: dict) -> None:
    rows = result["rows"]
    low = [r for r in rows if r["occupancy"] <= LOW_OCC]
    assert low, "sweep has no low-occupancy entry"
    for r in rows:
        # the sparsity-aware model predicts the compressed HLO bytes
        assert 0.8 < r["bytes_compressed"] / r["model_bytes_compressed"] \
            < 1.25, (r["entry"], r["engine"])
    # bytes-on-wire collapse to <= 35% of dense on a load-balanced
    # low-occupancy entry (diagonal-concentrated families keep panel
    # capacities at the densest panel — reported, not gated)
    assert any(r["bytes_ratio"] <= 0.35 for r in low), [
        (r["entry"], r["engine"], r["bytes_ratio"]) for r in low
    ]
    # >= 1.3x projected interconnect-bound improvement on at least one
    # low-occupancy corpus entry (measured bytes driving the projection)
    best = max(r["projected_speedup"] for r in low)
    assert best >= 1.3, [
        (r["entry"], r["engine"], r["projected_speedup"]) for r in low
    ]
    # the byte saving must shrink as fill rises (sanity of the sweep)
    by_occ = sorted(rows, key=lambda r: r["occupancy"])
    assert by_occ[0]["bytes_ratio"] < by_occ[-1]["bytes_ratio"], (
        by_occ[0], by_occ[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    result = run_bench(args.smoke)
    check(result)
    for r in result["rows"]:
        print(f"transport/{r['entry']}/{r['engine']}/bytes_ratio,"
              f"{r['bytes_ratio']:.3f},occ {r['occupancy']:.2f}; "
              f"projected x{r['projected_speedup']:.2f}; "
              f"host x{r['host_speedup']:.2f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
