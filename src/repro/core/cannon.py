"""Cannon's algorithm (the paper's PTP baseline) and the streaming
one-sided variant, as thin executors of a MultiplyPlan.

PTP baseline (Algorithm 1, ``ring_executor``):
  * pre-shift A row-wise by i, B column-wise by j  (``mpi_isend/irecv`` ->
    ``lax.ppermute`` over the flattened (r, c) axis; the per-row-different
    shift is one static permutation from the plan),
  * V = p ticks of  C += A_comp . B_comp  followed by a ring shift of A
    (left along c) and B (up along r); the last tick does not shift
    (paper: ``if itick < nticks``).

One-sided streaming variant (OS1 of the paper, ``onesided``):
  * no pre-shift; at every tick each device *pulls* the A/B panels it needs
    directly from their home location (``mpi_rget`` -> a statically known
    ppermute from the home buffer).  Receiver-indexed, sender never blocks —
    on TPU the schedule is static, which subsumes the paper's
    "synchronization only on the receiver" property.  This is the L = 1
    case of the generalized pull executor in ``repro.core.twofive`` (the
    paper's OSL with L = 1 == OS1), so it also runs on non-square grids.

Communication goes through the shared transport layer
(``repro.core.transport``, DESIGN.md §3): panels move either dense
(blocks + mask; norms are never shipped — recomputed on arrival) or
occupancy-compressed (packed blocks + indices, wire bytes proportional to
occupancy), and the tick loop is double-buffered — the ring hop feeding
tick t+1 is issued *before* the GEMM of tick t, so XLA overlaps the
permute with the multiply the way the paper's non-blocking rgets do.

Both engines communicate V*(S_A+S_B) per device (PTP additionally
pre-shifts) under dense transport — exactly the PTP == OS1 volume
equality of Table 2; compressed transport scales both by panel occupancy.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core import transport as T
from repro.core.bsm import BlockSparseMatrix
from repro.core.local_mm import local_filtered_mm


def ring_body(
    plan,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport: T.PanelTransport = T.DENSE,
):
    """The per-shard PTP Cannon body: shards in, ``(cb, cm, calls)``
    out, the C shard and the operand masks of this shard's local-stage
    calls (for ``local_mm.product_counts``; the ticks of the scanned ring
    stacked on a leading axis).

    Exposed separately from the executor so iteration chains
    (``core/signiter.py``) can inline the whole multiply into ONE
    enclosing shard_map — the engine body already operates on shards;
    the executor below only wraps it for the single-multiply call path.
    """
    mm_kw = dict(
        threshold=threshold, backend=backend,
        stack_capacity=stack_capacity, tile=tile, interpret=interpret,
    )
    axes = plan.axes
    ticks = plan.ticks
    tr = transport

    def body(ab, am, an, bb, bm, bn):
        del an, bn  # norms never ride the ring (recomputed at compute time)
        sa, sb = am.shape, bm.shape
        adt, bdt = ab.dtype, bb.dtype  # widen wire-cast panels back

        def compute(pa, pb, cb, cm):
            xb, xm = T.dense_view(tr, pa, *sa, dtype=adt)
            yb, ym = T.dense_view(tr, pb, *sb, dtype=bdt)
            dcb, dcm = local_filtered_mm(
                xb, xm, T.panel_norms(xb, threshold),
                yb, ym, T.panel_norms(yb, threshold), **mm_kw,
            )
            return cb + dcb, cm | dcm, (xm, ym)

        # --- pre-shift (Algorithm 1): A_ij <- A_{i,(j+i)}, B_ij <- B_{(i+j),j}
        pa = T.permute(T.ingest(tr, tr.cap_a, ab, am), axes, plan.pre_a)
        pb = T.permute(T.ingest(tr, tr.cap_b, bb, bm), axes, plan.pre_b)

        cb = jnp.zeros(
            (ab.shape[0], bb.shape[1], ab.shape[2], bb.shape[3]), ab.dtype
        )
        cm = jnp.zeros((ab.shape[0], bb.shape[1]), bool)
        cb = lax.pcast(cb, axes, to="varying")
        cm = lax.pcast(cm, axes, to="varying")

        if ticks == 1:
            cb, cm, call = compute(pa, pb, cb, cm)
            return cb, cm, [call]

        # --- double-buffered ring: the hop for tick t+1 is in flight
        # before the GEMM of tick t runs (paper §4 comm/compute overlap)
        na = T.permute(pa, "c", plan.shift_a)
        nb_ = T.permute(pb, "r", plan.shift_b)

        def tick(carry, _):
            pa, pb, na, nb_, cb, cm = carry
            fa = T.permute(na, "c", plan.shift_a)
            fb = T.permute(nb_, "r", plan.shift_b)
            cb, cm, call = compute(pa, pb, cb, cm)
            return (na, nb_, fa, fb, cb, cm), call

        calls = []
        if ticks > 2:
            (pa, pb, na, nb_, cb, cm), scanned = lax.scan(
                tick, (pa, pb, na, nb_, cb, cm), None, length=ticks - 2
            )
            calls.append(scanned)
        # last two ticks: compute only, no trailing shift (itick==nticks)
        cb, cm, call = compute(pa, pb, cb, cm)
        calls.append(call)
        cb, cm, call = compute(na, nb_, cb, cm)
        calls.append(call)
        return cb, cm, calls

    return body


def ring_executor(plan, **kw):
    """The PTP Cannon engine: plan's pre-shift + V ring hops."""
    blk = P("r", "c", None, None)
    m2 = P("r", "c")
    body = ring_body(plan, **kw)
    return shard_map(
        lambda *shards: body(*shards)[:2],
        mesh=plan.mesh,
        # check_vma=False: the pallas backend's pallas_call builds plain
        # ShapeDtypeStructs (no vma annotation); engine outputs are
        # oracle-tested instead (tests/_dist.py::check_engines)
        check_vma=False,
        in_specs=(blk, m2, m2, blk, m2, m2),
        out_specs=(blk, m2),
    )


def cannon_shardmap(mesh, *, threshold: float = 0.0, backend: str = "jnp"):
    """Back-compat: plan + executor for the PTP Cannon engine."""
    from repro.core import plan as plan_mod

    p = plan_mod.plan_multiply(mesh, "cannon")
    return plan_mod.build_program(
        p, threshold=threshold, backend=backend, c_layout="2d"
    )


def onesided_shardmap(mesh, *, threshold: float = 0.0, backend: str = "jnp"):
    """Back-compat: plan + executor for the OS1 pull engine."""
    from repro.core import plan as plan_mod

    p = plan_mod.plan_multiply(mesh, "onesided")
    return plan_mod.build_program(
        p, threshold=threshold, backend=backend, c_layout="2d"
    )


def multiply_2d(
    a: BlockSparseMatrix,
    b: BlockSparseMatrix,
    mesh,
    *,
    engine: str = "cannon",
    threshold: float = 0.0,
    backend: str = "jnp",
) -> BlockSparseMatrix:
    """Distributed C = A . B on a 2D (r, c) mesh (plan-cached program)."""
    from repro.core import plan as plan_mod

    return plan_mod.execute(
        a, b, mesh, engine, threshold=threshold, backend=backend
    )
