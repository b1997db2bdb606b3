"""2.5D communication-reducing SpGEMM engine (the paper's OSL, Algorithm 2).

Two executors, both thin interpreters of a
:class:`repro.core.plan.MultiplyPlan` (see DESIGN.md §3-§4):

``pull_executor``  — Algorithm 2 run directly on the 2D (r, c) process grid
    with the depth axis *virtual*, exactly as in the paper: the 2D block
    layout of A, B, C is retained ("no 3D redistribution"), every process
    pulls the panels of ``group_products`` from their home positions (each
    one-sided rget is a static partial permutation from the plan), performs
    its L pairwise products per tick group, and the L-1 partial-C panels
    are sent to their owners at the end.  This covers the paper's non-square
    topologies (P_R != P_C with forced L = mx/mn), L = 1 (= OS1, the
    ``onesided`` engine), and square grids with a square L.

``stacked_executor`` — the TPU mesh formulation on an (l, r, c) device
    mesh: A and B replicated over the depth axis ``l`` (the analogue of
    exposing panels in MPI windows every layer can rget from); layer ``l``
    runs a Cannon schedule over its k-chunk ``Topology.chunk(l)`` (pre-shift
    offset = the chunk start), and the partial C panels are combined with
    one psum / psum_scatter over ``l`` — the paper's L-1 partial-panel
    sends fused into the ICI-native collective.  Uneven chunks (L does not
    divide the grid side) are handled by masking ticks past a layer's chunk.

Panel movement goes through the shared transport layer
(``repro.core.transport``, DESIGN.md §3): dense (blocks + mask, norms
recomputed on arrival) or occupancy-compressed (packed blocks + one-based
indices — partial-permutation safe, so the pull formulation's rget rounds
compress too).  Both executors pipeline: the pull executor issues tick
group g+1's permutes before group g's pairwise products, the stacked
executor double-buffers its ring exactly like ``cannon.ring_body``.

Per-device communicated volume under dense transport: the pull executor
moves Eq. (7) verbatim — (V/sqrt(L))(S_A+S_B) panel pulls plus (L-1) S_C
partial sends per process; the stacked executor moves (s/L)(S_A+S_B)
panels + (L-1)/L S_C == O(1/sqrt(P L)) with P = L s^2 — the same
asymptotics in mesh coordinates (see commvolume.mesh25d_volume and
commvolume.plan_volume, which also models the compressed wire format).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.core import transport as T
from repro.core.bsm import BlockSparseMatrix
from repro.core.local_mm import local_filtered_mm


def pull_body(
    plan,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport: T.PanelTransport = T.DENSE,
):
    """The per-shard Algorithm-2 pull body: shards in, ``(cb, cm,
    calls)`` out, the C shard and the operand masks of this shard's
    local-stage calls (for ``local_mm.product_counts``); exposed so
    iteration chains can inline it into one enclosing shard_map
    (``core/signiter.py``)."""
    mm_kw = dict(
        threshold=threshold, backend=backend,
        stack_capacity=stack_capacity, tile=tile, interpret=interpret,
    )
    topo = plan.topo
    l_r, l_c, depth, s = topo.l_r, topo.l_c, topo.l, topo.side3d
    axes = plan.axes
    tr = transport

    def body(ab, am, an, bb, bm, bn):
        del an, bn  # norms are not pulled (recomputed per received panel)
        nr, nc = ab.shape[0], bb.shape[1]
        wa = ab.shape[1] // plan.ca  # A subpanel width (block cols)
        wb = bb.shape[0] // plan.cb  # B subpanel height (block rows)
        dtype = ab.dtype

        def pull_group(g):
            """Issue every one-sided pull of tick group ``g`` and return
            the accumulated dense (blocks, mask) panel per slot."""
            a_pan = [
                (
                    jnp.zeros((nr, wa) + ab.shape[2:], dtype),
                    jnp.zeros((nr, wa), bool),
                )
                for _ in range(l_r)
            ]
            b_pan = [
                (
                    jnp.zeros((wb, nc) + bb.shape[2:], dtype),
                    jnp.zeros((wb, nc), bool),
                )
                for _ in range(l_c)
            ]
            for rd in plan.a_pulls[g]:
                sl = slice(rd.q * wa, (rd.q + 1) * wa)
                st = T.ingest(tr, tr.cap_a, ab[:, sl], am[:, sl])
                rb, rm = T.dense_view(
                    tr, T.permute(st, axes, rd.pairs), nr, wa, dtype=dtype
                )
                pb, pm = a_pan[rd.slot]
                a_pan[rd.slot] = (pb + rb, pm | rm)
            for rd in plan.b_pulls[g]:
                sl = slice(rd.q * wb, (rd.q + 1) * wb)
                st = T.ingest(tr, tr.cap_b, bb[sl], bm[sl])
                rb, rm = T.dense_view(
                    tr, T.permute(st, axes, rd.pairs), wb, nc, dtype=dtype
                )
                pb, pm = b_pan[rd.slot]
                b_pan[rd.slot] = (pb + rb, pm | rm)
            return a_pan, b_pan

        # partial C accumulators, one per target panel slot t = j3*L_R + i3
        c_blk = [
            jnp.zeros((nr, nc, ab.shape[2], bb.shape[3]), dtype)
            for _ in range(depth)
        ]
        c_msk = [jnp.zeros((nr, nc), bool) for _ in range(depth)]
        calls = []

        # pipelined groups: group g+1's pulls are issued before group g's
        # pairwise products consume the current panels (rget overlap, §4)
        cur = pull_group(0)
        for g in range(plan.ticks):
            nxt = pull_group(g + 1) if g + 1 < plan.ticks else None
            a_pan, b_pan = cur
            a_n = [T.panel_norms(pb, threshold) for pb, _ in a_pan]
            b_n = [T.panel_norms(pb, threshold) for pb, _ in b_pan]
            # ---- the L pairwise panel products of this group -------------
            for i3 in range(l_r):
                for j3 in range(l_c):
                    t = j3 * l_r + i3
                    pa, pam = a_pan[i3]
                    pb, pbm = b_pan[j3]
                    dcb, dcm = local_filtered_mm(
                        pa, pam, a_n[i3], pb, pbm, b_n[j3], **mm_kw
                    )
                    c_blk[t] = c_blk[t] + dcb
                    c_msk[t] = c_msk[t] | dcm
                    calls.append((pam, pbm))
            cur = nxt

        if depth == 1:
            return c_blk[0], c_msk[0], calls

        # ---- the L-1 partial-C sends to the panel owners -----------------
        i = lax.axis_index("r")
        j = lax.axis_index("c")
        lay = (j // s) * l_r + (i // s)  # own layer == own panel slot
        stack_b = jnp.stack(c_blk)
        stack_m = jnp.stack(c_msk)
        total_b = jnp.take(stack_b, lay, axis=0)
        total_m = jnp.take(stack_m, lay, axis=0)
        for d, perm in enumerate(plan.c_rounds, start=1):
            t_send = (lay + d) % depth
            with jax.named_scope("spgemm.transport"):
                rb = lax.ppermute(
                    jnp.take(stack_b, t_send, axis=0), axes, list(perm)
                )
                rm = lax.ppermute(
                    jnp.take(stack_m, t_send, axis=0), axes, list(perm)
                )
            total_b = total_b + rb
            total_m = total_m | rm
        return total_b, total_m, calls

    return body


def pull_executor(plan, **kw):
    """Algorithm 2 as static pulls on the 2D (r, c) mesh (any valid grid)."""
    blk = P("r", "c", None, None)
    m2 = P("r", "c")
    body = pull_body(plan, **kw)
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


def stacked_body(
    plan,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    c_layout: str = "2d",
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport: T.PanelTransport = T.DENSE,
):
    """The per-shard (l, r, c)-mesh 2.5D body (exposed for chain fusion,
    like ``pull_body``, and returning ``(cb, cm, calls)`` like it, the
    ticks of the scanned ring stacked on a leading axis); with
    c_layout="2d" the returned C shard is replicated over ``l``, so
    chained multiplies compose."""
    ticks = plan.ticks
    groups = tuple(plan.layer_groups)
    uneven = len(set(groups)) > 1
    axes = plan.axes
    tr = transport

    def body(ab, am, an, bb, bm, bn):
        del an, bn  # norms never ride the ring (recomputed at compute time)
        sa, sb = am.shape, bm.shape
        adt, bdt = ab.dtype, bb.dtype  # widen wire-cast panels back
        mm_kw = dict(
            threshold=threshold, backend=backend,
            stack_capacity=stack_capacity, tile=tile, interpret=interpret,
        )
        my_groups = jnp.take(
            jnp.asarray(groups, jnp.int32), lax.axis_index("l")
        )

        def compute(pa, pb, cb, cm, t):
            xb, xm = T.dense_view(tr, pa, *sa, dtype=adt)
            yb, ym = T.dense_view(tr, pb, *sb, dtype=bdt)
            dcb, dcm = local_filtered_mm(
                xb, xm, T.panel_norms(xb, threshold),
                yb, ym, T.panel_norms(yb, threshold), **mm_kw,
            )
            if uneven:
                # mask ticks past this layer's k-chunk (uneven-L support);
                # a masked tick still computes its cube, but has no
                # products present
                active = t < my_groups
                dcb = dcb * active.astype(dcb.dtype)
                dcm = dcm & active
                xm = xm & active
            return cb + dcb, cm | dcm, (xm, ym)

        # pre-shift with per-layer chunk offset: A_ij <- A_{i, j+i+start_l},
        # B_ij <- B_{i+j+start_l, j}; one static flattened permutation.
        pa = T.permute(T.ingest(tr, tr.cap_a, ab, am), axes, plan.pre_a)
        pb = T.permute(T.ingest(tr, tr.cap_b, bb, bm), axes, plan.pre_b)

        cb = jnp.zeros(
            (ab.shape[0], bb.shape[1], ab.shape[2], bb.shape[3]), ab.dtype
        )
        cm = jnp.zeros((ab.shape[0], bb.shape[1]), bool)
        cb = lax.pcast(cb, axes, to="varying")
        cm = lax.pcast(cm, axes, to="varying")

        calls = []
        if ticks == 1:
            cb, cm, call = compute(pa, pb, cb, cm, jnp.asarray(0, jnp.int32))
            calls.append(call)
        else:
            # double-buffered ring: the hop for tick t+1 is in flight
            # before the GEMM of tick t (see cannon.ring_body)
            na = T.permute(pa, "c", plan.shift_a)
            nb_ = T.permute(pb, "r", plan.shift_b)

            def tick(carry, t):
                pa, pb, na, nb_, cb, cm = carry
                fa = T.permute(na, "c", plan.shift_a)
                fb = T.permute(nb_, "r", plan.shift_b)
                cb, cm, call = compute(pa, pb, cb, cm, t)
                return (na, nb_, fa, fb, cb, cm), call

            if ticks > 2:
                (pa, pb, na, nb_, cb, cm), scanned = lax.scan(
                    tick, (pa, pb, na, nb_, cb, cm),
                    jnp.arange(ticks - 2, dtype=jnp.int32),
                )
                calls.append(scanned)
            # last two ticks: compute only, no trailing shift
            cb, cm, call = compute(pa, pb, cb, cm,
                                   jnp.asarray(ticks - 2, jnp.int32))
            calls.append(call)
            cb, cm, call = compute(na, nb_, cb, cm,
                                   jnp.asarray(ticks - 1, jnp.int32))
            calls.append(call)

        # --- partial-C reduction over the depth axis (the L-1 sends)
        cmi = cm.astype(jnp.int32)
        with jax.named_scope("spgemm.transport"):
            if c_layout == "2d":
                return lax.psum(cb, "l"), lax.psum(cmi, "l") > 0, calls
            cb = lax.psum_scatter(cb, "l", scatter_dimension=0, tiled=True)
            cmi = lax.psum_scatter(cmi, "l", scatter_dimension=0,
                                   tiled=True)
        return cb, cmi > 0, calls

    return body


def stacked_executor(plan, *, c_layout: str = "2d", **kw):
    """The (l, r, c)-mesh 2.5D executor.

    c_layout:
      "2d"      — C replicated over l (psum), sharded (r, c): the paper's
                  layout (C lives on the 2D grid).
      "scatter" — C reduce-scattered over l along block rows: keeps the
                  result distributed over all P devices (cheaper reduction,
                  (L-1)/L instead of 2(L-1)/L traffic).
    """
    blk_in = P("r", "c", None, None)  # replicated over the unmentioned 'l'
    m2_in = P("r", "c")
    if c_layout == "2d":
        blk_out, m2_out = P("r", "c", None, None), P("r", "c")
    elif c_layout == "scatter":
        # psum_scatter splits each (r)-row panel over l: r-major, l-minor
        blk_out, m2_out = P(("r", "l"), "c", None, None), P(("r", "l"), "c")
    else:
        raise ValueError(f"unknown c_layout {c_layout!r}")
    body = stacked_body(plan, c_layout=c_layout, **kw)
    return shard_map(
        lambda *shards: body(*shards)[:2],
        mesh=plan.mesh,
        # check_vma=False: the pallas backend's pallas_call builds plain
        # ShapeDtypeStructs (no vma annotation); engine outputs are
        # oracle-tested instead (tests/_dist.py::check_engines)
        check_vma=False,
        in_specs=(blk_in, m2_in, m2_in, blk_in, m2_in, m2_in),
        out_specs=(blk_out, m2_out),
    )


def twofive_shardmap(
    mesh,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    c_layout: str = "2d",
):
    """Back-compat: compile the plan for ``mesh`` and build its executor."""
    from repro.core import plan as plan_mod

    p = plan_mod.plan_multiply(mesh, "twofive")
    return plan_mod.build_program(
        p, threshold=threshold, backend=backend, c_layout=c_layout
    )


def multiply_25d(
    a: BlockSparseMatrix,
    b: BlockSparseMatrix,
    mesh,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    c_layout: str = "2d",
) -> BlockSparseMatrix:
    """Distributed C = A . B with the 2.5D engine (plan-cached program)."""
    from repro.core import plan as plan_mod

    return plan_mod.execute(
        a, b, mesh, "twofive",
        threshold=threshold, backend=backend, c_layout=c_layout,
    )
