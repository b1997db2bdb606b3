"""All-gather ("pull from home") SpGEMM engine.

The TPU-native rendering of the paper's one-sided access pattern: every
device pulls the A panels of its block row (gather along ``c``) and the B
panels of its block column (gather along ``r``) directly from their home
positions — no pre-shift, no sender-side synchronization, 2D data layout
retained.  The per-device communicated volume equals Cannon's
(V * (S_A + S_B)), matching the PTP == OS1 equality in Table 2, but the
panels arrive as one fused ICI all-gather instead of V ring hops, so the
latency term is V times smaller (TPU all-gathers are the native multicast).

Memory: holds the full gathered row/column (p panels) instead of DBCSR's
double buffers — the TPU trade (VMEM/HBM is provisioned for this; the
kernel consumes the gathered panels tile by tile).

Works for any (r, c) grid, including the paper's non-square topologies.
Like the other engines it is a thin executor of a MultiplyPlan (the plan
carries no permutation tables here — the schedule is one fused collective —
but routing through the plan layer shares the program cache and the
predicted-volume model).
"""
from __future__ import annotations

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import transport as T
from repro.core.bsm import BlockSparseMatrix
from repro.core.local_mm import local_filtered_mm


def gather_body(
    plan,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport: T.PanelTransport = T.DENSE,
):
    """The per-shard all-gather body (exposed for chain fusion — the
    panel all-gathers here are the engine's *internal* pulls, not a
    C gather; C comes home sharded).  Returns ``(cb, cm, calls)``, the
    C shard and the operand masks of its one local-stage call (for
    ``local_mm.product_counts``).

    The gathers go through the transport layer: dense moves blocks +
    mask (norms recomputed after the gather), compressed all-gathers
    each home shard's packed buffer — still one fused collective pair
    per operand, with bytes proportional to occupancy.
    """
    tr = transport

    def body(ab, am, an, bb, bm, bn):
        del an, bn  # norms are not gathered (recomputed from the blocks)
        # pull the full block row of A / block column of B from home
        ab, am = T.all_gather_panels(tr, tr.cap_a, ab, am, "c", axis=1)
        bb, bm = T.all_gather_panels(tr, tr.cap_b, bb, bm, "r", axis=0)
        cb, cm = local_filtered_mm(
            ab, am, T.panel_norms(ab, threshold),
            bb, bm, T.panel_norms(bb, threshold),
            threshold=threshold, backend=backend,
            stack_capacity=stack_capacity, tile=tile, interpret=interpret,
        )
        return cb, cm, [(am, bm)]

    return body


def gather_executor(plan, **kw):
    blk = P("r", "c", None, None)
    m2 = P("r", "c")
    body = gather_body(plan, **kw)
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


def gather_shardmap(mesh, *, threshold: float = 0.0, backend: str = "jnp"):
    """Back-compat: plan + executor for the all-gather engine."""
    from repro.core import plan as plan_mod

    p = plan_mod.plan_multiply(mesh, "gather")
    return plan_mod.build_program(
        p, threshold=threshold, backend=backend, c_layout="2d"
    )


def multiply_gather(
    a: BlockSparseMatrix,
    b: BlockSparseMatrix,
    mesh,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
) -> BlockSparseMatrix:
    from repro.core import plan as plan_mod

    return plan_mod.execute(
        a, b, mesh, "gather", threshold=threshold, backend=backend
    )
