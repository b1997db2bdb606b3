"""Engine dispatcher: the public ``multiply`` entry point.

Engines (paper terminology in parentheses):

  cannon    — 2D Cannon, ring point-to-point shifts (PTP, Algorithm 1)
  onesided  — 2D pull-from-home streaming, no pre-shift (OS1, Alg. 2, L=1);
              any (r, c) grid
  gather    — 2D pull-from-home via fused all-gather (TPU-native OS1)
  twofive   — 2.5D with depth axis L (OSL, Algorithm 2): on an (l, r, c)
              mesh the stacked formulation (uneven L supported); on a 2D
              (r, c) mesh the pull formulation with a *virtual* depth axis,
              including non-square grids (L = mx/mn forced, paper §3)

Every engine executes a compiled :class:`repro.core.plan.MultiplyPlan`; the
jitted programs are LRU-cached (``repro.core.plan.get_compiled``) so the
hot paths — sign iteration, serving, benchmark loops — never retrace or
re-lower after the first multiply.

Local backends (``core/local_mm.py``): ``jnp`` dense masked einsum,
``stacks`` compacted gather-GEMM-scatter, ``pallas`` the scalar-prefetch
TPU kernel — plus ``"auto"``, the occupancy-driven heuristic: when the
sparsity pattern is concrete, the exact surviving-product fill is measured
on the host and the compacted backends are picked below
``AUTO_DENSE_FILL`` (DBCSR behaves the same way: stacks always, but its
batched GEMM only wins when occupancy is low; dense MXU einsum wins when
the cube is mostly full).  Auto also derives a *sound* static capacity for
the compacted backends — exact count single-device, per-device bound
distributed — so compaction never drops products.

A single-device reference (`multiply_reference`) implements the identical
filtered semantics without any mesh — the oracle for every engine test.
The compacted single-device path runs through the plan layer's
pattern-signature cache (``plan.get_product_stacks``): a repeated pattern
re-uses both its product list and its compiled program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod
from repro.core.bsm import (
    BlockSparseMatrix,
    ShardedBSM,
    block_norms,
    filter_bsm,
)
from repro.core.local_mm import (
    GATHER_OVERHEAD,
    choose_local_backend,
    local_filtered_mm,
)
from repro.kernels.stacks import bucket_capacity
from repro.obs import span

ENGINES = ("cannon", "onesided", "gather", "twofive")

# surviving-product fill at which the dense einsum and the compacted
# backends break even under the shared analytic model
# (``local_mm.backend_local_cost``); kept as a named constant for tests
AUTO_DENSE_FILL = 1.0 / GATHER_OVERHEAD


def _is_concrete(*arrays) -> bool:
    return not any(isinstance(x, jax.core.Tracer) for x in arrays)


def _host_pair_filter(a: BlockSparseMatrix, b: BlockSparseMatrix,
                      threshold: float) -> np.ndarray:
    """Concrete (i, k, j) filter cube on the host (numpy)."""
    from repro.kernels.stacks import pair_cube

    with span("spgemm.pair_walk"):
        return pair_cube(a.mask, b.mask, a.norms, b.norms, threshold)


def choose_backend(a: BlockSparseMatrix, b: BlockSparseMatrix,
                   threshold: float = 0.0, *, ok=None) -> str:
    """Cost-model-driven local-backend selection (the ``"auto"`` policy).

    Delegates to ``local_mm.choose_local_backend`` (the shared analytic
    model, also used by the tuner — DESIGN.md §6) at the exact bucketed
    capacity: dense einsum when the full-cube MXU work undercuts the
    compacted path's gathered products, or when the ``stacks`` list
    would not fit the device; compacted list otherwise, in
    ``local_mm.compacted_backend``'s flavor (the Pallas kernel on a TPU
    where the block shape has a lane-aligned tile, the XLA
    gather-GEMM-scatter elsewhere).  Traced inputs (inside
    someone else's jit) fall back to ``jnp`` — no concrete pattern to
    compact.

    ``ok`` — optional precomputed concrete filter cube, so one host walk
    serves both this heuristic and the capacity bound in ``multiply``.
    """
    if ok is None:
        if not _is_concrete(a.mask, a.norms, b.mask, b.norms):
            return "jnp"
        ok = _host_pair_filter(a, b, threshold)
    fill = float(ok.mean()) if ok.size else 0.0
    return choose_local_backend(
        a.nb_r, a.nb_c, b.nb_c, a.bs_r, a.bs_c, b.bs_c, fill, a.dtype,
        capacity=bucket_capacity(int(ok.sum())),
    )


# distributed per-device capacity bounds live in the plan layer
# (plan.device_stack_bound / plan.get_device_capacity — LRU-cached on the
# pattern signature alongside the product lists, cleared by clear_cache)
device_stack_bound = plan_mod.device_stack_bound


@partial(jax.jit, static_argnames=("threshold", "backend", "stack_capacity",
                                   "tile", "interpret"))
def _multiply_reference_jit(
    a: BlockSparseMatrix,
    b: BlockSparseMatrix,
    threshold: float = 0.0,
    backend: str = "jnp",
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
) -> BlockSparseMatrix:
    cb, cm = local_filtered_mm(
        a.blocks,
        a.mask,
        a.norms,
        b.blocks,
        b.mask,
        b.norms,
        threshold=threshold,
        backend=backend,
        stack_capacity=stack_capacity,
        tile=tile,
        interpret=interpret,
    )
    return BlockSparseMatrix(blocks=cb, mask=cm, norms=block_norms(cb))


def _reference_compacted(
    a: BlockSparseMatrix,
    b: BlockSparseMatrix,
    threshold: float,
    backend: str,
    tile: tuple[int, int, int] | None,
    interpret: bool | None,
    ok: np.ndarray | None = None,
) -> BlockSparseMatrix:
    """Single-device stacks/pallas path over the plan layer's caches.

    Host compaction with the *exact* bucketed capacity, product list
    cached per pattern signature, program cached per capacity bucket —
    DBCSR's stack generation amortized across repeated multiplies.
    """
    if ok is None:
        ok = _host_pair_filter(a, b, threshold)
    ni, nk, nj = ok.shape
    stacks, _n = plan_mod.get_product_stacks(ok)
    cm = jnp.asarray(ok.any(axis=1))
    if stacks.capacity == 0:
        cb = jnp.zeros((ni, nj, a.bs_r, b.bs_c), a.dtype)
        return BlockSparseMatrix(blocks=cb, mask=cm, norms=block_norms(cb))
    fn = plan_mod.get_local_compiled(
        ni, nk, nj, a.bs_r, a.bs_c, b.bs_c, a.dtype,
        backend=backend, capacity=stacks.capacity, tile=tile,
        interpret=interpret,
    )
    cb = fn(a.blocks, b.blocks, stacks)
    # the pallas grid only visits tiles with surviving products
    cb = jnp.where(cm[:, :, None, None], cb, jnp.zeros((), cb.dtype))
    return BlockSparseMatrix(blocks=cb, mask=cm, norms=block_norms(cb))


def multiply_reference(
    a: BlockSparseMatrix,
    b: BlockSparseMatrix,
    threshold: float = 0.0,
    backend: str = "jnp",
    *,
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    ok: np.ndarray | None = None,
) -> BlockSparseMatrix:
    """Single-device filtered block multiply (oracle).

    ``ok`` — optional precomputed concrete filter cube; one host walk then
    serves backend choice, compaction and the C mask.
    """
    concrete = _is_concrete(a.blocks, a.mask, a.norms, b.mask, b.norms)
    if backend == "auto":
        if ok is None and concrete:
            ok = _host_pair_filter(a, b, threshold)
        backend = choose_backend(a, b, threshold, ok=ok)
    if backend in ("stacks", "pallas") and concrete and stack_capacity is None:
        return _reference_compacted(a, b, threshold, backend, tile,
                                    interpret, ok)
    return _multiply_reference_jit(
        a, b, threshold, backend,
        stack_capacity=stack_capacity, tile=tile, interpret=interpret,
    )


def multiply(
    a: BlockSparseMatrix | ShardedBSM,
    b: BlockSparseMatrix | ShardedBSM,
    mesh=None,
    *,
    engine: str = "twofive",
    threshold: float = 0.0,
    filter_eps: float | None = None,
    backend: str | None = None,
    c_layout: str = "2d",
    l: int | None = None,
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport=None,
    assignment=None,
    envelope=None,
) -> BlockSparseMatrix | ShardedBSM:
    """Distributed filtered C = A . B.

    engine     — one of ``ENGINES``, or ``"auto"``: the pattern-aware
                 tuner (``repro.tuner``) picks engine, depth L, local
                 backend, stack capacity and panel transport from the
                 concrete sparsity pattern — analytic Eq. 6/7 pruning,
                 then short measured trials, with winners persisted in
                 the tuning DB so later runs resolve without timing
                 anything.
    threshold  — on-the-fly filter: skip block products with
                 norm(A_ik) * norm(B_kj) <= threshold.
    filter_eps — post-multiplication filter: drop result blocks with
                 norm <= filter_eps (defaults to ``threshold``).
    l          — depth override for the 2D-mesh ``twofive`` pull engine
                 (square grids; non-square grids force L = mx/mn).
    backend    — local stage: "jnp" | "stacks" | "pallas" | "auto"
                 (occupancy heuristic, see ``choose_backend``).  The
                 default (None) is "jnp" for static engines; under
                 ``engine="auto"`` it leaves the backend to the tuner —
                 pass an explicit backend to pin it.
    stack_capacity — static surviving-product bound for the compacted
                 backends; derived automatically from the concrete
                 pattern when omitted (exact single-device, sound
                 per-device bound distributed).
    interpret  — Pallas execution mode (None = platform auto-detect).
    transport  — panel transport: a ``transport.PanelTransport``, or
                 "auto" | "dense" | "compressed" (None = the configured
                 default, ``REPRO_TRANSPORT``/auto).  "auto" packs only
                 occupied blocks into bounded buffers when the pattern's
                 fill is low (wire bytes scale with occupancy — DESIGN.md
                 §3) and keeps the bit-exact dense panels otherwise; the
                 plan layer derives sound per-panel capacities from the
                 concrete masks (``plan.get_transport``).
    assignment — block→device distribution: None (identity, or under
                 ``engine="auto"`` the tuner's choice), a mode string
                 ("identity" | "randomized" | "nnz_greedy" — derived
                 deterministically from the concrete masks), or a ready
                 ``distribute.Assignment``.  Replicated operands are
                 permuted inside the compiled program (results come back
                 in original block coordinates); sharded operands already
                 carry their layout from ``shard_bsm`` and an explicit
                 value here can only confirm it.  Requires a mesh —
                 single-device multiplies have no devices to balance.
    envelope   — optional ``core.envelope.Envelope``: derive every
                 pattern-dependent static (stack capacity, transport
                 capacities, the auto-backend fill) from the envelope
                 instead of walking THIS call's concrete pattern.  A
                 stream of drifting patterns inside one envelope then
                 shares one compiled program (stable capacity buckets,
                 no per-call host cube walk) — the concrete mask does
                 the per-call work as data.  Concrete operands are
                 checked against the envelope (cheap 2D subset test); a
                 pattern that escaped it falls back to the exact
                 per-pattern derivation and counts ``drift_retunes`` in
                 ``cache_stats()``.  Traced operands trust the envelope
                 (there is no concrete pattern to check — the caller
                 guarantees coverage, as fused chains do by
                 construction).

    ShardedBSM operands take the device-resident path: the multiply runs
    on the shards (``plan.execute_sharded``) and returns a ShardedBSM —
    no gather, no re-shard; post-filtering happens shard-local with
    derived norms.  Both operands must be sharded on the same mesh.
    """
    with span("spgemm.multiply"):
        if engine != "auto" and engine not in ENGINES:
            raise ValueError(
                f"unknown engine {engine!r}; one of {ENGINES} or 'auto'"
            )
        env = envelope
        if (
            env is not None
            and _is_concrete(a.mask, b.mask)
            and not env.covers(np.asarray(a.mask, bool),
                               np.asarray(b.mask, bool))
        ):
            # the pattern drifted out of its envelope: abandon the warm path
            # and re-derive everything exactly for this call
            plan_mod.note_drift_retune()
            env = None
        # None = the caller left the backend open: static engines get the
        # historical "jnp" default, the tuner gets the full backend space
        pinned = backend if backend not in (None, "auto") else None
        if backend is None:
            backend = "jnp"
        if isinstance(a, ShardedBSM) or isinstance(b, ShardedBSM):
            if not (isinstance(a, ShardedBSM) and isinstance(b, ShardedBSM)):
                raise TypeError(
                    "mixed ShardedBSM / BlockSparseMatrix operands; shard both "
                    "(bsm.shard_bsm) or neither"
                )
            if a.mesh is not b.mesh and a.mesh != b.mesh:
                raise ValueError("operands sharded on different meshes")
            if mesh is not None and mesh is not a.mesh and mesh != a.mesh:
                raise ValueError("mesh argument conflicts with operand mesh")
            if c_layout != "2d":
                raise ValueError("sharded chains require c_layout='2d'")
            if engine == "auto":
                # full tuner resolution: one host walk of the device-resident
                # pattern, amortized by the decision cache across repeats.
                # assign is pinned to identity — the layout decision was made
                # at shard_bsm time and the tuner sees the permuted pattern.
                from repro import tuner

                dec = tuner.autotune(
                    a, b, a.mesh, threshold=threshold, backend=pinned,
                    l=l, interpret=interpret,
                    transport=_transport_pin(transport),
                    assign="identity", envelope=env,
                )
                engine, l, backend = dec.engine, dec.l, dec.backend
                if stack_capacity is None:
                    stack_capacity = dec.stack_capacity
                if tile is None:
                    tile = dec.tile
                if transport is None or transport == "auto":
                    # adopt the tuner's measured mode (as resolve_multiply
                    # does) — "auto" left in place would re-resolve through
                    # the static crossover and could contradict the trials
                    transport = dec.transport
            elif backend == "auto":
                if env is not None:
                    # envelope fill decides without touching device masks
                    backend = choose_backend(a, b, threshold,
                                             ok=np.asarray(env.cube))
                else:
                    # the auto heuristic walks the concrete pattern on the
                    # host — a round-trip the device-resident path avoids
                    backend = "jnp"
            if backend in ("stacks", "pallas") and stack_capacity is None:
                if env is not None:
                    # envelope capacity: stable across the whole drifting
                    # stream (one program), no per-call mask sync
                    stack_capacity = plan_mod.get_device_capacity(
                        env.cube, a.mesh, engine)
                elif _is_concrete(a.mask, a.norms, b.mask, b.norms):
                    # sound per-device bound from the concrete (and, under a
                    # non-identity assignment, already-permuted) shard masks
                    # — without it the compacted program pads every device to
                    # the full cube and the balanced layout's smaller hot
                    # device buys nothing.  Costs the same per-call host mask
                    # sync the auto transport resolution below already pays;
                    # pass an explicit stack_capacity to skip it.
                    stack_capacity = plan_mod.get_device_capacity(
                        _host_pair_filter(a, b, threshold), a.mesh, engine)
            if env is not None:
                transport = _envelope_transport(
                    env.mask_a, env.mask_b, transport, a.mesh, engine, l)
            with span("spgemm.dispatch"):
                c = plan_mod.execute_sharded(
                    a, b, engine,
                    threshold=threshold, backend=backend, l=l,
                    stack_capacity=stack_capacity, tile=tile,
                    interpret=interpret, transport=transport,
                    assignment=assignment,
                )
            eps = threshold if filter_eps is None else filter_eps
            return c.filter(eps) if eps > 0.0 else c
        if mesh is None and assignment not in (None, "identity"):
            raise ValueError(
                "assignment needs a mesh: a block→device distribution has no "
                "meaning on a single device"
            )
        if engine == "auto":
            if mesh is None:
                engine = "twofive"  # single-device: the engine is vestigial
            else:
                # delegate the whole (engine, L, backend, capacity, transport,
                # assignment) decision to the tuner (repro.tuner, DESIGN.md §6)
                from repro import tuner

                dec = tuner.autotune(
                    a, b, mesh, threshold=threshold, backend=pinned,
                    l=l, interpret=interpret,
                    transport=_transport_pin(transport),
                    assign=_assign_pin(assignment), envelope=env,
                )
                engine, l, backend = dec.engine, dec.l, dec.backend
                if stack_capacity is None:
                    stack_capacity = dec.stack_capacity
                if tile is None:
                    tile = dec.tile
                if transport is None or transport == "auto":
                    # adopt the tuner's measured mode (see the sharded path)
                    transport = dec.transport
                if assignment is None:
                    # adopt the tuner's winning layout (identity when the
                    # pattern is already balanced)
                    assignment = dec.assign
        # the layout every capacity bound below must be derived from
        asg = None
        if mesh is not None:
            asg = plan_mod.resolve_assignment(assignment, a, b, mesh)
        # one host walk of the concrete filter cube serves both the auto
        # heuristic and the distributed capacity bound; an envelope replaces
        # the walk entirely (its union cube is the bound for the stream)
        ok_np = None
        if (
            env is None
            and (backend == "auto" or (backend in ("stacks", "pallas")
                                       and mesh is not None
                                       and stack_capacity is None))
            and _is_concrete(a.mask, a.norms, b.mask, b.norms)
        ):
            ok_np = _host_pair_filter(a, b, threshold)
        if backend == "auto":
            backend = choose_backend(
                a, b, threshold,
                ok=np.asarray(env.cube) if env is not None else ok_np,
            )
        if mesh is None:
            if (
                env is not None
                and backend in ("stacks", "pallas")
                and stack_capacity is None
            ):
                # static envelope capacity routes the whole stream through
                # one traced compacted program (mask-as-data, no host walks)
                stack_capacity = env.local_capacity()
            with span("spgemm.dispatch"):
                c = multiply_reference(
                    a, b, threshold=threshold, backend=backend,
                    stack_capacity=stack_capacity, tile=tile,
                    interpret=interpret, ok=ok_np,
                )
        else:
            if backend in ("stacks", "pallas") and stack_capacity is None:
                # capacity must cover the PERMUTED pattern's hottest device —
                # the layout the engine actually partitions
                ok_cap = None
                if env is not None:
                    ok_cap = np.asarray(env.cube)
                elif ok_np is not None:
                    ok_cap = ok_np
                if ok_cap is not None:
                    if asg is not None:
                        from repro.core.distribute import permute_cube

                        ok_cap = permute_cube(ok_cap, asg.perm)
                    stack_capacity = plan_mod.get_device_capacity(
                        ok_cap, mesh, engine)
            if env is not None:
                em_a, em_b = env.mask_a, env.mask_b
                if asg is not None:
                    p = np.asarray(asg.perm)
                    em_a, em_b = em_a[p][:, p], em_b[p][:, p]
                transport = _envelope_transport(
                    em_a, em_b, transport, mesh, engine, l)
            with span("spgemm.dispatch"):
                c = plan_mod.execute(
                    a, b, mesh, engine,
                    threshold=threshold, backend=backend, c_layout=c_layout,
                    l=l, stack_capacity=stack_capacity, tile=tile,
                    interpret=interpret, transport=transport, assignment=asg,
                )
        eps = threshold if filter_eps is None else filter_eps
        if eps > 0.0:
            c = filter_bsm(c, eps)
        return c


def _envelope_transport(mask_a, mask_b, transport, mesh, engine: str,
                        l: int | None):
    """Resolve a transport spec against ENVELOPE operand-mask unions.

    Capacities derived from the unions cover every panel any pattern in
    the stream can ship and stay constant across it — one compiled
    program instead of per-call derivation from the concrete masks (and
    no per-call host mask sync on the sharded path).  A ready
    ``PanelTransport`` passes through untouched."""
    from repro.core import transport as T

    if isinstance(transport, T.PanelTransport):
        return transport
    if transport is None:
        from repro.config import transport_mode

        mode = transport_mode()
    else:
        mode = transport
    if mode == "dense":
        return T.DENSE
    if mode not in ("auto", "compressed"):
        raise ValueError(
            f"unknown transport {mode!r}; a PanelTransport or one of "
            "auto | dense | compressed"
        )
    return plan_mod.get_transport(mask_a, mask_b, mesh, engine, l, mode)


def _transport_pin(transport) -> str | None:
    """The tuner constraint a caller-supplied transport implies: explicit
    modes pin the decision, ``None``/"auto" leave it to the tuner."""
    from repro.core.transport import PanelTransport

    if isinstance(transport, PanelTransport):
        return transport.mode
    if transport in ("dense", "compressed"):
        return transport
    return None


def _assign_pin(assignment) -> str | None:
    """The tuner constraint a caller-supplied assignment implies: an
    explicit mode (or a ready ``Assignment``) pins the decision, ``None``
    leaves the layout to the tuner."""
    if assignment is None:
        return None
    return getattr(assignment, "mode", assignment)


def lower_multiply(
    mesh,
    nb: int,
    bs: int,
    *,
    engine: str = "twofive",
    threshold: float = 0.0,
    backend: str = "jnp",
    dtype=jnp.float32,
    c_layout: str = "2d",
    l: int | None = None,
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport=None,
    nb_k: int | None = None,
    nb_c: int | None = None,
    bs_k: int | None = None,
    bs_c: int | None = None,
):
    """Lower (without executing) one multiplication for HLO inspection —
    the source of the measured collective bytes in the benchmarks.  Shares
    the plan-layer program cache with ``multiply``.

    ``transport`` must be a resolved ``PanelTransport`` (or None = dense):
    lowering is abstract, so there is no pattern to resolve "auto" from —
    derive capacities from a concrete mask via ``plan.get_transport``.

    ``nb_k``/``nb_c``/``bs_k``/``bs_c`` (default: square) lower a
    rectangular matricized product A (nb x nb_k of bs x bs_k blocks) @
    B (nb_k x nb_c of bs_k x bs_c blocks).
    """
    nb_k = nb if nb_k is None else nb_k
    nb_c = nb if nb_c is None else nb_c
    bs_k = bs if bs_k is None else bs_k
    bs_c = bs if bs_c is None else bs_c
    square = (nb_k, nb_c, bs_k, bs_c) == (nb, nb, bs, bs)
    fn = plan_mod.get_compiled(
        mesh,
        engine,
        nb,
        bs,
        dtype,
        threshold=threshold,
        backend=backend,
        c_layout=c_layout,
        l=l,
        stack_capacity=stack_capacity,
        tile=tile,
        interpret=interpret,
        transport=transport,
        **({} if square else dict(nb_k=nb_k, nb_c=nb_c,
                                  bs_k=bs_k, bs_c=bs_c)),
    )
    a_blk = jax.ShapeDtypeStruct((nb, nb_k, bs, bs_k), dtype)
    b_blk = jax.ShapeDtypeStruct((nb_k, nb_c, bs_k, bs_c), dtype)
    am_b = jax.ShapeDtypeStruct((nb, nb_k), jnp.bool_)
    am_f = jax.ShapeDtypeStruct((nb, nb_k), jnp.float32)
    bm_b = jax.ShapeDtypeStruct((nb_k, nb_c), jnp.bool_)
    bm_f = jax.ShapeDtypeStruct((nb_k, nb_c), jnp.float32)
    return fn.lower(a_blk, am_b, am_f, b_blk, bm_b, bm_f)
