"""Matrix-sign iteration — the paper's driving application (linear-scaling
DFT density-matrix purification, Eqs. (1)-(3)).

    sign(A) = A (A^2)^{-1/2};   X_{n+1} = 1/2 X_n (3 I - X_n^2)

Each iteration is two block-sparse multiplications with on-the-fly and
post-multiplication filtering — exactly the workload DBCSR is built for
(SpGEMM > 80% of CP2K linear-scaling runtime).

Two execution modes (DESIGN.md §5):

``fused`` (default) — the device-resident iteration engine.  The operands
    are sharded ONCE at the chain boundary (``bsm.shard_bsm``) and the whole
    Newton-Schulz sweep — X², post-filter, 3I − X², X·Y, post-filter, the
    0.5 scale, residual and occupancy — compiles into ONE cached program per
    (mesh, shape, engine, backend, thresholds), fetched through
    ``plan.get_chain_compiled``.  Matrices, norms and the convergence
    residual stay on the mesh between sweeps; the host syncs the residual
    only every ``sync_every`` sweeps.  This is the paper's "never
    redistribute" design applied across a *chain* of multiplies: DBCSR
    keeps matrices home-resident for the whole purification (Lazzaro &
    Hutter 2017; arXiv:1910.13555).

``legacy`` — the original host-driven loop: each sweep re-enters
    ``multiply()`` from replicated arrays (re-shard A/B, gather C), runs the
    inter-multiply algebra as separate dispatches, and syncs the residual
    every sweep.  Kept as the parity oracle and the benchmark baseline
    (``benchmarks/bench_signiter.py`` measures the dispatch-overhead gap).

``density_matrix`` then evaluates P = 1/2 (I - sign(mu I - H)) — the
simplified (S = I, orthonormal basis) form of paper Eq. (1); the eigenvalue
counting identity trace(P) = #{eigenvalues < mu} is used as the convergence
observable in tests and examples.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core import bsm as B
from repro.core import plan as plan_mod
from repro.core.bsm import block_norms
from repro.core.engine import multiply
from repro.core.local_mm import count_value, local_filtered_mm, product_counts
from repro.obs import span


@dataclass
class SignIterStats:
    iterations: int
    converged: bool
    residual: float
    occupancy_trace: list[float]
    multiplications: int
    residual_trace: list[float] = field(default_factory=list)
    mode: str = "legacy"
    sync_every: int = 1
    host_syncs: int = 0  # device->host residual syncs (fused: ~it/sync_every)
    retraces: int = 0  # program (re)builds this chain triggered: fused =
    #   chain_misses delta (1 = whole chain ran one program), legacy =
    #   per-multiply program misses delta
    envelope: bool = False  # chain ran against a forecast pattern envelope
    # fused: block products whose A and B blocks are both present,
    # (X.X, X.Y) per sweep, summed over the mesh
    products_present: list[tuple[int, int]] = field(default_factory=list)
    # fused: block products the local stage multiplies per multiply
    # (static: the cube for jnp, the stack capacity otherwise)
    products_computed: int = 0


def _scale_any(x, s):
    """s * x for either matrix container (derived norms, no recompute)."""
    return x.scale(s) if isinstance(x, B.ShardedBSM) else B.scale(x, s)


def _resolve_engine(x, mesh, engine: str, threshold: float,
                    l: int | None, envelope=None) -> tuple[str, int | None]:
    """``engine="auto"`` for an iteration: ONE tuner resolution on the
    initial pattern (X ~ X0 . X0, the purification's own multiply shape),
    then every sweep of the chain runs the chosen (engine, L).

    Chains are tuned with ``chain=True``: without an envelope only
    chain-safe candidates (dense local backend, dense transport) are
    considered, because the fused sweep is traced once while the
    sparsity pattern evolves underneath it — see
    ``tuner.model.chain_safe``.  With ``envelope`` the capacities come
    from the forecast union cube, which covers every sweep's pattern, so
    the tuner ranks the full candidate space.
    """
    if engine != "auto":
        return engine, l
    if mesh is None:
        return "twofive", l  # single-device: the engine is vestigial
    from repro import tuner

    dec = tuner.autotune(x, x, mesh, threshold=threshold, l=l, chain=True,
                         envelope=envelope)
    return dec.engine, dec.l


def _scale_to_unit_spectrum(x):
    """Scale X0 so its spectrum lies in [-1, 1] (Frobenius bound)."""
    nrm = x.frobenius_norm()
    return _scale_any(x, 1.0 / jnp.maximum(nrm, 1e-30))


# ---------------------------------------------------------------------------
# the fused device-resident sweep
# ---------------------------------------------------------------------------


def _make_sweep(mm, dtype, filter_eps: float, *, total_blocks: int,
                psum_axes=None):
    """One whole Newton-Schulz sweep as a single traceable function.

    ``mm(ab, am, an, bb, bm, bn) -> (cb, cm)`` is the multiply body — the
    engine's raw per-shard body (``plan.build_shard_body``, less the masks
    ``_counted_sweep`` takes from it) when the sweep runs inside one
    enclosing shard_map, or ``local_filtered_mm`` on a single device.
    Everything between the two multiplies is shard-local algebra with
    incrementally-updated norms; the residual and occupancy leave as
    device scalars via ``psum_axes`` all-reduces — never a gather of the
    matrix.
    """
    eps = float(filter_eps)

    def post_filter(cb, cm, cn):
        if eps <= 0.0:
            return cb, cm, cn
        with jax.named_scope("signiter.filter"):
            keep = cm & (cn > eps)
            return (
                cb * keep[:, :, None, None].astype(cb.dtype),
                keep,
                jnp.where(keep, cn, 0.0),
            )

    def sweep(xb, xm, xn, ib, im):
        # X^2 (multiply 1) + post-filter, mirroring multiply(filter_eps=...)
        x2b, x2m = mm(xb, xm, xn, xb, xm, xn)
        x2n = block_norms(x2b)
        x2b, x2m, x2n = post_filter(x2b, x2m, x2n)
        # Y = 3I - X^2: elementwise on the shards, norms from the new blocks
        yb = ib * jnp.asarray(3.0, dtype) - x2b
        ym = im | x2m
        yn = block_norms(yb)
        # X . Y (multiply 2) + post-filter + the 1/2 scale (derived norms)
        cb, cm = mm(xb, xm, xn, yb, ym, yn)
        cn = block_norms(cb)
        cb, cm, cn = post_filter(cb, cm, cn)
        cb = cb * jnp.asarray(0.5, dtype)
        cn = cn * jnp.float32(0.5)
        # convergence: || X_{n+1} - X_n ||_F / || X_{n+1} ||_F — partial
        # sums per shard, all three scalars in ONE stacked all-reduce
        with jax.named_scope("signiter.residual"):
            diff = (cb - xb).astype(jnp.float32)
            partials = jnp.stack([
                jnp.sum(jnp.square(diff)),
                jnp.sum(jnp.square(cn)),
                jnp.sum(cm.astype(jnp.float32)),
            ])
            if psum_axes is not None:
                partials = jax.lax.psum(partials, psum_axes)
            num_sq, den_sq, occ_cnt = partials
            residual = jnp.sqrt(num_sq) / jnp.maximum(jnp.sqrt(den_sq),
                                                      1e-30)
            occupancy = occ_cnt / total_blocks
        return cb, cm, cn, residual, occupancy

    return sweep


def _counted_sweep(mm, dtype, filter_eps: float, *, total_blocks: int,
                   psum_axes=None, count_axes=None, backend: str = "jnp",
                   stack_capacity: int | None = None):
    """``_make_sweep``'s sweep with the block products of its two
    multiplies as a sixth output.  ``mm`` returns ``(cb, cm, calls)``,
    ``calls`` the operand masks of its local-stage calls (the engine's
    shard body); the sweep counts them (``local_mm.product_counts``) into
    an int32 (2, 2, 2) array — multiply, present / computed, hi / lo —
    summed over ``count_axes`` in one all-reduce."""
    calls = []  # filled while the sweep traces: one entry per multiply

    def mm_recorded(*args):
        cb, cm, c = mm(*args)
        calls.append(c)
        return cb, cm

    inner = _make_sweep(mm_recorded, dtype, filter_eps,
                        total_blocks=total_blocks, psum_axes=psum_axes)

    def sweep(xb, xm, xn, ib, im):
        calls.clear()
        out = inner(xb, xm, xn, ib, im)
        # count after the residual: counting, or reducing the counts,
        # between the multiplies moves XLA's schedule and memory
        # placement of the local stage (one einsum of the 2x2 twofive
        # sweep at nb 512 ran 4% slower on a v5e) and makes every device
        # wait for the slowest there
        recorded, _ = jax.lax.optimization_barrier((list(calls), out[3]))
        products = jnp.stack([
            product_counts(c, backend=backend, stack_capacity=stack_capacity)
            for c in recorded])
        if count_axes is not None:
            products = jax.lax.psum(products, count_axes)
        return out + (products,)

    return sweep


def _sweep_key(mesh, engine, nb_r, nb_c, bs_r, bs_c, dtype, threshold,
               filter_eps, backend, l, stack_capacity, tile, interpret,
               transport=None):
    key = (
        "signiter", mesh, engine, nb_r, nb_c, bs_r, bs_c,
        jnp.dtype(dtype).name, float(threshold), float(filter_eps),
        backend, l, stack_capacity, tile, interpret,
    )
    # appended ONLY for non-dense transport so pre-envelope chain keys
    # (and everything that pins them) keep their original shape
    if transport is not None and transport.mode != "dense":
        key = key + (transport.key,)
    return key


def get_sweep_program(
    x,
    mesh,
    *,
    engine: str,
    threshold: float,
    filter_eps: float,
    backend: str,
    l: int | None = None,
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    envelope=None,
    transport=None,
):
    """The compiled fused sweep for (mesh, shape, engine, backend, ...),
    cached in the plan layer's program cache (``plan.get_chain_compiled``,
    counted by ``chain_hits``/``chain_misses``).

    The program maps ``(xb, xm, xn, ib, im)`` to ``(xb, xm, xn, residual,
    occupancy, products)``; ``products`` are the block-product counts of
    the sweep's two multiplies (``_counted_sweep``).

    ``mesh=None`` builds the single-device sweep around
    ``local_filtered_mm``.  Otherwise the WHOLE sweep is one shard_map
    around the engine's raw per-shard body (``plan.build_shard_body``):
    both multiplies, the inter-multiply algebra and the residual partials
    run per-shard with no re-partitioning between them, so one sweep is
    one dispatch of one SPMD program — and one program build per distinct
    multiply shape, shared by both multiplies.

    ``envelope`` (a ``core.envelope.Envelope``) lifts the chain-safety
    pins: ``backend="auto"`` resolves against the envelope's union cube
    through the analytic cost model, a ``None`` ``stack_capacity`` takes
    the envelope's (bucketed) capacity, and non-dense ``transport``
    resolves its per-panel capacities from the envelope's operand-mask
    unions — all sound for every pattern the envelope covers, so the
    chain still compiles exactly once.  Without an envelope the historic
    pins stand: "auto" degrades to "jnp" and non-dense transport raises.
    """
    if engine == "auto":
        raise ValueError(
            "resolve engine='auto' before building a chain program "
            "(sign_iteration does this via the tuner); the chain key "
            "must carry a concrete engine"
        )
    from repro.core import transport as T
    if envelope is not None:
        if backend == "auto":
            # the envelope's union cube is the chain-wide fill bound:
            # feed it through the same analytic crossover the tuner uses
            from repro.core.local_mm import choose_local_backend

            backend = choose_local_backend(
                x.nb_r, x.nb_c, x.nb_c, x.bs_r, x.bs_c, x.bs_c,
                fill=float(envelope.cube.mean()), dtype=x.dtype,
            )
        if stack_capacity is None and backend in ("stacks", "pallas"):
            stack_capacity = (
                envelope.local_capacity() if mesh is None
                else envelope.device_capacity(mesh, engine)
            )
        if mesh is not None and not isinstance(transport, T.PanelTransport):
            mode = transport
            if mode is None or mode == "dense":
                transport = None  # dense inside build_shard_body
            elif mode in ("auto", "compressed"):
                transport = envelope.transport(mesh, engine, l, mode)
            else:
                raise ValueError(
                    f"unknown transport {mode!r}; a PanelTransport or "
                    "one of auto | dense | compressed"
                )
    else:
        if backend == "auto":
            # auto walks the concrete pattern on the host; inside the
            # fused (traced) sweep there is no concrete pattern — dense
            # einsum it is
            backend = "jnp"
        # without an envelope the panel transport is pinned dense for the
        # same reason: the sweep is traced once while the sparsity
        # pattern evolves underneath it, so a compressed capacity derived
        # from the initial pattern would silently drop fill-in blocks
        # mid-iteration (chain safety — tuner.model.chain_safe).  Dense
        # transport still gets the norm-free wire format and the
        # double-buffered pipelining from the shared layer.
        if transport is not None and not (
            isinstance(transport, T.PanelTransport)
            and transport.mode == "dense"
        ) and transport != "dense":
            raise ValueError(
                "non-dense chain transport needs an envelope: a static "
                "packing capacity derived from the initial pattern would "
                "silently drop fill-in panels mid-iteration "
                "(core/envelope.py)"
            )
        transport = None
    if backend == "pallas" and interpret is None:
        from repro.kernels.ops import _default_interpret

        interpret = _default_interpret()
    key = _sweep_key(mesh, engine, x.nb_r, x.nb_c, x.bs_r, x.bs_c, x.dtype,
                     threshold, filter_eps, backend, l, stack_capacity,
                     tile, interpret, transport)
    mm_kw = dict(threshold=threshold, backend=backend,
                 stack_capacity=stack_capacity, tile=tile,
                 interpret=interpret, transport=transport)
    total_blocks = x.nb_r * x.nb_c

    def builder():
        if mesh is None:
            local_kw = {k: v for k, v in mm_kw.items() if k != "transport"}

            def mm(ab, am, an, bb, bm, bn):
                cb, cm = local_filtered_mm(ab, am, an, bb, bm, bn,
                                           **local_kw)
                return cb, cm, [(am, bm)]

            return jax.jit(_counted_sweep(
                mm, x.dtype, filter_eps, total_blocks=total_blocks,
                backend=backend, stack_capacity=stack_capacity))
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        plan = plan_mod.plan_multiply(mesh, engine, l)
        plan.validate_blocks(x.nb_r, x.nb_c)
        # transport=None -> dense inside build_shard_body (chain-safe);
        # an envelope-resolved PanelTransport rides through untouched
        mm = plan_mod.build_shard_body(plan, **mm_kw)
        sweep = _counted_sweep(mm, x.dtype, filter_eps,
                               total_blocks=total_blocks,
                               psum_axes=("r", "c"),
                               count_axes=tuple(mesh.axis_names),
                               backend=backend,
                               stack_capacity=stack_capacity)
        blk = P("r", "c", None, None)
        m2 = P("r", "c")
        fn = shard_map(
            sweep,
            mesh=mesh,
            # check_vma=False for the same reason as the engine executors
            # (oracle-tested outputs; pallas bodies carry no vma)
            check_vma=False,
            in_specs=(blk, m2, m2, blk, m2),
            out_specs=(blk, m2, m2, P(), P(), P()),
        )
        return jax.jit(fn)

    return plan_mod.get_chain_compiled(key, builder)


class _ChainShape:
    """Abstract operand of a chain program: just the key fields of
    ``get_sweep_program``, no block data."""

    def __init__(self, nb: int, bs, dtype):
        self.nb_r = self.nb_c = nb
        self.bs_r, self.bs_c = B._block_shape(bs)
        self.dtype = jnp.dtype(dtype)


def lower_sweep(
    mesh,
    nb: int,
    bs: int,
    *,
    engine: str = "twofive",
    threshold: float = 0.0,
    filter_eps: float = 0.0,
    backend: str = "jnp",
    dtype=jnp.float32,
    l: int | None = None,
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
):
    """Lower (without executing) one fused sweep for HLO inspection — the
    proof that a sweep performs no global gather: X enters and leaves in
    the 2D home layout, so the only collectives are the engine's panel
    moves and the scalar residual/occupancy all-reduces."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    shape = _ChainShape(nb, bs, dtype)
    fn = get_sweep_program(shape, mesh, engine=engine, threshold=threshold,
                           filter_eps=filter_eps, backend=backend, l=l,
                           stack_capacity=stack_capacity, tile=tile,
                           interpret=interpret)
    bs_r, bs_c = shape.bs_r, shape.bs_c
    if mesh is None:
        blk = jax.ShapeDtypeStruct((nb, nb, bs_r, bs_c), dtype)
        m2b = jax.ShapeDtypeStruct((nb, nb), jnp.bool_)
        m2f = jax.ShapeDtypeStruct((nb, nb), jnp.float32)
    else:
        s_blk = NamedSharding(mesh, P("r", "c", None, None))
        s_m2 = NamedSharding(mesh, P("r", "c"))
        blk = jax.ShapeDtypeStruct((nb, nb, bs_r, bs_c), dtype, sharding=s_blk)
        m2b = jax.ShapeDtypeStruct((nb, nb), jnp.bool_, sharding=s_m2)
        m2f = jax.ShapeDtypeStruct((nb, nb), jnp.float32, sharding=s_m2)
    return fn.lower(blk, m2b, m2f, blk, m2b)


# ---------------------------------------------------------------------------
# iteration drivers
# ---------------------------------------------------------------------------


def sign_iteration_legacy(
    x0: B.BlockSparseMatrix,
    *,
    mesh=None,
    engine: str = "twofive",
    threshold: float = 0.0,
    filter_eps: float = 0.0,
    max_iter: int = 50,
    tol: float = 1e-6,
    scale_input: bool = True,
    backend: str = "jnp",
    l: int | None = None,
    storage_dtype=None,
    tile: tuple[int, int, int] | None = None,
    assignment=None,
) -> tuple[B.BlockSparseMatrix, SignIterStats]:
    """The host-driven per-op loop (parity oracle / benchmark baseline):
    two ``multiply()`` re-entries per sweep from replicated arrays, eager
    inter-multiply algebra, a host residual sync every sweep.  With a
    compacted ``backend`` every multiply walks X's concrete pattern — the
    pattern cache (``plan.cache_stats()['pattern_hits']``) re-hits as the
    iteration's sparsity structure stabilizes.  ``engine="auto"`` is
    resolved ONCE on the initial pattern (not per multiply): the tuner
    decision holds for the whole iteration.  ``assignment`` is threaded to
    every multiply (results come back in original block coordinates, so
    the inter-multiply algebra is layout-oblivious)."""
    engine, l = _resolve_engine(x0, mesh, engine, threshold, l)
    nb, bs = x0.nb_r, x0.bs_r
    ident = B.identity(nb, bs, x0.dtype)
    x = _scale_to_unit_spectrum(x0) if scale_input else x0
    if storage_dtype is not None:
        # reduced-precision block storage: cast AFTER the spectral scale
        # (the scale is a global scalar — quantize the scaled operand) and
        # recalibrate norms from the quantized blocks (bsm.astype) so the
        # on-the-fly filter sees the norms of what actually multiplies
        x = B.cast_bsm(x, storage_dtype)
        ident = B.cast_bsm(ident, storage_dtype)
    occ, res_trace = [], []
    n_mults = 0
    converged = False
    residual = float("inf")
    misses0 = plan_mod.cache_stats()["misses"]
    it = 0
    for it in range(1, max_iter + 1):
        x2 = multiply(
            x, x, mesh, engine=engine, threshold=threshold,
            filter_eps=filter_eps, backend=backend, l=l, tile=tile,
            assignment=assignment,
        )
        n_mults += 1
        # 3I - X^2
        y = B.add(B.scale(x2, -1.0), B.scale(ident, 3.0))
        xn = multiply(
            x, y, mesh, engine=engine, threshold=threshold,
            filter_eps=filter_eps, backend=backend, l=l, tile=tile,
            assignment=assignment,
        )
        xn = B.scale(xn, 0.5)
        n_mults += 1
        # convergence: || X_{n+1} - X_n ||_F / || X_n ||_F
        diff = B.add(xn, B.scale(x, -1.0))
        residual = float(diff.frobenius_norm() / jnp.maximum(xn.frobenius_norm(), 1e-30))
        res_trace.append(residual)
        occ.append(float(xn.occupancy()))
        x = xn
        if residual < tol:
            converged = True
            break
    stats = SignIterStats(
        iterations=it,
        converged=converged,
        residual=residual,
        occupancy_trace=occ,
        multiplications=n_mults,
        residual_trace=res_trace,
        mode="legacy",
        sync_every=1,
        host_syncs=it,
        retraces=plan_mod.cache_stats()["misses"] - misses0,
    )
    return x, stats


def sign_iteration(
    x0: B.BlockSparseMatrix | B.ShardedBSM,
    *,
    mesh=None,
    engine: str = "twofive",
    threshold: float = 0.0,
    filter_eps: float = 0.0,
    max_iter: int = 50,
    tol: float = 1e-6,
    scale_input: bool = True,
    mode: str = "fused",
    sync_every: int = 1,
    backend: str = "jnp",
    l: int | None = None,
    stack_capacity: int | None = None,
    storage_dtype=None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    assignment=None,
    envelope=None,
    transport=None,
) -> tuple[B.BlockSparseMatrix | B.ShardedBSM, SignIterStats]:
    """Newton-Schulz iteration X <- 1/2 X (3I - X^2) to sign(x0).

    mode       — "fused" (device-resident sweep, default) or "legacy"
                 (per-op host loop; parity oracle).
    sync_every — fused only: host-sync the device-resident residual every
                 k sweeps instead of every multiply.  With k > 1 the loop
                 may run up to k-1 sweeps past convergence (the sign fixed
                 point is stable, so extra sweeps only polish); residual
                 and occupancy traces stay complete either way.
    backend    — local stage for the fused sweep ("auto" degrades to
                 "jnp": the sweep is traced, there is no concrete pattern
                 to compact; "stacks"/"pallas" take ``stack_capacity`` as
                 their static product bound, full cube when omitted).
    storage_dtype — reduced-precision block storage for the whole chain
                 (e.g. ``jnp.bfloat16``): X and I are quantized ONCE at
                 the chain boundary (after the spectral scale) with norms
                 recalibrated from the quantized blocks (``bsm.astype``),
                 every multiply accumulates in f32 on the MXU, and panels
                 ride the wire at storage width — half the f32 bytes for
                 bf16.  Residual/occupancy stay f32.  Expect the bf16
                 fixed point within ~3e-2 of the f32 oracle elementwise
                 (``kernels.ref`` documents the tolerance model).
    tile       — MXU tile override (tm, tk, tn) for the pallas backend
                 (None = ``kernels.block_spgemm.default_tile``).
    envelope   — fused only: compile the chain against a forecast
                 pattern envelope (DESIGN.md §7).  ``"auto"`` (or
                 ``True``) forecasts it here from the finalized operand
                 via ``plan.get_envelope`` (``sweeps=max_iter``); a
                 ready ``core.envelope.Envelope`` is used as-is.  The
                 envelope lifts the chain-safety pins: ``backend="auto"``
                 resolves through the cost model against the union cube,
                 compacted backends take the envelope's capacity bound,
                 and non-dense ``transport`` becomes available — while
                 the whole drifting-pattern chain still compiles ONCE
                 (``stats.retraces == 1`` cold, 0 warm).
    transport  — fused only: panel-transport mode for the sweep's
                 multiplies ("auto" | "dense" | "compressed" or a ready
                 ``PanelTransport``).  Non-dense modes require
                 ``envelope`` (chain safety — see ``get_sweep_program``).
    assignment — block→device distribution for the WHOLE chain: resolved
                 ONCE at the shard boundary (None / a mode string / a
                 ``distribute.Assignment`` — see ``bsm.shard_bsm``).  The
                 Newton-Schulz fixed point is layout-equivariant
                 (sign(P X Pᵀ) = P sign(X) Pᵀ and P I Pᵀ = I), so every
                 sweep runs in the one permuted home layout with no
                 re-distribution; ``unshard`` at the exit boundary (or the
                 carried ``ShardedBSM.assignment``) restores original
                 block coordinates.

    A ShardedBSM ``x0`` stays sharded end-to-end (under its own carried
    assignment — passing a conflicting ``assignment`` raises) and the
    result is a ShardedBSM; a BlockSparseMatrix with ``mesh`` given is
    sharded once at entry and gathered once at exit (the chain
    boundaries).
    """
    if mode == "legacy":
        if isinstance(x0, B.ShardedBSM):
            raise TypeError("legacy mode operates on replicated matrices; "
                            "unshard first (bsm.unshard_bsm)")
        if envelope is not None or transport is not None:
            raise ValueError(
                "envelope/transport are fused-chain controls; the legacy "
                "loop re-enters multiply() per pattern (pass them to "
                "multiply directly if needed)"
            )
        return sign_iteration_legacy(
            x0, mesh=mesh, engine=engine, threshold=threshold,
            filter_eps=filter_eps, max_iter=max_iter, tol=tol,
            scale_input=scale_input, backend=backend, l=l,
            storage_dtype=storage_dtype, tile=tile, assignment=assignment,
        )
    if mode != "fused":
        raise ValueError(f"unknown mode {mode!r}; 'fused' or 'legacy'")
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")

    sharded_in = isinstance(x0, B.ShardedBSM)
    if sharded_in:
        if mesh is not None and mesh is not x0.mesh and mesh != x0.mesh:
            raise ValueError("mesh argument conflicts with operand mesh")
        mesh = x0.mesh
        if assignment is not None and (
            getattr(assignment, "mode", assignment)
            != B._assign_name(x0.assignment)
        ):
            raise ValueError(
                f"operand is sharded under assignment "
                f"{B._assign_name(x0.assignment)}; unshard before "
                f"iterating under a different layout"
            )
    nb, bs = x0.nb_r, x0.bs_r
    ident = B.identity(nb, bs, x0.dtype)
    if mesh is not None:
        # one layout decision for the whole chain, made HERE at the shard
        # boundary; the identity inherits it (P I Pᵀ = I, data unchanged)
        x = x0 if sharded_in else B.shard_bsm(x0, mesh,
                                              assignment=assignment)
        ident = B.shard_bsm(ident, mesh, assignment=x.assignment)
    else:
        if assignment not in (None, "identity"):
            raise ValueError("assignment needs a mesh: a block→device "
                             "distribution has no meaning on one device")
        x = x0
    x = _scale_to_unit_spectrum(x) if scale_input else x
    if storage_dtype is not None:
        # quantize once at the chain boundary, shard-local for ShardedBSM;
        # norms recalibrated from the quantized blocks (bsm.astype)
        x = B.cast_bsm(x, storage_dtype)
        ident = B.cast_bsm(ident, storage_dtype)
    env = envelope
    if env is True or env == "auto":
        # forecast from the FINALIZED operand (post-scale, post-cast, in
        # chain layout): the envelope's norm bounds must dominate the
        # norms the filters actually see.  One host sync of (mask, norms)
        # at the chain boundary; plan.get_envelope memoizes the forecast.
        import numpy as np

        env = plan_mod.get_envelope(
            np.asarray(x.mask, bool), np.asarray(x.norms, np.float32),
            sweeps=max_iter, threshold=threshold, filter_eps=filter_eps,
            bs=x.bs_r,
        )
    # engine resolution sees the finalized operand and the envelope: with
    # one, autotune(chain=True) ranks the full candidate space
    engine, l = _resolve_engine(x, mesh, engine, threshold, l, envelope=env)

    chain_misses0 = plan_mod.cache_stats()["chain_misses"]
    sweep = None
    xb, xm, xn = x.blocks, x.mask, x.norms
    ib, im = ident.blocks, ident.mask
    occ_trace: list[float] = []
    res_trace: list[float] = []
    present: list[tuple[int, int]] = []
    computed = 0
    pending: list[tuple] = []
    converged = False
    syncs = 0
    it = 0
    with span("signiter.chain") as chain:
        for it in range(1, max_iter + 1):
            with span("signiter.dispatch"):
                # fetched per sweep: the chain counters in
                # plan.cache_stats() then record how many sweeps of this
                # iteration reused one program
                sweep = get_sweep_program(
                    x, mesh, engine=engine, threshold=threshold,
                    filter_eps=filter_eps, backend=backend, l=l,
                    stack_capacity=stack_capacity, tile=tile,
                    interpret=interpret, envelope=env, transport=transport,
                )
                xb, xm, xn, res_d, occ_d, prod_d = sweep(xb, xm, xn, ib, im)
            pending.append((res_d, occ_d, prod_d))
            if it % sync_every == 0 or it == max_iter:
                syncs += 1
                with span("signiter.sync"):
                    fetched = jax.device_get(pending)
                for res, occ, prod in fetched:
                    r = float(res)
                    res_trace.append(r)
                    occ_trace.append(float(occ))
                    present.append((count_value(prod[0, 0]),
                                    count_value(prod[1, 0])))
                    computed = count_value(prod[0, 1])
                    if r < tol:
                        converged = True
                pending = []
                if converged:
                    break
        chain.counts.update(
            sweeps=it, host_syncs=syncs,
            products_present=sum(map(sum, present)),
            products_computed=2 * it * computed,
            block_flops=2 * x.bs_r * x.bs_c * x.bs_c,
        )

    if mesh is not None:
        out = B.ShardedBSM(blocks=xb, mask=xm, norms=xn, mesh=mesh,
                           assignment=x.assignment)
        result = out if sharded_in else out.unshard()
    else:
        result = B.BlockSparseMatrix(blocks=xb, mask=xm, norms=xn)
    stats = SignIterStats(
        iterations=it,
        converged=converged,
        residual=res_trace[-1] if res_trace else float("inf"),
        occupancy_trace=occ_trace,
        multiplications=2 * it,
        residual_trace=res_trace,
        mode="fused",
        sync_every=sync_every,
        host_syncs=syncs,
        retraces=plan_mod.cache_stats()["chain_misses"] - chain_misses0,
        envelope=env is not None,
        products_present=present,
        products_computed=computed,
    )
    return result, stats


def density_matrix(
    h: B.BlockSparseMatrix | B.ShardedBSM,
    mu: float,
    *,
    mesh=None,
    engine: str = "twofive",
    threshold: float = 0.0,
    filter_eps: float = 0.0,
    max_iter: int = 60,
    tol: float = 1e-6,
    mode: str = "fused",
    sync_every: int = 1,
    backend: str = "jnp",
    storage_dtype=None,
    tile: tuple[int, int, int] | None = None,
    assignment=None,
    envelope=None,
    transport=None,
) -> tuple[B.BlockSparseMatrix | B.ShardedBSM, SignIterStats]:
    """P = 1/2 (I - sign(H - mu I))  (paper Eq. (1) with S = I).

    The shift, sign iteration and projector assembly all run where ``h``
    lives: a ShardedBSM Hamiltonian yields a ShardedBSM density matrix
    with no intermediate gather (derived-norm algebra at both ends).
    ``assignment`` pins one block→device distribution for the whole
    purification (see ``sign_iteration``).
    """
    nb, bs = h.nb_r, h.bs_r
    ident = B.identity(nb, bs, h.dtype)
    if isinstance(h, B.ShardedBSM):
        # the identity joins h's layout (P I Pᵀ = I) so the shift algebra
        # stays shard-local under whatever assignment h was sharded with
        ident = B.shard_bsm(ident, h.mesh, assignment=h.assignment)
        shifted = ident.scale(-mu).add(h)
    else:
        shifted = B.add(h, B.scale(ident, -mu))
    sgn, stats = sign_iteration(
        shifted,
        mesh=mesh,
        engine=engine,
        threshold=threshold,
        filter_eps=filter_eps,
        max_iter=max_iter,
        tol=tol,
        mode=mode,
        sync_every=sync_every,
        backend=backend,
        storage_dtype=storage_dtype,
        tile=tile,
        assignment=assignment,
        envelope=envelope,
        transport=transport,
    )
    if sgn.dtype != ident.dtype:  # projector algebra in storage dtype
        ident = B.cast_bsm(ident, sgn.dtype)
    if isinstance(sgn, B.ShardedBSM):
        p = sgn.scale(-1.0).add(ident).scale(0.5)
    else:
        p = B.scale(B.add(ident, B.scale(sgn, -1.0)), 0.5)
    return p, stats


def trace(m: B.BlockSparseMatrix | B.ShardedBSM) -> jnp.ndarray:
    if isinstance(m, B.ShardedBSM):
        return m.trace()
    diag_blocks = m.blocks[jnp.arange(m.nb_r), jnp.arange(m.nb_c)]
    diag_mask = m.mask[jnp.arange(m.nb_r), jnp.arange(m.nb_c)]
    tr = jnp.trace(diag_blocks, axis1=-2, axis2=-1)
    return jnp.sum(tr * diag_mask)
