"""Topology-driven multiply plans: one scheduler for all four engines.

A ``MultiplyPlan`` compiles a :class:`repro.core.topology.Topology` (the
paper's Algorithm 2 coordinates) into the *static* communication schedule a
shard_map engine executes: pre-shift permutations, per-tick ring shifts or
one-sided pulls, per-layer k-chunks, and the partial-C reduction.  The four
engines (``cannon``, ``onesided``, ``gather``, ``twofive``) are thin
executors of a plan — none of them derives coordinates inline any more.

Plan kinds
----------

``ring``     Cannon / PTP (Algorithm 1): pre-shift + V ring shifts.  Square
             2D meshes only (the paper's baseline).
``pull``     Algorithm 2 run directly on the 2D (r, c) process grid with the
             depth axis *virtual* — the paper's actual topology, including
             non-square grids (P_R != P_C, L = mx/mn forced) and L = 1
             (= OS1).  Every one-sided ``rget`` of the paper becomes a
             static partial permutation: per tick, per A/B panel slot, per
             home-shard subpanel, the (home -> requester) pairs derived from
             ``group_products``; multicasts are split greedily into rounds
             so each round is a valid (partial) permutation.
``stacked``  The TPU mesh formulation on an (l, r, c) mesh: A/B replicated
             over ``l``, layer l runs a Cannon schedule over its k-chunk
             ``Topology.chunk(l)``, partial C reduced over ``l``.  Uneven
             chunks (L does not divide the grid side) are supported via
             per-layer tick masking.
``gather``   Fused all-gather pull-from-home (TPU-native OS1), any grid.

Compiled-program cache
----------------------

``get_compiled`` returns a jitted shard_map program, LRU-cached on
``(mesh, engine, nb, bs, dtype, threshold, backend, c_layout, l,
stack_capacity, interpret, transport, assignment)`` so the hot paths
(sign iteration, serving, benchmark loops) never retrace or re-lower
after the first call.

Distribution layer
------------------

``resolve_assignment`` / ``get_assignment`` resolve the block→device
assignment (``core.distribute``, DESIGN.md): a symmetric row+column
permutation that rebalances per-device product load before the engines
partition the grid.  Replicated execution applies it inside the
compiled program (permute-in / unpermute-out around the engine body);
sharded execution relies on ``shard_bsm`` having applied it at the
chain boundary.  Every capacity bound (stacks, transport) is derived
from the PERMUTED pattern.

Panel transport
---------------

Engines no longer inline their communication: panel movement goes
through ``repro.core.transport`` (DESIGN.md §3), either ``dense``
(bit-exact full-panel permutes, norms dropped from the wire) or
``compressed`` (occupancy-packed buffers whose capacities are derived
soundly per device here, like PR 2's stack bounds).  ``get_transport``
resolves mode + capacities from the concrete operand masks (LRU-cached
on the pattern signatures; ``REPRO_TRANSPORT`` overrides the mode) and
the result joins the program-cache key; ``transport_*`` counters in
``cache_stats()`` expose the resolutions.
``get_local_compiled`` does the same for the single-device compacted
local stage (the ``stacks``/``pallas`` backends), keyed on block-grid
shape and *capacity bucket* — patterns with equal bucketed product counts
share one executable.  ``cache_stats()`` exposes hit/miss/build counters
for tests and benchmarks.

Autotuned dispatch
------------------

``execute`` / ``execute_sharded`` accept ``engine="auto"``: the decision
layer above this cache (``repro.tuner``, DESIGN.md §6) resolves
``(engine, L, backend, stack_capacity)`` from the concrete sparsity
pattern — analytic Eq. 6/7 pruning, then short measured trials whose
winners persist in a tuning database.  Tuner decisions are counted in
``cache_stats()`` (``tuner_hits`` / ``tuner_misses`` / ``tuner_trials``)
and dropped by ``clear_cache()`` like every other cache level.

Pattern cache
-------------

``get_product_stacks`` compacts a *concrete* pair-filter cube into its
padded product list (``kernels/stacks.py``) and LRU-caches the result on
the sparsity-pattern signature — DBCSR's stack generation, amortized: the
sign-iteration / serving loops re-multiply the same (or slowly evolving)
pattern, so repeated patterns cost neither a host walk nor a recompile
(the local program key depends only on the capacity bucket).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from functools import lru_cache

import jax.numpy as jnp

from repro.core.topology import (
    Topology,
    coords3d,
    group_k,
    make_topology,
)

Perm = tuple[tuple[int, int], ...]


@dataclass(frozen=True)
class PullRound:
    """One partial permutation of one home-shard subpanel.

    ``slot``  — which of the device's L_R A panels / L_C B panels this
                round feeds (the i3 / j3 coordinate of ``group_products``).
    ``q``     — subpanel index within the home shard (virtual index modulo
                the shard's subpanel count); selects a static slice.
    ``pairs`` — (home, requester) flattened-mesh index pairs; a valid
                partial permutation (unique sources, unique destinations).
    """

    slot: int
    q: int
    pairs: Perm


@dataclass(frozen=True)
class MultiplyPlan:
    """Static communication schedule for one (mesh, engine) pair."""

    engine: str
    kind: str  # "ring" | "pull" | "stacked" | "gather"
    mesh: object  # the jax Mesh the schedule was compiled for
    axes: tuple[str, ...]  # mesh axes of the flattened permutation domain
    p_r: int
    p_c: int
    topo: Topology
    ticks: int
    # --- ring (cannon) ---
    pre_a: Perm = ()
    pre_b: Perm = ()
    shift_a: Perm = ()  # one ring hop of A (along c)
    shift_b: Perm = ()  # one ring hop of B (along r)
    # --- pull (Algorithm 2 on the 2D grid) ---
    a_pulls: tuple[tuple[PullRound, ...], ...] = ()  # [tick][round]
    b_pulls: tuple[tuple[PullRound, ...], ...] = ()
    c_rounds: tuple[Perm, ...] = ()  # L-1 partial-C sends
    ca: int = 1  # A subpanels per home shard (= V / P_C)
    cb: int = 1  # B subpanels per home shard (= V / P_R)
    # --- stacked ((l, r, c) mesh) ---
    layer_groups: tuple[int, ...] = ()  # ticks of each layer
    chunk_starts: tuple[int, ...] = ()  # k-chunk offset of each layer

    @property
    def l(self) -> int:
        return self.topo.l

    def validate_blocks(
        self, nb_r: int, nb_c: int, nb_k: int | None = None
    ) -> None:
        """Check the product's block grids divide this plan's topology.

        ``(nb_r, nb_c)`` is the output grid; ``nb_k`` is the contracted
        block count (A is ``nb_r x nb_k``, B is ``nb_k x nb_c``).  With
        ``nb_k=None`` the historical square contract applies (``nb_k`` is
        implied equal to both, as every pre-tensor caller guaranteed).
        Rectangular callers MUST pass ``nb_k``: the k axis is the one the
        engines slice hardest — A's column panels shard over ``p_c``, B's
        row panels over ``p_r``, and the pull formulation additionally
        cuts k into V virtual subpanels — and none of that is implied by
        the output grid.
        """
        v = self.topo.v
        if nb_r % self.p_r or nb_c % self.p_c:
            raise ValueError(
                f"block grid {nb_r}x{nb_c} does not divide the "
                f"{self.p_r}x{self.p_c} process grid"
            )
        if nb_k is None:
            if self.kind == "pull" and (nb_r % v or nb_c % v):
                raise ValueError(
                    f"block grid {nb_r}x{nb_c} does not divide the virtual "
                    f"grid V={v} (required for one-sided panel pulls)"
                )
            return
        if nb_k % self.p_c or nb_k % self.p_r:
            raise ValueError(
                f"contracted block count nb_k={nb_k} does not divide the "
                f"{self.p_r}x{self.p_c} process grid (A column panels "
                f"shard over p_c={self.p_c}, B row panels over "
                f"p_r={self.p_r})"
            )
        if self.kind == "pull" and nb_k % v:
            raise ValueError(
                f"contracted block count nb_k={nb_k} does not divide the "
                f"virtual grid V={v} (required for one-sided k-subpanel "
                f"pulls)"
            )


# ---------------------------------------------------------------------------
# schedule compilation
# ---------------------------------------------------------------------------


def _ring_perm(p: int, shift: int = 1) -> Perm:
    """Receive from (k + shift) % p: the Cannon ring hop."""
    return tuple((src, (src - shift) % p) for src in range(p))


def _partition_rounds(pairs: list[tuple[int, int]]) -> list[Perm]:
    """Split (src, dst) pairs into valid partial permutations.

    A source that must multicast (same panel requested by several devices in
    one tick — the sqrt(L) amortization of the paper) is serialized over
    rounds; each round has unique sources and unique destinations.
    """
    rounds: list[list[tuple[int, int]]] = []
    used: list[tuple[set[int], set[int]]] = []
    for src, dst in pairs:
        for r, (srcs, dsts) in zip(rounds, used):
            if src not in srcs and dst not in dsts:
                r.append((src, dst))
                srcs.add(src)
                dsts.add(dst)
                break
        else:
            rounds.append([(src, dst)])
            used.append(({src}, {dst}))
    return [tuple(r) for r in rounds]


def _pull_schedule(topo: Topology):
    """Per-tick pull rounds + C-reduction rounds from Algorithm 2.

    Drives everything from the topology's stated invariants: per tick group
    ``g`` a process at (i, j) pulls the L_R A panels (m, k) and L_C B panels
    (k, n) of ``group_products`` from their *home* 2D positions, where the
    home of virtual A panel (m, k) is process (m, k // ca) subpanel k % ca
    (ca = V / P_C) and of B panel (k, n) is (k // cb, n) subpanel k % cb.
    """
    p_r, p_c, v, s = topo.p_r, topo.p_c, topo.v, topo.side3d
    ca, cb = v // p_c, v // p_r

    def flat(i: int, j: int) -> int:
        return i * p_c + j

    a_ticks: list[tuple[PullRound, ...]] = []
    b_ticks: list[tuple[PullRound, ...]] = []
    for g in range(topo.ticks):
        a_classes: dict[tuple[int, int], list[tuple[int, int]]] = {}
        b_classes: dict[tuple[int, int], list[tuple[int, int]]] = {}
        for i in range(p_r):
            for j in range(p_c):
                _, _, lay = coords3d(topo, i, j)
                if g >= topo.layer_groups(lay):
                    continue  # this layer's k-chunk is exhausted
                k = group_k(topo, i, j, g)
                im, jn = i % s, j % s
                for i3 in range(topo.l_r):
                    m = i3 * s + im
                    a_classes.setdefault((i3, k % ca), []).append(
                        (flat(m, k // ca), flat(i, j))
                    )
                for j3 in range(topo.l_c):
                    n = j3 * s + jn
                    b_classes.setdefault((j3, k % cb), []).append(
                        (flat(k // cb, n), flat(i, j))
                    )
        a_ticks.append(
            tuple(
                PullRound(slot=slot, q=q, pairs=perm)
                for (slot, q), pairs in sorted(a_classes.items())
                for perm in _partition_rounds(pairs)
            )
        )
        b_ticks.append(
            tuple(
                PullRound(slot=slot, q=q, pairs=perm)
                for (slot, q), pairs in sorted(b_classes.items())
                for perm in _partition_rounds(pairs)
            )
        )

    # L-1 partial-C sends: round d moves the partial for the panel d steps
    # along the flattened layer ring to its home (a full permutation).
    c_rounds: list[Perm] = []
    for d in range(1, topo.l):
        pairs = []
        for i in range(p_r):
            for j in range(p_c):
                _, _, lay = coords3d(topo, i, j)
                t = (lay + d) % topo.l
                ti3, tj3 = t % topo.l_r, t // topo.l_r
                pairs.append(
                    (flat(i, j), flat(ti3 * s + i % s, tj3 * s + j % s))
                )
        c_rounds.append(tuple(pairs))
    return tuple(a_ticks), tuple(b_ticks), tuple(c_rounds), ca, cb


def _resolve_l(p_r: int, p_c: int, l: int | None) -> int:
    """Default depth: forced mx/mn on non-square grids (the paper's rule),
    1 on square grids unless the caller asks for more."""
    if l is not None:
        return l
    if p_r != p_c:
        mn, mx = min(p_r, p_c), max(p_r, p_c)
        if mx % mn == 0 and mx <= mn * mn:
            return mx // mn
    return 1


@lru_cache(maxsize=256)
def plan_multiply(mesh, engine: str, l: int | None = None) -> MultiplyPlan:
    """Compile the static schedule for (mesh, engine).

    2D meshes must carry ("r", "c") axes; the 2.5D stacked formulation uses
    an ("l", "r", "c") mesh.  ``l`` overrides the depth for pull plans on
    square grids (non-square grids force L = mx/mn as in the paper).
    """
    axis_names = tuple(mesh.axis_names)
    if engine not in ("cannon", "onesided", "gather", "twofive"):
        raise ValueError(f"unknown engine {engine!r}")
    if l is not None and engine in ("cannon", "onesided", "gather"):
        raise ValueError(
            f"engine {engine!r} has no depth parameter (L is fixed at 1); "
            "use engine='twofive' for L > 1"
        )

    if "l" in axis_names:
        if engine != "twofive":
            raise ValueError(f"engine {engine!r} does not use an 'l' mesh axis")
        l_size = mesh.shape["l"]
        if l is not None and l != l_size:
            raise ValueError(
                f"l={l} conflicts with the mesh's 'l' axis of size {l_size}; "
                "the stacked engine takes its depth from the mesh"
            )
        p = mesh.shape["r"]
        if mesh.shape["c"] != p:
            raise ValueError(
                "stacked 2.5D requires square layer grids; use a 2D "
                "(r, c) mesh for non-square topologies (virtual depth)"
            )
        # the mesh formulation's chunk structure: V = p, depth = l_size.
        topo = Topology(
            p_r=p, p_c=p, l=l_size, l_r=1, l_c=l_size, side3d=p,
            v=p, nbuffers_a=2, nbuffers_b=2,
        )
        groups = tuple(topo.layer_groups(li) for li in range(l_size))
        starts = tuple(topo.chunk(li)[0] for li in range(l_size))
        ticks = max(groups)
        pre_a = tuple(
            (
                (li * p + i) * p + j,
                (li * p + i) * p + (j - i - starts[li]) % p,
            )
            for li in range(l_size)
            for i in range(p)
            for j in range(p)
        )
        pre_b = tuple(
            (
                (li * p + i) * p + j,
                (li * p + (i - j - starts[li]) % p) * p + j,
            )
            for li in range(l_size)
            for i in range(p)
            for j in range(p)
        )
        return MultiplyPlan(
            engine=engine, kind="stacked", mesh=mesh, axes=("l", "r", "c"),
            p_r=p, p_c=p, topo=topo, ticks=ticks,
            pre_a=pre_a, pre_b=pre_b,
            shift_a=_ring_perm(p), shift_b=_ring_perm(p),
            layer_groups=groups, chunk_starts=starts,
        )

    p_r, p_c = mesh.shape["r"], mesh.shape["c"]
    if engine == "gather":
        topo = make_topology(p_r, p_c, 1)
        return MultiplyPlan(
            engine=engine, kind="gather", mesh=mesh, axes=("r", "c"),
            p_r=p_r, p_c=p_c, topo=topo, ticks=1,
        )

    if engine == "cannon":
        if p_r != p_c:
            raise ValueError("Cannon engine requires a square grid")
        p = p_r
        topo = make_topology(p, p, 1)
        pre_a = tuple(
            (i * p + j, i * p + (j - i) % p) for i in range(p) for j in range(p)
        )
        pre_b = tuple(
            (i * p + j, ((i - j) % p) * p + j) for i in range(p) for j in range(p)
        )
        return MultiplyPlan(
            engine=engine, kind="ring", mesh=mesh, axes=("r", "c"),
            p_r=p, p_c=p, topo=topo, ticks=topo.v,
            pre_a=pre_a, pre_b=pre_b,
            shift_a=_ring_perm(p), shift_b=_ring_perm(p),
        )

    # onesided / twofive on the plain 2D grid: the pull formulation.
    depth = 1 if engine == "onesided" else _resolve_l(p_r, p_c, l)
    topo = make_topology(p_r, p_c, depth)
    if l is not None and engine == "twofive" and topo.l != l:
        raise ValueError(
            f"L={l} is invalid for a {p_r}x{p_c} grid (paper rule); "
            f"topology resolved L={topo.l}"
        )
    a_pulls, b_pulls, c_rounds, ca, cb = _pull_schedule(topo)
    return MultiplyPlan(
        engine=engine, kind="pull", mesh=mesh, axes=("r", "c"),
        p_r=p_r, p_c=p_c, topo=topo, ticks=topo.ticks,
        a_pulls=a_pulls, b_pulls=b_pulls, c_rounds=c_rounds, ca=ca, cb=cb,
    )


# ---------------------------------------------------------------------------
# compiled-program cache
# ---------------------------------------------------------------------------


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    builds: int = 0  # program constructions (lower/trace roots)
    pattern_hits: int = 0  # compacted product-list reuse (same signature)
    pattern_misses: int = 0
    chain_hits: int = 0  # fused chain-step program reuse (sign iteration)
    chain_misses: int = 0
    tuner_hits: int = 0  # engine="auto" decisions served without trials
    tuner_misses: int = 0  # decisions that needed analytic rank / trials
    tuner_trials: int = 0  # candidates actually timed by the tuner
    transport_hits: int = 0  # transport resolutions served from the cache
    transport_misses: int = 0  # resolutions that walked the masks
    transport_dense: int = 0  # fresh resolutions that chose dense panels
    transport_compressed: int = 0  # ... that chose compressed panels
    envelope_hits: int = 0  # chain-envelope forecasts served from cache
    envelope_misses: int = 0  # forecasts that ran the symbolic propagation
    dispatch_hits: int = 0  # serving-dispatch bucket lookups served warm
    dispatch_misses: int = 0  # ... that warmed a new bucket
    drift_retunes: int = 0  # pattern drift that forced a re-tune/re-derive


_CACHE_MAXSIZE = 128
_program_cache: OrderedDict[tuple, object] = OrderedDict()
_pattern_cache: OrderedDict[bytes, tuple] = OrderedDict()
_bound_cache: OrderedDict[tuple, int] = OrderedDict()
_transport_cache: OrderedDict[tuple, object] = OrderedDict()
_assign_cache: OrderedDict[tuple, object] = OrderedDict()
_envelope_cache: OrderedDict[tuple, object] = OrderedDict()
_stats = CacheStats()


_extra_caches: list = []  # clear() callables of satellite layers (tuner)


def register_cache(clear_fn) -> None:
    """Register a satellite cache's clear callable: ``clear_cache()``
    must drop *every* cache level (program, pattern, chain, tuner) so
    test modules and drivers start from a genuinely clean slate."""
    if clear_fn not in _extra_caches:
        _extra_caches.append(clear_fn)


def cache_stats() -> dict:
    """Program/pattern/chain/tuner-cache counters (hits / misses / ...)."""
    return dataclasses.asdict(_stats)


def clear_cache() -> None:
    """Drop ALL plan-layer caches and zero every counter: compiled
    programs (incl. chain steps), pattern product-lists, capacity bounds,
    transport resolutions, the compiled-schedule LRU (``plan_multiply``)
    and any registered satellite caches (the tuner's decision cache +
    default-DB binding)."""
    _program_cache.clear()
    _pattern_cache.clear()
    _bound_cache.clear()
    _transport_cache.clear()
    _assign_cache.clear()
    _envelope_cache.clear()
    plan_multiply.cache_clear()
    for fn in _extra_caches:
        fn()
    global _stats
    _stats = CacheStats()


# ---------------------------------------------------------------------------
# compacted product lists (DBCSR stack generation), pattern-signature cached
# ---------------------------------------------------------------------------


def get_product_stacks(pair_ok):
    """Compacted product list of a concrete (ni, nk, nj) filter cube.

    Returns ``(stacks, n_products)``: a ``kernels.stacks.ProductStacks``
    padded to the power-of-two capacity bucket of the surviving-product
    count, LRU-cached on the pattern signature.  A repeated sparsity
    pattern is a pure cache hit — no host walk, and (because the local
    program key depends only on shapes and the capacity bucket) no
    recompile either.
    """
    from repro.kernels.stacks import (
        bucket_capacity,
        compact_pair_mask,
        pattern_signature,
        product_count,
    )

    sig = pattern_signature(pair_ok)
    hit = _pattern_cache.get(sig)
    if hit is not None:
        _stats.pattern_hits += 1
        _pattern_cache.move_to_end(sig)
        return hit
    _stats.pattern_misses += 1
    n = product_count(pair_ok)
    stacks = compact_pair_mask(
        jnp.asarray(pair_ok), capacity=bucket_capacity(n)
    )
    entry = (stacks, n)
    _pattern_cache[sig] = entry
    if len(_pattern_cache) > _CACHE_MAXSIZE:
        _pattern_cache.popitem(last=False)
    return entry


def device_stack_bound(ok, mesh, engine: str) -> int:
    """Sound per-call product-count bound for the distributed engines.

    Every engine computes each surviving global triple exactly once, and a
    single ``local_filtered_mm`` call never sees more than one device's
    share: for the own-C-tile engines (cannon / onesided / gather) that
    share is the triples of the device's C panel; the twofive
    formulations compute partial panels for other owners, so the loose but
    sound total count is used.
    """
    if engine == "twofive":
        return int(ok.sum())
    p_r, p_c = mesh.shape["r"], mesh.shape["c"]
    nb_r, _, nb_c = ok.shape
    rr, cc = nb_r // p_r, nb_c // p_c
    best = 0
    for r in range(p_r):
        for c in range(p_c):
            cnt = int(ok[r * rr:(r + 1) * rr, :, c * cc:(c + 1) * cc].sum())
            best = max(best, cnt)
    return best


def get_device_capacity(ok, mesh, engine: str) -> int:
    """Bucketed distributed stack capacity, LRU-cached like the product
    lists: keyed on (pattern signature, partition class) so the hot-path
    multiply loop re-derives nothing for a repeated pattern."""
    from repro.kernels.stacks import bucket_capacity, pattern_signature

    key = (
        pattern_signature(ok), mesh.shape["r"], mesh.shape["c"],
        "twofive" if engine == "twofive" else "own-panel",
    )
    hit = _bound_cache.get(key)
    if hit is not None:
        _stats.pattern_hits += 1
        _bound_cache.move_to_end(key)
        return hit
    _stats.pattern_misses += 1
    cap = bucket_capacity(device_stack_bound(ok, mesh, engine))
    _bound_cache[key] = cap
    if len(_bound_cache) > _CACHE_MAXSIZE:
        _bound_cache.popitem(last=False)
    return cap


def get_transport(
    mask_a,
    mask_b,
    mesh,
    engine: str,
    l: int | None = None,
    mode: str = "auto",
):
    """Resolve the panel transport for one (pattern pair, mesh, engine).

    Derives the sound bucketed per-panel capacities from the concrete
    operand masks — the maximum occupied-block count over every A / B
    panel the plan's schedule ships (whole shards for ring / stacked /
    gather, virtual-grid subpanels for the pull formulation) — and
    applies the ``auto`` crossover (``transport.resolve_mode``).
    LRU-cached on the pattern signatures like the product lists, so a
    repeated pattern re-derives nothing; counted by the ``transport_*``
    fields of ``cache_stats()``.
    """
    import numpy as np

    from repro.core import transport as T
    from repro.kernels.stacks import pattern_signature

    am = np.asarray(mask_a, bool)
    bm = np.asarray(mask_b, bool)
    key = (
        "transport", pattern_signature(am), pattern_signature(bm),
        tuple((n, int(mesh.shape[n])) for n in mesh.axis_names),
        engine, l, mode,
    )
    hit = _transport_cache.get(key)
    if hit is not None:
        _stats.transport_hits += 1
        _transport_cache.move_to_end(key)
        return hit
    _stats.transport_misses += 1
    plan = plan_multiply(mesh, engine, l)
    cap_a, cap_b, blocks_a, blocks_b = T.capacities_for(am, bm, plan)
    resolved = T.resolve_mode(mode, cap_a, cap_b, blocks_a, blocks_b)
    if resolved == "compressed":
        tr = T.PanelTransport("compressed", cap_a, cap_b)
        _stats.transport_compressed += 1
    else:
        tr = T.DENSE
        _stats.transport_dense += 1
    _transport_cache[key] = tr
    if len(_transport_cache) > _CACHE_MAXSIZE:
        _transport_cache.popitem(last=False)
    return tr


def resolve_transport(spec, a, b, mesh, engine: str, l: int | None = None):
    """Normalize a transport spec to a concrete ``PanelTransport``.

    ``spec`` may be a ready ``PanelTransport`` (revalidated against this
    engine's panel partition — see below), a mode string (``"auto"`` /
    ``"dense"`` / ``"compressed"``), or ``None`` — the configured
    default (``config.transport_mode``, overridable via
    ``REPRO_TRANSPORT``).  Mode strings other than ``"dense"`` need
    concrete operand masks to derive capacities from; traced operands
    fall back to dense under ``auto`` (no pattern to pack against — the
    same degradation ``backend="auto"`` applies) and are an error under
    a forced ``"compressed"``.

    An explicit compressed ``PanelTransport`` is checked against the
    sound bounds of THIS (mesh, engine, pattern): capacities derived for
    one plan kind (e.g. pull subpanels) can under-cover another's panels
    (e.g. cannon's whole shards), and ``pack_panel`` truncates silently —
    under-capacity must be an error here, never a wrong C.  Traced
    operands skip the check (no pattern to validate against).
    """
    import jax

    from repro.core import transport as T

    traced = (
        isinstance(a.mask, jax.core.Tracer)
        or isinstance(b.mask, jax.core.Tracer)
    )
    if isinstance(spec, T.PanelTransport):
        if spec.compressed and not traced:
            # compare against the RAW per-panel bounds (not the bucketed
            # capacities get_transport hands out): any capacity covering
            # the true maximum occupied count is sound
            import numpy as np

            plan = plan_multiply(mesh, engine, l)
            (ar, ac), (br, bc) = T.plan_panel_parts(plan)
            need_a = T.panel_nnz_bound(np.asarray(a.mask, bool), ar, ac)
            need_b = T.panel_nnz_bound(np.asarray(b.mask, bool), br, bc)
            if spec.cap_a < need_a or spec.cap_b < need_b:
                raise ValueError(
                    f"transport capacities ({spec.cap_a}, {spec.cap_b}) "
                    f"under-cover the {engine!r} plan's panels "
                    f"(need >= ({need_a}, {need_b})): packing would "
                    "silently drop blocks"
                )
        return spec
    if spec is None:
        from repro.config import transport_mode

        mode = transport_mode()
    else:
        mode = spec
    if mode == "dense":
        return T.DENSE
    if mode not in ("auto", "compressed"):
        raise ValueError(
            f"unknown transport {mode!r}; a PanelTransport or one of "
            "auto | dense | compressed"
        )
    if traced:
        if mode == "compressed":
            raise ValueError(
                "transport='compressed' needs concrete operand patterns "
                "to derive sound panel capacities (operands are traced)"
            )
        return T.DENSE
    return get_transport(a.mask, b.mask, mesh, engine, l, mode)


def get_assignment(mask_a, mask_b, mesh, mode: str):
    """Resolve the block→device assignment of one (pattern pair, mesh,
    mode) — the distribution layer's analogue of :func:`get_transport`.

    Derives the deterministic permutation of ``core.distribute`` from the
    concrete operand masks (``assignment_for`` on the integer mask
    product), LRU-cached on the pattern signatures so a repeated pattern
    re-walks nothing.
    """
    import numpy as np

    from repro.core import distribute as D
    from repro.kernels.stacks import pattern_signature

    am = np.asarray(mask_a, bool)
    bm = np.asarray(mask_b, bool)
    p_r, p_c = mesh.shape["r"], mesh.shape["c"]
    key = (
        "assign", pattern_signature(am), pattern_signature(bm),
        p_r, p_c, mode,
    )
    hit = _assign_cache.get(key)
    if hit is not None:
        _assign_cache.move_to_end(key)
        return hit
    asg = D.assignment_for(mode, D.product_counts(am, bm), (p_r, p_c))
    _assign_cache[key] = asg
    if len(_assign_cache) > _CACHE_MAXSIZE:
        _assign_cache.popitem(last=False)
    return asg


def resolve_assignment(spec, a, b, mesh):
    """Normalize an assignment spec to a ``distribute.Assignment`` or None
    (= identity layout).

    ``spec`` may be None / ``"identity"`` (no permutation), a mode string
    (``"randomized"`` / ``"nnz_greedy"`` — derived from the concrete
    operand masks via :func:`get_assignment`; traced operands are an
    error, exactly like a forced compressed transport), or a ready
    ``Assignment`` (validated against the operands' block grid; an
    explicitly-identity permutation collapses to None so cache keys stay
    in their pre-assignment shape).
    """
    if spec is None:
        return None
    from repro.core import distribute as D

    if isinstance(spec, str):
        if spec == "identity":
            return None
        if spec not in D.MODES:
            raise ValueError(
                f"unknown assignment {spec!r}; an Assignment or one of "
                f"{D.MODES}"
            )
        import jax

        if (isinstance(a.mask, jax.core.Tracer)
                or isinstance(b.mask, jax.core.Tracer)):
            raise ValueError(
                f"assignment={spec!r} needs concrete operand patterns to "
                "derive the permutation from (operands are traced); "
                "resolve the Assignment outside the trace"
            )
        asg = get_assignment(a.mask, b.mask, mesh, spec)
    elif isinstance(spec, D.Assignment):
        asg = spec
    else:
        raise TypeError(
            f"assignment must be None, a mode string {D.MODES}, or a "
            f"distribute.Assignment; got {type(spec).__name__}"
        )
    asg.validate(a.nb_r, a.nb_c)
    asg.validate(b.nb_r, b.nb_c)
    return None if asg.is_identity else asg


def get_envelope(
    mask,
    norms,
    *,
    sweeps: int,
    threshold: float = 0.0,
    filter_eps: float = 0.0,
    bs: int = 1,
    margin: float | None = None,
):
    """Forecast (or fetch) the pattern envelope of a purification chain.

    LRU-caches :func:`repro.core.envelope.forecast_chain` on the digest of
    the concrete entering pattern (mask bits + norm bytes) and the chain
    spec, so a serving loop that re-runs the same chain — or the warm
    sweeps of one iteration — pays the symbolic propagation exactly once.
    Counted by ``envelope_hits`` / ``envelope_misses`` in
    ``cache_stats()``.
    """
    import hashlib

    import numpy as np

    from repro.core import envelope as E

    if margin is None:
        margin = E.DEFAULT_MARGIN
    am = np.ascontiguousarray(np.asarray(mask, bool))
    an = np.ascontiguousarray(np.asarray(norms, np.float32))
    h = hashlib.sha1(np.packbits(am).tobytes())
    h.update(an.tobytes())
    key = (
        "envelope", h.digest(), am.shape, int(sweeps), float(threshold),
        float(filter_eps), int(bs), float(margin),
    )
    hit = _envelope_cache.get(key)
    if hit is not None:
        _stats.envelope_hits += 1
        _envelope_cache.move_to_end(key)
        return hit
    _stats.envelope_misses += 1
    env = E.forecast_chain(
        am, an, sweeps=sweeps, threshold=threshold, filter_eps=filter_eps,
        bs=bs, margin=margin,
    )
    _envelope_cache[key] = env
    if len(_envelope_cache) > _CACHE_MAXSIZE:
        _envelope_cache.popitem(last=False)
    return env


def note_drift_retune() -> None:
    """Count one drift-forced re-resolution (``drift_retunes``): a
    concrete pattern escaped its envelope, or a tuned decision stream's
    coarse feature bucket changed — either way the warm path was
    abandoned and capacities/modes were re-derived."""
    _stats.drift_retunes += 1


def note_dispatch_lookup(hit: bool) -> None:
    """Count one serving-dispatch bucket lookup (``dispatch_hits`` /
    ``dispatch_misses``): the pattern-bucketed serving cache
    (``core.envelope.DispatchCache``) resolved a per-batch dispatch mask
    against its warmed per-bucket envelopes — a hit means zero per-batch
    pattern walks (the warm serving path), a miss means a new bucket was
    warmed (once per request-mix regime, not per batch)."""
    if hit:
        _stats.dispatch_hits += 1
    else:
        _stats.dispatch_misses += 1


def get_local_compiled(
    ni: int,
    nk: int,
    nj: int,
    bs_r: int,
    bs_k: int,
    bs_c: int,
    dtype,
    *,
    backend: str,
    capacity: int,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
):
    """Jitted single-device compacted local-stage program, LRU-cached.

    The program maps ``(a_blocks, b_blocks, stacks) -> c_blocks`` where
    ``stacks`` is a padded product list of exactly ``capacity`` entries.
    The key carries no pattern data — only shapes, dtype, backend, the
    capacity bucket and (pallas) the MXU tile shape — so every pattern in
    a bucket shares one executable.
    """
    import jax

    if backend == "pallas" and interpret is None:
        # resolve before keying: the env/platform default must not get
        # baked into a None-keyed entry (REPRO_PALLAS_INTERPRET may change)
        from repro.kernels.ops import _default_interpret

        interpret = _default_interpret()
    key = (
        "local", ni, nk, nj, bs_r, bs_k, bs_c, jnp.dtype(dtype).name,
        backend, capacity, tile, interpret,
    )
    prog = _program_cache.get(key)
    if prog is not None:
        _stats.hits += 1
        _program_cache.move_to_end(key)
        return prog
    _stats.misses += 1
    _stats.builds += 1
    if backend == "stacks":
        from repro.core.local_mm import stacks_mm

        def fn(a_blocks, b_blocks, stacks):
            return stacks_mm(a_blocks, b_blocks, stacks, ni=ni, nj=nj)

    elif backend == "pallas":
        from repro.kernels.block_spgemm import block_spgemm_stacks

        interp = bool(interpret)

        def fn(a_blocks, b_blocks, stacks):
            return block_spgemm_stacks(
                a_blocks, b_blocks, stacks, ni=ni, nj=nj, tile=tile,
                interpret=interp,
            )

    else:
        raise ValueError(
            f"backend {backend!r} has no compacted local program"
        )
    prog = jax.jit(fn)
    _program_cache[key] = prog
    if len(_program_cache) > _CACHE_MAXSIZE:
        _program_cache.popitem(last=False)
    return prog


def build_program(plan: MultiplyPlan, *, threshold: float, backend: str,
                  c_layout: str, stack_capacity: int | None = None,
                  tile: tuple[int, int, int] | None = None,
                  interpret: bool | None = None, transport=None):
    """Construct (untraced) the shard_map executor for a plan."""
    if c_layout != "2d" and plan.kind != "stacked":
        raise ValueError(
            f"c_layout={c_layout!r} needs the stacked (l, r, c) mesh; "
            f"the {plan.kind!r} plan keeps C in the 2D (r, c) layout"
        )
    from repro.core import transport as T

    _stats.builds += 1
    kw = dict(
        threshold=threshold, backend=backend,
        stack_capacity=stack_capacity, tile=tile, interpret=interpret,
        transport=transport if transport is not None else T.DENSE,
    )
    if plan.kind == "ring":
        from repro.core.cannon import ring_executor

        return ring_executor(plan, **kw)
    if plan.kind == "pull":
        from repro.core.twofive import pull_executor

        return pull_executor(plan, **kw)
    if plan.kind == "stacked":
        from repro.core.twofive import stacked_executor

        return stacked_executor(plan, c_layout=c_layout, **kw)
    if plan.kind == "gather":
        from repro.core.gather import gather_executor

        return gather_executor(plan, **kw)
    raise ValueError(plan.kind)


def build_shard_body(plan: MultiplyPlan, *, threshold: float, backend: str,
                     stack_capacity: int | None = None,
                     tile: tuple[int, int, int] | None = None,
                     interpret: bool | None = None, transport=None):
    """The engine's raw per-shard body: ``(ab, am, an, bb, bm, bn) ->
    (cb, cm, calls)`` on shards, no shard_map wrapper, under the
    ``spgemm.engine`` named scope.  ``calls`` are the operand masks of
    the shard's local-stage calls (``local_mm.product_counts``).

    Iteration chains (``core/signiter.py``) inline this into ONE enclosing
    shard_map spanning a whole sweep — multiple multiplies plus the
    inter-multiply algebra run per-shard with no re-partitioning between
    them, which is what makes the fused chain step a single cheap
    dispatch.  C always comes home in the 2D (r, c) layout (the stacked
    plan uses its c_layout="2d" psum), so chained calls compose.

    ``transport`` defaults to dense: chains are traced once while the
    sparsity pattern evolves underneath them, so a static compressed
    capacity from the initial pattern would be unsound — the same reason
    chains pin the dense local backend (``tuner.model.chain_safe``).
    """
    from repro.core import transport as T

    _stats.builds += 1
    kw = dict(
        threshold=threshold, backend=backend,
        stack_capacity=stack_capacity, tile=tile, interpret=interpret,
        transport=transport if transport is not None else T.DENSE,
    )
    if plan.kind == "ring":
        from repro.core.cannon import ring_body

        body = ring_body(plan, **kw)
    elif plan.kind == "pull":
        from repro.core.twofive import pull_body

        body = pull_body(plan, **kw)
    elif plan.kind == "stacked":
        from repro.core.twofive import stacked_body

        body = stacked_body(plan, c_layout="2d", **kw)
    elif plan.kind == "gather":
        from repro.core.gather import gather_body

        body = gather_body(plan, **kw)
    else:
        raise ValueError(plan.kind)

    def scoped(*shards):
        import jax

        with jax.named_scope("spgemm.engine"):
            return body(*shards)

    return scoped


def get_compiled(
    mesh,
    engine: str,
    nb_r: int,
    bs: int,
    dtype,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    c_layout: str = "2d",
    l: int | None = None,
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    transport=None,
    assignment=None,
    nb_k: int | None = None,
    nb_c: int | None = None,
    bs_k: int | None = None,
    bs_c: int | None = None,
):
    """Jitted multiply program for the key, LRU-cached.

    Repeated multiplies with the same key return the *same* jitted callable,
    so jax's compilation cache is hit and no retracing/relowering happens —
    the per-call dispatch cost collapses to argument handling.

    ``transport`` must already be concrete here (a ``PanelTransport`` or
    None = dense): mode and capacities are part of the key, so callers
    resolve patterns *before* keying (``execute`` / ``execute_sharded``
    via :func:`resolve_transport`) — an auto decision must never get
    baked into a None-keyed entry.

    ``assignment`` likewise must be concrete (a ``distribute.Assignment``
    or None = identity; :func:`resolve_assignment` normalizes specs).
    Non-identity assignments wrap the program with the symmetric
    permute-in / unpermute-out reindex — callers hand UNPERMUTED triples
    and get the result back in original block coordinates; the engine
    body in between only ever sees the permuted layout.  The assignment
    signature joins the key only when non-identity, so pre-assignment
    keys (and any state keyed on them) are unchanged.  Capacities in the
    key (``stack_capacity``, ``transport``) must have been derived from
    the PERMUTED pattern — a permutation changes which products land on
    which device, and an identity-layout bound can under-cover a hot
    permuted panel.

    ``nb_k`` / ``nb_c`` / ``bs_k`` / ``bs_c`` describe a rectangular
    product (A ``nb_r x nb_k`` of ``bs x bs_k`` blocks, B ``nb_k x nb_c``
    of ``bs_k x bs_c``).  Left at None they default to the square contract
    every pre-tensor caller used — the key is unchanged for those callers.
    When any is set, the full shape joins the key and the k dimension is
    validated against the plan (the engine bodies themselves are
    shape-polymorphic: one cache entry per full shape, jit retraces per
    input shape anyway).  Non-identity assignments are square-only — the
    symmetric block permutation has no meaning on a rectangular grid — so
    a rectangular shape plus an assignment is rejected here, loudly.
    """
    import jax

    from repro.core import transport as T

    if backend == "pallas" and interpret is None:
        # resolve before keying (as in get_local_compiled): the
        # env/platform default must not get baked into a None-keyed entry
        from repro.kernels.ops import _default_interpret

        interpret = _default_interpret()
    if transport is None:
        transport = T.DENSE
    elif not isinstance(transport, T.PanelTransport):
        raise TypeError(
            "get_compiled takes a resolved PanelTransport (or None = "
            f"dense), got {transport!r}; resolve mode strings with "
            "plan.resolve_transport first"
        )
    if assignment is not None and assignment.is_identity:
        assignment = None
    rect = (nb_k, nb_c, bs_k, bs_c) != (None, None, None, None)
    if rect and assignment is not None:
        raise ValueError(
            "block->device assignments permute rows and columns "
            "symmetrically; a rectangular product "
            f"({nb_r}x{nb_k or nb_r} @ {nb_k or nb_r}x{nb_c or nb_r}) "
            "has no symmetric layout — use assignment=None/'identity'"
        )
    key = (
        mesh, engine, nb_r, bs, jnp.dtype(dtype).name,
        float(threshold), backend, c_layout, l, stack_capacity, tile,
        interpret, transport.key,
    )
    if rect:
        key = key + (("rect", nb_k, nb_c, bs_k, bs_c),)
    if assignment is not None:
        key = key + (("assign",) + assignment.key,)
    prog = _program_cache.get(key)
    if prog is not None:
        _stats.hits += 1
        _program_cache.move_to_end(key)
        return prog
    _stats.misses += 1
    plan = plan_multiply(mesh, engine, l)
    if rect:
        plan.validate_blocks(
            nb_r, nb_r if nb_c is None else nb_c,
            nb_r if nb_k is None else nb_k,
        )
    else:
        plan.validate_blocks(nb_r, nb_r)
    fn = build_program(
        plan, threshold=threshold, backend=backend, c_layout=c_layout,
        stack_capacity=stack_capacity, tile=tile, interpret=interpret,
        transport=transport,
    )
    if assignment is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        inner = fn
        perm = jnp.asarray(assignment.perm)
        inv = jnp.asarray(assignment.inv)
        # The reindex gathers live OUTSIDE the engine's shard_map; pin
        # them replicated so the SPMD partitioner never tries to push the
        # engine's (r, c) home-layout shardings backwards through a
        # cross-shard gather (it cannot, and fails at HLO verification).
        # The replicated path hands replicated triples in anyway, and its
        # result is consumed replicated — the constraints cost nothing
        # beyond what the layout-oblivious caller already pays.
        rep = None if mesh is None else NamedSharding(mesh, P())

        def fn(ab, am, an, bb, bm, bn):
            def to(x):
                y = x[perm][:, perm]
                return y if rep is None else jax.lax.with_sharding_constraint(y, rep)

            cb, cm = inner(to(ab), to(am), to(an), to(bb), to(bm), to(bn))
            if rep is not None:
                cb = jax.lax.with_sharding_constraint(cb, rep)
                cm = jax.lax.with_sharding_constraint(cm, rep)
            return cb[inv][:, inv], cm[inv][:, inv]

    prog = jax.jit(fn)
    _program_cache[key] = prog
    if len(_program_cache) > _CACHE_MAXSIZE:
        _program_cache.popitem(last=False)
    return prog


def _rect_dims(a, b) -> dict:
    """Full-shape kwargs for :func:`get_compiled` from an operand pair.

    Square pairs (the entire pre-tensor surface) return ``{}`` so their
    program-cache keys are byte-identical to before; rectangular pairs —
    matricized tensor operands — return the four extra dims.  Incompatible
    inner shapes fail here, before any program is keyed.
    """
    if a.nb_c != b.nb_r or a.bs_c != b.bs_r:
        raise ValueError(
            f"operand shapes do not contract: A is {a.nb_r}x{a.nb_c} "
            f"blocks of {a.bs_r}x{a.bs_c}, B is {b.nb_r}x{b.nb_c} "
            f"blocks of {b.bs_r}x{b.bs_c}"
        )
    if (a.nb_c, b.nb_c, a.bs_c, b.bs_c) == (a.nb_r, a.nb_r, a.bs_r, a.bs_r):
        return {}
    return dict(nb_k=a.nb_c, nb_c=b.nb_c, bs_k=a.bs_c, bs_c=b.bs_c)


def _permuted_mask_views(a, b, asg):
    """Lightweight stand-ins carrying the PERMUTED operand masks, for
    deriving transport capacities in the layout the engine will run in.
    Traced masks pass through unpermuted — every consumer falls back to
    pattern-free behavior on tracers anyway."""
    import types

    import jax
    import numpy as np

    if (isinstance(a.mask, jax.core.Tracer)
            or isinstance(b.mask, jax.core.Tracer)):
        return a, b
    p = np.asarray(asg.perm)
    return (
        types.SimpleNamespace(mask=np.asarray(a.mask, bool)[p][:, p]),
        types.SimpleNamespace(mask=np.asarray(b.mask, bool)[p][:, p]),
    )


def execute(a, b, mesh, engine: str, **kw):
    """Run one cached multiply and rebuild the BlockSparseMatrix result.

    The shared execution path behind ``engine.multiply`` and the per-engine
    back-compat wrappers (``multiply_2d``/``multiply_gather``/
    ``multiply_25d``); keyword args are those of :func:`get_compiled`.

    ``assignment`` (None / mode string / ``distribute.Assignment``)
    selects the block→device distribution the multiply runs under; the
    permute/unpermute pair lives inside the compiled program, so the
    caller's matrices stay in original block coordinates throughout.
    Transport capacities are derived from the permuted masks — the
    pattern the engine actually ships.
    """
    from repro.core.bsm import BlockSparseMatrix, block_norms

    if engine == "auto":
        from repro.tuner import resolve_multiply

        engine, kw = resolve_multiply(a, b, mesh, kw)
    asg = resolve_assignment(kw.pop("assignment", None), a, b, mesh)
    ta, tb = (a, b) if asg is None else _permuted_mask_views(a, b, asg)
    kw["transport"] = resolve_transport(
        kw.get("transport"), ta, tb, mesh, engine, kw.get("l")
    )
    kw.update(_rect_dims(a, b))
    fn = get_compiled(mesh, engine, a.nb_r, a.bs_r, a.dtype,
                      assignment=asg, **kw)
    cb, cm = fn(a.blocks, a.mask, a.norms, b.blocks, b.mask, b.norms)
    return BlockSparseMatrix(blocks=cb, mask=cm, norms=block_norms(cb))


def execute_sharded(a, b, engine: str, **kw):
    """Sharded multiply: ShardedBSM in, ShardedBSM out, no gather.

    The shard_map engine bodies already operate on shards; this path hands
    them operands that are *born* in the specs they declare, so XLA inserts
    no resharding, and the result triple stays in the 2D home layout.
    Keyword args are those of :func:`get_compiled` (``c_layout`` is pinned
    to ``"2d"`` — a chain's C must come home to the same layout its next
    multiply consumes).

    Sharded operands already LIVE in their assignment's permuted home
    layout (``shard_bsm`` applied it before the scatter), so the engine
    runs as-is — their permuted masks are the pattern every capacity is
    derived from, and the result inherits the layout.  An ``assignment``
    kwarg here can only confirm the carried layout; redistributing a
    sharded matrix means unsharding first.
    """
    from repro.core.bsm import ShardedBSM, _assign_name, block_norms

    mesh = a.mesh
    if kw.pop("c_layout", "2d") != "2d":
        raise ValueError("sharded chains require c_layout='2d'")
    asg = a._join_assignment(b)
    spec = kw.pop("assignment", None)
    if spec is not None:
        want = getattr(spec, "mode", spec)
        if want != _assign_name(asg):
            raise ValueError(
                f"operands are sharded under assignment "
                f"{_assign_name(asg)}; cannot execute under {want!r} — "
                "unshard and redistribute instead"
            )
    if engine == "auto":
        # one host walk of the (concrete, device-resident) pattern; the
        # tuner's decision cache makes repeats free for a stable pattern.
        # The assignment is pinned to identity: the layout decision was
        # made at shard_bsm time and the pattern the tuner sees is
        # already the permuted one.
        from repro.tuner import resolve_multiply

        kw["assignment"] = "identity"
        engine, kw = resolve_multiply(a, b, mesh, kw)
        kw.pop("assignment", None)
    # transport resolution under the default "auto" costs one host pull
    # + digest of the 2D masks PER CALL (the signature hash, not the
    # cache lookup, is the cost — it must sync the device-resident
    # mask).  Latency-critical async loops that cannot afford the sync
    # pin the mode (transport="dense" / REPRO_TRANSPORT=dense skips the
    # walk entirely); fused chains (signiter) never reach here.
    kw["transport"] = resolve_transport(
        kw.get("transport"), a, b, mesh, engine, kw.get("l")
    )
    kw.update(_rect_dims(a, b))
    fn = get_compiled(mesh, engine, a.nb_r, a.bs_r, a.dtype,
                      c_layout="2d", **kw)
    cb, cm = fn(a.blocks, a.mask, a.norms, b.blocks, b.mask, b.norms)
    return ShardedBSM(blocks=cb, mask=cm, norms=block_norms(cb), mesh=mesh,
                      assignment=asg)


def get_chain_compiled(key: tuple, builder):
    """Fused chain-step program (a whole sign-iteration sweep — or any
    multi-multiply algebra chain), LRU-cached like the multiply programs
    but counted separately (``chain_hits`` / ``chain_misses``): the
    per-chain counters tell a benchmark how many sweeps of an iteration
    reused one compiled step.

    ``builder`` constructs the jitted program on a miss; program builds it
    performs (``build_program`` / ``get_local_compiled``) are counted by
    the ordinary ``builds`` counter, so "at most one program per distinct
    multiply shape across a 10-sweep iteration" is assertable from
    ``cache_stats()`` alone.

    Chains with ``engine="auto"`` resolve the engine through the tuner
    *before* keying (``signiter.sign_iteration``): the chain key always
    carries a concrete engine, and the tuner's decision join the same
    ``cache_stats()`` counters (``tuner_hits`` / ``tuner_misses`` /
    ``tuner_trials``).
    """
    key = ("chain",) + tuple(key)
    prog = _program_cache.get(key)
    if prog is not None:
        _stats.chain_hits += 1
        _program_cache.move_to_end(key)
        return prog
    _stats.chain_misses += 1
    prog = builder()
    _program_cache[key] = prog
    if len(_program_cache) > _CACHE_MAXSIZE:
        _program_cache.popitem(last=False)
    return prog
