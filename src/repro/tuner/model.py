"""Analytic candidate model: enumerate, cost, and prune (DESIGN.md §6).

The tuner's first stage is purely analytic — no device work.  For a mesh
and a feature vector it enumerates every feasible ``(engine, L, backend,
stack_capacity)`` combination, prices each one with

* the paper's communication-volume model evaluated on the *actual
  compiled schedule* (``commvolume.plan_volume``, Eq. (7) incl.
  non-square grids), converted to seconds at the roofline ICI rate, and
* the local-stage roofline FLOP models (``roofline.hlo_cost``), dense
  cube for the ``jnp`` backend, surviving-products for the compacted
  backends (with the gather/scatter overhead factor that sets the
  dense/compacted crossover — ``local_mm.backend_local_cost``),

and prunes every candidate whose per-device memory footprint — the
Eq. (6) buffer model (``commvolume.device_memory_bytes``) plus the
compacted stack arrays sized by ``plan.get_device_capacity`` — exceeds
the per-device budget.  The surviving candidates, ranked by modeled time,
are what ``tuner.measure`` actually times: the analytic stage exists to
keep the measured stage short, exactly as in DBCSR's autotuning
(arXiv:1910.13555) and Hong et al.'s sparsity-aware algorithm selection
(arXiv:2408.14558).

Absolute times use the device's published peaks
(``roofline.device_peaks``; off a TPU, the v5e as a modelling target, so
there they are wrong in scale but consistent in *ranking* — which is all
the prune needs); measurement has the final word.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from repro.core import commvolume
from repro.core import plan as plan_mod
from repro.core.local_mm import (  # noqa: F401 - re-exported
    choose_local_backend,
    compacted_backend,
    device_memory_budget,
    local_stage_cost,
)
from repro.core.topology import validate_l
from repro.roofline import device_peaks
from repro.tuner.features import PairFeatures


# modeled per-tick dispatch/latency overhead: serializes the many-tick
# schedules (Cannon's V hops) against the one-shot gather engine even when
# their byte volumes tie.  Seconds; coarse on purpose — measurement refines.
TICK_OVERHEAD_S = 20e-6


_ASSIGN_TAGS = {"randomized": "@rand", "nnz_greedy": "@nnz"}


@dataclass(frozen=True)
class Candidate:
    """One point of the tuner's decision space."""

    engine: str
    l: int | None = None  # depth for twofive pull plans (None = plan default)
    backend: str = "jnp"
    stack_capacity: int | None = None  # compacted backends: device bound
    transport: str = "dense"  # panel transport mode ("dense"|"compressed")
    tile: tuple[int, int, int] | None = None  # pallas MXU tile (None=default)
    assign: str = "identity"  # block→device assignment mode (distribute.MODES)

    @property
    def label(self) -> str:
        tag = self.engine if self.l is None else f"{self.engine}-l{self.l}"
        tag = f"{tag}/{self.backend}"
        if self.tile is not None:
            tm, tk, tn = self.tile
            tag = f"{tag}/t{tm}x{tk}x{tn}"
        if self.transport == "compressed":
            tag += "+ct"
        return tag + _ASSIGN_TAGS.get(self.assign, "")


@dataclass(frozen=True)
class Estimate:
    """Analytic cost of one candidate on one (mesh, features) pair."""

    candidate: Candidate
    comm_s: float
    compute_s: float
    mem_bytes: float
    feasible: bool
    reason: str = ""  # why infeasible (empty when feasible)

    @property
    def total_s(self) -> float:
        return self.comm_s + self.compute_s


@dataclass(frozen=True)
class ModelReport:
    """Ranked feasible candidates + everything that was pruned."""

    ranked: tuple[Estimate, ...]  # feasible, best modeled time first
    pruned: tuple[Estimate, ...] = field(default=())


def mesh_signature(mesh) -> tuple:
    """Hashable, JSON-able identity of a mesh for decision/DB keys."""
    return tuple((name, int(mesh.shape[name])) for name in mesh.axis_names)


def valid_square_depths(p: int) -> list[int]:
    """Depths L > 1 valid on a square p x p grid (paper §3 rule)."""
    return [k * k for k in range(2, p + 1) if p % k == 0]


def assignment_space(
    counts, mesh, *, assigns: tuple[str, ...] | None = None
) -> dict[str, object]:
    """The assignment modes worth ranking for one (counts, mesh) pair,
    resolved to their deterministic ``distribute.Assignment`` objects
    (identity maps to None).

    Without concrete ``counts`` there is nothing to derive a permutation
    from, so only identity survives — the same degradation as compressed
    transport without masks.  Non-square block grids cannot take a
    symmetric permutation and also collapse to identity.
    """
    from repro.core import distribute as D

    if assigns is None:
        assigns = D.MODES
    out: dict[str, object] = {}
    for mode in assigns:
        if mode == "identity":
            out["identity"] = None
            continue
        if counts is None:
            continue
        c = np.asarray(counts)
        if c.shape[0] != c.shape[1] or c.shape[0] % math.lcm(
            int(mesh.shape["r"]), int(mesh.shape["c"])
        ):
            continue
        out[mode] = D.assignment_for(mode, c, (mesh.shape["r"],
                                               mesh.shape["c"]))
    if not out:
        out["identity"] = None
    return out


def enumerate_candidates(
    mesh,
    feats: PairFeatures,
    *,
    ok=None,
    counts=None,
    engines: tuple[str, ...] | None = None,
    backends: tuple[str, ...] | None = None,
    l: int | None = None,
    transports: tuple[str, ...] | None = None,
    assigns: tuple[str, ...] | None = None,
) -> list[Candidate]:
    """All (engine, L, backend, capacity, transport, assignment) points
    feasible for ``mesh``.

    ``ok`` — optional concrete filter cube; with it the compacted
    backends get their exact bucketed per-device capacity
    (``plan.get_device_capacity``), without it they are skipped (no sound
    static bound to hand the compiled program) and so is compressed
    transport (capacities are derived from the concrete masks at
    execution).  ``engines`` / ``l`` / ``backends`` / ``transports`` /
    ``assigns`` restrict the space (caller-pinned choices).

    The ``pallas`` backend additionally fans out over the MXU tile shapes
    worth measuring for this block shape and storage dtype
    (``kernels.block_spgemm.tile_candidates``; ``tile=None`` = the
    shipped ``default_tile``).  The searched axis is the *tile*; the
    storage dtype is a feature (part of the DB key), not a choice — the
    tuner never trades precision for speed on its own.

    ``counts`` — the integer mask product; with it non-identity block
    assignments (``core.distribute``) join the space for the candidates
    they can actually change: the compacted backends (whose capacity is a
    max over devices — derived here from the PERMUTED cube) and
    compressed transport (max-over-panels capacities).  For a dense-jnp
    candidate every device does identical dense work whatever the
    layout, so fanning assignments out there would only burn trial time.
    """
    axes = tuple(mesh.axis_names)
    if transports is None:
        transports = ("dense", "compressed") if ok is not None else ("dense",)
    elif ok is None:
        transports = tuple(t for t in transports if t == "dense")
    if backends is None:
        backends = ("jnp", compacted_backend(
            feats.bs_r, feats.bs_k, feats.bs_c, np.dtype(feats.dtype)))
    assign_map = assignment_space(counts, mesh, assigns=assigns)

    pairs: list[tuple[str, int | None]] = []
    if "l" in axes:
        # stacked (l, r, c) mesh: the depth is physical, twofive only
        pairs = [("twofive", None)]
    else:
        p_r, p_c = int(mesh.shape["r"]), int(mesh.shape["c"])
        if p_r == p_c:
            pairs = [("cannon", None), ("onesided", None), ("gather", None)]
            pairs += [("twofive", d) for d in valid_square_depths(p_r)]
        else:
            pairs = [("onesided", None), ("gather", None)]
            mn, mx = min(p_r, p_c), max(p_r, p_c)
            if validate_l(p_r, p_c, mx // mn) and mx // mn > 1:
                pairs.append(("twofive", mx // mn))
    if engines is not None:
        pairs = [(e, d) for e, d in pairs if e in engines]
    if l is not None:
        pairs = [(e, d) for e, d in pairs
                 if (d == l if e == "twofive" else False) or e != "twofive"]

    out: list[Candidate] = []
    for engine, depth in pairs:
        try:
            plan = plan_mod.plan_multiply(mesh, engine, depth)
            plan.validate_blocks(feats.nb_r, feats.nb_c, feats.nb_k)
        except ValueError:
            continue  # block grid does not divide this topology
        for backend in backends:
            for tp in transports:
                for mode, asg in assign_map.items():
                    if (mode != "identity" and backend == "jnp"
                            and tp != "compressed"):
                        # dense panels + dense cube: every device does
                        # identical work in any layout
                        continue
                    if backend == "jnp":
                        out.append(Candidate(
                            engine, depth, "jnp", None, tp, None, mode
                        ))
                    elif ok is not None:
                        ok_m = ok
                        if asg is not None:
                            from repro.core.distribute import permute_cube

                            ok_m = permute_cube(ok, asg.perm)
                        cap = plan_mod.get_device_capacity(ok_m, mesh,
                                                           engine)
                        if cap > 0:
                            for tile in _backend_tiles(backend, feats):
                                out.append(Candidate(
                                    engine, depth, backend, cap, tp,
                                    tile, mode
                                ))
    return out


def _backend_tiles(
    backend: str, feats: PairFeatures
) -> list[tuple[int, int, int] | None]:
    """Tile axis of the search space: only the pallas kernel is tiled
    (``[None]`` — the backend default — for everything else)."""
    if backend != "pallas":
        return [None]
    from repro.kernels.block_spgemm import tile_candidates
    from repro.kernels.ops import _default_interpret

    return tile_candidates(
        feats.bs_r, feats.bs_k, feats.bs_c, np.dtype(feats.dtype),
        interpret=_default_interpret(),
    )


def _n_devices(mesh) -> int:
    n = 1
    for name in mesh.axis_names:
        n *= int(mesh.shape[name])
    return n


def estimate_candidate(
    cand: Candidate,
    mesh,
    feats: PairFeatures,
    *,
    budget_bytes: float | None = None,
    imbalance: float | None = None,
) -> Estimate:
    """Model one candidate: comm seconds + local-compute seconds + the
    Eq. (6) memory-feasibility verdict.

    ``imbalance`` — max/mean per-device product load under THIS
    candidate's block assignment (``commvolume.load_imbalance`` on the
    exact mesh grid; ``rank_candidates`` computes it per assignment mode
    from the mask-product counts).  Defaults to the feature vector's
    canonical-grid statistic.  It scales the local-compute term for the
    compacted backends — their work is product-proportional, and the
    slowest device gates every tick barrier — while the dense ``jnp``
    einsum contracts the full uniform cube on every device and is immune.
    """
    budget = device_memory_budget() if budget_bytes is None else budget_bytes
    plan = plan_mod.plan_multiply(mesh, cand.engine, cand.l)
    itemsize = float(np.dtype(feats.dtype).itemsize)
    # sparsity-aware volume: compressed transport scales the Eq. (7) A/B
    # term by panel occupancy (analytic flavor — execution derives the
    # exact bucketed capacities from the concrete masks)
    vol = commvolume.plan_volume(
        plan, feats.nb_r, feats.bs_r, itemsize=itemsize,
        transport=cand.transport, occ_a=feats.occ_a, occ_b=feats.occ_b,
        nb_k=feats.nb_k, nb_c=feats.nb_c,
        bs_k=feats.bs_k, bs_c=feats.bs_c,
    )
    peaks = device_peaks()
    comm_s = vol.total / peaks.ici_bw + plan.ticks * TICK_OVERHEAD_S

    ndev = _n_devices(mesh)
    if cand.backend == "jnp":
        fill = 1.0  # dense einsum contracts the full cube
    else:
        fill = feats.product_fill
    # dtype- and tile-aware local cost: MXU throughput scales with the
    # storage width and a tile must fit the double-buffered VMEM budget —
    # a tile that does not is infeasible, same verdict as Eq. (6)
    lc = local_stage_cost(
        feats.nb_r, feats.nb_k, feats.nb_c,
        feats.bs_r, feats.bs_k, feats.bs_c,
        fill=fill, backend=cand.backend,
        dtype=feats.dtype, tile=cand.tile,
        capacity=cand.stack_capacity,
    )
    compute_s = lc.effective / ndev / peaks.flops
    if cand.backend != "jnp":
        # mean-load cost -> slowest-device cost (see the docstring)
        imb = imbalance if imbalance is not None else feats.imbalance
        compute_s *= max(float(imb), 1.0)

    mem = commvolume.device_memory_bytes(
        plan, feats.nb_r, feats.bs_r, itemsize=itemsize,
        stack_capacity=cand.stack_capacity or 0,
        nb_k=feats.nb_k, nb_c=feats.nb_c,
        bs_k=feats.bs_k, bs_c=feats.bs_c,
    )
    feasible = mem <= budget and lc.feasible
    if feasible:
        reason = ""
    elif not lc.feasible:
        reason = (
            f"tile {cand.tile or 'default'} working set exceeds the VMEM "
            f"budget for blocks {feats.bs_r}x{feats.bs_k}x{feats.bs_c} "
            f"({feats.dtype})"
        )
    else:
        reason = (
            f"memory {mem / 1e9:.2f} GB exceeds budget {budget / 1e9:.2f} GB "
            f"(Eq. 6, L={plan.topo.l})"
        )
    return Estimate(
        candidate=cand, comm_s=comm_s, compute_s=compute_s,
        mem_bytes=mem, feasible=feasible, reason=reason,
    )


def assignment_imbalances(counts, mesh, modes=None) -> dict[str, float]:
    """Exact per-mesh max/mean product-load factor of every assignment
    mode (identity included) — the numbers ``rank_candidates`` scales
    compacted compute by, and what the benchmarks report as the
    per-device load spread."""
    from repro.core.commvolume import load_imbalance

    p_r, p_c = int(mesh.shape["r"]), int(mesh.shape["c"])
    out: dict[str, float] = {}
    for mode, asg in assignment_space(counts, mesh, assigns=modes).items():
        perm = None if asg is None else asg.perm
        out[mode] = load_imbalance(counts, p_r, p_c, perm=perm) \
            if counts is not None else 1.0
    return out


def rank_candidates(
    mesh,
    feats: PairFeatures,
    *,
    ok=None,
    counts=None,
    engines: tuple[str, ...] | None = None,
    backends: tuple[str, ...] | None = None,
    l: int | None = None,
    transports: tuple[str, ...] | None = None,
    assigns: tuple[str, ...] | None = None,
    budget_bytes: float | None = None,
    top_k: int | None = None,
) -> ModelReport:
    """Enumerate -> estimate -> prune -> rank.  Raises ``ValueError`` when
    no candidate fits the per-device memory budget (the caller must then
    shrink the problem or raise the budget — silently over-committing
    device memory is the one thing the tuner must never do).

    With ``counts`` (the integer mask product) the estimates price each
    candidate at its OWN assignment's exact per-mesh load imbalance; the
    coarse canonical-grid feature only backstops the counts-free path.
    """
    cands = enumerate_candidates(
        mesh, feats, ok=ok, counts=counts, engines=engines,
        backends=backends, l=l, transports=transports, assigns=assigns,
    )
    if not cands:
        raise ValueError(
            f"no engine candidate fits mesh {mesh_signature(mesh)} and "
            f"block grid {feats.nb_r}x{feats.nb_c}"
        )
    imbs = assignment_imbalances(counts, mesh, modes=assigns) \
        if counts is not None else {}
    ests = [
        estimate_candidate(c, mesh, feats, budget_bytes=budget_bytes,
                           imbalance=imbs.get(c.assign))
        for c in cands
    ]
    feasible = sorted((e for e in ests if e.feasible), key=lambda e: e.total_s)
    pruned = tuple(e for e in ests if not e.feasible)
    if not feasible:
        raise ValueError(
            "every candidate exceeds the per-device memory budget: "
            + "; ".join(f"{e.candidate.label}: {e.reason}" for e in pruned)
        )
    if top_k is not None:
        feasible = feasible[:top_k]
    return ModelReport(ranked=tuple(feasible), pruned=pruned)


def chain_safe(cand: Candidate, *, envelope: bool = False) -> bool:
    """Whether a candidate is sound for a *fused iteration chain*: the
    sweep is traced once and the sparsity pattern evolves underneath it
    (fill-in), so a static stack capacity derived from the initial
    pattern could silently drop products mid-iteration — and a static
    compressed-transport capacity could silently drop *panels*.  Without
    further information only the dense local backend with dense
    transport is chain-safe.  Under ``envelope=True`` the capacities are
    derived from a forecast pattern envelope that over-approximates
    every per-sweep pattern (``core/envelope.py``), so *every* candidate
    is chain-safe — the restriction the envelope layer exists to lift."""
    if envelope:
        return True
    return cand.backend == "jnp" and cand.transport == "dense"


def _sqrt_l_note(l: int) -> str:  # pragma: no cover - debug helper
    return f"sqrt(L)={math.isqrt(l)}"
