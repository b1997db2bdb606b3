"""Panel transport layer: how A/B panels move between devices.

Every engine used to inline its communication — ``lax.ppermute`` of the
full (blocks, mask, norms) triple in the ring/pull bodies, fused
``all_gather`` in the gather engine — so bytes-on-wire were independent
of occupancy and strictly serialized with the local GEMM.  This module
extracts that stage into one shared abstraction with two jointly-designed
capabilities (DESIGN.md §3):

**Occupancy-compressed panels** (``mode="compressed"``).  Before a panel
is shifted or pulled, only its *occupied* blocks are packed into a
bounded-capacity buffer plus a one-based index array::

    packed : (capacity, bs_r, bs_c)   occupied blocks, padding zeroed
    idx1   : (capacity,) int32        flat position + 1; 0 = padding

and unpacked (scatter into a zero panel, mask rebuilt from the indices)
on arrival, so wire bytes scale with block occupancy instead of dense
panel size — the sparsity-aware communication of Hong et al.
(arXiv:2408.14558) rendered on the static-shape collectives TPUs have.
The one-based encoding makes the format *partial-permutation safe*:
devices a ``ppermute`` does not address receive zeros, and an all-zero
``idx1`` decodes as an empty panel, never as block (0, 0).

Capacity is derived soundly per device from the concrete sparsity
pattern by the plan layer (``plan.get_transport`` — the transport
analogue of PR 2's distributed stack bounds): the bucketed maximum
occupied-block count over every panel the schedule ships.  A capacity
that covers every panel makes compressed transport *bit-exact* vs dense:
the same blocks arrive, the mask is reconstructed exactly, and norms are
recomputed from the identical block data (see below).

**Norm-free wire format** (both modes).  Per-block norms are only
consumed by the on-the-fly threshold filter, and they are a pure
function of the blocks (``bsm.block_norms``, f32), so shipping them with
every hop was redundant traffic.  Neither mode moves norms any more:
``panel_norms`` recomputes them from the received blocks at compute time
(bit-identical — same op, same data), or skips the work entirely when
``threshold == 0``.

**Double-buffered pipelining.**  The engines' tick loops are
restructured (in ``cannon.py``/``twofive.py``, using these helpers) so
the permute feeding tick t+1 is *issued before* the GEMM of tick t: the
GEMM never depends on a collective issued in its own step, which lets
XLA overlap communication with compute the way the paper's non-blocking
``mpi_rget`` does (§4).  The cost is one extra in-flight panel set — the
paper's double buffering, already counted by the Eq. (6) buffer model.

``mode="dense"`` keeps the original bit-exact full-panel permutes (minus
the norms) and is chosen automatically when fill is high; the mode and
capacities join the compiled-program cache key in ``plan.get_compiled``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MODES = ("dense", "compressed")

# wire element formats: "native" ships blocks at their storage dtype
# (bf16-stored matrices therefore already halve wire bytes — losslessly);
# a reduced wire on wider storage ("bfloat16", optionally "float8_e4m3fn"
# where the platform has it) is a LOSSY opt-in: blocks are rounded at the
# sender and widened back at the receiver, so it never rides the auto
# path — callers choose it explicitly (and the tuner never enumerates it,
# keeping its correctness guards exact).
WIRES = ("native", "bfloat16", "float8_e4m3fn")

# bucketed-capacity fill above which auto transport keeps dense panels:
# the packed hop ships capacity * (block + 4B index) — once the bucketed
# capacity approaches the panel's block count the index overhead and the
# pack/unpack scatter stop paying for the byte saving (and iteration
# loops whose fill-in climbs through the crossover would churn program
# keys; see plan.get_transport).
AUTO_COMPRESS_MAX_FILL = 0.25

# smallest compressed buffer: collectives over zero-length arrays are
# not worth lowering, and tiny buckets churn program keys (kernels/
# stacks.bucket_capacity uses the same floor for product lists)
MIN_CAPACITY = 8


@dataclass(frozen=True)
class PanelTransport:
    """Resolved transport of one multiply: mode + per-panel capacities.

    ``cap_a`` / ``cap_b`` are the packed-buffer capacities (occupied
    blocks) of one shipped A / B panel — 0 in dense mode.  They are part
    of the compiled-program cache key: a pattern whose bucketed bounds
    change compiles a new program, exactly like the stack-capacity
    buckets of the compacted local backends.

    ``wire`` selects the wire element format (see ``WIRES``): "native"
    (the default) ships at storage width; a narrower wire casts blocks
    down before the hop and back up on arrival (lossy on wider storage,
    a no-op on matching storage).
    """

    mode: str = "dense"
    cap_a: int = 0
    cap_b: int = 0
    wire: str = "native"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown transport mode {self.mode!r}; "
                             f"one of {MODES}")
        if self.mode == "compressed" and min(self.cap_a, self.cap_b) <= 0:
            raise ValueError(
                "compressed transport needs positive panel capacities "
                f"(got cap_a={self.cap_a}, cap_b={self.cap_b})"
            )
        if self.wire not in WIRES:
            raise ValueError(f"unknown wire format {self.wire!r}; "
                             f"one of {WIRES}")

    @property
    def compressed(self) -> bool:
        return self.mode == "compressed"

    @property
    def wire_dtype(self):
        """jnp dtype blocks are cast to on the wire; None = storage."""
        return None if self.wire == "native" else jnp.dtype(self.wire)

    def wire_itemsize(self, storage_itemsize: float) -> float:
        """Bytes per block element on the wire (what the volume model
        charges): the storage width under a native wire, the reduced
        width otherwise."""
        wd = self.wire_dtype
        return storage_itemsize if wd is None else float(wd.itemsize)

    @property
    def key(self) -> tuple:
        """Program-cache key contribution.  The wire element is appended
        ONLY when non-native, so pre-wire cache keys (and every test /
        record that pins them) keep their 3-element shape."""
        base = (self.mode, self.cap_a, self.cap_b)
        return base if self.wire == "native" else base + (self.wire,)


DENSE = PanelTransport()


# ---------------------------------------------------------------------------
# packing format
# ---------------------------------------------------------------------------


def pack_panel(blocks: jax.Array, mask: jax.Array, capacity: int):
    """Pack a (nr, nc, bs_r, bs_c) panel into its wire form.

    Returns ``(packed, idx1)`` — occupied blocks gathered into a
    ``(capacity, bs_r, bs_c)`` buffer (padding zeroed) and the one-based
    flat positions (0 = padding).  ``capacity`` must bound the occupied
    count or the excess is silently dropped; the plan layer's
    ``get_transport`` derives sound bounds, and the property tests
    (tests/test_transport.py) pin the roundtrip exactness.
    """
    nr, nc = mask.shape
    flat = jnp.flatnonzero(
        mask.ravel(), size=capacity, fill_value=-1
    ).astype(jnp.int32)
    valid = flat >= 0
    safe = jnp.where(valid, flat, 0)
    packed = blocks.reshape((nr * nc,) + blocks.shape[2:])[safe]
    packed = jnp.where(
        valid[:, None, None], packed, jnp.zeros((), blocks.dtype)
    )
    return packed, (flat + 1) * valid.astype(jnp.int32)


def unpack_panel(packed: jax.Array, idx1: jax.Array, nr: int, nc: int):
    """Inverse of :func:`pack_panel`: scatter the wire form back into a
    dense ``(nr, nc, bs_r, bs_c)`` panel + its boolean mask.

    Safe on partial-permute output: an unaddressed receiver holds zeros,
    which decode as an empty panel (``idx1 == 0`` is padding).
    """
    valid = idx1 > 0
    safe = jnp.where(valid, idx1 - 1, 0)
    guarded = packed * valid[:, None, None].astype(packed.dtype)
    flatb = jnp.zeros((nr * nc,) + packed.shape[1:], packed.dtype)
    flatb = flatb.at[safe].add(guarded)
    mask = jnp.zeros((nr * nc,), bool).at[safe].max(valid)
    return flatb.reshape((nr, nc) + packed.shape[1:]), mask.reshape(nr, nc)


def panel_norms(blocks: jax.Array, threshold: float) -> jax.Array:
    """Per-block norms of a received panel, for the on-the-fly filter.

    Norms are no longer transported: with ``threshold > 0`` they are
    recomputed from the (exactly transported) blocks — bit-identical to
    home norms that came from ``block_norms`` (same op, same data) —
    and with ``threshold == 0`` the filter never reads them, so a zero
    placeholder skips the reduction entirely.

    Caveat: PR 3's derived-norm algebra (``scale`` stores
    ``norms * |s|``) can differ from ``block_norms(blocks * s)`` in the
    final f32 ULPs, so a block product whose norm product lies *exactly*
    on the threshold boundary could filter differently than the
    stored-norm oracle — the measure-zero ambiguity every
    threshold-filter implementation has across backends (DBCSR's GPU vs
    LIBXSMM paths included); away from the boundary the decisions agree
    exactly.
    """
    if threshold > 0.0:
        from repro.core.bsm import block_norms

        return block_norms(blocks)
    return jnp.zeros(blocks.shape[:2], jnp.float32)


# ---------------------------------------------------------------------------
# panel streams (what the engine bodies carry through their tick loops)
# ---------------------------------------------------------------------------


def _to_wire(tr: PanelTransport, blocks: jax.Array) -> jax.Array:
    """Cast blocks to the wire element format (no-op for native)."""
    wd = tr.wire_dtype
    return blocks if wd is None or blocks.dtype == wd else blocks.astype(wd)


def ingest(tr: PanelTransport, capacity: int, blocks, mask):
    """Panel state entering an engine body: packed pair or (blocks, mask),
    blocks cast down to the wire dtype when one is selected."""
    if tr.compressed:
        packed, idx1 = pack_panel(blocks, mask, capacity)
        return (_to_wire(tr, packed), idx1)
    return (_to_wire(tr, blocks), mask)


def permute(state, axes, pairs):
    """One transport hop: permute both wire arrays (mode-independent —
    dense state is (blocks, mask), compressed is (packed, idx1))."""
    with jax.named_scope("spgemm.transport"):
        return tuple(lax.ppermute(x, axes, list(pairs)) for x in state)


def dense_view(tr: PanelTransport, state, nr: int, nc: int, dtype=None):
    """(blocks, mask) view of a panel state for the local GEMM.

    ``dtype`` — the compute/storage dtype to widen wire-cast blocks back
    to (engine bodies pass their operand dtype); None leaves blocks at
    whatever width they arrived."""
    if tr.compressed:
        blocks, mask = unpack_panel(state[0], state[1], nr, nc)
    else:
        blocks, mask = state
    if dtype is not None and blocks.dtype != jnp.dtype(dtype):
        blocks = blocks.astype(dtype)
    return blocks, mask


def all_gather_panels(
    tr: PanelTransport, capacity: int, blocks, mask, axis_name: str,
    axis: int,
):
    """The gather engine's fused pull-from-home, transport-aware.

    Dense: tiled all-gather of blocks + mask (the original schedule,
    minus the norms).  Compressed: all-gather of each home shard's packed
    buffer + indices, then one scatter rebuilding the concatenated
    row/column panel — still a single fused collective pair, but the
    gathered bytes scale with occupancy.
    """
    with jax.named_scope("spgemm.transport"):
        dtype = blocks.dtype  # widen wire-cast blocks back after the gather
        if not tr.compressed:
            gb = lax.all_gather(
                _to_wire(tr, blocks), axis_name, axis=axis, tiled=True
            )
            gm = lax.all_gather(mask, axis_name, axis=axis, tiled=True)
            return gb.astype(dtype), gm
        nr, nc = mask.shape
        packed, idx1 = pack_panel(blocks, mask, capacity)
        packed = _to_wire(tr, packed)
        ps = lax.all_gather(packed, axis_name, axis=0, tiled=False)
        ix = lax.all_gather(idx1, axis_name, axis=0, tiled=False)
        p = ps.shape[0]
        valid = ix > 0
        loc = jnp.where(valid, ix - 1, 0)
        r, c = loc // nc, loc % nc
        src = jnp.arange(p, dtype=jnp.int32)[:, None]
        if axis == 1:  # A row panel: source s owns columns [s*nc, (s+1)*nc)
            gf = r * (p * nc) + src * nc + c
            out_r, out_c = nr, p * nc
        elif axis == 0:  # B column panel: source s owns rows [s*nr, (s+1)*nr)
            gf = (src * nr + r) * nc + c
            out_r, out_c = p * nr, nc
        else:
            raise ValueError(f"gather axis must be 0 or 1, got {axis}")
        guarded = ps * valid[..., None, None].astype(ps.dtype)
        flatb = jnp.zeros((out_r * out_c,) + ps.shape[2:], ps.dtype)
        flatb = flatb.at[gf.ravel()].add(
            guarded.reshape((-1,) + ps.shape[2:])
        )
        gm = jnp.zeros((out_r * out_c,), bool).at[gf.ravel()].max(valid.ravel())
        out = flatb.reshape((out_r, out_c) + ps.shape[2:]).astype(dtype)
        return out, gm.reshape(out_r, out_c)


# ---------------------------------------------------------------------------
# capacity bounds (host-side, numpy — the transport analogue of
# plan.device_stack_bound)
# ---------------------------------------------------------------------------


def panel_nnz_bound(mask, row_parts: int, col_parts: int) -> int:
    """Max occupied-block count over a (row_parts x col_parts) partition
    of ``mask`` — the sound capacity for a schedule that ships those
    partitions as panels.  Pure numpy; hypothesis-tested for soundness
    against every partition cell (tests/test_transport.py)."""
    m = np.asarray(mask, bool)
    nb_r, nb_c = m.shape
    if nb_r % row_parts or nb_c % col_parts:
        raise ValueError(
            f"mask {m.shape} does not divide a {row_parts}x{col_parts} "
            "panel partition"
        )
    hr, hc = nb_r // row_parts, nb_c // col_parts
    counts = m.reshape(row_parts, hr, col_parts, hc).sum(axis=(1, 3))
    return int(counts.max()) if counts.size else 0


def plan_panel_parts(plan) -> tuple[tuple[int, int], tuple[int, int]]:
    """(row_parts, col_parts) of the A and B panels a plan ships.

    Ring / stacked / gather schedules move whole 2D home shards; the pull
    formulation moves virtual-grid subpanels — ``ca`` column slices of an
    A shard, ``cb`` row slices of a B shard (DESIGN.md §3).
    """
    if plan.kind == "pull":
        return ((plan.p_r, plan.p_c * plan.ca),
                (plan.p_r * plan.cb, plan.p_c))
    return ((plan.p_r, plan.p_c), (plan.p_r, plan.p_c))


def bucket(n: int) -> int:
    """Power-of-two capacity bucket with the transport floor."""
    from repro.kernels.stacks import bucket_capacity

    return max(MIN_CAPACITY, bucket_capacity(n))


def capacities_for(mask_a, mask_b, plan) -> tuple[int, int, int, int]:
    """Bucketed per-panel packing capacities + panel block counts of one
    (operand-mask pair, plan): ``(cap_a, cap_b, blocks_a, blocks_b)``.

    The single derivation point behind ``plan.get_transport`` — monotone
    in the masks, so capacities derived from a pattern *envelope* (the
    union of every mask a chain can ship, ``core/envelope.py``) soundly
    cover every concrete per-sweep panel."""
    am = np.asarray(mask_a, bool)
    bm = np.asarray(mask_b, bool)
    (ar, ac), (br, bc) = plan_panel_parts(plan)
    cap_a = bucket(panel_nnz_bound(am, ar, ac))
    cap_b = bucket(panel_nnz_bound(bm, br, bc))
    blocks_a = (am.shape[0] // ar) * (am.shape[1] // ac)
    blocks_b = (bm.shape[0] // br) * (bm.shape[1] // bc)
    return cap_a, cap_b, blocks_a, blocks_b


def resolve_mode(
    mode: str, cap_a: int, cap_b: int, blocks_a: int, blocks_b: int
) -> str:
    """``auto`` policy: compress only while the bucketed capacities stay
    well under the panel block counts (crossover ``AUTO_COMPRESS_MAX_FILL``
    — past it the index overhead and scatter cost eat the byte saving,
    and evolving patterns would flap across the boundary)."""
    if mode != "auto":
        return mode
    fill_a = cap_a / max(blocks_a, 1)
    fill_b = cap_b / max(blocks_b, 1)
    if max(fill_a, fill_b) <= AUTO_COMPRESS_MAX_FILL:
        return "compressed"
    return "dense"
