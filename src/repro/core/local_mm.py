"""Local (per-device) filtered block multiplication.

This is DBCSR's "batched small-block GEMM with on-the-fly filtering" stage
(handled by LIBXSMM / GPU kernels in the paper).  Three implementations:

* ``jnp`` — a dense einsum.  The (i,k,j) product is included only if
  both blocks are occupied AND ``norm(A_ik)*norm(B_kj) > threshold`` — the
  paper's on-the-fly filter.  Runs everywhere; FLOPs are *not* skipped (the
  einsum contracts the full cube) but the semantics are exact.  Right for
  high fill, where dense MXU work beats gather/scatter overhead.  Above a
  zero threshold the filter couples i, k and j, so the einsum is weighted
  by the float (ni, nk, nj) filter cube; at threshold 0 the filter is the
  outer AND of the two masks and ``separable_mm`` zeroes the absent
  blocks and contracts the plain 4-D product, with no cube.
* ``stacks`` — DBCSR's stack design (DESIGN.md §2): compact the filter cube
  into a padded product list (``kernels/stacks.py``), gather the surviving
  A/B blocks, run ONE batched ``dot_general`` over the list, segment-sum
  into C tiles.  FLOPs and memory traffic scale with the survivors:
  ``2 * capacity * bs_r * bs_k * bs_c`` instead of the
  ``ni * nk * nj``-cube.
* ``pallas`` — the scalar-prefetch TPU kernel
  (``repro.kernels.block_spgemm``): the grid iterates the same compacted
  list, one product per step, f32 VMEM accumulation per output-tile k-run.

``stack_capacity`` bounds the surviving products for the compacted
backends (static; None = full cube, always sound).  Callers with concrete
sparsity get exact bucketed capacities from the plan layer
(``plan.get_product_stacks`` / ``engine.multiply``); traced callers
(shard_map engine bodies) pass a host-derived upper bound.

Blocks may be rectangular: a_blocks (ni, nk, bs_r, bs_k) times b_blocks
(nk, nj, bs_k, bs_c) gives c_blocks (ni, nj, bs_r, bs_c).

All backends return (c_blocks, c_mask); norms of C are recomputed by the
caller (after the cross-device reduction, where applicable).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.block_spgemm import (
    VMEM_BUDGET_BYTES,
    tile_candidates,
    tile_working_set_bytes,
)
from repro.kernels.stacks import (
    ProductStacks,
    bucket_capacity,
    compact_pair_mask,
    resolve_capacity,
)

BACKENDS = ("jnp", "stacks", "pallas")

# Effective-FLOP penalty of the compacted backends' gather/scatter stage
# relative to the dense einsum's streaming MXU access: the dense/compacted
# crossover sits where fill * GATHER_OVERHEAD == 1 (0.25 — DBCSR's batched
# GEMM wins at low occupancy, dense MXU work wins when the cube is mostly
# full; calibrated against benchmarks/bench_local_mm.py's sweep).
GATHER_OVERHEAD = 4.0

# MXU throughput multiplier per storage itemsize (f32 baseline; bf16
# doubles, 8-bit quadruples on hardware that packs the systolic array).
_MXU_DTYPE_SPEEDUP = {4: 1.0, 2: 2.0, 1: 4.0}


def _flops_per_byte() -> float:
    """FLOP-equivalents of one HBM byte on this device (peak FLOP/s over
    HBM bytes/s, from ``roofline.device_peaks``)."""
    from repro.roofline import device_peaks

    peaks = device_peaks()
    return peaks.flops / peaks.hbm_bw


@dataclass(frozen=True)
class LocalCost:
    """Cost breakdown of one local-stage call.

    ``flops`` are *logical* MACs-times-two — the number XLA's
    ``cost_analysis`` reports for the compiled program (asserted in
    ``tests/test_roofline.py``) — independent of storage dtype since the
    MXU accumulates in f32 either way.  ``hbm_bytes`` is operand/output
    traffic at the *storage width* (bf16 halves it), including the
    re-streaming a pallas tile grid adds.  ``effective`` is the
    FLOP-equivalent ranking cost (dtype throughput, gather overhead, VMEM
    pressure) the tuner and ``engine.choose_backend`` compare;
    ``feasible`` is False when the tile working set cannot fit VMEM at
    all (``effective`` is inf there).
    """

    flops: float
    hbm_bytes: float
    effective: float
    feasible: bool = True


def local_stage_cost(
    ni: int,
    nk: int,
    nj: int,
    bs_r: int,
    bs_k: int,
    bs_c: int,
    *,
    fill: float,
    backend: str,
    dtype=jnp.float32,
    tile: tuple[int, int, int] | None = None,
    capacity: int | None = None,
) -> LocalCost:
    """Dtype- and tile-aware analytic cost of one local-stage call.

    ``jnp`` always pays the dense cube (the einsum contracts everything,
    amortizing MXU padding over the full grid dims); the compacted
    backends pay the surviving products (``capacity`` when the caller has
    the exact bucketed count, else ``fill`` times the cube) times the
    gather/scatter overhead.  A pallas ``tile`` adds its re-streaming
    traffic (A tiles fetched once per output-column tile, B once per
    output-row tile) and the VMEM-pressure terms: past half the budget
    the operand pipeline loses double buffering (DMA serializes with the
    MXU — traffic joins the critical path), past the full budget the
    kernel cannot run at all.  Shared by ``engine.choose_backend`` and
    the tuner's candidate model (``repro.tuner.model``) so the
    single-device heuristic and the distributed autotuner agree —
    including for rectangular atomic blocks and reduced storage dtypes.
    """
    itemsize = float(jnp.dtype(dtype).itemsize)
    speed = _MXU_DTYPE_SPEEDUP.get(int(itemsize), 1.0)
    cube = float(ni) * nk * nj
    block = float(bs_r) * bs_k * bs_c
    dense_flops = 2.0 * cube * block
    if backend == "jnp":
        hbm = (ni * nk * bs_r * bs_k + nk * nj * bs_k * bs_c
               + ni * nj * bs_r * bs_c) * itemsize
        return LocalCost(dense_flops, hbm, dense_flops / speed)
    if backend not in ("stacks", "pallas"):
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    cap = float(capacity) if capacity is not None else fill * cube
    flops = 2.0 * cap * block
    compute = GATHER_OVERHEAD * fill * dense_flops / speed
    per_product = (bs_r * bs_k + bs_k * bs_c + bs_r * bs_c) * itemsize
    if backend == "stacks":
        return LocalCost(flops, cap * per_product, compute)
    tm, tk, tn = tile or (bs_r, bs_k, bs_c)
    n_tm, n_tn = -(-bs_r // tm), -(-bs_c // tn)
    hbm = cap * (n_tn * bs_r * bs_k + n_tm * bs_k * bs_c
                 + bs_r * bs_c) * itemsize
    extra = cap * ((n_tn - 1) * bs_r * bs_k
                   + (n_tm - 1) * bs_k * bs_c) * itemsize
    ws = tile_working_set_bytes(bs_r, bs_k, bs_c, (tm, tk, tn), dtype)
    if ws > VMEM_BUDGET_BYTES:
        return LocalCost(flops, hbm, float("inf"), feasible=False)
    if ws > VMEM_BUDGET_BYTES / 2:
        # double buffering lost: the full traffic joins the critical path
        return LocalCost(flops, hbm, compute + hbm * _flops_per_byte())
    return LocalCost(flops, hbm, compute + extra * _flops_per_byte())


def backend_local_cost(
    ni: int,
    nk: int,
    nj: int,
    bs_r: int,
    bs_k: int,
    bs_c: int,
    *,
    fill: float,
    backend: str,
    dtype=jnp.float32,
    tile: tuple[int, int, int] | None = None,
) -> float:
    """Effective-FLOP ranking cost (``local_stage_cost(...).effective``)."""
    return local_stage_cost(
        ni, nk, nj, bs_r, bs_k, bs_c, fill=fill, backend=backend,
        dtype=dtype, tile=tile,
    ).effective


def device_memory_budget() -> float:
    """Per-device byte budget for memory-bound choices: the device's HBM
    (``roofline.device_peaks``) with a 10% reserve.
    ``REPRO_DEVICE_MEMORY_BYTES`` overrides it for tests."""
    from repro.roofline import device_peaks

    raw = os.environ.get("REPRO_DEVICE_MEMORY_BYTES", "").strip()
    return float(raw) if raw else 0.9 * device_peaks().hbm_bytes


def stack_entry_bytes(bs_r: int, bs_k: int, bs_c: int) -> float:
    """Device bytes per product-list entry of the ``stacks`` path: the
    gathered A and B blocks and the product, all f32, plus the int32
    arrays of ``ProductStacks``.  On a TPU the minor two dims of each
    (capacity, rows, cols) f32 array are laid out in (8, 128) tiles, so a
    23 x 23 block takes 24 x 128 words, 5.8x its size (the compiled
    program's ``memory_analysis`` agrees: ``tests/test_chip_compile.py``).
    """
    def words(rows: int, cols: int) -> int:
        if jax.default_backend() == "tpu":
            rows, cols = -(-rows // 8) * 8, -(-cols // 128) * 128
        return rows * cols

    return 4.0 * (words(bs_r, bs_k) + words(bs_k, bs_c) + words(bs_r, bs_c)
                  + len(ProductStacks._fields))


def stacks_memory_bytes(
    ni: int, nk: int, nj: int, bs_r: int, bs_k: int, bs_c: int,
    capacity: int, dtype=jnp.float32,
) -> float:
    """Device footprint of one ``stacks`` local stage: the A, B and C
    blocks at storage width plus ``capacity`` stack entries."""
    itemsize = jnp.dtype(dtype).itemsize
    operands = (ni * nk * bs_r * bs_k + nk * nj * bs_k * bs_c
                + ni * nj * bs_r * bs_c) * itemsize
    return operands + capacity * stack_entry_bytes(bs_r, bs_k, bs_c)


def choose_local_backend(
    ni: int, nk: int, nj: int,
    bs_r: int, bs_k: int, bs_c: int,
    fill: float,
    dtype=jnp.float32,
    capacity: int | None = None,
) -> str:
    """The ``"auto"`` local backend of one multiply: ``"jnp"`` or
    ``compacted_backend``'s choice.

    The dense einsum wins where its full-cube MXU work undercuts the
    compacted path's gathered products (``backend_local_cost``).  A
    ``"stacks"`` pick must also fit the device: where its footprint
    (``stacks_memory_bytes`` at ``capacity`` entries, else the bucketed
    ``fill`` of the cube) exceeds ``device_memory_budget``, the choice is
    ``"jnp"``, which never materialises the product list.  Shared by
    ``engine.choose_backend``, the tuner and envelope chains.
    """
    dense = backend_local_cost(ni, nk, nj, bs_r, bs_k, bs_c,
                               fill=1.0, backend="jnp", dtype=dtype)
    compact = backend_local_cost(ni, nk, nj, bs_r, bs_k, bs_c,
                                 fill=fill, backend="stacks", dtype=dtype)
    if dense <= compact:
        return "jnp"
    backend = compacted_backend(bs_r, bs_k, bs_c, dtype)
    if capacity is None:
        capacity = bucket_capacity(math.ceil(fill * ni * nk * nj))
    if backend == "stacks" and stacks_memory_bytes(
        ni, nk, nj, bs_r, bs_k, bs_c, capacity, dtype
    ) > device_memory_budget():
        return "jnp"
    return backend


def compacted_backend(bs_r: int, bs_k: int, bs_c: int, dtype=jnp.float32) -> str:
    """The compacted local backend for one block shape on this platform.

    The rule: ``"pallas"`` only on a TPU, and only where the block shape
    has a (tm, tk, tn) tile that compiled Mosaic accepts
    (``validate_tile(..., interpret=False)``: tk and tn multiples of 128,
    tm a multiple of the dtype's sublane count).  Everywhere else
    ``"stacks"``, the XLA gather / batched GEMM / segment-sum path, which
    on a TPU runs on the same chip.  DBCSR's atomic blocks of 23, 6 and 32
    have no such tile, so on a TPU they take ``"stacks"``.  The one policy
    point of ``choose_local_backend`` and the tuner's candidate
    enumeration.
    """
    if jax.default_backend() == "tpu" and tile_candidates(
        bs_r, bs_k, bs_c, dtype, interpret=False
    ):
        return "pallas"
    return "stacks"


def pair_filter(
    a_mask: jax.Array,
    a_norms: jax.Array,
    b_mask: jax.Array,
    b_norms: jax.Array,
    threshold: float,
) -> jax.Array:
    """On-the-fly filter mask over (i, k, j) block-product triples."""
    ok = a_mask[:, :, None] & b_mask[None, :, :]
    if threshold > 0.0:
        ok = ok & (a_norms[:, :, None] * b_norms[None, :, :] > threshold)
    return ok


def stacks_mm(
    a_blocks: jax.Array,
    b_blocks: jax.Array,
    stacks: ProductStacks,
    *,
    ni: int,
    nj: int,
    precision=jax.lax.Precision.HIGHEST,
) -> jax.Array:
    """Gather -> batched GEMM -> scatter over a compacted product list.

    The whole local stage is one (capacity, bs_r, bs_k) x (capacity, bs_k,
    bs_c) batched ``dot_general`` (f32 accumulation, as the MXU does) plus
    an unsorted segment-sum over output tiles; padding products are zeroed
    by the ``valid`` weights before the scatter.
    """
    bs_r, bs_c = a_blocks.shape[2], b_blocks.shape[3]
    dtype = a_blocks.dtype
    if stacks.capacity == 0:
        return jnp.zeros((ni, nj, bs_r, bs_c), dtype)
    ag = a_blocks[stacks.ia, stacks.ik].astype(jnp.float32)
    bg = b_blocks[stacks.ik, stacks.ij].astype(jnp.float32)
    prod = jax.lax.dot_general(
        ag, bg, (((2,), (1,)), ((0,), (0,))), precision=precision
    )
    prod = prod * stacks.valid.astype(jnp.float32)[:, None, None]
    seg = jnp.where(stacks.valid == 1, stacks.tile, ni * nj)
    c = jax.ops.segment_sum(prod, seg, num_segments=ni * nj + 1)
    return c[: ni * nj].reshape(ni, nj, bs_r, bs_c).astype(dtype)


def separable_mm(
    a_blocks: jax.Array,
    a_mask: jax.Array,
    b_blocks: jax.Array,
    b_mask: jax.Array,
    *,
    precision=jax.lax.Precision.HIGHEST,
) -> tuple[jax.Array, jax.Array]:
    """(mA ⊙ A)(mB ⊙ B): the cube-weighted einsum of the ``jnp`` backend
    under the threshold-0 filter, whose cube is the outer AND of the
    masks, without building the cube.

    Blocks under a false mask entry are zeroed, so their data is ignored
    as the cube would ignore it; the 4-D contraction keeps the block axes
    apart (a reshape to (N, N) doubles XLA's temporaries).  The C mask is
    ``any_k mA_ik & mB_kj``, from a 0/1 product whose terms are all
    nonnegative, so ``> 0`` is exact.
    """
    zero = jnp.zeros((), jnp.float32)
    a = jnp.where(a_mask[:, :, None, None], a_blocks.astype(jnp.float32), zero)
    b = jnp.where(b_mask[:, :, None, None], b_blocks.astype(jnp.float32), zero)
    c_blocks = jnp.einsum(
        "ikab,kjbc->ijac", a, b, precision=precision
    ).astype(a_blocks.dtype)
    c_mask = jnp.dot(a_mask.astype(jnp.float32),
                     b_mask.astype(jnp.float32)) > 0
    return c_blocks, c_mask


def local_filtered_mm(
    a_blocks: jax.Array,
    a_mask: jax.Array,
    a_norms: jax.Array,
    b_blocks: jax.Array,
    b_mask: jax.Array,
    b_norms: jax.Array,
    *,
    threshold: float = 0.0,
    backend: str = "jnp",
    stack_capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    precision=jax.lax.Precision.HIGHEST,
) -> tuple[jax.Array, jax.Array]:
    """C_ij += sum_k A_ik B_kj with on-the-fly norm filtering.

    Shapes: a_blocks (ni, nk, bs_r, bs_k), b_blocks (nk, nj, bs_k, bs_c)
    Returns: c_blocks (ni, nj, bs_r, bs_c), c_mask (ni, nj) bool.

    Every backend accumulates in f32 regardless of the storage dtype (the
    MXU semantics), so bf16/f8 operands lose precision only at block
    storage, never across the k-contraction.  ``tile`` selects the pallas
    kernel's MXU sub-tile shape (ignored elsewhere).  ``interpret``
    controls the pallas backend only: None auto-detects the platform
    (compiled Mosaic on TPU, interpreter elsewhere — see
    ``repro.config.pallas_interpret``).  ``jnp`` at a threshold of 0 runs
    ``separable_mm`` under the nested ``separable`` scope, so a device
    trace shows which form ran.
    """
    with jax.named_scope("spgemm.local"):
        if backend == "jnp" and not threshold > 0.0:
            with jax.named_scope("separable"):
                return separable_mm(a_blocks, a_mask, b_blocks, b_mask,
                                    precision=precision)
        ni, nk = a_blocks.shape[:2]
        nj = b_blocks.shape[1]
        ok = pair_filter(a_mask, a_norms, b_mask, b_norms, threshold)
        if backend == "pallas":
            from repro.kernels import ops as kops

            c_blocks = kops.block_spgemm(
                a_blocks, b_blocks, ok, capacity=stack_capacity, tile=tile,
                interpret=interpret,
            )
        elif backend == "stacks":
            cap = resolve_capacity(stack_capacity, ni * nk * nj)
            stacks = compact_pair_mask(ok, capacity=cap)
            c_blocks = stacks_mm(
                a_blocks, b_blocks, stacks, ni=ni, nj=nj, precision=precision
            )
        elif backend == "jnp":
            okf = ok.astype(jnp.float32)
            c_blocks = jnp.einsum(
                "ikj,ikab,kjbc->ijac",
                okf,
                a_blocks.astype(jnp.float32),
                b_blocks.astype(jnp.float32),
                precision=precision,
            ).astype(a_blocks.dtype)
        else:
            raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
        c_mask = jnp.any(ok, axis=1)
        return c_blocks, c_mask


# Block-product counts leave the device as int32 (hi, lo) pairs, value
# hi * 2**16 + lo: exact for any count below 2**47, where one int32 would
# overflow at a 1,290-block cube.
_COUNT_BITS = 16
_LO = (1 << _COUNT_BITS) - 1


def count_value(pair) -> int:
    """The Python int of one (hi, lo) count pair fetched to the host."""
    hi, lo = (int(v) for v in pair)
    return (hi << _COUNT_BITS) + lo


def product_counts(calls, *, backend: str = "jnp",
                   stack_capacity: int | None = None) -> jax.Array:
    """Block products of local-stage calls, given by each call's operand
    masks ``(a_mask, b_mask)``, (ni, nk) and (nk, nj), or stacked calls
    with leading axes.  Returns a (2, 2) int32 array of (hi, lo) pairs.
    Row 0: the products whose A and B blocks are both present,
    sum_k colcount_A(k) * rowcount_B(k), the same whichever backend
    runs.  Row 1: the products the backend multiplies, static — the whole
    cube for ``jnp``, the stack capacity for the compacted backends."""
    per_k, computed = [], 0
    for a_mask, b_mask in calls:
        ni, nk = a_mask.shape[-2:]
        nj = b_mask.shape[-1]
        if ni * nj >= 1 << 31:
            raise ValueError(f"a {ni}x{nk}x{nj} block cube is past the "
                             "int32 product counts")
        cube = ni * nk * nj
        n_calls = math.prod(a_mask.shape[:-2])
        computed += n_calls * (cube if backend == "jnp"
                               else resolve_capacity(stack_capacity, cube))
        per_k.append((jnp.sum(a_mask, axis=-2, dtype=jnp.int32)
                      * jnp.sum(b_mask, axis=-1, dtype=jnp.int32)).ravel())
    per_k = jnp.concatenate(per_k)
    if per_k.size >= 1 << (31 - _COUNT_BITS):
        raise ValueError(f"{per_k.size} contracted blocks are past the "
                         "int32 product counts")
    lo = jnp.sum(per_k & _LO)
    hi = jnp.sum(per_k >> _COUNT_BITS) + (lo >> _COUNT_BITS)
    return jnp.stack([
        jnp.stack([hi, lo & _LO]),
        jnp.asarray([computed >> _COUNT_BITS, computed & _LO], jnp.int32),
    ])
