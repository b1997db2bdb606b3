"""Pallas TPU kernel: filtered block-sparse matmul (DBCSR's batched
small-block GEMM stage, adapted to the MXU).

The paper offloads batches of small-block multiplications to LIBXSMM/GPU
with an on-the-fly norm filter.  TPU adaptation (DESIGN.md §2): the kernel
iterates the *compacted product list* (``kernels/stacks.py`` — DBCSR's
stacks), not the (ni, nj, nk) cube.  The list's int32 index arrays are
scalar-prefetched (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec
index maps steer each grid step's HBM->VMEM DMA straight to the blocks of
the n-th surviving product: filtered triples cost neither grid steps nor
memory traffic.

**Tile grid.**  Each (bs_r, bs_k, bs_c) block product is decomposed into a
(tm, tk, tn) tile grid — grid = (bs_r/tm, bs_c/tn, capacity, bs_k/tk) with
the output-tile coordinates outermost and the contraction tiles innermost,
so one (tm, tn) f32 VMEM accumulator still fuses a whole k-run: ``first``
resets it at the run's first product and tk == 0, ``write`` casts it back
at the run's last product and the final tk.  Pallas double-buffers the
operand tile DMAs across grid steps (the revision pipeline), so a block
larger than one VMEM-resident tile streams tile-by-tile instead of
overflowing VMEM; blocks at or under the tile size keep the one-step-per-
product shape of the whole-block kernel (the degenerate 1x1x·x1 grid).
The cost of tiling is operand re-streaming — A tiles are fetched once per
output column tile and B tiles once per output row tile — which
``local_mm.local_stage_cost`` prices when the tuner searches tile shapes.

Mixed precision: operand tiles may be stored in bf16 (or f8 where the
platform supports it); the MXU accumulates in f32 regardless
(``preferred_element_type``), and the output tile is cast back to the
storage dtype only at write-back.

Atomic blocks may be rectangular (bs_r x bs_k times bs_k x bs_c).  On real
hardware every tile must be lane-aligned — ``validate_tile`` raises a
clear error up front instead of a Mosaic compile failure; interpret mode
(tests, CPU CI) sweeps small unaligned sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.stacks import (
    ProductStacks,
    compact_pair_mask,
    resolve_capacity,
)

LANE = 128  # minor-dim tiling of every TPU vreg / the MXU edge
# minimum sublane count (second-to-minor dim) per storage itemsize:
# f32 -> (8, 128), bf16 -> (16, 128), int8/f8 -> (32, 128)
_SUBLANES = {4: 8, 2: 16, 1: 32}

# Default ceiling on a single tile dimension: keeps the double-buffered
# working set a small fraction of VMEM (see tile_working_set_bytes) while
# staying MXU-shaped.  Blocks at or under this stay whole-block.
MAX_TILE = 256

# Per-core VMEM the operand/accumulator pipeline must fit in (TPU v4/v5
# class hardware).  Above half of it, Pallas can no longer double-buffer.
VMEM_BUDGET_BYTES = 16 * 2**20

# Products per kernel launch.  Each one scalar-prefetches 16 bytes of
# indices and control words into SMEM (1 MiB per core); 2^15 of them take
# half of it.  Longer product lists run as several launches.
MAX_PREFETCH_PRODUCTS = 2**15


def min_sublane(dtype) -> int:
    """Minimum sublane multiple of a VMEM tile for this storage dtype."""
    return _SUBLANES.get(jnp.dtype(dtype).itemsize, 8)


def _divisor_tile(n: int, cap: int, align: int) -> int:
    """Largest divisor of ``n`` that is <= cap, preferring multiples of
    ``align`` (so the chosen tile wastes no lanes/sublanes)."""
    if n <= cap:
        return n
    best, best_aligned = 1, 0
    for d in range(1, n + 1):
        if d > cap:
            break
        if n % d:
            continue
        best = d
        if d % align == 0:
            best_aligned = d
    return best_aligned or best


def default_tile(
    bs_r: int, bs_k: int, bs_c: int, dtype=jnp.float32
) -> tuple[int, int, int]:
    """The shipped tile choice for a block shape: whole-block up to
    ``MAX_TILE`` per dim, else the largest lane-preferring divisor.  The
    tuner may override this per (block shape, dtype, platform)."""
    sl = min_sublane(dtype)
    return (
        _divisor_tile(bs_r, MAX_TILE, sl),
        _divisor_tile(bs_k, MAX_TILE, LANE),
        _divisor_tile(bs_c, MAX_TILE, LANE),
    )


def validate_tile(
    bs_r: int,
    bs_k: int,
    bs_c: int,
    tile: tuple[int, int, int],
    dtype=jnp.float32,
    *,
    interpret: bool = False,
) -> tuple[int, int, int]:
    """Validate a (tm, tk, tn) tile against a block shape *up front*.

    Raises ``ValueError`` with an actionable message instead of letting an
    unaligned or non-dividing tile surface as a Mosaic compile failure.
    Interpret mode only requires divisibility (the interpreter has no lane
    layout); compiled mode additionally requires lane/sublane alignment:
    tk and tn are minor (lane) dims of the A/B/C tiles and must be
    multiples of 128; tm is a sublane dim and must be a multiple of the
    dtype's minimum sublane count (8 f32 / 16 bf16 / 32 f8).
    """
    try:
        tm, tk, tn = (int(t) for t in tile)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"tile must be a (tm, tk, tn) integer triple, got {tile!r}"
        ) from e
    if min(tm, tk, tn) <= 0:
        raise ValueError(f"tile dims must be positive, got {(tm, tk, tn)}")
    for name, bs, t in (("bs_r", bs_r, tm), ("bs_k", bs_k, tk),
                        ("bs_c", bs_c, tn)):
        if bs % t:
            raise ValueError(
                f"tile dim {t} does not divide block dim {name}={bs}: the "
                f"tile grid must cover the block exactly — pick a divisor "
                f"of {bs} or pad the atomic block"
            )
    if not interpret:
        sl = min_sublane(dtype)
        if tk % LANE or tn % LANE:
            raise ValueError(
                f"tile (tm={tm}, tk={tk}, tn={tn}) cannot be lane-aligned "
                f"on this platform: tk and tn are minor (lane) dims and "
                f"must be multiples of {LANE} for compiled Mosaic — use "
                f"interpret mode for small blocks, or pad the block"
            )
        if tm % sl:
            raise ValueError(
                f"tile dim tm={tm} is not sublane-aligned for "
                f"{jnp.dtype(dtype).name} (requires a multiple of {sl})"
            )
    return tm, tk, tn


def tile_working_set_bytes(
    bs_r: int,
    bs_k: int,
    bs_c: int,
    tile: tuple[int, int, int] | None,
    dtype=jnp.float32,
) -> float:
    """VMEM bytes the pipeline holds resident for one grid step: the
    double-buffered A/B operand tiles and C output tile at storage width,
    plus the single f32 accumulator."""
    tm, tk, tn = tile or (bs_r, bs_k, bs_c)
    itemsize = jnp.dtype(dtype).itemsize
    db = 2.0  # Pallas revision double-buffering
    return (
        db * (tm * tk + tk * tn + tm * tn) * itemsize  # A, B, C tiles
        + tm * tn * 4.0  # f32 accumulator scratch
    )


def tile_candidates(
    bs_r: int,
    bs_k: int,
    bs_c: int,
    dtype=jnp.float32,
    *,
    interpret: bool = False,
) -> list[tuple[int, int, int] | None]:
    """Distinct tile shapes worth measuring for one block shape.

    ``None`` (the default_tile resolution) leads; explicit candidates
    cover the whole block, the MXU edge, and the default ceiling —
    deduplicated and, like ``None``, kept only where ``validate_tile``
    accepts them.  In compiled mode a block shape with no lane-aligned
    tile (DBCSR's atomic 23, 6 or 32) therefore gets an empty list.  In
    interpret mode half-block tiles join so CPU tests/benchmarks exercise
    a real tile grid at small sizes.
    """
    raw: list[tuple[int, int, int]] = [(bs_r, bs_k, bs_c)]
    sl = min_sublane(dtype)
    for cap in (LANE, MAX_TILE):
        raw.append((
            _divisor_tile(bs_r, cap, sl),
            _divisor_tile(bs_k, cap, LANE),
            _divisor_tile(bs_c, cap, LANE),
        ))
    if interpret:
        if bs_r % 2 == 0 and bs_k % 2 == 0 and bs_c % 2 == 0:
            raw.append((bs_r // 2, bs_k // 2, bs_c // 2))
    default = default_tile(bs_r, bs_k, bs_c, dtype)
    out: list[tuple[int, int, int] | None] = []
    for t in dict.fromkeys([default, *raw]):  # default first, deduplicated
        try:
            validate_tile(bs_r, bs_k, bs_c, t, dtype, interpret=interpret)
        except ValueError:
            continue
        out.append(None if t == default else t)
    return out


# Flag bits of the packed per-product control word (``_chunk_flags``).
_RESET, _WRITE, _VALID, _LOAD = 1, 2, 4, 8


def _chunk_flags(stacks: ProductStacks, start: int, stop: int) -> jax.Array:
    """Control words of products ``[start, stop)`` as one grid launch sees them.

    RESET — zero the accumulator (first product of an output tile's k-run);
    WRITE — write the accumulator back (last product of the run, or the
    launch's last step, which leaves a partial sum in C); VALID — a real
    product; LOAD — seed the accumulator from C (the launch opens in the
    middle of a k-run that an earlier launch started).
    """
    first = stacks.first[start:stop]
    write = stacks.write[start:stop].at[-1].set(1)
    load = jnp.zeros_like(first).at[0].set(1 - first[0])
    return (first * _RESET + write * _WRITE + stacks.valid[start:stop] * _VALID
            + load * _LOAD)


def _mxu_dot(a, b):
    """One tile product with f32 accumulation. f32 operands multiply at
    full f32 precision (Mosaic's default may use fewer bf16 passes);
    narrower storage multiplies natively in bf16, whose products are exact
    in f32."""
    if a.dtype == jnp.float32:
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _tiled_kernel(ia_ref, ik_ref, ij_ref, flag_ref, a_ref, b_ref, *refs):
    # refs = ([c_in_ref,] c_ref, acc_ref): c_in (aliased to c) is present
    # only when the product list spans several launches
    c_ref, acc_ref = refs[-2:]
    n = pl.program_id(2)
    tk = pl.program_id(3)
    ntk = pl.num_programs(3)
    flags = flag_ref[n]

    @pl.when(((flags & _RESET) != 0) & (tk == 0))
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if len(refs) == 3:
        c_in_ref = refs[0]

        @pl.when(((flags & _LOAD) != 0) & (tk == 0))
        def _load():
            acc_ref[...] = c_in_ref[0, 0].astype(jnp.float32)

    @pl.when((flags & _VALID) != 0)
    def _mac():
        acc_ref[...] += _mxu_dot(a_ref[0, 0], b_ref[0, 0])

    @pl.when(((flags & _WRITE) != 0) & (tk == ntk - 1))
    def _write():
        c_ref[0, 0] = acc_ref[...].astype(c_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("ni", "nj", "tile", "interpret", "chunk")
)
def block_spgemm_stacks(
    a_blocks: jax.Array,  # (ni, nk, bs_r, bs_k)
    b_blocks: jax.Array,  # (nk, nj, bs_k, bs_c)
    stacks: ProductStacks,
    *,
    ni: int,
    nj: int,
    tile: tuple[int, int, int] | None = None,
    interpret: bool = False,
    chunk: int | None = None,
) -> jax.Array:
    """C tiles of the compacted product list over the (tm, tk, tn) grid.

    Only output tiles with at least one surviving product are written —
    callers zero the rest via the tile mask (``jnp.any(pair_ok, axis=1)``),
    exactly the ``c_mask`` they already compute.  ``tile=None`` resolves
    ``default_tile`` (whole-block for blocks up to ``MAX_TILE`` per dim).

    The product list's index and control words are scalar-prefetched into
    SMEM, 16 bytes per product.  A list longer than ``chunk`` (default
    ``MAX_PREFETCH_PRODUCTS``) runs as several launches of at most
    ``chunk`` products over one f32 C buffer that each launch aliases: a
    k-run cut by a launch boundary is written back as a partial sum and
    re-loaded by the next launch, so accumulation is unchanged.
    """
    from jax.experimental.pallas import tpu as pltpu

    _, _, bs_r, bs_k = a_blocks.shape
    nk, nj2, bs_k2, bs_c = b_blocks.shape
    assert bs_k == bs_k2, (a_blocks.shape, b_blocks.shape)
    assert nj2 == nj, (nj2, nj)
    dtype = a_blocks.dtype
    cap = stacks.capacity
    if cap == 0:
        return jnp.zeros((ni, nj, bs_r, bs_c), dtype)
    if tile is None:
        tile = default_tile(bs_r, bs_k, bs_c, dtype)
    tm, tk, tn = validate_tile(
        bs_r, bs_k, bs_c, tile, dtype, interpret=interpret
    )
    n_tm, n_tk, n_tn = bs_r // tm, bs_k // tk, bs_c // tn
    chunk = min(cap, chunk or MAX_PREFETCH_PRODUCTS)
    multi = cap > chunk
    # several launches accumulate partial sums in f32 between them
    c_dtype = jnp.float32 if multi else dtype

    # Output sub-tile coordinates outermost, contraction tiles innermost:
    # for one (ti, tj) the whole product list streams past the single
    # (tm, tn) accumulator, so k-run fusion is preserved per sub-tile.
    # Index maps receive (grid idx..., *scalar prefetch refs).
    def c_map(ti, tj, n, tkk, ia, ik, ij, *_):
        return ia[n], ij[n], ti, tj

    c_spec = pl.BlockSpec((1, 1, tm, tn), c_map)
    in_specs = [
        pl.BlockSpec(
            (1, 1, tm, tk),
            lambda ti, tj, n, tkk, ia, ik, ij, *_: (ia[n], ik[n], ti, tkk),
        ),
        pl.BlockSpec(
            (1, 1, tk, tn),
            lambda ti, tj, n, tkk, ia, ik, ij, *_: (ik[n], ij[n], tkk, tj),
        ),
    ]
    out_shape = jax.ShapeDtypeStruct((ni, nj, bs_r, bs_c), c_dtype)

    def launch(length):
        return pl.pallas_call(
            _tiled_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n_tm, n_tn, length, n_tk),
                in_specs=in_specs + [c_spec] if multi else in_specs,
                out_specs=c_spec,
                scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
            ),
            out_shape=out_shape,
            # operand 6 (after 4 prefetch words, A and B) is C itself
            input_output_aliases={6: 0} if multi else {},
            interpret=interpret,
        )

    c = jnp.zeros(out_shape.shape, c_dtype)
    for start in range(0, cap, chunk):
        stop = min(start + chunk, cap)
        c = launch(stop - start)(
            stacks.ia[start:stop], stacks.ik[start:stop],
            stacks.ij[start:stop], _chunk_flags(stacks, start, stop),
            a_blocks, b_blocks, *((c,) if multi else ()),
        )
    return c.astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("capacity", "tile", "interpret", "chunk")
)
def block_spgemm(
    a_blocks: jax.Array,  # (ni, nk, bs_r, bs_k)
    b_blocks: jax.Array,  # (nk, nj, bs_k, bs_c)
    pair_ok: jax.Array,  # (ni, nk, nj) bool/int
    *,
    capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool = False,
    chunk: int | None = None,
) -> jax.Array:
    """C_ij = sum_k ok[i,k,j] * A_ik @ B_kj via the compacted product list.

    ``capacity`` bounds the surviving products (static).  None means the
    full cube — always sound, no compaction win; callers with a concrete
    pattern pass the exact bucketed count (``plan.get_product_stacks``) so
    grid steps and DMA traffic shrink to the survivors.  ``tile`` picks
    the MXU sub-tile shape (None = ``default_tile``), ``chunk`` the
    products per launch (None = ``MAX_PREFETCH_PRODUCTS``).
    """
    ni, nk, bs_r, bs_k = a_blocks.shape
    nk2, nj, bs_k2, bs_c = b_blocks.shape
    assert nk == nk2 and bs_k == bs_k2, (a_blocks.shape, b_blocks.shape)
    assert pair_ok.shape == (ni, nk, nj)
    cap = resolve_capacity(capacity, ni * nk * nj)
    stacks = compact_pair_mask(pair_ok, capacity=cap)
    c = block_spgemm_stacks(
        a_blocks, b_blocks, stacks, ni=ni, nj=nj, tile=tile,
        interpret=interpret, chunk=chunk,
    )
    # tiles with no surviving product are never visited by the grid
    c_mask = jnp.any(pair_ok.astype(bool), axis=1)
    return jnp.where(c_mask[:, :, None, None], c, jnp.zeros((), c.dtype))
