"""Jit'd public wrappers for the Pallas kernels.

``interpret`` resolution (``repro.config.pallas_interpret``): an explicit
argument wins, then the ``REPRO_PALLAS_INTERPRET`` env override, then
platform auto-detection — False (compiled Mosaic kernels) on real TPU,
True (validation mode — the kernel body executes in Python) elsewhere.
On a TPU the interpreter runs only where a caller passes
``interpret=True`` itself: an environment that resolves to it there
raises, so a timed run can never be an interpreted one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.config import pallas_interpret
from repro.kernels import ref
from repro.kernels.block_spgemm import block_spgemm as _block_spgemm
from repro.kernels.flash_attention import flash_attention_single
from repro.kernels.stacks import ProductStacks  # noqa: F401  (re-export)


def _default_interpret() -> bool:
    on_tpu = jax.default_backend() == "tpu"
    cfg = pallas_interpret()
    if cfg is None:
        return not on_tpu
    if cfg and on_tpu:
        raise ValueError(
            "REPRO_PALLAS_INTERPRET resolves the Pallas kernels to the "
            "interpreter on a TPU; pass interpret=True explicitly to run "
            "them interpreted there"
        )
    return cfg


def block_spgemm(
    a_blocks,
    b_blocks,
    pair_ok,
    *,
    capacity: int | None = None,
    tile: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
):
    """Filtered block-sparse matmul (see kernels/block_spgemm.py).

    ``capacity`` — static bound on surviving products (None = full cube);
    the scalar-prefetch grid iterates only that many steps.  ``tile`` —
    the MXU sub-tile shape (None resolves ``default_tile``).
    """
    if interpret is None:
        interpret = _default_interpret()
    return _block_spgemm(
        a_blocks, b_blocks, pair_ok, capacity=capacity, tile=tile,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "bq", "bkv", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (b, h, sq, d)
    k: jax.Array,  # (b, hkv, skv, d)
    v: jax.Array,  # (b, hkv, skv, d)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Batched multi-head flash attention with GQA (hkv | h)."""
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0, (h, hkv)
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    fn = functools.partial(
        flash_attention_single,
        causal=causal,
        window=window,
        softcap=softcap,
        scale=scale,
        bq=bq,
        bkv=bkv,
        interpret=interpret,
    )
    return jax.vmap(jax.vmap(fn))(q, k, v)


__all__ = ["block_spgemm", "flash_attention", "ref"]
