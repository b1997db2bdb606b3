"""Gradient compression with exact error feedback.

Distributed-optimization trick for the 1000+-node regime (DESIGN.md §9):
the DP gradient all-reduce is the largest recurring collective; casting the
payload to bf16 halves it.  Plain casting biases the update; *error
feedback* (Seide et al. 2014; Karimireddy et al. 2019) keeps an fp32
residual accumulator per parameter so the quantization error of step t is
re-injected at step t+1 — the sum of applied updates telescopes to the true
gradient sum (memoryless in expectation; tested in tests/test_optim.py).

Two entry points:
  * ``compress_grads``             — jit/GSPMD path: quantize + residual
    update as pure pytree math (the all-reduce itself is GSPMD-inserted and
    runs on the bf16 payload because the quantize happens *before* psum in
    the train step's shard_map'd grad sync).
  * ``compressed_allreduce_shardmap`` — explicit shard_map DP sync: bf16
    psum over the data axis with the residual kept locally.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


CompressState = Any  # pytree of fp32 residuals, same structure as grads


def init_compress_state(params: Any) -> CompressState:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compress_grads(
    grads: Any, residual: CompressState, dtype=jnp.bfloat16
) -> tuple[Any, CompressState]:
    """(compressed bf16 grads, new residual).  g_c = cast(g + r); r' = g + r - g_c."""

    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        q = corrected.astype(dtype)
        return q, corrected - q.astype(jnp.float32)

    q = jax.tree.map(lambda *a: one(*a)[0], grads, residual)
    r = jax.tree.map(lambda *a: one(*a)[1], grads, residual)
    return q, r


def compressed_allreduce_shardmap(mesh, *, axis: str = "data", dtype=jnp.bfloat16):
    """f(grads, residual) -> (synced fp32 grads, residual'): bf16 psum over
    ``axis`` with per-device error feedback (half the DP collective bytes)."""

    def body(grads, residual):
        q, r = compress_grads(grads, residual, dtype)
        synced = jax.tree.map(
            lambda g: lax.pmean(g.astype(dtype), axis).astype(jnp.float32), q
        )
        return synced, r

    spec = P(axis)  # leaves carry per-device replicas stacked on dim 0
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec),
    )
