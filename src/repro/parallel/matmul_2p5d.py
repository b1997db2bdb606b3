"""The paper's 2.5D schedule applied to the LM's largest matmuls.

Beyond-paper carry-over (DESIGN.md §4): the 2.5D SpGEMM insight — split the
contraction dimension over a depth axis L, compute partial products against
the *home* layout, and fuse the partial-result reduction into one collective
— applies verbatim to the LM-head / embedding matmul, whose (d_model x
vocab) weight is the biggest single GEMM in most of the assigned archs
(vocab 50k-256k).

On the multi-pod mesh the ``pod`` axis plays L:

    W (d, V)  sharded  P("pod", "model")     — d split over L, V over TP
    x (T, d)  sharded  P(None, "pod")        — activations split over d too
    partial = x_l @ W_l                      — no communication
    logits  = psum_scatter(partial, "pod")   — the (L-1)-panel reduction

Per-device communication: psum_scatter moves (L-1)/L of the logits shard
instead of all-gathering a d-replicated weight — the same
"(L-1) S_C vs V/sqrt(L) (S_A+S_B)" trade as paper Eq. (7).  ``plan_2p5d``
evaluates that trade analytically (it is the hillclimb napkin math).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P



def matmul_2p5d_shardmap(mesh, *, depth_axis: str = "pod", tp_axis: str = "model",
                         reduce: str = "scatter"):
    """Returns f(x, w) computing x @ w with the contraction dim split over
    ``depth_axis`` and the output dim over ``tp_axis``.

    x: (..., T, d) sharded (..., None, depth); w: (d, V) sharded (depth, tp).
    Output: (..., T, V) sharded over tp (and depth when reduce == "scatter",
    P(..., depth, tp) — token-sharded logits, the chunked-CE-friendly form).
    """

    def body(x, w):
        partial = jnp.einsum("...td,dv->...tv", x, w)  # local (T, V/tp)
        if reduce == "scatter":
            return lax.psum_scatter(
                partial, depth_axis, scatter_dimension=partial.ndim - 2, tiled=True
            )
        return lax.psum(partial, depth_axis)

    # (T, d) specs; callers with batch dims use the same trailing axes
    x_spec = P(None, depth_axis)
    w_spec = P(depth_axis, tp_axis)
    out_spec = P(depth_axis, tp_axis) if reduce == "scatter" else P(None, tp_axis)
    return shard_map(
        body, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=out_spec
    )


@dataclass(frozen=True)
class Plan2p5d:
    l: int
    bytes_baseline: float  # all-gather the d-sharded weight per step
    bytes_2p5d: float  # psum_scatter of the partial logits
    wins: bool


def plan_2p5d(
    tokens: int, d_model: int, vocab: int, l: int, tp: int, bytes_per_el: int = 2
) -> Plan2p5d:
    """Napkin math for claiming the pod axis as 2.5D depth on the LM head.

    Baseline (pure DP over pod): weight fully resident, logits local — but
    the FSDP variant all-gathers W (d x V / tp) per step: d*V/tp bytes.
    2.5D: psum_scatter moves (l-1)/l of the partial logits: T*V/tp*(l-1)/l.
    """
    base = d_model * vocab / tp * bytes_per_el
    ours = tokens * vocab / tp * (l - 1) / l * bytes_per_el
    return Plan2p5d(l=l, bytes_baseline=base, bytes_2p5d=ours, wins=ours < base)
