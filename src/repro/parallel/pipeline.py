"""GPipe-style pipeline schedule over a mesh axis (scan + ppermute).

Not used by the assigned meshes (every assigned model fits TP x DP on a
16x16 pod) but required for >2-pod scale-out, where the pod axis becomes
the stage axis.  The schedule is the classic fill/drain microbatch stream:

    T = n_micro + n_stages - 1 steps; at step t, stage s computes
    microbatch t - s (when in range); activations hop stage->stage+1 via
    one collective_permute per step.

Bubble fraction (n_stages-1)/T — the standard GPipe overhead; interleaved
1F1B is left as a documented extension point (the schedule function is the
only thing that would change).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P



def pipeline_shardmap(mesh, stage_fn, *, axis: str = "pod"):
    """Build f(stage_params, xs) running `stage_fn` as a pipeline.

    stage_params: pytree with leading dim n_stages (sharded over ``axis``).
    xs: (n_micro, ...) microbatch stream (replicated over ``axis``).
    Returns (n_micro, ...) outputs (replicated — psum-broadcast from the
    last stage).
    """
    n_stages = mesh.shape[axis]

    def body(stage_params, xs):
        # under shard_map: stage_params leaves (1, ...) — this stage's slice
        local = jax.tree.map(lambda a: a[0], stage_params)
        idx = lax.axis_index(axis)
        n_micro = xs.shape[0]
        t_total = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        y0 = stage_fn(local, xs[0])  # shape probe (traced once, reused)
        out0 = jnp.zeros((n_micro,) + y0.shape, y0.dtype)

        def step(carry, t):
            recv, outs = carry
            x_in = jnp.where(
                idx == 0,
                xs[jnp.clip(t, 0, n_micro - 1)],
                recv.astype(xs.dtype) if recv.dtype != xs.dtype else recv,
            )
            y = stage_fn(local, x_in)
            # last stage banks microbatch t-(n_stages-1) when in range
            mb = t - (n_stages - 1)
            valid = (idx == n_stages - 1) & (mb >= 0) & (mb < n_micro)
            outs = lax.cond(
                valid,
                lambda o: lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(mb, 0),) + (0,) * y.ndim
                ),
                lambda o: o,
                outs,
            )
            recv = lax.ppermute(y, axis, fwd)
            return (recv, outs), None

        (_, outs), _ = lax.scan(
            step, (jnp.zeros_like(y0), out0), jnp.arange(t_total)
        )
        # broadcast the last stage's banked outputs to every stage
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        # the fill/drain cond branches mix varying (stage-local) and
        # unvarying buffers; correctness is oracle-tested (tests/_dist.py)
        check_vma=False,
    )


def split_microbatches(x: jax.Array, n_micro: int) -> jax.Array:
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])
