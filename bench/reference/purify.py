"""Plain reference of a purification: the density matrix P = (I - sign(H -
mu I)) / 2 by a dense Newton-Schulz iteration with no filtering.

    X_0 = (H - mu I) / ||H - mu I||_F,   X_{n+1} = X_n (3 I - X_n^2) / 2

It runs until a sweep changes X by less than ``tol`` relative, or for
``max_iter`` sweeps, whichever is first (at ``"high"`` the rounding keeps
the change above ``tol`` and it runs all ``max_iter``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference.dense import matmul


@partial(jax.jit, static_argnames=("precision",))
def _sweep(x, precision):
    x2 = matmul(x, x, precision)
    new = 0.5 * (3.0 * x - matmul(x, x2, precision))
    return new, jnp.linalg.norm(new - x) / jnp.linalg.norm(new)


def density_matrix(h, mu: float = 0.0, *, tol: float = 1e-6,
                   max_iter: int = 100, precision: str = "highest"):
    """Returns ``(P, sweeps)`` for the dense symmetric ``h``."""
    n = h.shape[0]
    x = h - mu * jnp.eye(n, dtype=jnp.float32)
    x = x / jnp.linalg.norm(x)
    it = 0
    for it in range(1, max_iter + 1):
        x, res = _sweep(x, precision)
        if float(res) < tol:
            break
    return 0.5 * (jnp.eye(n, dtype=jnp.float32) - x), it
