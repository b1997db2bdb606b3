"""Plain dense linear algebra for the references: block grids to dense
matrices, matrix products at a stated precision, and errors.

Nothing here imports the program under test.  ``matmul`` computes in
float32 at one of two precisions:

- ``"highest"``: float32 products (``Precision.HIGHEST``; six bf16 passes
  on a TPU's MXU, exact float32 products on a CPU);
- ``"high"``: three bf16 passes, written out (``a_hi b_hi + a_hi b_lo +
  a_lo b_hi`` with each operand split into two bf16 parts), which is what
  ``Precision.HIGH`` does on a TPU, so it gives the same numbers on any
  platform.  It is the precision just below the configurations' float32
  at HIGHEST, and computes the correctness control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")


def to_dense(blocks, mask=None):
    """(nb_r, nb_c, bs_r, bs_c) blocks, zeroed where ``mask`` is False, as
    an (nb_r * bs_r, nb_c * bs_c) float32 matrix."""
    nb_r, nb_c, bs_r, bs_c = blocks.shape
    x = blocks.astype(jnp.float32)
    if mask is not None:
        x = jnp.where(jnp.asarray(mask)[:, :, None, None], x, 0.0)
    return x.transpose(0, 2, 1, 3).reshape(nb_r * bs_r, nb_c * bs_c)


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _bf16_dot(x, y):
    return jnp.matmul(x, y, preferred_element_type=jnp.float32)


def matmul(x, y, precision: str = "highest"):
    """x @ y in float32 at ``precision`` (see the module docstring)."""
    if precision == "highest":
        return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        xh, xl = _split(x)
        yh, yl = _split(y)
        return _bf16_dot(xh, yh) + (_bf16_dot(xh, yl) + _bf16_dot(xl, yh))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def rel_err(x, ref) -> float:
    """||x - ref||_F / ||ref||_F, accumulated in float32 on the device."""
    return float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))
