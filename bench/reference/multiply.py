"""Plain reference of a block product: C = A B of the dense matrices."""
from __future__ import annotations

from reference.dense import matmul, to_dense


def product(a_blocks, a_mask, b_blocks, b_mask, precision: str = "highest"):
    """Dense C = A B, (nb_r * bs_r, nb_c * bs_c), float32."""
    return matmul(to_dense(a_blocks, a_mask), to_dense(b_blocks, b_mask),
                  precision)
