"""Useful work of a block-sparse product, counted from the operands'
masks: the block products A_ik B_kj with both blocks present, each
2 * bs_r * bs_k * bs_c FLOP.  It is the same whichever local backend runs
the product."""
from __future__ import annotations

import numpy as np


def surviving_products(mask_a, mask_b) -> int:
    """Number of (i, k, j) with mask_a[i, k] and mask_b[k, j]:
    sum_k colcount_a[k] * rowcount_b[k]."""
    a = np.asarray(mask_a, bool)
    b = np.asarray(mask_b, bool)
    return int(np.dot(a.sum(axis=0, dtype=np.int64),
                      b.sum(axis=1, dtype=np.int64)))


def product_flops(mask_a, mask_b, bs_r: int, bs_k: int, bs_c: int) -> float:
    return 2.0 * bs_r * bs_k * bs_c * surviving_products(mask_a, mask_b)
