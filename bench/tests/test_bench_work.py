"""The useful-work count against a brute-force count over the cube."""
import numpy as np
import pytest

from benchlib.work import product_flops, surviving_products


@pytest.mark.parametrize("shape,fill", [((7, 5, 6), 0.3), ((12, 12, 12), 0.1),
                                        ((9, 4, 11), 1.0), ((5, 8, 3), 0.0)])
def test_surviving_products_matches_brute_force(shape, fill):
    ni, nk, nj = shape
    rng = np.random.default_rng(ni * 100 + nk * 10 + nj)
    a = rng.random((ni, nk)) < fill
    b = rng.random((nk, nj)) < fill
    brute = sum(1 for i in range(ni) for k in range(nk) for j in range(nj)
                if a[i, k] and b[k, j])
    assert surviving_products(a, b) == brute
    assert product_flops(a, b, 23, 23, 23) == 2.0 * 23 ** 3 * brute
