"""The H2O generator at a small size: H is symmetric with exactly
``occupied_per_block`` states per block below mu = 0 and a gap around it,
and the program's purification of it gives trace(P) = 4 nb with X's
occupancy inside the band calibrated at this size
(``calibration.small`` in ``configs/h2o_dft_ls.json``)."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH

CONFIG = os.path.join(BENCH, "configs", "h2o_dft_ls.json")


@pytest.mark.parametrize("seed", [1, 2, 3000000007])
def test_gapped_h_and_its_purification(seed):
    import jax.numpy as jnp

    from generators import gapped_decay
    from repro.core import bsm as B
    from repro.core.signiter import density_matrix
    from repro.launch.mesh import make_spgemm_mesh

    with open(CONFIG) as f:
        cfg = json.load(f)
    cal = cfg["calibration"]["small"]
    nb, occ = cal["block_rows"], cfg["occupied_per_block"]
    blocks, mask = gapped_decay.make(cfg, nb, seed)["h"]
    h = B.make_bsm(blocks, jnp.asarray(mask))
    dense = np.asarray(h.to_dense(), np.float64)
    assert np.array_equal(dense, dense.T)
    w = np.linalg.eigvalsh(dense)
    assert int((w < 0).sum()) == occ * nb
    assert np.min(np.abs(w)) >= cal["gap_half_width_min"]

    p, stats = density_matrix(
        B.shard_bsm(h, make_spgemm_mesh(p=1)), 0.0, engine="twofive",
        threshold=cfg["threshold"], filter_eps=cfg["filter_eps"],
        max_iter=100, tol=1e-6, mode="fused", sync_every=4)
    assert stats.converged
    assert abs(float(p.trace()) - occ * nb) < 1e-3
    lo, hi = cal["x_occupancy_mean"]
    assert lo <= float(np.mean(stats.occupancy_trace)) <= hi
