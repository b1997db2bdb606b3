"""A gapped, decaying model Hamiltonian of a molecular liquid.

One block of ``block_size`` orbitals per molecule.  Molecules sit at
uniform random positions in a periodic unit box, indexed in that random
order (as DBCSR's randomized distribution would place them).  H has

- diagonal blocks ``R_i diag(levels) R_i``: the same ``occupied`` levels
  below mu = 0 and ``block_size - occupied`` levels above it in every
  molecule, turned by a random Householder reflection ``R_i``;
- off-diagonal blocks between molecules closer than ``cutoff`` (in box
  units, minimum image): a symmetric Gaussian block scaled by
  ``coupling * exp(-d / decay_length)``.

While the coupling stays well below the gap between the two groups of
levels, exactly ``occupied`` states per molecule lie below mu and the
density matrix P decays exponentially with distance (an insulator).  A
weak coupling keeps most products of couplings (the fill-in of X^2)
below the filter threshold, so X's filtered occupancy grows only from
H's, the share of molecule pairs within ``cutoff``, to a few times that,
nearly the same at every block rows held (the configuration records the
calibration).

The mask is made on the host with numpy, the blocks on the device in one
jitted call, both from the seed.
"""
from __future__ import annotations

from functools import partial

import numpy as np


def host_pattern(nb: int, seed: int, p: dict):
    """Positions, mask and coupling scale of the block grid (numpy).

    Returns ``(mask, scale)``: ``mask`` (nb, nb) bool with the diagonal
    set, ``scale`` (nb, nb) float32, the coupling factor of each
    off-diagonal block (0 outside the mask and on the diagonal).
    """
    rng = np.random.default_rng([seed, 0x6A77])
    pos = rng.random((nb, 3))
    delta = pos[:, None, :] - pos[None, :, :]
    delta -= np.round(delta)  # minimum image in the periodic unit box
    d = np.sqrt(np.sum(delta * delta, axis=-1))
    cut, lam = float(p["cutoff"]), float(p["decay_length"])
    off = (d < cut) & ~np.eye(nb, dtype=bool)
    scale = np.where(off, p["coupling"] * np.exp(-d / lam), 0.0)
    return off | np.eye(nb, dtype=bool), scale.astype(np.float32)


def levels(bs: int, occupied: int, p: dict) -> np.ndarray:
    """The diagonal block's levels: ``occupied`` evenly spaced in
    ``p["occupied_levels"]`` and the rest in ``p["virtual_levels"]``."""
    lo = np.linspace(*p["occupied_levels"], occupied)
    hi = np.linspace(*p["virtual_levels"], bs - occupied)
    return np.concatenate([lo, hi]).astype(np.float32)


def device_blocks(key, scale, lv, nb: int, bs: int, sharding=None):
    """The (nb, nb, bs, bs) f32 block grid of H, made on the device (laid
    out by ``sharding`` where given)."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, out_shardings=sharding)
    def build(key, scale, lv):
        kg, kv = jax.random.split(key)
        g = jax.random.normal(kg, (nb, nb, bs, bs), jnp.float32)
        g = g / np.sqrt(2.0 * bs)
        blocks = (g + g.transpose(1, 0, 3, 2)) * scale[:, :, None, None]
        v = jax.random.normal(kv, (nb, bs), jnp.float32)
        v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
        refl = jnp.eye(bs, dtype=jnp.float32) - 2.0 * v[:, :, None] * v[:, None, :]
        diag = jnp.einsum("nab,b,ncb->nac", refl, lv, refl,
                          precision=jax.lax.Precision.HIGHEST)
        idx = jnp.arange(nb)
        blocks = blocks.at[idx, idx].set(diag)
        # mirror the upper triangle, so that H is symmetric bit for bit
        # whatever the compiler fuses
        i, a = jnp.arange(nb), jnp.arange(bs)
        upper = ((i[:, None, None, None] < i[None, :, None, None])
                 | ((i[:, None, None, None] == i[None, :, None, None])
                    & (a[None, None, :, None] <= a[None, None, None, :])))
        return jnp.where(upper, blocks, blocks.transpose(1, 0, 3, 2))

    return build(key, jnp.asarray(scale), jnp.asarray(lv))


def make(config: dict, nb: int, seed: int, sharding=None):
    """H of ``config`` at ``nb`` block rows from ``seed``.

    Returns ``{"h": (blocks, mask)}``: ``blocks`` a device array laid out
    by ``sharding`` (default: the default device), ``mask`` a numpy bool
    array.
    """
    import jax

    p = config["generator_params"]
    bs, occ = int(config["block_size"]), int(config["occupied_per_block"])
    mask, scale = host_pattern(nb, seed, p)
    key = jax.random.key(int(np.random.default_rng([seed, 0x4B]).integers(2**31)))
    blocks = device_blocks(key, scale, levels(bs, occ, p), nb, bs, sharding)
    return {"h": (blocks, mask)}
