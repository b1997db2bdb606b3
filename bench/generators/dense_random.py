"""Two fully occupied block matrices A and B with standard normal entries
scaled by 1 / sqrt(block_size), made on the device in one jitted call from
the seed; the masks (all set) are made on the host."""
from __future__ import annotations

import numpy as np


def make(config: dict, nb: int, seed: int):
    """Returns ``{"a": (blocks, mask), "b": (blocks, mask)}``."""
    import jax
    import jax.numpy as jnp

    bs = int(config["block_size"])

    @jax.jit
    def build(key):
        ka, kb = jax.random.split(key)
        shape = (nb, nb, bs, bs)
        scale = 1.0 / np.sqrt(bs)
        return (jax.random.normal(ka, shape, jnp.float32) * scale,
                jax.random.normal(kb, shape, jnp.float32) * scale)

    key = jax.random.key(int(np.random.default_rng([seed, 0xD5]).integers(2**31)))
    a, b = build(key)
    mask = np.ones((nb, nb), bool)
    return {"a": (a, mask), "b": (b, mask)}
