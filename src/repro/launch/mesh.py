"""Meshes: the one place in the repository that builds a ``jax.sharding.Mesh``.

Every mesh has Auto axis types: the engines place their operands with
``NamedSharding`` and run their bodies under ``shard_map``, and the rest is
left to XLA's sharding propagation. ``jax.make_mesh`` builds Explicit axes
by default, on which a reshape, gather or duplicated spec of a sharded array
raises instead of propagating.

Functions (not module-level constants) so importing never touches jax
device state; the dry-run sets XLA_FLAGS for 512 fake devices before any
jax import, everything else sees the real device count.
"""
from __future__ import annotations

from collections.abc import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(
    shape: tuple[int, ...],
    axes: tuple[str, ...],
    *,
    devices: Sequence | None = None,
) -> Mesh:
    """An Auto-axis mesh of ``shape`` named ``axes``.

    ``devices`` — the devices to lay out, in row-major order over ``shape``
    (for example a prefix of ``jax.devices()`` or the devices of a described
    topology); default: ``jax.make_mesh``'s choice among all devices.
    """
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)
    devs = np.asarray(devices, dtype=object).reshape(tuple(shape))
    return Mesh(devs, tuple(axes), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_spgemm_mesh(
    *,
    p: int | None = None,
    l: int = 1,
    p_r: int | None = None,
    p_c: int | None = None,
    devices: Sequence | None = None,
) -> Mesh:
    """Mesh for the SpGEMM engines.

    ``p``          — square (r, c) grid side (``p_r = p_c = p``).
    ``p_r, p_c``   — non-square (r, c) grid (the paper's non-ideal
                     topologies); the 2.5D pull engine derives its virtual
                     depth L = max/min from the grid itself.
    ``l > 1``      — adds a depth axis: (l, r, c) mesh of l layer grids for
                     the stacked 2.5D formulation (square layers only).
    ``devices``    — as for :func:`make_mesh`.
    """
    if p is not None:
        p_r = p_c = p
    if p_r is None or p_c is None:
        raise ValueError("pass p= or both p_r= and p_c=")
    if l == 1:
        return make_mesh((p_r, p_c), ("r", "c"), devices=devices)
    if p_r != p_c:
        raise ValueError(
            "stacked (l, r, c) meshes need square layer grids; non-square "
            "topologies run the 2.5D pull engine on the 2D (r, c) mesh"
        )
    return make_mesh((l, p_r, p_c), ("l", "r", "c"), devices=devices)
