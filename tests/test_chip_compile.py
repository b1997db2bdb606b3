"""Compile-only rehearsals for a described TPU v5e (nothing runs).

The TPU compiler refuses what interpret mode and the CPU accept: kernels
that use more SMEM or VMEM than a core has, tiles not aligned to the
(8, 128) layout, programs larger than HBM.  These cases compile the main
path's kernels and programs at real sizes for a ``v5e:2x2`` topology that
is described, not attached.  The topology is described inside a fixture,
after a test has started, and the cases skip where it cannot be.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_spgemm_compiles_at_capacity_2_17(one_chip, dtype):
    """A 2^17-product list (four launches of 2^15) fits SMEM; the scalar-
    prefetched index words of one launch used to exceed it."""
    from repro.kernels.block_spgemm import block_spgemm_stacks
    from repro.kernels.stacks import ProductStacks

    nb, bs, cap = 32, 128, 2**17
    blk = jax.ShapeDtypeStruct((nb, nb, bs, bs), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)
    stacks = ProductStacks(*[idx] * len(ProductStacks._fields))
    compiled = jax.jit(
        lambda a, b, s: block_spgemm_stacks(a, b, s, ni=nb, nj=nb)
    ).lower(blk, blk, stacks).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < HBM_BYTES


def test_local_stage_bs23_through_tpu_backend_choice(one_chip, monkeypatch):
    """On a TPU the atomic block size 23 has no lane-aligned tile, so the
    backend helper picks the XLA ``stacks`` path, which compiles."""
    from repro.core.local_mm import (
        compacted_backend,
        local_filtered_mm,
        stacks_memory_bytes,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    backend = compacted_backend(23, 23, 23)
    assert backend == "stacks"
    assert compacted_backend(128, 128, 128) == "pallas"
    nb, cap = 32, 2**14
    blk = jax.ShapeDtypeStruct((nb, nb, 23, 23), jnp.float32,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((nb, nb), jnp.bool_, sharding=one_chip)
    norms = jax.ShapeDtypeStruct((nb, nb), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda ab, am, an, bb, bm, bn: local_filtered_mm(
            ab, am, an, bb, bm, bn, backend=backend, stack_capacity=cap)
    ).lower(blk, mask, norms, blk, mask, norms).compile()
    assert _bytes(compiled) < HBM_BYTES / 2
    # the tile-padded footprint the backend choice prices is what the
    # compiler lays out
    model = stacks_memory_bytes(nb, nb, nb, 23, 23, 23, cap)
    assert 0.8 < model / _bytes(compiled) < 1.25, (model, _bytes(compiled))


def test_cannon_sweep_compiles_on_2x2_mesh(topo):
    """The fused purification sweep around Cannon's shard body, on a 2x2
    mesh of described chips: the ring hops are collective-permutes."""
    from repro.core.signiter import lower_sweep
    from repro.launch.mesh import make_spgemm_mesh

    mesh = make_spgemm_mesh(p=2, devices=topo.devices[:4])
    compiled = lower_sweep(mesh, 32, 23, engine="cannon", threshold=1e-9,
                           filter_eps=1e-9).compile()
    assert "collective-permute" in compiled.as_text()
    assert _bytes(compiled) < HBM_BYTES / 2


# temp bytes of the dense multiply (nb 512, bs 32) when its local stage
# weighted the einsum by the float filter cube (compile for v5e)
MASKED_DENSE_TEMP_BYTES = 2_684_419_072


def test_dense_multiply_local_stage_builds_no_filter_cube(one_chip):
    """At threshold 0 the single-device multiply contracts the masked
    blocks directly: no (512, 512, 512) f32 cube, no more temporaries than
    the cube-weighted form, the contraction under ``spgemm.local/separable``."""
    from repro.core.bsm import BlockSparseMatrix
    from repro.core.engine import _multiply_reference_jit

    nb, bs = 512, 32
    m = BlockSparseMatrix(
        jax.ShapeDtypeStruct((nb, nb, bs, bs), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((nb, nb), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((nb, nb), jnp.float32, sharding=one_chip),
    )
    compiled = _multiply_reference_jit.lower(m, m, 0.0, "jnp").compile()
    text = compiled.as_text()
    assert "f32[512,512,512]" not in text
    assert "spgemm.local/separable/ikab,kjbc->ijac" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= MASKED_DENSE_TEMP_BYTES, temp


def test_h2o_sweep_keeps_the_masked_local_stage(topo):
    """Above threshold 0 the filter couples i, k and j: the one-chip H2O
    sweep (nb 384, bs 23, threshold 1e-6) still weights its einsums by
    the (384, 384, 384) filter cube."""
    from repro.core.signiter import lower_sweep
    from repro.launch.mesh import make_spgemm_mesh

    mesh = make_spgemm_mesh(p=1, devices=topo.devices[:1])
    text = lower_sweep(mesh, 384, 23, engine="twofive", threshold=1e-6,
                       filter_eps=1e-6).compile().as_text()
    assert "f32[384,384,384]" in text
    assert "spgemm.local/ikj,ikab,kjbc->ijac" in text
    assert "spgemm.local/separable" not in text
