"""Multi-device integration tests.

Each test spawns ``python -m tests._dist <check>`` with 16 fake CPU devices
(XLA_FLAGS is set inside _dist.py, never in this process — the rest of the
suite must keep seeing the real single device).
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*checks: str, timeout: int = 900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + _ROOT
    proc = subprocess.run(
        [sys.executable, "-m", "tests._dist", *checks],
        cwd=_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"check {checks} failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}"
        )
    return proc.stdout


def test_engines_match_reference():
    out = _run("engines")
    assert "engines OK" in out


def test_transport_compressed_bit_exact():
    """Compressed panel transport == dense transport bitwise for every
    engine across occupancies, rectangular meshes and uneven L; auto
    crossover + REPRO_TRANSPORT override."""
    out = _run("transport")
    assert "transport OK" in out


def test_stacks_backends_distributed():
    """Compacted backends + auto capacity bounds across engines/grids."""
    out = _run("stacks_backends")
    assert "stacks_backends OK" in out


def test_engines_rectangular_grids():
    out = _run("engines_rectangular")
    assert "OK" in out


def test_plan_rectangular_grids():
    """2.5D on (2,4)/(4,2) and square L=4: == reference == Algorithm 2."""
    out = _run("plan_rectangular")
    assert "plan_rectangular OK" in out


def test_plan_cache_no_relower():
    """Second multiply hits the compiled-plan cache (no re-lowering)."""
    out = _run("plan_cache")
    assert "plan_cache OK" in out


def test_signiter_sharded_device_resident():
    """Fused device-resident purification == legacy loop on a mesh; one
    program per multiply shape; no global gather in the fused step."""
    out = _run("signiter_sharded")
    assert "signiter_sharded OK" in out


def test_sweep_product_counts_on_four_devices():
    out = _run("product_counts")
    assert "product_counts OK" in out


def test_envelope_chain_sharded():
    """Envelope-compiled drifting-pattern chains on a mesh: builds == 1,
    bitwise == the chain-safe fused chain, compressed transport unlocked,
    warm path re-hits the forecast cache with zero retraces."""
    out = _run("envelope_sharded")
    assert "envelope_sharded OK" in out


def test_tuner_auto_multi_device():
    """engine="auto": tuned multiplies == oracle on 2x2/2x4/stacked
    meshes, warm-DB resolution is measurement-free, autotuned
    purification matches the static loop."""
    out = _run("tuner_auto")
    assert "tuner_auto OK" in out


def test_comm_volume_matches_paper_model():
    out = _run("comm_volume", "spgemm_scaling")
    assert "comm_volume OK" in out and "spgemm_scaling OK" in out


def test_train_steps_execute_and_learn():
    out = _run("train_steps")
    assert out.count("OK") == 2  # with and without gradient compression


def test_serve_steps_match_single_device():
    out = _run("serve_steps")
    assert "serve_steps OK" in out


def test_checkpoint_cross_mesh_restore():
    out = _run("checkpoint_cross_mesh")
    assert "OK" in out


def test_data_pipeline_sharded():
    out = _run("data_global_batch")
    assert "OK" in out


def test_matmul_2p5d_lm_head():
    out = _run("matmul_2p5d")
    assert "OK" in out


def test_compressed_allreduce():
    out = _run("compressed_allreduce")
    assert "OK" in out


def test_microbatch_gradient_accumulation():
    out = _run("microbatch")
    assert "microbatch_equivalence OK" in out


def test_pipeline_schedule():
    out = _run("pipeline")
    assert "pipeline OK" in out


def test_assignment_distributed():
    out = _run("assignment")
    assert "assignment OK" in out


def test_tensor_contraction():
    out = _run("tensor")
    assert "tensor OK" in out
