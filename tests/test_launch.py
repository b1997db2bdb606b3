"""Entry-point plumbing: the compile-cache location and the purification
launcher's device choice."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_compile_cache_dir_is_fixed_or_from_environment(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = cache.compile_cache_dir()
    assert fixed == os.path.join(_ROOT, ".jax_cache")
    # the same path in other processes, from another working directory
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = "from repro.launch.cache import compile_cache_dir as d; print(d())"
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=tmp_path, capture_output=True, text=True,
                             timeout=120, check=True)
        assert out.stdout.strip() == fixed
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)


def test_enable_compile_cache_points_jax_there(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "flags,mesh",
    [
        ([], "{'r': 1, 'c': 1}"),  # the real (single CPU) device by default
        (["--fake-devices", "4"], "{'r': 2, 'c': 2}"),
    ],
)
def test_purify_runs_on_the_devices_present(tmp_path, flags, mesh):
    """``purify`` uses the platform's devices unless fake host devices are
    asked for, and sizes its (r, c) grid from them; the compile cache goes
    where JAX_COMPILATION_CACHE_DIR says."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.purify", "--nb", "8",
         "--bs", "4", "--repeats", "2", *flags],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)), cwd=_ROOT,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert f"mesh {mesh}" in proc.stdout
    assert "purify OK" in proc.stdout
    assert any(tmp_path.iterdir())  # compiled programs were cached there
