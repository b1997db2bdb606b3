"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in extra_env:
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "h2o_purify_1chip",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "PYTHONPATH")
    assert p.returncode != 0
    assert _no_result(p.stdout)
