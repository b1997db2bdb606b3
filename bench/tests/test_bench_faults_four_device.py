"""The four-chip purification cell on four CPU devices: a sound run is
correct, and one with the exchange between devices left out is not."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_exchange_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(HERE,
                                                     "four_device_run.py"),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["sound"]["device"]["count"] == 4
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert not out["no_exchange"]["correct"], out["no_exchange"]["checks"]
