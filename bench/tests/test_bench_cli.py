"""``bench/run.py`` refuses to run without a TPU: non-zero exit, no result
line."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "spd16k.solve",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
