"""run.py refuses to run without a TPU and prints no result."""
import os
import subprocess
import sys

from benchmark import harness


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "text-512.typing-steady", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"],
        cwd=harness.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
