"""With no TPU, a run exits non-zero and prints no result."""

import os
import subprocess
import sys

from bench import harness


def test_cpu_only_run_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16.rows",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout
    assert "TPU" in proc.stderr
