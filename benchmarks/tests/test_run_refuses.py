"""Without a TPU the benchmark measures nothing and says which platform it found."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_exits_non_zero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b-prefix-reuse",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "Nothing was measured" in proc.stderr
    assert proc.stdout.strip() == ""
