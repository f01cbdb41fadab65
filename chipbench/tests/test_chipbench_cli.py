"""The benchmark's command without a TPU: a non-zero exit, no result."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def test_exits_non_zero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload",
         "fig28-mva-w", "--seed", "3000000017", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
