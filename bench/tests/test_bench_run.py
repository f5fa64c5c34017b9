"""``run.py`` without a TPU exits non-zero and prints no result line."""

import os
import subprocess
import sys

import harness


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload",
         "tellme-0.7b.decode-1k", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_cell_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload",
         "no-such-cell", "--seed", "1", "--seconds", "1"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
