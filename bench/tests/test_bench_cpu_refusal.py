"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cpu_exits_nonzero_without_metrics(trace):
    p = run("--workload", "r50-infer-hi3.warm-sweep", "--seed", "3",
            "--seconds", "1", "--trace", trace)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and not p.stdout.strip()
    assert "no chip" in p.stderr


def test_unknown_workload_exits_nonzero():
    p = run("--workload", "no-such-cell", "--seed", "3", "--seconds", "1",
            "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()
