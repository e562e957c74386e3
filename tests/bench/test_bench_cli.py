"""``bench/run.py`` measures only on a TPU: without one it exits non-zero
and prints no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_no_tpu_no_result(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_unknown_workload_is_refused():
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()
