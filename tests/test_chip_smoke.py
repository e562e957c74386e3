"""chip_smoke.py's phases, rehearsed on the CPU at tiny sizes.

The script itself refuses to run without a TPU; these tests call its
phase functions directly, so a wrong path, argument or gate shows up
here before it costs a chip run.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

#: a 2 x 2 corner of the Figure-2 grid over a short job.
TINY = dict(mus=(120.0, 300.0), rhos=(1.0, 5.0), n_trials=16,
            T_base=1000.0)


def test_refuses_without_tpu():
    with pytest.raises(SystemExit, match="no TPU"):
        cs.require_tpu(1)


def test_sweep_phase():
    rec = cs.sweep_phase(oracle_points=2, oracle_trials=4, **TINY)
    assert rec["points"] == 4 and rec["trials"] == 16
    for pol in ("compensated_f32", "f64"):
        gate = rec["closed_form"][pol]
        assert max(gate.values()) <= 1e-6
    assert sum(rec["capacity_buckets"].values()) == 4
    assert rec["gap_schedule_bytes_per_dispatch"] > 0
    assert rec["engines_same_failure_count_frac"] > 0.9
    assert rec["oracle"]["event_rel_max"] <= 1e-9
    # interpreted on the CPU: no Mosaic kernel in the program
    assert rec["tpu_custom_call"] is False


def test_advisor_phase():
    rec = cs.advisor_phase()
    assert rec["requests"] > 0 and rec["rps"] > 0
    assert rec["hit_rate"] > 0


def test_trainer_phase(tmp_path):
    rec = cs.trainer_phase(batch=2, seq=32, reduce=True,
                           ckpt_dir=tmp_path / "ckpt")
    assert rec["final_step"] == 24
    assert rec["failures"] >= 1 and rec["rollbacks"] >= 1
    assert rec["saves"] >= 1 and rec["store_restores"] >= 1
    assert not (tmp_path / "ckpt").exists()


def test_sharded_phase_on_four_virtual_devices():
    """The ``--chips 4`` path on four virtual CPU devices (the device
    count must be fixed before jax starts, hence the subprocess)."""
    script = (
        "import json, sys; sys.path.insert(0, %r)\n"
        "import chip_smoke as cs\n"
        "print(json.dumps(cs.sharded_phase(mus=(120.0, 300.0), "
        "rhos=(1.0, 5.0, 9.0), n_trials=16, T_base=1000.0)))"
    ) % str(ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    assert rec["event_bit_equal"] and rec["pallas_bit_equal"]
