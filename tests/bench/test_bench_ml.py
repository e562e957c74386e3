"""The two-level sweep cell and the four-chip sweep cell at tiny sizes on
the CPU: the ``mc_sweep_ml`` kind against ``bench/reference_ml.py``, the
float32 control and planted faults that its comparison has to reject, the
reference against the program's scalar phase machine, and the four-chip
mix on four virtual devices."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import reference_ml as ref
from bench import run as harness

ROOT = Path(__file__).resolve().parents[2]
ML = "ml-exascale-buddy-pfs-weibull07.sweep-ml"
FOUR = "fig2-exascale-weibull07.sweep-4chip"

#: cell -> the configuration keys that these tests shrink.
TINY = {
    ML: dict(mu_minutes=[60.0, 300.0], q=[0.1, 0.5], trials_per_point=16),
    FOUR: dict(mu_minutes=[60.0, 300.0], rho=[2.0, 8.0],
               trials_per_point=16),
}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.Cell(name)
    cell.config = dict(cell.config, **TINY[name])
    return cell


@pytest.fixture(scope="module")
def unit_run():
    cell = tiny_cell(ML)
    work = harness.load_module(cell.kind).Workload(cell.config, cell.mix, 1)
    outs = [(s, work.unit(s)) for s in (2**40 + 3, 2**33 - 5)]
    return cell, work, outs


def _fails(values: dict, limits: dict) -> list:
    return [k for k, v in values.items() if not v <= limits[k]]


@pytest.mark.parametrize("seed", [1, 2**41 + 9])
def test_program_matches_reference(unit_run, seed):
    cell, work, outs = unit_run
    sample = work.sample(outs, seed)
    got = work.compare(work.extract(outs, sample),
                       work.reference(sample, np.float64))
    assert set(got) == set(cell.mix["limits"])
    assert _fails(got, cell.mix["limits"]) == []


@pytest.mark.parametrize("seed", [3, 2**35 + 1])
def test_control_fails(unit_run, seed):
    cell, work, outs = unit_run
    sample = work.sample(outs, seed)
    want = work.reference(sample, np.float64)
    control = work.compare(work.reference(sample, np.float32), want)
    assert _fails(control, cell.mix["limits"])


def test_grid_is_two_level(unit_run):
    """The optima use the buddy level: every m* > 1, and the sweep draws
    hard and soft failures and deep and buddy checkpoints."""
    _, work, outs = unit_run
    assert (work.m > 1).all()
    out = outs[0][1]
    assert 0 < out.n_hard_failures.sum() < out.n_failures.sum()
    assert (out.n_ckpt1 > 0).any() and (out.n_ckpt2 > 0).any()


def test_describe_reports_counters(unit_run):
    _, work, outs = unit_run
    d = work.describe(outs[0][1])
    assert 0.0 < d["wall_time_se_rel_max"] < 1.0
    assert 0.0 < d["energy_se_rel_max"] < 1.0
    assert d["mean_failures"] > 1.0 and d["mean_deep_checkpoints"] > 1.0
    assert 0.05 < d["hard_share"] < 0.5
    assert 0.0 < d["scan_occupancy"] < 1.0


def test_bytes_moved():
    kind = harness.load_module(harness.BENCH / "traffic" / "mc_sweep_ml.py")
    assert kind.OUTPUT_BYTES == 58
    assert kind.trajectory_bytes([0]) == 9 + 58
    assert kind.trajectory_bytes([[3, 0], [1, 5]]) == (4 + 1 + 2 + 6) * 9 \
        + 4 * 58


def test_reference_matches_scalar_phase_machine():
    """A hand case with soft and hard failures (the engine tests' T = 10,
    C1 = 1, C2 = 2, m = 2 platform) and a seeded Weibull schedule at an
    operating point of the configuration."""
    from repro.core import (MultilevelCheckpointParams,
                            MultilevelPowerParams, simulate_once_ml)

    ck = MultilevelCheckpointParams(C1=1.0, R1=1.0, C2=2.0, R2=2.0, D1=0.5,
                                    D2=1.0, mu=1e9, q=0.5, omega=0.0)
    pw = MultilevelPowerParams(P_static=1.0, P_cal=2.0, P_io1=3.0,
                               P_io2=5.0, P_down=7.0)
    cases = [(10.0, 2, ck, pw, 40.0, [33.0, 1e9], [True, False]),
             (10.0, 2, ck, pw, 40.0, [33.0, 1e9], [False, False])]
    rng = np.random.default_rng(4)
    pts = ref.platform_points(json.loads(
        (ROOT / "bench/configs/ml-exascale-buddy-pfs-weibull07.json")
        .read_text()))
    i = 7          # MTBF 90 min, q = 0.2
    ck2 = MultilevelCheckpointParams(
        C1=pts["C1"][i], R1=pts["R1"][i], C2=pts["C2"][i], R2=pts["R2"][i],
        D1=pts["D1"][i], D2=pts["D2"][i], mu=pts["mu"][i], q=pts["q"][i],
        omega=pts["omega1"][i])
    pw2 = MultilevelPowerParams(P_static=10.0, P_cal=10.0, P_io1=20.0,
                                P_io2=100.0)
    cases.append((13.9, 11, ck2, pw2, 1440.0,
                  pts["mu"][i] * rng.weibull(0.7, 200) / 1.2658,
                  rng.random(200) < pts["q"][i]))
    for T, m, c, p, Tb, gaps, hard in cases:
        want = simulate_once_ml(T, m, c, p, Tb, gaps, hard)
        lane = {"C1": c.C1, "C2": c.C2, "R1": c.R1, "R2": c.R2, "D1": c.D1,
                "D2": c.D2, "omega1": c.w1, "omega2": c.w2,
                "P_static": p.P_static, "P_cal": p.P_cal, "P_io1": p.P_io1,
                "P_io2": p.P_io2, "P_down": p.P_down}
        got = ref.simulate(T, m, lane, Tb, np.asarray(gaps)[None],
                           np.asarray(hard)[None])
        assert not got["truncated"][0] and not got["gaps_exhausted"][0]
        for f in ref.FLOAT_FIELDS + ref.COUNT_FIELDS:
            assert got[f][0] == getattr(want, f), f
    assert want.n_hard_failures > 0 and want.n_ckpt2 > 0


def test_reference_periods_match_closed_form_solver():
    """The reference's (T*, m*) is the program's f64 AlgoE at every point
    of the configuration (the check the cell makes every run)."""
    from repro.sim import MultilevelParamGrid, evaluate_multilevel_grid

    config = harness.Cell(ML).config
    p = ref.platform_points(config)
    grid = MultilevelParamGrid(omega=p["omega1"], **p)
    res = evaluate_multilevel_grid(grid, m_values=tuple(range(1, 33)),
                                   precision="f64")
    T, m = ref.algo_e(p, tuple(range(1, 33)), config["job_minutes"])
    np.testing.assert_array_equal(m, res.m_energy)
    assert ref.periods_gap(res.T_energy, res.m_energy, T, m) < 1e-12
    assert 5 <= m.min() and m.max() <= 27


# ---------------------------------------------------------------------------
# Whole runs through the harness, sound and with faults planted
# ---------------------------------------------------------------------------

def _run(name: str) -> dict:
    return harness.run(tiny_cell(name), seed=2**40 + 77, seconds=0.0,
                       trace=False, require_chip=False)


@pytest.fixture
def fresh_runners():
    """Compiled runners built for this test only: a planted fault must not
    leave its runner behind for the next test."""
    from repro.sim import dispatch

    dispatch._RUNNERS.clear()
    yield
    dispatch._RUNNERS.clear()


def _flags_ignored(kernel):
    def k(*a):
        *head, gaps, hard, n_steps = a
        return kernel(*head, gaps, hard & False, n_steps)
    return k


def _deep_as_buddy(kernel):
    """No period is ever deep: every checkpoint writes and commits the
    buddy level only."""
    def k(T, m, *a):
        return kernel(T, m + 10**6, *a)
    return k


def _schedule_shifted(kernel):
    import jax.numpy as jnp

    def k(*a):
        *head, gaps, hard, n_steps = a
        gaps = jnp.concatenate([gaps[1:], jnp.full((1,), jnp.inf,
                                                   gaps.dtype)])
        return kernel(*head, gaps, hard, n_steps)
    return k


FAULTS = {"flags_ignored": _flags_ignored, "deep_as_buddy": _deep_as_buddy,
          "schedule_shifted": _schedule_shifted}


def test_sound_run_is_correct(fresh_runners):
    res = _run(ML)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, fresh_runners):
    from repro.sim import engine

    monkeypatch.setattr(engine, "_run_one_ml_event",
                        FAULTS[fault](engine._run_one_ml_event))
    res = _run(ML)
    assert res["correct"] is False, (fault, res["checks"])


FOUR_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys, json
sys.path[:0] = [r"%(root)s", r"%(root)s/src", r"%(root)s/tests/bench"]
import numpy as np
import jax
from repro.sim import dispatch
from test_bench_ml import FOUR, _run

sound = _run(FOUR)
runner_for = dispatch._runner_for


def first_shard_everywhere(key, build, ndev, in_axes, out_axes, backend=None):
    fn = runner_for(key, build, ndev, in_axes, out_axes, backend)

    def run(*args):
        def copy(x):
            n = x.shape[0] // ndev
            return jax.numpy.concatenate([x[:n]] * ndev)
        return jax.tree.map(copy, fn(*args))
    return run


dispatch._RUNNERS.clear()
dispatch._runner_for = first_shard_everywhere
broken = _run(FOUR)
print(json.dumps({"devices": jax.device_count(),
                  "sound": sound["correct"], "broken": broken["correct"],
                  "checks": broken["checks"]}))
"""


def test_four_chip_mix_on_four_virtual_devices():
    """The four-chip sweep on four virtual devices comes out correct, and
    not correct when every shard's results are replaced by the first
    shard's (the exchange between chips left out)."""
    out = subprocess.run([sys.executable, "-c",
                          FOUR_SCRIPT % {"root": str(ROOT)}],
                         capture_output=True, text=True, timeout=900,
                         cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["sound"] is True
    assert res["broken"] is False, res["checks"]
