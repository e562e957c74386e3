"""Two-level (buddy + PFS) event engine against its plain reference.

The contract under test:

- ``simulate_trajectories_ml`` == the scalar phase machine
  ``core.simulator.simulate_once_ml`` on the same ``(gap, hard)``
  schedule, to 1e-12 relative in every float field and exactly in every
  count, across failure processes, cadences ``m``, loss probabilities
  ``q``, per-level overlap and downtime; bit for bit on a dyadic
  schedule, period-boundary ties included;
- at ``m = 1`` with equal levels it is the single-level event engine;
- the in-program sampler draws the documented streams (gaps from the
  lane key, flags from ``level_loss_flags`` of the same key), and
  buckets, chunks, trial blocks and devices never change a fixed seed's
  results;
- the scan reads its schedule without a gather.
"""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
from jax import enable_x64

from repro.core import (EXASCALE_POWER_RHO55, CheckpointParams, Exponential,
                        MultilevelCheckpointParams, MultilevelPowerParams,
                        Weibull, simulate_once_ml)
from repro.core.failures import as_process, level_loss_flags
from repro.sim import (DispatchConfig, MultilevelParamGrid, ParamGrid,
                       buddy_ratio_grid, simulate_trajectories,
                       simulate_trajectories_ml)
from repro.sim import engine

ROOT = Path(__file__).resolve().parents[1]

#: a two-level platform with every per-level knob distinct.
CK = MultilevelCheckpointParams(C1=1.0, R1=1.5, C2=10.0, R2=12.0, D1=0.5,
                                D2=2.0, mu=120.0, q=0.3, omega=0.5,
                                omega1=0.3, omega2=0.8)
PW = MultilevelPowerParams(P_static=10.0, P_cal=10.0, P_io1=20.0,
                           P_io2=100.0, P_down=5.0)

PROCESSES = [Exponential(), Weibull(shape=0.5), Weibull(shape=0.7)]

FLOATS = ("wall_time", "energy", "work_executed", "io1_time", "io2_time",
          "down_time")
COUNTS = ("n_failures", "n_hard_failures", "n_ckpt1", "n_ckpt2")

_DYADIC = 2.0 ** 16


def _dyadic(x):
    return np.maximum(np.round(x * _DYADIC) / _DYADIC, 1.0 / _DYADIC)


def _grid(ck=CK, pw=PW, q=None):
    if q is not None:
        ck = MultilevelCheckpointParams(**{**ck.__dict__, "q": q})
    return MultilevelParamGrid.from_params(ck, pw).reshape((1,))


def _schedule(proc, q, trials=12, cap=160, seed=7, mu=CK.mu):
    rng = np.random.default_rng(seed)
    gaps = np.asarray(proc.sample(rng, size=(1, trials, cap), mean=mu),
                      dtype=np.float64)
    hard = rng.random(size=(1, trials, cap)) < q
    return gaps, hard


def _oracle(T, m, grid, T_base, gaps, hard):
    ck, pw = grid.ckpt_at(0), grid.power_at(0)
    rows = [simulate_once_ml(T, m, ck, pw, T_base, g, h)
            for g, h in zip(gaps[0], hard[0])]
    return {f: np.array([getattr(r, f) for r in rows]) for f in
            FLOATS + COUNTS}


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(got == want, 0.0, np.abs(got - want) / np.abs(want))
    return float(np.max(r))


def _check_against_oracle(tb, ref, exact=False):
    assert not tb.truncated.any() and not tb.gaps_exhausted.any()
    for f in FLOATS:
        got = getattr(tb, f)[0]
        if exact:
            np.testing.assert_array_equal(got, ref[f], err_msg=f)
        else:
            assert _rel(got, ref[f]) <= 1e-12, f
    for f in COUNTS:
        np.testing.assert_array_equal(getattr(tb, f)[0], ref[f], err_msg=f)


class TestOracleParity:
    """The engine against ``simulate_once_ml`` on seeded schedules."""

    @pytest.mark.parametrize("q", [0.0, 0.3, 1.0])
    @pytest.mark.parametrize("m", [1, 2, 3, 7])
    @pytest.mark.parametrize("proc", PROCESSES,
                             ids=lambda p: f"{p.name}{getattr(p, 'shape', '')}")
    def test_matches_scalar_phase_machine(self, proc, m, q):
        grid = _grid(q=q)
        gaps, hard = _schedule(proc, q)
        T, T_base = 23.7, 1500.0
        tb = simulate_trajectories_ml(T, m, grid, T_base=T_base, gaps=gaps,
                                      hard=hard)
        ref = _oracle(T, m, grid, T_base, gaps, hard)
        _check_against_oracle(tb, ref)
        assert ref["n_failures"].sum() > 3 * gaps.shape[1]
        if 0.0 < q < 1.0:
            assert 0 < ref["n_hard_failures"].sum() < ref["n_failures"].sum()
        if m > 1:
            assert ref["n_ckpt1"].sum() > 0 and ref["n_ckpt2"].sum() > 0

    @pytest.mark.parametrize("m", [1, 3, 4])
    def test_bit_exact_on_dyadic_schedule(self, m):
        """Every quantity exactly representable: closed forms and the
        phase machine's sums both compute exactly."""
        ck = MultilevelCheckpointParams(C1=1.0, R1=1.5, C2=8.0, R2=12.0,
                                        D1=0.5, D2=2.0, mu=120.0, q=0.5,
                                        omega=0.5, omega1=0.25, omega2=0.5)
        grid = _grid(ck)
        gaps, hard = _schedule(Weibull(shape=0.7), 0.5)
        gaps = _dyadic(gaps)
        tb = simulate_trajectories_ml(24.0, m, grid, T_base=1536.0,
                                      gaps=gaps, hard=hard)
        _check_against_oracle(
            tb, _oracle(24.0, m, grid, 1536.0, gaps, hard), exact=True)

    @pytest.mark.parametrize("hard_first", [False, True])
    def test_period_boundary_tie(self, hard_first):
        """A failure exactly at a period's end: the checkpoint ending at
        that instant does not commit (failure wins ties), in the engine as
        in the phase machine; nor does a run end at a failure that strikes
        at its completion instant."""
        ck = MultilevelCheckpointParams(C1=1.0, R1=1.0, C2=2.0, R2=2.0,
                                        D1=0.5, D2=1.0, mu=1e9, q=0.5,
                                        omega=0.5)
        grid = _grid(ck)
        T, m, T_base = 8.0, 2, 40.0

        def run(gaps, hard):
            gaps, hard = np.array([[gaps]]), np.array([[hard]])
            tb = simulate_trajectories_ml(T, m, grid, T_base=T_base,
                                          gaps=gaps, hard=hard)
            _check_against_oracle(
                tb, _oracle(T, m, grid, T_base, gaps, hard), exact=True)
            return tb

        # Periods end at 8 (buddy, commits 7 of the 7.5 done) and 16 (deep,
        # commits 13.5 of the 14.5 done).  A failure at 16 loses the deep
        # write: a soft one rolls back to 7, a hard one to 0.
        tie = run([16.0, 1e9], [hard_first, False])
        assert tie.work_executed[0, 0] == T_base + (14.5 if hard_first
                                                    else 7.5)
        late = run([16.0 + 2.0**-10, 1e9], [hard_first, False])
        assert late.work_executed[0, 0] == T_base + 1.0 + 2.0**-10
        free = run([1e9], [False])
        ends = run([float(free.wall_time[0, 0]), 1e9], [hard_first, False])
        assert int(ends.n_failures[0, 0]) == 1

    def test_mixed_grid_per_point_cadence(self):
        """Points with their own (T, m, q) in one call, each against the
        phase machine."""
        grid = buddy_ratio_grid([0.1, 0.3], [0.05, 0.5], mu_min=90.0)
        flat = grid.ravel()
        T = np.array([[14.0, 22.5], [31.0, 18.25]])
        m = np.array([[5, 2], [1, 3]])
        rng = np.random.default_rng(11)
        gaps = rng.exponential(90.0, size=(4, 6, 128))
        hard = rng.random(size=(4, 6, 128)) < flat.q[:, None, None]
        tb = simulate_trajectories_ml(T, m, grid, T_base=900.0, gaps=gaps,
                                      hard=hard)
        for i in range(4):
            point = MultilevelParamGrid(**{f: v[i:i + 1] for f, v in
                                           flat.fields().items()})
            ref = _oracle(T.ravel()[i], m.ravel()[i], point, 900.0,
                          gaps[i:i + 1], hard[i:i + 1])
            sub = engine.MultilevelTrajectoryBatch(**{
                f: getattr(tb, f).reshape(4, 6)[i:i + 1]
                for f in engine.MultilevelTrajectoryBatch.__dataclass_fields__})
            _check_against_oracle(sub, ref)


def _single_level_pair():
    ck = CheckpointParams(C=10.0, R=10.0, D=1.0, mu=300.0, omega=0.5)
    sl = ParamGrid.from_params(ck, EXASCALE_POWER_RHO55).reshape((1,))
    return ck, sl


class TestSingleLevelReduction:
    """m = 1 with equal levels is the single-level event engine."""

    @pytest.mark.parametrize("q", [0.0, 1.0])
    def test_bit_exact_on_dyadic_schedule(self, q):
        _, sl = _single_level_pair()
        grid = MultilevelParamGrid.from_single_level(sl, q=q)
        rng = np.random.default_rng(5)
        gaps = _dyadic(rng.exponential(300.0, size=(1, 10, 128)))
        hard = rng.random(size=(1, 10, 128)) < q
        ml = simulate_trajectories_ml(40.0, 1, grid, T_base=4000.0,
                                      gaps=gaps, hard=hard)
        ev = simulate_trajectories(40.0, sl, T_base=4000.0, gaps=gaps,
                                   engine_kind="event")
        for f in ("wall_time", "energy", "work_executed", "down_time",
                  "n_failures", "truncated", "gaps_exhausted"):
            np.testing.assert_array_equal(getattr(ml, f), getattr(ev, f),
                                          err_msg=f)
        np.testing.assert_array_equal(ml.io1_time + ml.io2_time, ev.io_time)
        np.testing.assert_array_equal(ml.n_ckpt1 + ml.n_ckpt2,
                                      ev.n_checkpoints)
        assert (ml.n_ckpt1 == 0).all()
        np.testing.assert_array_equal(ml.n_hard_failures,
                                      ml.n_failures if q else 0)

    @pytest.mark.parametrize("T", [40.0, 53.3])
    def test_raw_schedule(self, T):
        _, sl = _single_level_pair()
        grid = MultilevelParamGrid.from_single_level(sl, q=0.4)
        rng = np.random.default_rng(8)
        gaps = rng.exponential(300.0, size=(1, 10, 128))
        hard = rng.random(size=(1, 10, 128)) < 0.4
        ml = simulate_trajectories_ml(T, 1, grid, T_base=4000.0, gaps=gaps,
                                      hard=hard)
        ev = simulate_trajectories(T, sl, T_base=4000.0, gaps=gaps,
                                   engine_kind="event")
        for f in ("wall_time", "energy", "work_executed", "down_time"):
            assert _rel(getattr(ml, f), getattr(ev, f)) <= 1e-13, f
        assert _rel(ml.io1_time + ml.io2_time, ev.io_time) <= 1e-13
        np.testing.assert_array_equal(ml.n_failures, ev.n_failures)
        np.testing.assert_array_equal(ml.n_ckpt1 + ml.n_ckpt2,
                                      ev.n_checkpoints)


def _sampled_grid():
    """Mixed MTBF and q, so the grid splits into several buckets."""
    grid = buddy_ratio_grid([0.1], [0.1, 0.5], mu_min=60.0)
    g2 = buddy_ratio_grid([0.1], [0.1, 0.5], mu_min=600.0)
    return MultilevelParamGrid(**{
        f: np.concatenate([getattr(grid, f).ravel(), getattr(g2, f).ravel()])
        for f in grid.fields()})


_PERIODS = (np.array([11.0, 14.0, 40.0, 52.0]), np.array([6, 3, 9, 4]))
_KW = dict(T_base=1500.0, n_trials=8, seed=2**40 + 3,
           process=Weibull(shape=0.7))


def _sampled(**kw):
    return simulate_trajectories_ml(*_PERIODS, _sampled_grid(),
                                    **{**_KW, **kw})


def _fields(tb):
    return {f: getattr(tb, f)
            for f in engine.MultilevelTrajectoryBatch.__dataclass_fields__}


class TestSampledPath:
    @pytest.fixture(scope="class")
    def base(self):
        return _sampled()

    def test_runs_in_buckets_without_running_dry(self, base):
        caps = engine.fail_capacity_points_ml(
            _PERIODS[0], _PERIODS[1], _sampled_grid(), 1500.0,
            process=_KW["process"])
        assert len(np.unique(caps)) >= 2
        assert not base.truncated.any() and not base.gaps_exhausted.any()
        assert base.n_hard_failures.sum() > 0
        assert (base.n_hard_failures <= base.n_failures).all()

    @pytest.mark.parametrize("knob", [
        dict(n_steps=4096),                                   # one bucket
        dict(dispatch=DispatchConfig(chunk=1)),
        dict(dispatch=DispatchConfig(memory_budget_bytes=600_000)),
    ], ids=["one_bucket", "chunk_1", "trial_blocks"])
    def test_knobs_never_change_results(self, base, knob):
        other = _sampled(**knob)
        for f, a in _fields(base).items():
            np.testing.assert_array_equal(getattr(other, f), a, err_msg=f)

    def test_seed_changes_results(self, base):
        other = _sampled(seed=_KW["seed"] + 1)
        assert not np.array_equal(other.wall_time, base.wall_time)

    def test_replays_the_documented_streams(self, base):
        """The lane key ``fold_in(fold_in(PRNGKey(seed), i), t)`` gives the
        gaps through the process's traced sampler and the flags through
        ``level_loss_flags``; fed back as an explicit schedule they give
        the sampled results."""
        grid = _sampled_grid()
        caps = engine.fail_capacity_points_ml(
            _PERIODS[0], _PERIODS[1], grid, 1500.0, process=_KW["process"])
        cap = int(caps.max())
        proc = as_process(_KW["process"]).ravel()
        _, params, fn = proc.traced_sampler()
        with enable_x64():
            key = jax.random.PRNGKey(_KW["seed"])

            def lane(i, t, mean, q):
                kl = jax.random.fold_in(jax.random.fold_in(key, i), t)
                return (fn(kl, (cap,), mean, tuple(np.ravel(p)[0]
                                                     for p in params)),
                        level_loss_flags(kl, (cap,), q))
            out = [[lane(i, t, grid.mu[i], grid.q[i])
                    for t in range(_KW["n_trials"])] for i in range(4)]
            gaps = np.array([[np.asarray(g) for g, _ in row] for row in out])
            hard = np.array([[np.asarray(h) for _, h in row] for row in out])
        again = simulate_trajectories_ml(*_PERIODS, grid, T_base=1500.0,
                                         gaps=gaps, hard=hard)
        for f, a in _fields(base).items():
            np.testing.assert_array_equal(getattr(again, f), a, err_msg=f)
        assert hard.mean() == pytest.approx(0.3, abs=0.05)

    def test_exponential_default(self):
        """No process: exponential gaps at the grid's MTBF."""
        tb = simulate_trajectories_ml(*_PERIODS, _sampled_grid(),
                                      T_base=1500.0, n_trials=8, seed=1)
        assert not tb.truncated.any() and tb.n_failures.sum() > 0

    def test_half_schedule_is_refused(self):
        with pytest.raises(ValueError):
            simulate_trajectories_ml(10.0, 2, _grid(), gaps=np.ones(4))


class TestShardedSubprocess:
    """Four virtual CPU devices: the sharded sampled path gives the
    single-device results bit for bit."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, r"%(src)s")
sys.path.insert(0, r"%(tests)s")
import numpy as np
import jax
from repro.sim import DispatchConfig
from test_multilevel_engine import _fields, _sampled

one = _sampled(dispatch=DispatchConfig(shard=False))
four = _sampled()
eq = all(np.array_equal(getattr(four, f), a) for f, a in _fields(one).items())
print(json.dumps({"n_devices": jax.device_count(), "equal": eq}))
"""

    def test_four_devices_match_one(self):
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT % {
                "src": str(ROOT / "src"), "tests": str(ROOT / "tests")}],
            capture_output=True, text=True, timeout=600, cwd=str(ROOT))
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res == {"n_devices": 4, "equal": True}


class TestLowering:
    """The two-level scan reads its schedule without a gather."""

    @staticmethod
    def _lowered(build: str) -> str:
        flat = _sampled_grid()
        B, cap = flat.size, 64
        per_point = (np.full(B, 20.0), np.full(B, 3, np.int32)) + tuple(
            getattr(flat, f) for f in engine._ML_PARAMS)
        Tb = np.full(B, 900.0)
        if build == "explicit":
            f = engine._grid_fn_ml(cap + 1)
            args = per_point + (Tb, np.full((B, 3, cap), 50.0),
                                np.zeros((B, 3, cap), bool))
        else:
            _, params, fn, mean, idx, key = engine._sampler_inputs(
                as_process(Weibull(shape=0.7)).ravel(), flat, 3)
            f = engine._sampled_build_ml(fn, cap, cap + 1)
            args = per_point + (Tb, flat.q, mean, idx,
                                np.arange(3, dtype=np.uint32), key) + params
        with enable_x64():
            return jax.jit(f).lower(*args).as_text()

    @pytest.mark.parametrize("build", ["explicit", "sampled"])
    def test_no_gather_in_the_loop(self, build):
        from test_event_engine import _loop_ops

        text = self._lowered(build)
        assert _loop_ops(text, "stablehlo.gather") == 0
        assert _loop_ops(text, "stablehlo.dynamic_slice") >= 2

    def test_program_name(self):
        assert "@jit_build_ml" in self._lowered("sampled")

    @pytest.mark.parametrize("build", ["explicit", "sampled"])
    def test_outputs_packed_by_dtype(self, build):
        """A chunk comes back as one f64 and one int32 array, fields
        leading, and unpacks to the eleven named fields."""
        flat = _sampled_grid()
        B, cap, n = flat.size, 16, 3
        per_point = (np.full(B, 20.0), np.full(B, 3, np.int32)) + tuple(
            getattr(flat, f) for f in engine._ML_PARAMS)
        Tb = np.full(B, 900.0)
        if build == "explicit":
            f = engine._grid_fn_ml(cap + 1)
            args = per_point + (Tb, np.full((B, n, cap), 50.0),
                                np.zeros((B, n, cap), bool))
        else:
            _, params, fn, mean, idx, key = engine._sampler_inputs(
                as_process(Weibull(shape=0.7)).ravel(), flat, 3)
            f = engine._sampled_build_ml(fn, cap, cap + 1)
            args = per_point + (Tb, flat.q, mean, idx,
                                np.arange(n, dtype=np.uint32), key) + params
        with enable_x64():
            floats, ints = jax.eval_shape(f, *args)
        assert (floats.shape, floats.dtype) == ((5, B, n), np.float64)
        assert (ints.shape, ints.dtype) == ((6, B, n), np.int32)
        out = engine._unpack_ml((np.zeros((5, B, n)),
                                 np.ones((6, B, n), np.int32)))
        assert set(out) == {f.name for f in dataclasses.fields(
            engine.MultilevelTrajectoryBatch)} - {"energy"}
        assert out["truncated"].dtype == np.bool_
        assert out["n_ckpt2"].dtype == np.int32


def _grid_max_build_ml(proc_fn, cap_sample, cap_used, n_steps):
    """The two-level bucket program drawing every lane's gaps and flags at
    ``cap_sample`` (the grid-wide maximum capacity) and cutting both to
    the bucket's ``cap_used``.  The same program as
    ``engine._sampled_build_ml`` otherwise, expression for expression."""
    def build_ml(T, m, C1, C2, R1, R2, D1, D2, omega1, omega2, Tb, q, mean,
                 idx, t_idx, key, *params):
        def per_point(t, mm, c1, c2, r1, r2, d1, d2, o1, o2, tb, qq, mu, i,
                      *pp):
            with jax.named_scope(engine.SAMPLE_SCOPE):
                kp = jax.random.fold_in(key, i)

            def per_trial(ti):
                with jax.named_scope(engine.SAMPLE_SCOPE):
                    kl = jax.random.fold_in(kp, ti)
                    g = proc_fn(kl, (cap_sample,), mu, pp)
                    h = level_loss_flags(kl, (cap_sample,), qq)
                with jax.named_scope(engine.SCAN_SCOPE):
                    return engine._run_one_ml_event(
                        t, mm, c1, c2, r1, r2, d1, d2, o1, o2, tb,
                        g[:cap_used], h[:cap_used], n_steps)
            return jax.vmap(per_trial)(t_idx)
        return engine._pack_ml(jax.vmap(per_point)(
            T, m, C1, C2, R1, R2, D1, D2, omega1, omega2, Tb, q, mean, idx,
            *params))
    return build_ml


class TestBucketDraw:
    """Each bucket draws its lanes' gaps and flags at its own capacity, a
    prefix of each stream: the grid-maximum draw cut to the bucket's
    capacity gives the same bits."""

    @pytest.mark.parametrize("knob", [
        dict(),
        dict(dispatch=DispatchConfig(chunk=1)),
        dict(dispatch=DispatchConfig(memory_budget_bytes=30_000)),
    ], ids=["default", "chunk_1", "trial_blocks"])
    def test_bit_identical_to_grid_max_draw(self, knob, monkeypatch):
        from repro.sim import dispatch

        caps = engine.fail_capacity_points_ml(
            _PERIODS[0], _PERIODS[1], _sampled_grid(), 1500.0,
            process=_KW["process"])
        assert len(np.unique(caps)) >= 3
        cap_sample = int(caps.max())
        dispatch._RUNNERS.clear()
        own = _sampled(**knob)
        with monkeypatch.context() as m:
            m.setattr(engine, "_sampled_build_ml",
                      lambda fn, cap, n: _grid_max_build_ml(
                          fn, cap_sample, cap, n))
            dispatch._RUNNERS.clear()
            grid_max = _sampled(**knob)
        dispatch._RUNNERS.clear()
        for f, a in _fields(grid_max).items():
            np.testing.assert_array_equal(getattr(own, f), a, err_msg=f)
        assert own.n_hard_failures.any()


class TestBucketDrawLowering:
    """A two-level bucket's program holds no tensor of the grid maximum's
    length: its gaps and flag uniforms are the bucket's capacity long."""

    CAP, GRID_MAX = 128, 4096

    @staticmethod
    def _args():
        flat = _sampled_grid()
        B = flat.size
        _, params, fn, mean, idx, key = engine._sampler_inputs(
            as_process(Weibull(shape=0.7)).ravel(), flat, 3)
        per_point = (np.full(B, 20.0), np.full(B, 3, np.int32)) + tuple(
            getattr(flat, f) for f in engine._ML_PARAMS)
        return fn, per_point + (np.full(B, 900.0), flat.q, mean, idx,
                                np.arange(2, dtype=np.uint32), key) + params

    def _lowered(self, grid_max: bool) -> str:
        from test_event_engine import _tensor_dims

        fn, args = self._args()
        n = self.CAP + 1
        f = (_grid_max_build_ml(fn, self.GRID_MAX, self.CAP, n) if grid_max
             else engine._sampled_build_ml(fn, self.CAP, n))
        with enable_x64():
            return _tensor_dims(jax.jit(f).lower(*args).as_text())

    def test_no_grid_max_tensor(self):
        dims = self._lowered(grid_max=False)
        assert self.GRID_MAX not in dims
        assert self.CAP in dims

    def test_grid_max_draw_shows_its_tensor(self):
        assert self.GRID_MAX in self._lowered(grid_max=True)

    def test_every_draw_at_bucket_capacity(self, monkeypatch):
        """The gap sampler and the flag stream both ask for the bucket's
        capacity: not a draw short of it, not one past it."""
        fn, args = self._args()
        gaps, flags = [], []

        def spy(key, size, mean, params):
            gaps.append(tuple(size))
            return fn(key, size, mean, params)

        def spy_flags(key, size, q):
            flags.append(tuple(size))
            return level_loss_flags(key, size, q)
        monkeypatch.setattr(engine, "level_loss_flags", spy_flags)
        with enable_x64():
            jax.eval_shape(engine._sampled_build_ml(spy, self.CAP,
                                                    self.CAP + 1), *args)
        assert gaps and set(gaps) == {(self.CAP,)}
        assert flags and set(flags) == {(self.CAP,)}
