"""Event-kernel engine: cross-engine parity, on-device sampling, budgets.

The contract under test (PR 4 acceptance):

- event kernel == step kernel == scalar oracle, trajectory-for-trajectory
  AND trial-mean-for-trial-mean, bit-for-bit, under a shared host-supplied
  DYADIC gap schedule (every quantity exactly representable: the closed
  forms and the step accumulations then perform exact arithmetic), for
  every FailureProcess;
- the same at ~1e-12 relative tolerance for arbitrary float schedules;
- the on-device threefry sampler is deterministic in the seed and
  distribution-identical to the host sampler;
- per-point power-of-two budget bucketing dispatches each grid point at
  its own scan length without changing results.
"""
import math

import numpy as np
import pytest

from repro.core import (CheckpointParams, EXASCALE_POWER_RHO55,
                        Exponential, LogNormal, TraceReplay, Weibull,
                        fig12_checkpoint, simulate_once)
from repro.core import optimal
from repro.sim import ParamGrid, simulate_candidates, simulate_trajectories
from repro.sim.engine import (fail_capacity_points, presample_gaps,
                              presample_gaps_device, step_budget_points)

CK = fig12_checkpoint(300.0)
PW = EXASCALE_POWER_RHO55

PROCESSES = [
    Exponential(),
    Weibull(shape=0.6),
    LogNormal(sigma=1.0),
    TraceReplay(gaps=[40.0, 500.0, 120.0, 90.0, 800.0, 33.0]),
]

#: dyadic rounding grid: coarse enough that boundary coincidences with the
#: engines' 1e-12 completion slack are impossible, fine enough to keep the
#: schedule's distribution intact.
_DYADIC = 2.0 ** 16


def _dyadic(gaps):
    return np.maximum(np.round(gaps * _DYADIC) / _DYADIC, 1.0 / _DYADIC)


def _fields(tb):
    return {f: getattr(tb, f) for f in
            ("wall_time", "energy", "work_executed", "io_time", "down_time",
             "n_failures", "n_checkpoints", "truncated", "gaps_exhausted")}


class TestCrossEngineParity:
    """event == step == scalar under shared host schedules."""

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_bitexact_on_dyadic_schedule(self, proc):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = _dyadic(presample_gaps(grid, 8, 128, seed=9, process=proc))
        ev = simulate_trajectories(60.0, grid, T_base=3000.0, gaps=gaps,
                                   engine_kind="event")
        st = simulate_trajectories(60.0, grid, T_base=3000.0, gaps=gaps,
                                   engine_kind="step")
        assert not ev.truncated.any() and not st.truncated.any()
        for name, a in _fields(ev).items():
            np.testing.assert_array_equal(a, getattr(st, name),
                                          err_msg=f"{proc.name}/{name}")
        # trial means bit-for-bit (the acceptance criterion's phrasing)
        assert np.array_equal(ev.wall_time.mean(axis=-1),
                              st.wall_time.mean(axis=-1))
        assert np.array_equal(ev.energy.mean(axis=-1),
                              st.energy.mean(axis=-1))
        # ...and the scalar oracle agrees exactly on the same schedules
        for k in range(gaps.shape[1]):
            ref = simulate_once(60.0, CK, PW, 3000.0,
                                np.random.default_rng(0), gaps=gaps[0, k])
            assert float(ev.wall_time[0, k]) == ref.wall_time
            assert float(ev.energy[0, k]) == ref.energy
            assert float(ev.io_time[0, k]) == ref.io_time
            assert float(ev.work_executed[0, k]) == ref.work_executed
            assert int(ev.n_failures[0, k]) == ref.n_failures
            assert int(ev.n_checkpoints[0, k]) == ref.n_checkpoints

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_tolerance_on_raw_schedule(self, proc):
        """Arbitrary float schedules: closed-form vs accumulated rounding
        differs only in the last few ulps."""
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = presample_gaps(grid, 6, 128, seed=3, process=proc)
        ev = simulate_trajectories(53.3, grid, T_base=3000.0, gaps=gaps,
                                   engine_kind="event")
        st = simulate_trajectories(53.3, grid, T_base=3000.0, gaps=gaps,
                                   engine_kind="step")
        for name in ("wall_time", "energy", "work_executed", "io_time"):
            np.testing.assert_allclose(getattr(ev, name), getattr(st, name),
                                       rtol=1e-12, err_msg=name)
        np.testing.assert_array_equal(ev.n_failures, st.n_failures)
        np.testing.assert_array_equal(ev.n_checkpoints, st.n_checkpoints)

    def test_parameter_batch_parity(self):
        """Mixed (ckpt, power) batch + per-point dyadic schedules."""
        from repro.sim import get_scenario, grid_from_scenarios
        scens = [get_scenario("fig12", mu_min=120.0),
                 get_scenario("exascale_rho7", mu_min=300.0)]
        grid = grid_from_scenarios(scens)
        rng = np.random.default_rng(5)
        gaps = _dyadic(rng.exponential(1.0, size=(2, 4, 96))
                       * grid.mu[:, None, None])
        T = np.array([40.0, 60.0])
        ev = simulate_trajectories(T, grid, T_base=500.0, gaps=gaps,
                                   engine_kind="event")
        st = simulate_trajectories(T, grid, T_base=500.0, gaps=gaps,
                                   engine_kind="step")
        for name, a in _fields(ev).items():
            np.testing.assert_array_equal(a, getattr(st, name), err_msg=name)

    def test_exhaustion_flags_match_step(self):
        """A schedule that runs dry flags gaps_exhausted identically in
        both kernels (the step kernel's one-draw-per-stretch accounting)."""
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = np.array([50.0, 70.0])        # far too short for T_base=4000
        ev = simulate_trajectories(60.0, grid, T_base=4000.0, gaps=gaps,
                                   engine_kind="event")
        st = simulate_trajectories(60.0, grid, T_base=4000.0, gaps=gaps,
                                   engine_kind="step")
        assert ev.gaps_exhausted.all() and st.gaps_exhausted.all()
        np.testing.assert_array_equal(ev.wall_time, st.wall_time)

    def test_event_truncates_on_tiny_budget(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        tb = simulate_trajectories(60.0, grid, T_base=50000.0, n_trials=4,
                                   seed=0, n_steps=2, engine_kind="event")
        assert tb.truncated.any()

    def test_unknown_engine_kind_raises(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        with pytest.raises(ValueError, match="engine_kind"):
            simulate_trajectories(60.0, grid, T_base=100.0, n_trials=2,
                                  engine_kind="warp")


class TestDeviceSampling:
    """On-device threefry sampling: determinism + distribution parity."""

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_fixed_seed_determinism(self, proc):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        a = np.asarray(presample_gaps_device(grid, 4, 32, seed=7,
                                             process=proc))
        b = np.asarray(presample_gaps_device(grid, 4, 32, seed=7,
                                             process=proc))
        c = np.asarray(presample_gaps_device(grid, 4, 32, seed=8,
                                             process=proc))
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)
        assert (a > 0).all() and np.isfinite(a).all()

    @pytest.mark.parametrize("proc", [Exponential(), Weibull(shape=0.6),
                                      LogNormal(sigma=1.0)],
                             ids=lambda p: p.name)
    def test_device_matches_host_distribution(self, proc):
        """Same distribution as the numpy sampler: mean and CV agree to
        CLT tolerance (different streams by design)."""
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        n = 40_000
        dev = np.asarray(presample_gaps_device(grid, 1, n, seed=0,
                                               process=proc)).ravel()
        host = presample_gaps(grid, 1, n, seed=0, process=proc).ravel()
        cv = float(np.max(np.asarray(proc.gap_cv())))
        tol = 6.0 * cv / math.sqrt(n)
        assert abs(dev.mean() / host.mean() - 1.0) < 2.0 * tol
        assert abs(dev.std() / dev.mean() - cv) < 0.1 * max(cv, 1.0)

    def test_trace_replay_device_rows_are_rotations(self):
        tr = TraceReplay(gaps=[1.0, 2.0, 3.0, 6.0])
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        g = np.asarray(presample_gaps_device(grid, 4, 9, seed=2,
                                             process=TraceReplay(
                                                 gaps=[1.0, 2.0, 3.0, 6.0],
                                                 rescale=False)))[0]
        base = np.array([1.0, 2.0, 3.0, 6.0])
        for row in g:
            assert any(np.allclose(row, np.resize(np.roll(base, -s), 9))
                       for s in range(4)), row
        # rescale=True anchors the replay to the grid's mu
        g2 = np.asarray(presample_gaps_device(grid, 64, 16, seed=2,
                                              process=tr))
        assert g2.mean() == pytest.approx(CK.mu, rel=0.25)

    def test_auto_sampled_trajectories_deterministic(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        kw = dict(T_base=2000.0, n_trials=16, process=Weibull(shape=0.7))
        a = simulate_trajectories(60.0, grid, seed=11, **kw)
        b = simulate_trajectories(60.0, grid, seed=11, **kw)
        c = simulate_trajectories(60.0, grid, seed=12, **kw)
        np.testing.assert_array_equal(a.wall_time, b.wall_time)
        assert not np.array_equal(a.wall_time, c.wall_time)

    def test_host_fallback_for_unknown_process(self):
        """A process without a jax sampler still runs (host numpy gate)."""
        class Odd(Exponential):
            name = "odd"

            def sample_gaps(self, key, size, mean=None):
                raise NotImplementedError("no device sampler")
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        tb = simulate_trajectories(60.0, grid, T_base=1000.0, n_trials=4,
                                   seed=0, process=Odd())
        assert not tb.truncated.any()


class TestBudgetBuckets:
    """Per-point pow2 budgets + bucketed dispatch."""

    def _mixed_grid(self):
        base = ParamGrid.from_params(CK, PW)
        mus = np.array([80.0, 3000.0])       # ~40x failure-rate spread
        return ParamGrid(**{f: (mus if f == "mu"
                                else np.broadcast_to(v, (2,)))
                            for f, v in base.fields().items()})

    def test_budgets_are_per_point_pow2(self):
        grid = self._mixed_grid()
        caps = fail_capacity_points(60.0, grid, 2000.0,
                                    process=Weibull(shape=0.7))
        steps = step_budget_points(60.0, grid, 2000.0,
                                   process=Weibull(shape=0.7))
        for arr in (caps, steps):
            assert arr.shape == (2,)
            assert all((int(v) & (int(v) - 1)) == 0 for v in arr)  # pow2
        # the mixed grid really does split: the fragile point pays more
        assert caps[0] > caps[1]
        assert steps[0] > steps[1]

    def test_budget_knobs_never_change_the_randomness(self):
        """The schedule is sampled once for the whole grid and sliced per
        bucket, so scan-length knobs are PURE performance knobs: explicit
        n_steps (single bucket) and the default bucketed dispatch give
        bit-identical results, and the step kernel consumes the very same
        auto-sampled schedules as the event kernel."""
        grid = self._mixed_grid()
        proc = Weibull(shape=0.7)
        kw = dict(T_base=2000.0, n_trials=8, seed=4, process=proc)
        base = simulate_trajectories(60.0, grid, **kw)          # 2 buckets
        big = simulate_trajectories(60.0, grid, n_steps=8192, **kw)
        for name, a in _fields(base).items():
            np.testing.assert_array_equal(a, getattr(big, name),
                                          err_msg=name)
        st = simulate_trajectories(60.0, grid, engine_kind="step", **kw)
        np.testing.assert_array_equal(base.n_failures, st.n_failures)
        np.testing.assert_allclose(base.wall_time, st.wall_time,
                                   rtol=1e-12)

    def test_array_shape_process_buckets(self):
        """Array-valued Weibull shape: per-point cv feeds per-point
        budgets and the per-bucket process subsets line up."""
        base = ParamGrid.from_params(CK, PW)
        grid = ParamGrid(**{f: np.broadcast_to(v, (3,))
                            for f, v in base.fields().items()})
        proc = Weibull(shape=np.array([0.5, 1.0, 2.0]))
        caps = fail_capacity_points(60.0, grid, 2000.0, process=proc)
        # per-point cv: the k=0.5 row (cv ~ 2.2) pays a larger capacity
        # than the wear-out k=2 row (cv ~ 0.5) — the old np.max would have
        # charged every row the k=0.5 budget
        assert caps[0] > caps[2]
        tb = simulate_trajectories(60.0, grid, T_base=2000.0, n_trials=32,
                                   seed=0, process=proc)
        assert not tb.truncated.any() and not tb.gaps_exhausted.any()


class TestCandidateAxis:
    """simulate_candidates: shared-schedule candidate vmap."""

    def test_matches_per_row_runs(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = presample_gaps(grid, 6, 128, seed=1, process=Weibull(0.7))
        Ts = np.array([40.0, 60.0, 90.0])
        cand = simulate_candidates(Ts, grid, T_base=2000.0, gaps=gaps)
        assert cand.wall_time.shape == (3, 1, 6)
        for m, T in enumerate(Ts):
            row = simulate_trajectories(T, grid, T_base=2000.0, gaps=gaps)
            np.testing.assert_array_equal(cand.wall_time[m], row.wall_time)
            np.testing.assert_array_equal(cand.energy[m], row.energy)

    def test_grid_shaped_candidates(self):
        base = ParamGrid.from_params(CK, PW)
        grid = ParamGrid(**{f: np.broadcast_to(v, (2,))
                            for f, v in base.fields().items()})
        gaps = presample_gaps(grid, 4, 128, seed=2)
        Ts = np.array([[40.0, 50.0], [60.0, 70.0]])      # (M, B)
        cand = simulate_candidates(Ts, grid, T_base=1000.0, gaps=gaps)
        assert cand.wall_time.shape == (2, 2, 4)
        solo = simulate_trajectories(Ts[1], grid, T_base=1000.0, gaps=gaps)
        np.testing.assert_array_equal(cand.wall_time[1], solo.wall_time)

    def test_mc_surrogate_engines_agree(self):
        """The MC solvers land on the same optimum through either kernel
        (same CRN schedules, same surrogate, different arithmetic path)."""
        sur_e = optimal.MCSurrogate(CK, PW, Weibull(shape=0.7),
                                    T_base=1500.0, n_trials=48, seed=0,
                                    engine_kind="event")
        sur_s = optimal.MCSurrogate(CK, PW, Weibull(shape=0.7),
                                    T_base=1500.0, n_trials=48, seed=0,
                                    engine_kind="step")
        t_e = sur_e.argmin("time")
        t_s = sur_s.argmin("time")
        assert t_e == pytest.approx(t_s, rel=5e-3)

    def test_period_guard(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        with pytest.raises(ValueError, match="period too short"):
            simulate_candidates(np.array([4.0]), grid, T_base=100.0,
                                n_trials=2)

    def test_float32_device_schedule_is_upcast(self):
        """Regression: a schedule parked on device OUTSIDE an x64 context
        arrives float32; the engine must upcast it instead of aborting
        the scan with a carry-dtype error."""
        import jax.numpy as jnp
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = presample_gaps(grid, 4, 64, seed=0)
        dev = jnp.asarray(gaps)              # jax default config: float32
        got = simulate_trajectories(60.0, grid, T_base=1000.0, gaps=dev)
        want = simulate_trajectories(60.0, grid, T_base=1000.0,
                                     gaps=np.asarray(dev, np.float64))
        np.testing.assert_array_equal(got.wall_time, want.wall_time)


class TestEventEngineStatistics:
    def test_matches_closed_form_model(self):
        """Auto-sampled exponential trajectories agree with the paper's
        first-order expectation at moderate failure rates."""
        from repro.core import model
        ck = CheckpointParams(C=10, R=10, D=1, mu=1000.0, omega=0.5)
        grid = ParamGrid.from_params(ck, PW).reshape((1,))
        tb = simulate_trajectories(60.0, grid, T_base=3000.0,
                                   n_trials=600, seed=0)
        want = float(model.time_final(60.0, ck, 3000.0))
        got = float(tb.wall_time.mean())
        se = float(tb.wall_time.std(ddof=1) / math.sqrt(600))
        assert abs(got - want) < 4.0 * se + 0.01 * want


def _gather_event(T, C, R, D, omega, T_base, gaps, n_steps):
    """The event kernel with its gap read in gather form: each lane reads
    ``gaps[n_fail]``, a per-lane index.  The same step arithmetic as
    ``engine._run_one_event``, expression for expression."""
    import jax.numpy as jnp
    from jax import lax
    from repro.sim.engine import _EPS

    f64 = gaps.dtype
    n_gaps = gaps.shape[0]
    Tc = T - C
    w = T - (1.0 - omega) * C
    omega_safe = jnp.where(omega > 0.0, omega, 1.0)
    init = ((jnp.zeros((), f64),) * 5 + (jnp.zeros((), jnp.int32),) * 2
            + (jnp.zeros((), jnp.bool_),) * 2)

    def step(carry, _):
        (wall, committed, work_exec, io_time, down_time,
         n_fail, n_ckpt, used_inf, done) = carry
        in_range = n_fail < n_gaps
        g = jnp.where(in_range, gaps[jnp.minimum(n_fail, n_gaps - 1)],
                      jnp.inf)
        rem = T_base - committed
        j = jnp.maximum(jnp.floor((rem - _EPS) / w), 0.0)
        r = rem - j * w
        rr = r - Tc
        t_in = jnp.where(rr > 0.0, Tc + rr / omega_safe, r)
        t_fin = j * T + t_in
        complete = t_fin < g
        wall_a = wall + t_fin
        work_a = work_exec + rem
        io_a = io_time + j * C + jnp.maximum(rr, 0.0) / omega_safe
        s = jnp.where(jnp.isfinite(g), g, 0.0)
        k = jnp.floor(s / T)
        k = jnp.where((k > 0.0) & (k * T >= s), k - 1.0, k)
        u = s - k * T
        uc = u - Tc
        work_b = work_exec + k * w + jnp.where(uc > 0.0,
                                               Tc + omega * uc, u)
        io_b = io_time + k * C + jnp.maximum(uc, 0.0) + R
        wall_b = (wall + s) + D + R
        committed_b = jnp.where(k >= 1.0,
                                committed + (k - 1.0) * w + Tc, committed)

        def sel(a_val, b_val):
            return jnp.where(complete, a_val, b_val)

        new = (sel(wall_a, wall_b),
               sel(committed, committed_b),
               sel(work_a, work_b),
               sel(io_a, io_b),
               sel(down_time, down_time + D),
               sel(n_fail, n_fail + 1).astype(jnp.int32),
               (n_ckpt + sel(j, k).astype(jnp.int32)).astype(jnp.int32),
               jnp.logical_or(used_inf, ~in_range),
               jnp.logical_or(done, complete))
        keep = lambda old, upd: jnp.where(done, old, upd)
        return tuple(keep(o, u) for o, u in zip(carry, new)), None

    final, _ = lax.scan(step, init, None, length=n_steps)
    (wall, _committed, work_exec, io_time, down_time,
     n_fail, n_ckpt, used_inf, done) = final
    return {"wall_time": wall, "work_executed": work_exec,
            "io_time": io_time, "down_time": down_time,
            "n_failures": n_fail, "n_checkpoints": n_ckpt,
            "truncated": ~done, "gaps_exhausted": used_inf}


@pytest.fixture
def gather_read(monkeypatch):
    """Run a call once through the engine as it is and once with the
    gather-form kernel in its place, on runners compiled for each."""
    from repro.sim import dispatch, engine

    def both(call):
        dispatch._RUNNERS.clear()
        slab = call()
        with monkeypatch.context() as m:
            m.setitem(engine._KERNELS, "event", _gather_event)
            dispatch._RUNNERS.clear()
            gather = call()
        dispatch._RUNNERS.clear()
        return slab, gather
    return both


def _mixed_mu_grid():
    base = ParamGrid.from_params(CK, PW)
    mus = np.array([60.0, 400.0, 3000.0])
    return ParamGrid(**{f: (mus if f == "mu" else np.broadcast_to(v, (3,)))
                        for f, v in base.fields().items()})


#: (schedule, capacity, n_steps) per path: the sampled builds at their own
#: budgets; explicit schedules cut short (n_steps below the capacity),
#: padded by one step (the default capacity + 1), and padded far past the
#: capacity so that lanes run the schedule dry.
_SLAB_PATHS = {
    "sampled": (None, None, None),
    "explicit_cut": ("explicit", 64, 4),
    "explicit_default": ("explicit", 64, None),
    "explicit_dry": ("explicit", 12, 300),
    "cand_sampled": (None, None, None),
    "cand_explicit_dry": ("explicit", 12, 300),
}


class TestSlabRead:
    """The event scan reads gap ``i`` at step ``i`` from a capacity-major
    slab; the gather-form read of ``gaps[n_fail]`` gives the same bits."""

    @pytest.mark.parametrize("path", sorted(_SLAB_PATHS))
    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_bit_identical_to_gather_read(self, gather_read, proc, path):
        grid = _mixed_mu_grid()
        sched, cap, n_steps = _SLAB_PATHS[path]
        kw = dict(T_base=4000.0, n_steps=n_steps, engine_kind="event")
        if sched is None:
            kw.update(n_trials=6, seed=2**40 + 9, process=proc)
        else:
            kw["gaps"] = presample_gaps(grid, 6, cap, seed=4, process=proc)
        if path.startswith("cand"):
            Ts = np.array([[40.0, 60.0, 90.0], [55.0, 80.0, 130.0]])
            slab, gather = gather_read(
                lambda: simulate_candidates(Ts, grid, **kw))
        else:
            slab, gather = gather_read(
                lambda: simulate_trajectories(60.0, grid, **kw))
        for name, a in _fields(gather).items():
            np.testing.assert_array_equal(getattr(slab, name), a,
                                          err_msg=name)
        if path == "explicit_cut":
            assert slab.truncated.any()
        if path.endswith("dry"):
            assert slab.gaps_exhausted.any() and not slab.gaps_exhausted.all()

    @pytest.mark.parametrize("n_steps", [0, 1, 33, 40])
    def test_kernel_at_any_step_count(self, n_steps):
        """The kernel itself, vmapped over lanes: no step at all (every
        lane truncated, nothing accumulated), one step, the capacity + 1,
        and past it."""
        import jax
        from jax import enable_x64
        from repro.sim.engine import _KERNELS

        rng = np.random.default_rng(7)
        gaps = rng.exponential(150.0, size=(16, 32))
        T = np.linspace(30.0, 120.0, 16)
        args = (T, 10.0, 8.0, 1.0, 0.5, 900.0)
        axes = (0,) + (None,) * 5 + (0,)
        with enable_x64():
            slab = jax.jit(jax.vmap(
                lambda *a: _KERNELS["event"](*a, n_steps), axes))(
                    *args, gaps)
            gather = jax.jit(jax.vmap(
                lambda *a: _gather_event(*a, n_steps), axes))(*args, gaps)
        for name, a in gather.items():
            np.testing.assert_array_equal(np.asarray(slab[name]),
                                          np.asarray(a), err_msg=name)
        if n_steps == 0:
            assert np.asarray(slab["truncated"]).all()
            assert not np.asarray(slab["wall_time"]).any()
        if n_steps == 40:
            assert not np.asarray(slab["truncated"]).any()


def _loop_ops(text: str, op: str) -> int:
    """How many ``op`` the ``stablehlo.while`` loops of a lowered module
    run: the loops' regions, and the bodies of the functions they call."""
    import re

    funcs = {}
    heads = list(re.finditer(r"func\.func (?:\w+ )?@([\w$.-]+)", text))
    for h, nxt in zip(heads, heads[1:] + [None]):
        funcs[h.group(1)] = text[h.end():nxt.start() if nxt else len(text)]

    def region(start: int) -> str:
        depth, blocks, i = 0, 0, text.index("{", start)
        for i in range(i, len(text)):
            depth += {"{": 1, "}": -1}.get(text[i], 0)
            if depth == 0 and text[i] == "}":
                blocks += 1
                if blocks == 2:              # the cond and the do block
                    return text[start:i]
        return text[start:]

    def count(body: str, seen: frozenset) -> int:
        n = body.count(op)
        for name in re.findall(r"call @([\w$.-]+)", body):
            if name in funcs and name not in seen:
                n += count(funcs[name], seen | {name})
        return n

    loops = [m.start() for m in re.finditer(r"stablehlo\.while", text)]
    assert loops, "no loop in the lowered program"
    return sum(count(region(s), frozenset()) for s in loops)


class TestSlabLowering:
    """No gather runs inside the event scan's loop: the schedule is laid
    out capacity-major once, before it, and each step slices one row."""

    @staticmethod
    def _lowered(runner: str) -> str:
        import jax
        from jax import enable_x64
        from repro.sim import engine

        B, trials, cap = 2, 3, 128
        grid_args = (np.full(B, 10.0), np.full(B, 8.0), np.ones(B),
                     np.full(B, 0.5), np.full(B, 900.0))
        T = np.full(B, 60.0)
        gaps = np.full((B, trials, cap), 100.0)
        if runner == "_grid_fn":
            f, args = engine._grid_fn(cap + 1, "event"), (T,)
        else:
            f, args = engine._cand_fn(cap + 1, "event"), (
                np.stack([T, 2 * T]),)
        with enable_x64():
            return jax.jit(f).lower(*args, *grid_args, gaps).as_text()

    @pytest.mark.parametrize("runner", ["_grid_fn", "_cand_fn"])
    def test_no_gather_in_the_loop(self, runner):
        text = self._lowered(runner)
        assert _loop_ops(text, "stablehlo.gather") == 0
        assert _loop_ops(text, "stablehlo.dynamic_slice") >= 1

    @pytest.mark.parametrize("runner", ["_grid_fn", "_cand_fn"])
    def test_gather_form_shows_its_gathers(self, runner, monkeypatch):
        """The check sees a per-lane gather where there is one."""
        from repro.sim import engine

        monkeypatch.setitem(engine._KERNELS, "event", _gather_event)
        assert _loop_ops(self._lowered(runner), "stablehlo.gather") >= 1


def _grid_max_build(proc_fn, cap_sample, cap_used, n_steps, kind,
                    policy=None):
    """The sampled bucket program drawing every lane's schedule at
    ``cap_sample`` (the grid-wide maximum capacity) and cutting it to the
    bucket's ``cap_used``.  The same program as
    ``engine._sampled_build`` otherwise, expression for expression."""
    import jax
    from jax import lax
    from repro.sim.engine import (_KERNELS, SAMPLE_SCOPE, SCAN_SCOPE,
                                  _grid_fn)

    if kind == "pallas":
        run_grid = _grid_fn(n_steps, kind, policy)

        def build(T, C, R, D, omega, Tb, mean, idx, t_idx, key, *params):
            def sample_point(m, i, *pp):
                kp = jax.random.fold_in(key, i)

                def sample_trial(ti):
                    return proc_fn(jax.random.fold_in(kp, ti),
                                   (cap_sample,), m, pp)
                return jax.vmap(sample_trial)(t_idx)
            with jax.named_scope(SAMPLE_SCOPE):
                gaps = lax.map(lambda a: sample_point(*a),
                               (mean, idx) + tuple(params))
            return run_grid(T, C, R, D, omega, Tb, gaps[:, :, :cap_used])
        return build
    kernel = _KERNELS[kind]

    def build(T, C, R, D, omega, Tb, mean, idx, t_idx, key, *params):
        def per_point(t, c, r, d, o, tb, m, i, *pp):
            with jax.named_scope(SAMPLE_SCOPE):
                kp = jax.random.fold_in(key, i)

            def per_trial(ti):
                with jax.named_scope(SAMPLE_SCOPE):
                    g = proc_fn(jax.random.fold_in(kp, ti), (cap_sample,),
                                m, pp)
                with jax.named_scope(SCAN_SCOPE):
                    return kernel(t, c, r, d, o, tb, g[:cap_used], n_steps)
            return jax.vmap(per_trial)(t_idx)
        return jax.vmap(per_point)(T, C, R, D, omega, Tb, mean, idx,
                                   *params)
    return build


@pytest.fixture
def grid_max_draw(monkeypatch):
    """Run a call once through the engine as it is and once with every
    bucket drawing at ``cap_sample``, on runners compiled for each."""
    from repro.sim import dispatch, engine

    def both(call, cap_sample):
        dispatch._RUNNERS.clear()
        own = call()
        with monkeypatch.context() as m:
            m.setattr(engine, "_sampled_build",
                      lambda fn, cap, n, kind, policy=None: _grid_max_build(
                          fn, cap_sample, cap, n, kind, policy))
            dispatch._RUNNERS.clear()
            grid_max = call()
        dispatch._RUNNERS.clear()
        return own, grid_max
    return both


#: dispatch settings of the bucket-draw comparison: one chunk a bucket,
#: one point a chunk, and a budget that cuts the larger buckets' trials
#: into blocks.
_DRAW_DISPATCH = {
    "default": None,
    "chunk_1": dict(chunk=1),
    "trial_blocks": dict(memory_budget_bytes=20_000),
}


class TestBucketDraw:
    """Each bucket draws its lanes' schedules at its own capacity, a
    prefix of each lane's stream: the grid-maximum draw cut to the
    bucket's capacity gives the same bits."""

    @staticmethod
    def _compare(grid_max_draw, proc, kind, dispatch):
        from repro.sim import DispatchConfig

        grid = _mixed_mu_grid()
        caps = fail_capacity_points(np.full(3, 60.0), grid.ravel(),
                                    np.full(3, 4000.0), process=proc)
        assert len(np.unique(caps)) == 3
        cfg = _DRAW_DISPATCH[dispatch]
        own, grid_max = grid_max_draw(
            lambda: simulate_trajectories(
                60.0, grid, T_base=4000.0, n_trials=6, seed=2**40 + 9,
                process=proc, engine_kind=kind,
                dispatch=None if cfg is None else DispatchConfig(**cfg)),
            int(caps.max()))
        for name, a in _fields(grid_max).items():
            np.testing.assert_array_equal(getattr(own, name), a,
                                          err_msg=name)
        assert own.n_failures.any()

    @pytest.mark.parametrize("dispatch", sorted(_DRAW_DISPATCH))
    @pytest.mark.parametrize("kind", ["event", "step", "pallas"])
    def test_bit_identical_to_grid_max_draw(self, grid_max_draw, kind,
                                            dispatch):
        self._compare(grid_max_draw, Weibull(shape=0.6), kind, dispatch)

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_every_process(self, grid_max_draw, proc):
        self._compare(grid_max_draw, proc, "event", "default")


def _tensor_dims(text: str) -> set:
    """Every dimension of every ranked tensor type in a lowered module."""
    import re

    return {int(d) for shape in re.findall(r"tensor<((?:\d+x)+)", text)
            for d in shape.split("x") if d}


class TestBucketDrawLowering:
    """A bucket's program holds no tensor of the grid maximum's length:
    its random bits and f64 draws are the bucket's own capacity long."""

    CAP, GRID_MAX = 128, 4096

    @staticmethod
    def _args():
        from repro.core.failures import as_process
        from repro.sim import engine

        flat = _mixed_mu_grid().ravel()
        _, params, fn, mean, idx, key = engine._sampler_inputs(
            as_process(Weibull(shape=0.7)).ravel(), flat, 3)
        B = flat.size
        args = (np.full(B, 60.0), flat.C, flat.R, flat.D, flat.omega,
                np.full(B, 4000.0), mean, idx, np.arange(2, dtype=np.uint32),
                key) + params
        return fn, args

    def _lowered(self, kind: str, grid_max: bool) -> str:
        import jax
        from jax import enable_x64
        from repro.sim import engine

        fn, args = self._args()
        n = self.CAP + 1
        f = (_grid_max_build(fn, self.GRID_MAX, self.CAP, n, kind)
             if grid_max else engine._sampled_build(fn, self.CAP, n, kind))
        with enable_x64():
            return jax.jit(f).lower(*args).as_text()

    @pytest.mark.parametrize("kind", ["event", "step", "pallas"])
    def test_no_grid_max_tensor(self, kind):
        dims = _tensor_dims(self._lowered(kind, grid_max=False))
        assert self.GRID_MAX not in dims
        assert self.CAP in dims

    @pytest.mark.parametrize("kind", ["event", "step", "pallas"])
    def test_grid_max_draw_shows_its_tensor(self, kind):
        """The check sees the grid maximum's length where it is drawn."""
        assert self.GRID_MAX in _tensor_dims(self._lowered(kind,
                                                           grid_max=True))

    @pytest.mark.parametrize("kind", ["event", "step", "pallas"])
    def test_every_draw_at_bucket_capacity(self, kind):
        """Every call of the sampler asks for the bucket's capacity: not a
        gap short of it, not one past it."""
        import jax
        from jax import enable_x64
        from repro.sim import engine

        fn, args = self._args()
        sizes = []

        def spy(key, size, mean, params):
            sizes.append(tuple(size))
            return fn(key, size, mean, params)
        with enable_x64():
            jax.eval_shape(engine._sampled_build(spy, self.CAP,
                                                 self.CAP + 1, kind), *args)
        assert sizes and set(sizes) == {(self.CAP,)}
