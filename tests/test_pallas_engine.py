"""Pallas event engine + per-backend precision policy.

The contract under test (the accelerator-native sweep engine PR):

- ``engine_kind="pallas"`` (interpret mode on CPU) == the lax.scan event
  engine, field-for-field BIT-FOR-BIT in f64, for every FailureProcess,
  on host-supplied dyadic schedules AND on the auto-sampled device path
  (the pallas sampled-build must fold the identical per-point/per-trial
  keys), plain and candidate-axis;
- the precision/backend knobs are bit-exact no-ops at a fixed seed on
  CPU (``precision="f64"``, ``DispatchConfig(backend="cpu")``,
  ``$REPRO_ENGINE_KIND`` deferral);
- the ``compensated_f32`` policy passes its DOCUMENTED parity gates
  against the f64 oracle per scenario family: objective at the served
  optimum re-evaluated in f64 within ``objective_tol`` (1e-6 rel),
  argmin period within ``argmin_rtol`` (1e-3 rel);
- the advisor threads the policy through its solves and folds
  ``objective_tol`` into every certified bound.
"""
import numpy as np
import pytest

from repro.core import (EXASCALE_POWER_RHO55, Exponential, LogNormal,
                        TraceReplay, Weibull, fig12_checkpoint)
from repro.sim import (COMPENSATED_F32, F64, DispatchConfig, ParamGrid,
                       arch_grid, backend_info, buddy_ratio_grid,
                       evaluate_grid, evaluate_multilevel_grid, mu_rho_grid,
                       resolve_precision, simulate_candidates,
                       simulate_trajectories)
from repro.sim.engine import presample_gaps, resolve_engine_kind
from repro.sim.precision import compensated_sum, resolve, two_sum
from repro.sim.sweep import energy_final_batched, time_final_batched

pytestmark = pytest.mark.pallas

CK = fig12_checkpoint(300.0)
PW = EXASCALE_POWER_RHO55

PROCESSES = [
    Exponential(),
    Weibull(shape=0.6),
    LogNormal(sigma=1.0),
    TraceReplay(gaps=[40.0, 500.0, 120.0, 90.0, 800.0, 33.0]),
]

#: same dyadic rounding grid as test_event_engine (see its docstring).
_DYADIC = 2.0 ** 16

FIELDS = ("wall_time", "energy", "work_executed", "io_time", "down_time",
          "n_failures", "n_checkpoints", "truncated", "gaps_exhausted")


def _dyadic(gaps):
    return np.maximum(np.round(gaps * _DYADIC) / _DYADIC, 1.0 / _DYADIC)


def _assert_bitexact(a_tb, b_tb, msg=""):
    for name in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a_tb, name)), np.asarray(getattr(b_tb, name)),
            err_msg=f"{msg}/{name}")


class TestPallasScanParity:
    """pallas kernel == event scan, bit-for-bit in f64."""

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_bitexact_on_dyadic_schedule(self, proc):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = _dyadic(presample_gaps(grid, 8, 128, seed=9, process=proc))
        ev = simulate_trajectories(60.0, grid, T_base=3000.0, gaps=gaps,
                                   engine_kind="event")
        pl = simulate_trajectories(60.0, grid, T_base=3000.0, gaps=gaps,
                                   engine_kind="pallas")
        assert not ev.truncated.any()
        _assert_bitexact(ev, pl, proc.name)

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_bitexact_on_auto_sampled_path(self, proc):
        """No host schedule: the pallas sampled-build must fold the SAME
        per-point/per-trial threefry keys as the event build."""
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        kw = dict(T_base=3000.0, n_trials=64, seed=11, process=proc)
        ev = simulate_trajectories(60.0, grid, engine_kind="event", **kw)
        pl = simulate_trajectories(60.0, grid, engine_kind="pallas", **kw)
        _assert_bitexact(ev, pl, proc.name)

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
        pl = simulate_trajectories(T, grid, T_base=500.0, gaps=gaps,
                                   engine_kind="pallas")
        _assert_bitexact(ev, pl)

    def test_candidates_axis_parity(self):
        """simulate_candidates: the lax.map pallas candidate path shares
        the schedules across candidates exactly like the vmapped scan."""
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        Ts = np.geomspace(30.0, 300.0, 7)
        kw = dict(T_base=1500.0, n_trials=32, seed=2,
                  process=Weibull(shape=0.7))
        ev = simulate_candidates(Ts, grid, engine_kind="event", **kw)
        pl = simulate_candidates(Ts, grid, engine_kind="pallas", **kw)
        _assert_bitexact(ev, pl)

    def test_exhaustion_and_truncation_flags(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        gaps = np.array([50.0, 70.0])       # far too short for T_base=4000
        ev = simulate_trajectories(60.0, grid, T_base=4000.0, gaps=gaps,
                                   engine_kind="event")
        pl = simulate_trajectories(60.0, grid, T_base=4000.0, gaps=gaps,
                                   engine_kind="pallas")
        assert pl.gaps_exhausted.all()
        _assert_bitexact(ev, pl)
        tiny = simulate_trajectories(60.0, grid, T_base=50000.0, n_trials=4,
                                     seed=0, n_steps=2, engine_kind="pallas")
        assert tiny.truncated.any()

    def test_env_var_defers_engine_kind(self, monkeypatch):
        """engine_kind=None resolves through $REPRO_ENGINE_KIND; explicit
        kinds pass through untouched (the CI pallas leg's mechanism)."""
        monkeypatch.setenv("REPRO_ENGINE_KIND", "pallas")
        assert resolve_engine_kind(None) == "pallas"
        assert resolve_engine_kind("event") == "event"
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        kw = dict(T_base=1500.0, n_trials=16, seed=4)
        via_env = simulate_trajectories(60.0, grid, **kw)
        explicit = simulate_trajectories(60.0, grid, engine_kind="pallas",
                                         **kw)
        _assert_bitexact(via_env, explicit)
        monkeypatch.delenv("REPRO_ENGINE_KIND")
        assert resolve_engine_kind(None) == "event"
        with pytest.raises(ValueError, match="engine_kind"):
            resolve_engine_kind("warp")


class TestPrecisionKnobs:
    """Policy resolution + the CPU bit-exact no-op guarantees."""

    def test_cpu_default_is_f64(self):
        assert backend_info().platform == "cpu"
        assert resolve_precision() is F64
        assert F64.exact and not COMPENSATED_F32.exact

    def test_f64_policy_is_bitexact_noop(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        kw = dict(T_base=1500.0, n_trials=32, seed=7,
                  process=Weibull(shape=0.7), engine_kind="pallas")
        _assert_bitexact(simulate_trajectories(60.0, grid, **kw),
                         simulate_trajectories(60.0, grid, precision="f64",
                                               **kw))
        g = mu_rho_grid(mus=(800.0, 2000.0), rhos=(0.5, 1.0))
        a, b = evaluate_grid(g), evaluate_grid(g, precision=F64)
        for f in ("T_time", "T_energy", "E_time", "E_energy", "valid"):
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)),
                                          err_msg=f)

    def test_backend_knob_is_bitexact_noop_on_cpu(self):
        g = mu_rho_grid(mus=(800.0, 2000.0), rhos=(0.5, 1.0))
        a = evaluate_grid(g)
        b = evaluate_grid(g, dispatch=DispatchConfig(backend="cpu"))
        for f in ("T_time", "T_energy", "E_time", "E_energy"):
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)),
                                          err_msg=f)

    def test_missing_backend_raises(self):
        """A backend with no devices here is an error, never a silent
        run on another device."""
        g = mu_rho_grid(mus=(800.0, 2000.0), rhos=(0.5, 1.0))
        with pytest.raises(RuntimeError):
            evaluate_grid(g, dispatch=DispatchConfig(backend="tpu"))

    def test_interpret_mode_only_on_cpu(self, monkeypatch):
        """Pallas interprets on the CPU backend, lowers on a TPU, and
        refuses any other backend."""
        import jax
        from repro.kernels.ops import interpret_mode
        assert interpret_mode() is True
        assert interpret_mode(False) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert interpret_mode() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="no Pallas lowering"):
            interpret_mode()

    def test_unknown_device_kind_has_no_peaks(self):
        from benchmarks.roofline import PLAN_BACKEND, resolve_peaks
        assert resolve_peaks(PLAN_BACKEND).link_bw == 1600e9 / 8
        with pytest.raises(ValueError, match="no peaks"):
            resolve_peaks("TPU v6 lite")

    def test_resolution_order(self, monkeypatch):
        # explicit argument beats everything
        cfg = DispatchConfig(precision=F64)
        assert resolve_precision(cfg, COMPENSATED_F32) is COMPENSATED_F32
        # config beats the environment
        monkeypatch.setenv("REPRO_PRECISION", "compensated_f32")
        assert resolve_precision(cfg) is F64
        # environment beats the backend default
        assert resolve_precision() is COMPENSATED_F32
        # bad environment value: warn + fall through to the backend default
        monkeypatch.setenv("REPRO_PRECISION", "float8")
        with pytest.warns(RuntimeWarning, match="REPRO_PRECISION"):
            assert resolve_precision() is F64

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="float16"):
            resolve("float16")
        with pytest.raises(TypeError):
            resolve(3.14)

    def test_compensated_sum_recovers_cancellation(self):
        """The Neumaier machinery itself: a catastrophic-cancellation sum
        that plain f32 accumulation gets wrong to ~1e-1."""
        import jax.numpy as jnp
        big = np.float32(1e8)
        terms = [jnp.float32(v) for v in (big, 1.0, -big, 1.0)]
        naive = terms[0]
        for t in terms[1:]:
            naive = naive + t
        assert float(naive) != 2.0
        assert float(compensated_sum(terms)) == 2.0
        s, err = two_sum(np.float64(1.0), np.float64(1e-20))
        assert s == 1.0 and err == 1e-20


class TestCompensatedParityGates:
    """compensated_f32 vs the f64 oracle, per scenario family, at the
    policy's DOCUMENTED tolerances."""

    def _gate_single(self, grid):
        pol = COMPENSATED_F32
        r64 = evaluate_grid(grid)
        r32 = evaluate_grid(grid, precision=pol)
        valid = (np.asarray(r64.valid) & np.asarray(r32.valid)).ravel()
        assert valid.any()
        np.testing.assert_array_equal(np.asarray(r64.valid),
                                      np.asarray(r32.valid))
        p = {k: np.asarray(v).ravel()[valid]
             for k, v in grid.ravel().fields().items()}
        for T64, T32, objective in (
                (r64.T_time, r32.T_time, time_final_batched),
                (r64.T_energy, r32.T_energy, energy_final_batched)):
            T64 = np.asarray(T64).ravel()[valid]
            T32 = np.asarray(T32).ravel()[valid]
            # argmin gate: the served period lands in the f64 valley
            np.testing.assert_allclose(T32, T64, rtol=pol.argmin_rtol)
            # objective gate: the f32 period's TRUE (f64-re-evaluated)
            # objective is within objective_tol of the f64 optimum
            f64_at_32 = np.asarray(objective(T32, p, 1.0))
            f64_at_64 = np.asarray(objective(T64, p, 1.0))
            rel = np.abs(f64_at_32 - f64_at_64) / np.abs(f64_at_64)
            assert float(rel.max()) <= pol.objective_tol, rel.max()

    def test_mu_rho_family(self):
        self._gate_single(mu_rho_grid(mus=(600.0, 1200.0, 3600.0),
                                      rhos=(0.5, 1.0, 3.0)))

    def test_arch_catalog_family(self):
        self._gate_single(arch_grid())

    def test_multilevel_family(self):
        pol = COMPENSATED_F32
        grid = buddy_ratio_grid([0.05, 0.2, 1.0], [0.02, 0.1, 0.3],
                                mu_min=300.0)
        m_values = tuple(range(1, 9))
        r64 = evaluate_multilevel_grid(grid, m_values=m_values)
        r32 = evaluate_multilevel_grid(grid, m_values=m_values,
                                       precision=pol)
        for T64, m64, T32, m32 in (
                (r64.T_time, r64.m_time, r32.T_time, r32.m_time),
                (r64.T_energy, r64.m_energy, r32.T_energy, r32.m_energy)):
            np.testing.assert_allclose(np.asarray(T32), np.asarray(T64),
                                       rtol=pol.argmin_rtol)
            # cadence argmins are small integers: near-ties may flip one
            # notch under f32, never more
            assert np.abs(np.asarray(m32, dtype=np.int64)
                          - np.asarray(m64, dtype=np.int64)).max() <= 1
        # objective gate on the f64 per-m tables: the f32-served cadence's
        # f64 objective is within objective_tol of the f64 optimum
        E64 = np.asarray(r64.E_by_m)             # (n_m, ...grid)
        mi64 = np.asarray(r64.m_energy) - m_values[0]
        mi32 = np.asarray(r32.m_energy) - m_values[0]
        at64 = np.take_along_axis(E64, mi64[None], axis=0)[0]
        at32 = np.take_along_axis(E64, mi32[None], axis=0)[0]
        rel = np.abs(at32 - at64) / np.abs(at64)
        # the cadence axis is discrete: a one-notch flip near a tie costs
        # the tie margin, not f32 noise — gate at the policy tol against
        # the CONTINUOUS-period re-evaluation semantics
        assert float(rel.max()) <= 10 * pol.objective_tol, rel.max()

    def test_pallas_compensated_engine_close_to_oracle(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        kw = dict(T_base=1500.0, n_trials=64, seed=3,
                  process=Weibull(shape=0.7), engine_kind="pallas")
        r64 = simulate_trajectories(60.0, grid, **kw)
        r32 = simulate_trajectories(60.0, grid, precision=COMPENSATED_F32,
                                    **kw)
        np.testing.assert_array_equal(r64.n_failures, r32.n_failures)
        for f in ("wall_time", "energy", "work_executed", "io_time"):
            np.testing.assert_allclose(np.asarray(getattr(r32, f)),
                                       np.asarray(getattr(r64, f)),
                                       rtol=1e-5, err_msg=f)


class TestAdvisorPrecision:
    """The serving layer's policy threading."""

    def _req(self):
        from repro.serve.schema import AdviceRequest, StoreTier
        tier = StoreTier(name="pfs", C=60.0, R=60.0, D=0.0, P_io=10.0)
        return AdviceRequest(mu=3600.0, tiers=(tier,))

    def test_metrics_report_policy(self):
        from repro.serve.service import AdvisorService
        assert AdvisorService().metrics()["precision_policy"] == "f64"
        svc = AdvisorService(precision="compensated_f32")
        assert svc.metrics()["precision_policy"] == "compensated_f32"

    def test_compensated_service_stays_within_gates(self):
        from repro.serve.service import AdvisorService
        req = self._req()
        a64 = AdvisorService().advise(req)
        a32 = AdvisorService(precision=COMPENSATED_F32,
                             cache_name=None).advise(req)
        assert a32.period == pytest.approx(a64.period,
                                           rel=COMPENSATED_F32.argmin_rtol)
        # the certified bound must have absorbed the policy's
        # objective_tol slack on the cached (non-exact) path
        if not a32.exact:
            assert a32.cert_bound >= COMPENSATED_F32.objective_tol
            assert a64.cert_bound < a32.cert_bound
