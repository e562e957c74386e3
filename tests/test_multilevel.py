"""Multilevel (buddy + PFS) checkpointing: model reductions, joint (T, m)
solver parity (scalar vs batched vs the single-level seed optima), engine
trajectory semantics (hand-computed + bit-for-bit m=1 oracle parity), and
the Monte-Carlo validation of the closed forms (2% acceptance gate).
"""
import numpy as np
import pytest

from repro.core import (CheckpointParams, MultilevelCheckpointParams,
                        MultilevelPowerParams, PowerParams,
                        EXASCALE_POWER_RHO55, EXASCALE_ML_POWER,
                        simulate_once, t_opt_time, t_opt_energy,
                        t_opt_time_multilevel, t_opt_energy_multilevel,
                        time_final, energy_final, phase_times,
                        ml_time_final, ml_energy_final, ml_phase_times,
                        ml_energy_final_prime, ml_K_dE_dT,
                        ml_energy_quadratic_coefficients,
                        evaluate, evaluate_multilevel, sweep_buddy_ratio)
from repro.sim import (MultilevelParamGrid, ParamGrid, ScheduledRNG,
                       buddy_ratio_grid, evaluate_multilevel_grid,
                       get_scenario, list_scenarios,
                       multilevel_grid_from_scenarios, simulate_grid_ml,
                       simulate_trajectories_ml)

CK = CheckpointParams(C=10.0, R=10.0, D=1.0, mu=300.0, omega=0.5)
PW = EXASCALE_POWER_RHO55

#: a genuine two-level operating point (cheap buddy, rare level loss).
ML = MultilevelCheckpointParams(C1=1.0, R1=1.0, C2=10.0, R2=10.0,
                                D1=0.5, D2=1.0, mu=300.0, q=0.1, omega=0.5)


def degenerate(q=0.0):
    """Levels collapsed onto the single-level CK (exact-reduction lift)."""
    return MultilevelCheckpointParams.from_single(CK, q=q)


DPW = MultilevelPowerParams.from_power(PW)


# ---------------------------------------------------------------------------
# Model reduction: m=1 / degenerate levels reproduce the single-level model
# ---------------------------------------------------------------------------

class TestModelReduction:
    TS = np.linspace(22.0, 250.0, 9)

    def test_m1_time_is_bit_identical(self):
        """T_final(T, m=1) == the seed time_final, exactly — any q."""
        for q in (0.0, 0.3, 1.0):
            got = ml_time_final(self.TS, 1, degenerate(q))
            want = time_final(self.TS, CK)
            assert np.array_equal(got, want)

    def test_m1_energy_reduces_exactly_at_q0(self):
        got = ml_energy_final(self.TS, 1, degenerate(0.0), DPW)
        want = energy_final(self.TS, CK, PW)
        np.testing.assert_allclose(got, want, rtol=1e-13)

    def test_m1_energy_reduces_at_any_q(self):
        """q only splits the (identical) levels -> 1-ulp wobble at most."""
        got = ml_energy_final(self.TS, 1, degenerate(0.4), DPW)
        want = energy_final(self.TS, CK, PW)
        np.testing.assert_allclose(got, want, rtol=1e-12)

    def test_degenerate_levels_any_m_reduce(self):
        """C1=C2, q=0: buddy periods are indistinguishable from deep ones,
        so every m reproduces the single-level expectations."""
        ph_sl = phase_times(self.TS, CK)
        for m in (2, 3, 7):
            ck = degenerate(0.0)
            np.testing.assert_allclose(ml_time_final(self.TS, m, ck),
                                       ph_sl.T_final, rtol=1e-13)
            ph = ml_phase_times(self.TS, m, ck)
            np.testing.assert_allclose(ph.T_cal, ph_sl.T_cal, rtol=1e-13)
            np.testing.assert_allclose(ph.T_io1 + ph.T_io2, ph_sl.T_io,
                                       rtol=1e-13)
            np.testing.assert_allclose(ph.T_down, ph_sl.T_down, rtol=1e-13)

    def test_phase_times_compose_to_energy(self):
        ph = ml_phase_times(60.0, 3, ML)
        e = (ph.T_cal * EXASCALE_ML_POWER.P_cal
             + ph.T_io1 * EXASCALE_ML_POWER.P_io1
             + ph.T_io2 * EXASCALE_ML_POWER.P_io2
             + ph.T_final * EXASCALE_ML_POWER.P_static)
        assert float(e) == pytest.approx(
            float(ml_energy_final(60.0, 3, ML, EXASCALE_ML_POWER)),
            rel=1e-12)

    def test_energy_prime_matches_finite_difference(self):
        for m in (1, 2, 5):
            for T in (40.0, 90.0):
                h = 1e-6 * T
                fd = (ml_energy_final(T + h, m, ML, EXASCALE_ML_POWER)
                      - ml_energy_final(T - h, m, ML, EXASCALE_ML_POWER)) \
                    / (2 * h)
                an = ml_energy_final_prime(T, m, ML, EXASCALE_ML_POWER)
                assert float(an) == pytest.approx(float(fd), rel=1e-6)

    def test_K_dE_dT_is_quadratic(self):
        """The §3.2 cancellation survives the two-level extension."""
        for m in (1, 2, 6):
            c2, c1, c0 = ml_energy_quadratic_coefficients(
                ML, EXASCALE_ML_POWER, m)
            lo, hi = ML.valid_period_range(m)
            for frac in (0.15, 0.55, 0.85):
                t = lo + frac * (hi - lo)
                q = float(ml_K_dE_dT(t, m, ML, EXASCALE_ML_POWER))
                assert q == pytest.approx(c2 * t**2 + c1 * t + c0,
                                          rel=1e-7, abs=1e-9)

    def test_param_validation(self):
        with pytest.raises(ValueError):
            MultilevelCheckpointParams(C1=1, R1=1, C2=10, R2=10, D1=1, D2=1,
                                       mu=300.0, q=1.5)
        with pytest.raises(ValueError):
            MultilevelCheckpointParams(C1=-1, R1=1, C2=10, R2=10, D1=1, D2=1,
                                       mu=300.0)
        with pytest.raises(ValueError):
            MultilevelPowerParams(P_static=0.0, P_cal=1, P_io1=1, P_io2=1)


# ---------------------------------------------------------------------------
# Scalar joint (T, m) solvers
# ---------------------------------------------------------------------------

class TestScalarSolvers:
    def test_m1_algo_t_reproduces_seed_exactly(self):
        for q in (0.0, 0.3):
            t, m = t_opt_time_multilevel(degenerate(q), m_max=1)
            assert m == 1 and t == t_opt_time(CK)

    def test_m1_algo_e_reproduces_seed(self):
        t, m = t_opt_energy_multilevel(degenerate(0.0), DPW, m_max=1)
        assert m == 1
        assert t == pytest.approx(t_opt_energy(CK, PW), rel=1e-12)

    def test_degenerate_levels_never_beat_single(self):
        """With C1=C2 and q=0 all m are equivalent; the solver must return
        the single-level optimum value (any m)."""
        t, m = t_opt_time_multilevel(degenerate(0.0), m_max=6)
        assert float(ml_time_final(t, m, degenerate(0.0))) == pytest.approx(
            float(time_final(t_opt_time(CK), CK)), rel=1e-12)

    def test_cheap_buddy_prefers_m_gt_1(self):
        t, m = t_opt_time_multilevel(ML, m_max=12)
        te, me = t_opt_energy_multilevel(ML, EXASCALE_ML_POWER, m_max=12)
        assert m > 1 and me > 1
        # and it strictly beats the forced single-level schedule:
        t1, _ = t_opt_time_multilevel(ML, m_max=1)
        assert float(ml_time_final(t, m, ML)) < float(
            ml_time_final(t1, 1, ML))

    def test_evaluate_multilevel_point(self):
        pt = evaluate_multilevel(ML, EXASCALE_ML_POWER, m_max=8)
        assert pt.time_ratio >= 1.0 and pt.energy_ratio >= 1.0
        # the buddy level must pay for itself vs the PFS-only seed model
        assert pt.time_vs_single < 1.0
        assert pt.energy_vs_single < 1.0
        assert 0.0 < pt.energy_saving < 1.0

    def test_no_valid_m_raises(self):
        bad = MultilevelCheckpointParams(C1=50.0, R1=50.0, C2=500.0,
                                         R2=500.0, D1=1, D2=1, mu=300.0,
                                         q=0.1, omega=0.0)
        with pytest.raises(ValueError):
            t_opt_time_multilevel(bad, m_max=4)


# ---------------------------------------------------------------------------
# Batched joint solver vs the scalar reference
# ---------------------------------------------------------------------------

class TestPerLevelOverlap:
    """Async deep flush (VELOC): omega1/omega2 split of the shared omega."""

    def test_shared_omega_reduces_bit_for_bit(self):
        split = MultilevelCheckpointParams(
            C1=1.0, R1=1.0, C2=10.0, R2=10.0, D1=0.5, D2=1.0,
            mu=300.0, q=0.1, omega=0.0, omega1=0.5, omega2=0.5)
        for m in (1, 2, 5, 9):
            for T in (20.0, 40.0, 80.0):
                assert ml_time_final(T, m, split) == ml_time_final(T, m, ML)
                assert ml_energy_final(T, m, split, DPW) == \
                    ml_energy_final(T, m, ML, DPW)

    def test_flush_window_and_hard_loss(self):
        ck = MultilevelCheckpointParams(
            C1=1.0, R1=1.0, C2=10.0, R2=10.0, D1=0.5, D2=1.0,
            mu=300.0, q=0.1, omega1=0.2, omega2=0.9)
        assert ck.flush_window(3) == pytest.approx(0.9 * 10.0)
        # a hard failure pays the in-flight deep write on top of D2 + R2
        assert ck.expected_fixed_loss(3) == pytest.approx(
            (1 - 0.1) * (0.5 + 1.0 + ck.C_omega_mean(3))
            + 0.1 * (1.0 + 10.0 + 0.9 * 10.0))

    def test_time_overhead_monotone_in_omega2(self):
        """More overlap never makes the critical path worse."""
        prev = None
        for w2 in (0.0, 0.3, 0.6, 0.9, 1.0):
            ck = MultilevelCheckpointParams(
                C1=1.0, R1=1.0, C2=10.0, R2=10.0, D1=0.5, D2=1.0,
                mu=300.0, q=0.1, omega1=0.0, omega2=w2)
            tf = float(ml_time_final(30.0, 6, ck))
            if prev is not None:
                assert tf < prev
            prev = tf

    def test_async_scalar_batched_parity(self):
        """Batched omega1 != omega2 grid point matches the scalar solver."""
        ck = MultilevelCheckpointParams(
            C1=1.0, R1=1.0, C2=10.0, R2=10.0, D1=0.5, D2=1.0,
            mu=300.0, q=0.1, omega1=0.2, omega2=0.9)
        grid = MultilevelParamGrid.from_params(
            ck, EXASCALE_ML_POWER).reshape((1,))
        res = evaluate_multilevel_grid(grid, m_values=tuple(range(1, 9)))
        pt = evaluate_multilevel(ck, EXASCALE_ML_POWER, m_max=8)
        tf_b = float(ml_time_final(res.T_time[0], int(res.m_time[0]), ck))
        tf_s = float(ml_time_final(pt.T_time, pt.m_time, ck))
        assert tf_b == pytest.approx(tf_s, rel=1e-9)
        e_b = float(ml_energy_final(res.T_energy[0], int(res.m_energy[0]),
                                    ck, EXASCALE_ML_POWER))
        e_s = float(ml_energy_final(pt.T_energy, pt.m_energy, ck,
                                    EXASCALE_ML_POWER))
        assert e_b == pytest.approx(e_s, rel=1e-9)
        assert res.time_ratio[0] == pytest.approx(pt.time_ratio, rel=1e-7)
        assert res.energy_ratio[0] == pytest.approx(pt.energy_ratio,
                                                    rel=1e-7)


class TestBatchedSolverParity:
    def test_grid_matches_scalar(self):
        ratios, qs = [0.05, 0.2, 1.0], [0.02, 0.1, 0.3]
        grid = buddy_ratio_grid(ratios, qs, mu_min=300.0)
        res = evaluate_multilevel_grid(grid, m_values=tuple(range(1, 9)))
        for i in range(len(ratios)):
            for j in range(len(qs)):
                ck, pw = grid.ckpt_at((i, j)), grid.power_at((i, j))
                pt = evaluate_multilevel(ck, pw, m_max=8)
                # objective values must agree tightly; the argmin cadence may
                # only differ where two m are near-ties, so compare the
                # realized objectives rather than m itself.
                tf_b = float(ml_time_final(res.T_time[i, j],
                                           int(res.m_time[i, j]), ck))
                tf_s = float(ml_time_final(pt.T_time, pt.m_time, ck))
                assert tf_b == pytest.approx(tf_s, rel=1e-9)
                e_b = float(ml_energy_final(res.T_energy[i, j],
                                            int(res.m_energy[i, j]), ck, pw))
                e_s = float(ml_energy_final(pt.T_energy, pt.m_energy, ck, pw))
                assert e_b == pytest.approx(e_s, rel=1e-9)
                assert res.time_ratio[i, j] == pytest.approx(pt.time_ratio,
                                                             rel=1e-7)
                assert res.energy_ratio[i, j] == pytest.approx(
                    pt.energy_ratio, rel=1e-7)
                assert res.time_vs_single[i, j] == pytest.approx(
                    pt.time_vs_single, rel=1e-7)

    def test_m1_reproduces_single_level_batched(self):
        """Degenerate grid at m_values=(1,) == the seed batched solver."""
        sl = ParamGrid.from_params(CK, PW).reshape((1,))
        grid = MultilevelParamGrid.from_single_level(sl, q=0.0)
        res = evaluate_multilevel_grid(grid, m_values=(1,))
        assert res.T_time[0] == pytest.approx(t_opt_time(CK), rel=1e-12)
        assert res.T_energy[0] == pytest.approx(t_opt_energy(CK, PW),
                                                rel=1e-9)
        pt = evaluate(CK, PW)
        assert res.time_ratio[0] == pytest.approx(pt.time_ratio, rel=1e-9)
        assert res.energy_ratio[0] == pytest.approx(pt.energy_ratio,
                                                    rel=1e-9)
        # degenerate levels: the "two-level" scheme IS the single-level one
        assert res.time_vs_single[0] == pytest.approx(1.0, rel=1e-9)
        assert res.energy_vs_single[0] == pytest.approx(1.0, rel=1e-9)

    def test_degenerate_grid_point_collapses(self):
        """C2 of the order of the MTBF: no valid period at any m."""
        bad = MultilevelCheckpointParams(C1=20.0, R1=20.0, C2=200.0,
                                         R2=200.0, D1=1, D2=1, mu=120.0,
                                         q=0.1, omega=0.5)
        g1 = multilevel_grid_from_scenarios(
            [get_scenario("multilevel_exascale")])
        g2 = MultilevelParamGrid.from_params(
            bad, EXASCALE_ML_POWER).reshape((1,))
        both = MultilevelParamGrid(
            **{f: np.concatenate([getattr(g1, f), getattr(g2, f)])
               for f in g1.fields()})
        res = evaluate_multilevel_grid(both, m_values=(1, 2, 4))
        assert res.valid[0] and not res.valid[1]
        assert res.time_ratio[1] == 1.0 and res.energy_ratio[1] == 1.0
        assert res.T_time[1] == both.C2[1] and res.m_time[1] == 1

    def test_infeasible_single_level_comparator_gives_nan(self):
        """Regression: a platform only the buddy level makes feasible (no
        valid PFS-only period) must report NaN vs-single ratios, not the
        garbage of the comparator's masked-out placeholder bracket."""
        ck = MultilevelCheckpointParams(C1=5.0, R1=5.0, C2=100.0, R2=100.0,
                                        D1=0.5, D2=1.0, mu=120.0, q=0.1,
                                        omega=0.0)
        lo, hi = ck.single_level().valid_period_range()
        assert hi <= lo             # PFS-only truly infeasible
        grid = MultilevelParamGrid.from_params(
            ck, EXASCALE_ML_POWER).reshape((1,))
        res = evaluate_multilevel_grid(grid, m_values=(1, 2, 3, 4))
        assert res.valid[0]         # ...but the two-level scheme works
        assert np.isnan(res.time_vs_single[0])
        assert np.isnan(res.energy_vs_single[0])
        pt = evaluate_multilevel(ck, EXASCALE_ML_POWER, m_max=4)
        assert np.isnan(pt.time_vs_single) and np.isnan(pt.energy_vs_single)
        # the genuine two-level outputs stay well-defined
        assert res.time_ratio[0] >= 1.0 and np.isfinite(res.Tf_time[0])
        assert pt.T_time == pytest.approx(float(res.T_time[0]), rel=1e-9)

    def test_tradeoff_sweep_engines_agree(self):
        ratios, qs = [0.1, 0.4], [0.05, 0.2]
        fast = sweep_buddy_ratio(ratios, qs, mu_minutes=300.0, m_max=6)
        slow = sweep_buddy_ratio(ratios, qs, mu_minutes=300.0, m_max=6,
                                 engine="scalar")
        for rf, rs in zip(fast, slow):
            for pf, ps in zip(rf, rs):
                assert pf.time_ratio == pytest.approx(ps.time_ratio,
                                                      rel=1e-7)
                assert pf.energy_ratio == pytest.approx(ps.energy_ratio,
                                                        rel=1e-7)


# ---------------------------------------------------------------------------
# Two-level engine: hand-computed trajectories + m=1 oracle parity
# ---------------------------------------------------------------------------

def _hand_grid():
    ck = MultilevelCheckpointParams(C1=1.0, R1=1.0, C2=2.0, R2=2.0,
                                    D1=0.5, D2=1.0, mu=1.0e9, q=0.5,
                                    omega=0.0)
    pw = MultilevelPowerParams(P_static=1.0, P_cal=2.0, P_io1=3.0,
                               P_io2=5.0, P_down=7.0)
    return MultilevelParamGrid.from_params(ck, pw).reshape((1,))


class TestEngineTrajectories:
    """T=10, C1=1, C2=2, m=2, omega=0 (blocking), T_base=40.

    Fault-free schedule: [cmp 9 | ck1 1 | cmp 8 | ck2 2] x 2, then 6 units
    of compute -> wall 46.  A failure at t=33 strikes the 4th period
    (k=1 compute), when committed1=26 (buddy, t=30) > committed2=17
    (deep, t=20) — so soft and hard recovery genuinely differ.
    """

    def _run(self, gaps, hard):
        tb = simulate_trajectories_ml(
            10.0, 2, _hand_grid(), T_base=40.0,
            gaps=np.asarray(gaps)[None, None, :],
            hard=np.asarray(hard)[None, None, :])
        assert not tb.truncated.any() and not tb.gaps_exhausted.any()
        return tb

    def test_fault_free(self):
        tb = self._run([1e9, 1e9], [False, False])
        assert tb.wall_time[0, 0] == 46.0
        assert tb.work_executed[0, 0] == 40.0
        assert tb.io1_time[0, 0] == 2.0      # two buddy writes
        assert tb.io2_time[0, 0] == 4.0      # two deep writes
        assert int(tb.n_ckpt1[0, 0]) == 2 and int(tb.n_ckpt2[0, 0]) == 2
        assert int(tb.n_failures[0, 0]) == 0

    def test_soft_failure_rolls_back_to_buddy(self):
        tb = self._run([33.0, 1e9], [False, False])
        # lose 3 work units (26 -> 29), resume at period k=1: recovery at
        # t=34.5, compute 8, ck2 2, compute 6 -> wall 50.5.
        assert tb.wall_time[0, 0] == 50.5
        assert tb.work_executed[0, 0] == 43.0
        assert tb.io1_time[0, 0] == 3.0      # 2 writes + R1 recovery
        assert tb.io2_time[0, 0] == 4.0      # 2 deep writes (one re-planned)
        assert tb.down_time[0, 0] == 0.5
        assert int(tb.n_failures[0, 0]) == 1
        assert int(tb.n_hard_failures[0, 0]) == 0

    def test_hard_failure_rolls_back_to_deep(self):
        tb = self._run([33.0, 1e9], [True, False])
        # lose 12 work units (17 -> 29), restart superperiod at k=0:
        # recovery at t=36, then cmp 9 | ck1 1 | cmp 8 | ck2 2 | cmp 6 -> 62.
        assert tb.wall_time[0, 0] == 62.0
        assert tb.work_executed[0, 0] == 52.0
        assert tb.io1_time[0, 0] == 3.0      # 2 writes + 1 re-executed write
        assert tb.io2_time[0, 0] == 6.0      # 2 deep writes + R2 recovery
        assert tb.down_time[0, 0] == 1.0
        assert int(tb.n_failures[0, 0]) == 1
        assert int(tb.n_hard_failures[0, 0]) == 1
        # energy composes the per-level powers
        want = (1.0 * 62.0 + 2.0 * 52.0 + 3.0 * 3.0 + 5.0 * 6.0 + 7.0 * 1.0)
        assert tb.energy[0, 0] == pytest.approx(want, rel=1e-12)

    def test_too_short_period_raises(self):
        with pytest.raises(ValueError):
            simulate_trajectories_ml(1.5, 2, _hand_grid(), T_base=40.0,
                                     n_trials=2)
        with pytest.raises(ValueError):
            simulate_trajectories_ml(10.0, 0, _hand_grid(), T_base=40.0,
                                     n_trials=2)


class TestEngineOracleParity:
    """m=1 + degenerate levels: the scalar single-level oracle under a
    shared failure schedule (hard flags are inert).  The event kernel
    takes closed forms where the oracle accumulates, so they agree to
    rounding (the event kernel's contract, docs/simulation.md) and
    exactly in counts; bit for bit on a dyadic schedule
    (tests/test_multilevel_engine.py)."""

    @pytest.mark.parametrize("T", [40.0, 53.3])
    def test_m1_matches_scalar_oracle(self, T):
        sl = ParamGrid.from_params(CK, PW).reshape((1,))
        grid = MultilevelParamGrid.from_single_level(sl, q=0.3)
        rng = np.random.default_rng(123)
        gaps = rng.exponential(CK.mu, size=(1, 8, 64))
        hard = rng.random(size=(1, 8, 64)) < 0.3
        tb = simulate_trajectories_ml(T, 1, grid, T_base=4000.0, gaps=gaps,
                                      hard=hard)
        assert not tb.truncated.any()
        for k in range(gaps.shape[1]):
            ref = simulate_once(T, CK, PW, 4000.0, ScheduledRNG(gaps[0, k]))
            close = lambda a, b: a == pytest.approx(b, rel=1e-12, abs=0)
            assert close(tb.wall_time[0, k], ref.wall_time)
            assert close(tb.energy[0, k], ref.energy)
            assert close(tb.work_executed[0, k], ref.work_executed)
            assert close(tb.io1_time[0, k] + tb.io2_time[0, k], ref.io_time)
            assert tb.down_time[0, k] == ref.down_time
            assert int(tb.n_failures[0, k]) == ref.n_failures
            assert int(tb.n_ckpt1[0, k] + tb.n_ckpt2[0, k]) \
                == ref.n_checkpoints


# ---------------------------------------------------------------------------
# Monte-Carlo validation of the closed forms (acceptance gate)
# ---------------------------------------------------------------------------

class TestMonteCarloValidation:
    """Batched (T, m) solvers vs the two-level Monte-Carlo engine: expected
    makespan and energy within 2% at both AlgoT and AlgoE optima across the
    multilevel scenario grid (first-order validity regime: m*T < mu)."""

    RATIOS = [0.1, 0.25]
    QS = [0.1, 0.3]

    @pytest.fixture(scope="class")
    def solved(self):
        grid = buddy_ratio_grid(self.RATIOS, self.QS, mu_min=600.0)
        res = evaluate_multilevel_grid(grid, m_values=(1, 2, 3, 4))
        return grid, res

    @pytest.mark.parametrize("algo", ["time", "energy"])
    def test_within_2pct(self, solved, algo):
        grid, res = solved
        Ts = res.T_time if algo == "time" else res.T_energy
        ms = res.m_time if algo == "time" else res.m_energy
        out = simulate_grid_ml(Ts, ms, grid, 4000.0, n_trials=400, seed=5)
        for i in range(len(self.RATIOS)):
            for j in range(len(self.QS)):
                t, m = float(Ts[i, j]), int(ms[i, j])
                ck = grid.ckpt_at((i, j))
                pw = grid.power_at((i, j))
                tf_model = float(ml_time_final(t, m, ck, 4000.0))
                e_model = float(ml_energy_final(t, m, ck, pw, 4000.0))
                assert abs(out["T_final"][i, j] / tf_model - 1) < 0.02, (
                    f"T_final off at ratio={self.RATIOS[i]} q={self.QS[j]}")
                assert abs(out["E_final"][i, j] / e_model - 1) < 0.02, (
                    f"E_final off at ratio={self.RATIOS[i]} q={self.QS[j]}")

    def test_solver_choice_beats_forced_single_level_in_simulation(self, solved):
        """The (T*, m*) choice must win IN THE SIMULATOR, not just in the
        model: lower measured makespan than the PFS-only optimum."""
        grid, res = solved
        sl = evaluate_multilevel_grid(grid, m_values=(1,))
        two = simulate_grid_ml(res.T_time, res.m_time, grid, 4000.0,
                               n_trials=300, seed=9)
        one = simulate_grid_ml(sl.T_time, sl.m_time, grid, 4000.0,
                               n_trials=300, seed=9)
        assert (two["T_final"] < one["T_final"]).all()


# ---------------------------------------------------------------------------
# Scenario registry
# ---------------------------------------------------------------------------

class TestMultilevelScenarios:
    def test_registry_contains_family(self):
        names = set(list_scenarios())
        assert {"multilevel_exascale", "multilevel_fig12",
                "multilevel_arch"} <= names

    def test_grid_views_roundtrip(self):
        grid = buddy_ratio_grid([0.1, 0.5], [0.05, 0.2, 0.4])
        assert grid.shape == (2, 3)
        ck = grid.ckpt_at((1, 2))
        assert ck.C1 == pytest.approx(5.0) and ck.q == pytest.approx(0.4)
        pw = grid.power_at((0, 0))
        assert pw.P_io2 == pytest.approx(100.0)
        assert pw.P_io1 < pw.P_io2

    def test_single_level_projection(self):
        grid = buddy_ratio_grid([0.1], [0.2])
        sl = grid.single_level()
        assert sl.C[0, 0] == grid.C2[0, 0]
        assert sl.P_io[0, 0] == grid.P_io2[0, 0]


# ---------------------------------------------------------------------------
# Benchmark regression gate (pure comparison logic)
# ---------------------------------------------------------------------------

class TestBenchRegressionGate:
    def _payload(self, speedup):
        return {"fig2_seed_grid": {"speedup_warm": speedup},
                "dense_grid": {"speedup_warm": speedup}}

    def test_within_budget_passes(self):
        from benchmarks.bench_sweep import check_regression
        # speedup halved is the limit; just above it passes
        assert check_regression(self._payload(12.0),
                                self._payload(6.1)) == []

    def test_speedup_drop_fails(self):
        from benchmarks.bench_sweep import check_regression
        bad = check_regression(self._payload(12.0), self._payload(4.0))
        assert len(bad) == 2 and "3.0x" in bad[0]

    def test_faster_than_baseline_passes(self):
        from benchmarks.bench_sweep import check_regression
        assert check_regression(self._payload(12.0),
                                self._payload(40.0)) == []

    def test_missing_gated_grid_fails_loudly(self):
        """A grid the committed baseline gates must not vanish silently."""
        from benchmarks.bench_sweep import check_regression
        partial = self._payload(12.0)
        del partial["dense_grid"]
        bad = check_regression(self._payload(12.0), partial)
        assert len(bad) == 1 and "missing" in bad[0]

    def test_unbaselined_payload_grid_fails_loudly(self):
        """A gated bench the baseline doesn't know is an UNGATED bench —
        it must fail until BENCH_sweep.json is regenerated with it."""
        from benchmarks.bench_sweep import check_regression
        pay = self._payload(12.0)
        pay["brand_new_bench"] = {"speedup_warm": 0.1}
        bad = check_regression(self._payload(12.0), pay)
        assert len(bad) == 1 and "brand_new_bench" in bad[0]
        assert "missing from the committed baseline" in bad[0]

    def test_ungated_reference_entry_is_skipped(self):
        """Entries flagged ``ungated`` (the step-kernel reference) are
        excluded from the gate by design: an arbitrarily low ratio must
        not fail, and their presence on either side must not trip the
        missing/unbaselined checks."""
        from benchmarks.bench_sweep import check_regression
        base = self._payload(12.0)
        base["weibull_step_engine_reference"] = {"speedup_warm": 0.48,
                                                 "ungated": True}
        pay = self._payload(12.0)
        pay["weibull_step_engine_reference"] = {"speedup_warm": 0.01,
                                                "ungated": True}
        assert check_regression(base, pay) == []
        # payload-only ungated entry: still no complaint (not gated)
        pay["another_reference"] = {"speedup_warm": 0.2, "ungated": True}
        assert check_regression(base, pay) == []
        # baseline-only ungated entry: likewise skipped
        base["old_reference"] = {"speedup_warm": 3.0, "ungated": True}
        assert check_regression(base, pay) == []

    def test_async_overlap_collapse_matches_committed_baseline(self):
        """The async-flush entry is DETERMINISTIC model arithmetic: a
        fresh measurement must reproduce the committed baseline's gated
        quantity exactly, and the collapse story must hold (overhead
        ratio > 2x, time-optimal cadence m* -> 1 at full overlap)."""
        import json
        from benchmarks.bench_sweep import (CANONICAL,
                                            _time_async_overlap_collapse)
        entry = _time_async_overlap_collapse(repeat=1)
        assert entry["speedup_warm"] > 2.0
        assert entry["m_opt_time"][-1] == 1
        assert all(b < a for a, b in zip(entry["time_overhead"],
                                         entry["time_overhead"][1:]))
        committed = json.loads(CANONICAL.read_text())
        assert entry["speedup_warm"] == pytest.approx(
            committed["async_overlap_collapse"]["speedup_warm"], rel=1e-12)
