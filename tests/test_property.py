"""Property-based tests (hypothesis) on the system's invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings, strategies as st, assume, HealthCheck

from repro.core import (CheckpointParams, PowerParams, energy_final,
                        time_final, t_opt_time, t_opt_time_numeric,
                        t_opt_energy, t_opt_energy_numeric,
                        energy_quadratic_coefficients,
                        Exponential, LogNormal, Weibull,
                        fig12_checkpoint, simulate_once,
                        EXASCALE_POWER_RHO55)
from repro.core.optimal import derived_coefficients
from repro.kernels import ref

SETTINGS = dict(max_examples=40, deadline=None,
                suppress_health_check=[HealthCheck.too_slow])

# --- strategies -------------------------------------------------------------

ckpt_params = st.builds(
    CheckpointParams,
    C=st.floats(0.5, 20.0),
    R=st.floats(0.1, 20.0),
    D=st.floats(0.0, 5.0),
    mu=st.floats(100.0, 10_000.0),
    omega=st.floats(0.0, 0.95),
)

power_params = st.builds(
    PowerParams,
    P_static=st.floats(1.0, 50.0),
    P_cal=st.floats(0.1, 100.0),
    P_io=st.floats(0.1, 500.0),
    P_down=st.floats(0.0, 20.0),
)


class TestAnalyticalInvariants:
    @settings(**SETTINGS)
    @given(ckpt_params)
    def test_closed_form_time_optimum_is_argmin(self, ck):
        assume(ck.valid_period_range()[1] > ck.valid_period_range()[0] * 1.01)
        t_star = t_opt_time(ck)
        t_num = t_opt_time_numeric(ck)
        # the two optimizers agree...
        assert t_star == pytest.approx(t_num, rel=1e-4)
        # ...and perturbations never improve the objective
        f = lambda t: float(time_final(t, ck))
        lo, hi = ck.valid_period_range()
        for c in (0.8, 0.95, 1.05, 1.2):
            t = min(max(t_star * c, lo * 1.001), hi * 0.999)
            assert f(t_star) <= f(t) + 1e-9 * abs(f(t))

    @settings(**SETTINGS)
    @given(ckpt_params, power_params)
    def test_energy_root_is_argmin_and_quadratic_is_exact(self, ck, pw):
        assume(ck.valid_period_range()[1] > ck.valid_period_range()[0] * 1.01)
        te = t_opt_energy(ck, pw)
        tn = t_opt_energy_numeric(ck, pw)
        e = lambda t: float(energy_final(t, ck, pw))
        assert e(te) <= e(tn) * (1 + 1e-6)
        # interpolated quadratic == closed-form derived coefficients
        qi = energy_quadratic_coefficients(ck, pw)
        qd = derived_coefficients(ck, pw)
        for a, b in zip(qi, qd):
            assert a == pytest.approx(b, rel=1e-6, abs=1e-12)

    @settings(**SETTINGS)
    @given(ckpt_params, power_params)
    def test_energy_never_below_static_floor(self, ck, pw):
        assume(ck.valid_period_range()[1] > ck.valid_period_range()[0] * 1.01)
        te = t_opt_energy(ck, pw)
        # E >= P_static * T_final >= P_static * T_base
        assert float(energy_final(te, ck, pw)) >= pw.P_static * 1.0

    @settings(**SETTINGS)
    @given(ckpt_params)
    def test_more_failures_longer_runtime(self, ck):
        """T_final is monotonically decreasing in mu at fixed T."""
        assume(ck.valid_period_range()[1] > ck.valid_period_range()[0] * 1.01)
        t = t_opt_time(ck)
        worse = CheckpointParams(C=ck.C, R=ck.R, D=ck.D, mu=ck.mu / 2,
                                 omega=ck.omega)
        lo, hi = worse.valid_period_range()
        assume(lo * 1.01 < t < hi * 0.99)
        assert float(time_final(t, worse)) > float(time_final(t, ck))


class TestFailureProcessProperties:
    """Every failure process's sampled gap mean converges to its declared
    mu, and exponential instances reproduce the legacy paths bit-for-bit."""

    @settings(**SETTINGS)
    @given(st.sampled_from(["exponential", "weibull", "lognormal"]),
           st.floats(0.45, 2.5), st.floats(10.0, 1000.0),
           st.integers(0, 2**31 - 1))
    def test_sampled_gap_mean_converges_to_mu(self, name, shape, mu, seed):
        if name == "weibull":
            proc = Weibull(shape=shape)
        elif name == "lognormal":
            proc = LogNormal(sigma=min(shape, 1.3))
        else:
            proc = Exponential()
        n = 50_000
        g = proc.sample(np.random.default_rng(seed), size=(n,), mean=mu)
        cv = float(np.max(np.asarray(proc.gap_cv())))
        # 8 sigma of the sample mean: astronomically unlikely to flake while
        # still catching any mis-scaled parameterization (which shifts the
        # mean by O(10%+)).
        assert abs(float(g.mean()) - mu) < 8.0 * cv * mu / math.sqrt(n)
        assert (g > 0).all()

    @settings(**SETTINGS)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 6),
           st.integers(4, 64))
    def test_exponential_presample_bit_for_bit(self, seed, n_trials, cap):
        from repro.sim import ParamGrid
        from repro.sim.engine import presample_gaps
        grid = ParamGrid.from_params(fig12_checkpoint(300.0),
                                     EXASCALE_POWER_RHO55).reshape((1,))
        legacy = presample_gaps(grid, n_trials, cap, seed=seed)
        via = presample_gaps(grid, n_trials, cap, seed=seed,
                             process=Exponential())
        np.testing.assert_array_equal(legacy, via)

    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 2**31 - 1), st.floats(40.0, 120.0))
    def test_exponential_simulate_once_bit_for_bit(self, seed, T):
        ck = fig12_checkpoint(300.0)
        r1 = simulate_once(T, ck, EXASCALE_POWER_RHO55, 1500.0,
                           np.random.default_rng(seed))
        r2 = simulate_once(T, ck, EXASCALE_POWER_RHO55, 1500.0,
                           np.random.default_rng(seed),
                           process=Exponential())
        assert r1 == r2


class TestKernelProperties:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 4), st.sampled_from([128, 256]),
           st.sampled_from([128, 256]))
    def test_flash_attention_rows_sum_to_convex_combination(self, b, s, dh):
        """Attention outputs are convex combinations of V rows: outputs are
        bounded by V's min/max per dim."""
        q = jax.random.normal(jax.random.key(0), (b, s, dh))
        k = jax.random.normal(jax.random.key(1), (b, s, dh))
        v = jax.random.normal(jax.random.key(2), (b, s, dh))
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(q, k, v, mode="causal", qb=128, kb=128,
                              interpret=True)
        vmax = np.asarray(v).max(axis=1, keepdims=True)
        vmin = np.asarray(v).min(axis=1, keepdims=True)
        o = np.asarray(out)
        assert (o <= vmax + 1e-4).all() and (o >= vmin - 1e-4).all()

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1))
    def test_quant_roundtrip_error_bound_random(self, seed):
        x = jax.random.normal(jax.random.key(seed), (64, 256)) * \
            (10.0 ** jax.random.uniform(jax.random.key(seed + 1), (), minval=-3, maxval=3))
        q, s = ref.quant_ref(np.asarray(x))
        back = ref.dequant_ref(q, s)
        blocks = np.asarray(x).reshape(64, -1, 128)
        bound = np.abs(blocks).max(-1, keepdims=True) / 127.0 * 0.5 + 1e-9
        err = np.abs(np.asarray(back).reshape(64, -1, 128) - blocks)
        # Slack of one f32 ulp of each element (the rounding of q * s):
        # a fixed 1e-6 is below f32 resolution once |x| passes ~8.
        assert (err <= bound + np.spacing(np.abs(blocks))).all()

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 1000))
    def test_rglru_decay_bounds_state(self, seed):
        """With |a|<1 and bounded inputs, the linear scan stays bounded by
        max|b|/(1-max|a|) + |h0|."""
        key = jax.random.key(seed)
        a = jax.nn.sigmoid(jax.random.normal(key, (2, 128, 64)))
        a = jnp.minimum(a, 0.95)
        b = jax.random.normal(jax.random.key(seed + 1), (2, 128, 64))
        h0 = jnp.zeros((2, 64))
        h = ref.rglru_ref(a, b, h0)
        bound = float(jnp.max(jnp.abs(b))) / (1 - 0.95) + 1e-3
        assert float(jnp.max(jnp.abs(h))) <= bound


class TestDataPipelineProperties:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 10_000), st.integers(0, 100))
    def test_batches_are_pure_functions_of_state(self, seed, step):
        from repro.data import SyntheticLM, DataConfig
        cfg = DataConfig(vocab_size=512, batch=2, seq_len=16, seed=seed)
        d1 = SyntheticLM(cfg, step=step)
        d2 = SyntheticLM(cfg, step=step)
        b1, b2 = d1.peek(), d2.peek()
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))
        # tokens in range
        t = np.asarray(b1["tokens"])
        assert (t >= 0).all() and (t < 512).all()

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 50), st.integers(1, 20))
    def test_restore_resumes_exact_stream(self, start, advance):
        from repro.data import SyntheticLM, DataConfig
        cfg = DataConfig(vocab_size=128, batch=2, seq_len=8, seed=7)
        d = SyntheticLM(cfg, step=start)
        state = d.state()
        stream1 = [np.asarray(next(d)["tokens"]) for _ in range(advance)]
        d2 = SyntheticLM(cfg)
        d2.restore(state)
        stream2 = [np.asarray(next(d2)["tokens"]) for _ in range(advance)]
        for a, b in zip(stream1, stream2):
            np.testing.assert_array_equal(a, b)


class TestShardingProperties:
    @settings(max_examples=30, deadline=None)
    @given(st.sampled_from(["batch", "vocab", "heads", "mlp", "experts"]),
           st.integers(1, 64))
    def test_resolution_never_breaks_divisibility(self, name, dim):
        from repro.parallel.sharding import resolve_pspec
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(len(jax.devices()))
        spec = resolve_pspec((name,), mesh, shape=(dim,))
        size = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                size *= mesh.shape[a]
        assert dim % size == 0


class TestAdvisorQuantizationContract:
    """serve.fingerprint's tolerance contract, hypothesis-driven.

    For arbitrary platforms, the answer served from the quantized-key
    cache must cost at most ``(1 + cert_bound)`` times the exact
    per-request optimum in the served objective — with ``cert_bound``
    within the documented tolerance whenever the cache was allowed to
    serve it (uncertifiable cells fall back to exact solves, so the
    contract holds unconditionally).  The seeded-random sweep (including
    multilevel (T, m)) lives in tests/test_advisor.py.
    """

    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(ckpt_params, power_params,
           st.sampled_from(["time", "energy"]))
    def test_cached_answer_within_documented_tolerance(self, ck, pw, obj):
        from repro.serve import AdviceRequest, AdvisorService, Quantization
        from repro.sim.sweep import (energy_final_batched,
                                     time_final_batched)

        req = AdviceRequest.from_params(ck, pw, objective=obj)
        quant = AdvisorService(cache_name=None)
        exact = AdvisorService(
            quantization=Quantization(rel=0.0, absolute=0.0),
            cache_name=None)
        a, t = quant.advise(req), exact.advise(req)
        assume(a.valid and t.valid)
        if not a.exact:
            assert a.cert_bound <= quant.quant.tol

        p = dict(C=ck.C, R=ck.R, D=ck.D, mu=ck.mu, omega=ck.omega,
                 P_static=pw.P_static, P_cal=pw.P_cal, P_io=pw.P_io,
                 P_down=pw.P_down)
        J = (time_final_batched if obj == "time"
             else energy_final_batched)
        assert float(J(a.period, p)) <= float(J(t.period, p)) * (
            1.0 + max(a.cert_bound, 1e-12))
