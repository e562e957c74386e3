"""Sharded, memory-bounded dispatch layer (``repro.sim.dispatch``).

The contract under test: chunk size, shard count, memory budget, and the
persistent compile cache are PURE performance knobs — for a fixed seed
every grid entry point returns bit-identical results no matter how the
work is cut.  Multi-device (sharded) cases run in-process when the suite
itself is launched with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(the CI multi-device leg) and are skipped cleanly on a single-device
host; one subprocess test covers the sharded path even there.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import fig12_checkpoint, EXASCALE_POWER_RHO55
from repro.core.failures import (Exponential, LogNormal, TraceReplay,
                                 Weibull)
from repro.sim import (DispatchConfig, ParamGrid, evaluate_grid,
                       evaluate_multilevel_grid, evaluate_periods_grid,
                       get_scenario, mu_rho_grid, simulate_candidates,
                       simulate_trajectories, MultilevelParamGrid)
from repro.sim import dispatch as dsp

ROOT = Path(__file__).resolve().parents[1]

CK = fig12_checkpoint(300.0)
PW = EXASCALE_POWER_RHO55

PROCESSES = [Exponential(), Weibull(shape=0.7), LogNormal(sigma=1.0),
             TraceReplay(gaps=(30.0, 90.0, 300.0, 500.0))]

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (run with "
           "XLA_FLAGS=--xla_force_host_platform_device_count=N)")


def _mixed_grid(n=5):
    base = ParamGrid.from_params(CK, PW)
    mus = np.linspace(120.0, 2500.0, n)
    return ParamGrid(**{f: (mus if f == "mu" else np.broadcast_to(v, (n,)))
                        for f, v in base.fields().items()})


def _fields(tb):
    return {k: getattr(tb, k) for k in
            ("wall_time", "energy", "work_executed", "io_time", "down_time",
             "n_failures", "n_checkpoints", "truncated", "gaps_exhausted")}


# ---------------------------------------------------------------------------
# Chunk planning
# ---------------------------------------------------------------------------

class TestChunkPlan:
    def test_single_chunk_when_budget_suffices(self):
        cfg = DispatchConfig(memory_budget_bytes=1 << 30)
        assert dsp.chunk_plan(100, 1, 1024, cfg) == [(0, 100, 100)]

    def test_chunks_are_device_multiples_pow2(self):
        cfg = DispatchConfig(memory_budget_bytes=64 * 1024)
        for ndev in (1, 2, 4):
            plan = dsp.chunk_plan(1000, ndev, 1024, cfg)
            # full chunks share one ndev * 2^k shape
            sizes = {padded for _, _, padded in plan}
            for padded in sizes:
                assert padded % ndev == 0
                q = padded // ndev
                assert q & (q - 1) == 0
            # budget respected by the nominal chunk
            assert max(sizes) * 1024 <= 64 * 1024 or max(sizes) == ndev
            # coverage is exact and ordered
            assert plan[0][0] == 0 and plan[-1][1] == 1000
            for (a, b, _), (c, _d, _e) in zip(plan, plan[1:]):
                assert b == c

    def test_explicit_chunk_override(self):
        plan = dsp.chunk_plan(10, 1, 0, DispatchConfig(chunk=4))
        assert [(s, e) for s, e, _ in plan] == [(0, 4), (4, 8), (8, 10)]

    def test_sharded_whole_grid_pads_to_device_multiple(self):
        (start, stop, padded), = dsp.chunk_plan(
            7, 4, 0, DispatchConfig(memory_budget_bytes=1 << 30))
        assert (start, stop) == (0, 7) and padded == 8


class TestLaunch:
    """``launch`` is ``run`` in two halves: several dispatches launched
    before any is finished give what ``run`` gives, one chunk or many."""

    @staticmethod
    def _build(x, w, k):
        return {"y": x * w[:, None] + k, "n": (x > 3.0).astype(np.int32)}

    @pytest.mark.parametrize("chunk", [None, 3])
    def test_launched_ahead_equals_run(self, chunk):
        cfg = DispatchConfig(chunk=chunk, shard=False)
        x = np.arange(10.0).reshape(10, 1) * np.ones((1, 4))
        calls = [dict(key=("launch-test", k), build=self._build,
                      args=(x * (k + 1), np.arange(10.0), float(k)),
                      in_axes=(0, 0, None), out_axes=0, size=10,
                      config=cfg) for k in range(3)]
        finishes = [dsp.launch(**c) for c in calls]
        got = [f() for f in finishes]
        for c, g in zip(calls, got):
            want = dsp.run(**c)
            assert set(g) == {"y", "n"}
            for k in g:
                assert g[k].dtype == want[k].dtype
                np.testing.assert_array_equal(g[k], want[k])
        np.testing.assert_array_equal(
            got[2]["y"], 3 * x * np.arange(10.0)[:, None] + 2.0)


# ---------------------------------------------------------------------------
# Chunked == unchunked bit parity (single device)
# ---------------------------------------------------------------------------

class TestChunkedParity:
    def test_model_grid(self):
        # 150 points: larger than the 64-lane pad quantum, not a multiple
        # of it — chunk boundaries, tail padding, and the budget-driven
        # chunker all really engage.
        grid = mu_rho_grid(list(np.linspace(40, 900, 25)),
                           [2.0, 4.0, 5.5, 6.0, 7.0, 9.0])
        ref = evaluate_grid(grid)
        for cfg in (DispatchConfig(chunk=64),
                    DispatchConfig(chunk=100),
                    DispatchConfig(memory_budget_bytes=1 << 18)):
            out = evaluate_grid(grid, dispatch=cfg)
            for f in ("T_time", "T_energy", "Tf_energy", "E_time",
                      "time_ratio", "energy_ratio", "valid"):
                np.testing.assert_array_equal(
                    getattr(ref, f), getattr(out, f), err_msg=f)

    def test_model_grid_with_degenerate_points(self):
        # mu=20 is degenerate for C=10 (no valid period): the NaN/fallback
        # lanes must survive chunk boundaries and padding untouched.
        grid = mu_rho_grid([20, 60, 300], [5.5])
        ref = evaluate_grid(grid)
        out = evaluate_grid(grid, dispatch=DispatchConfig(chunk=2))
        assert not ref.valid[0, 0] and ref.valid[1, 0]
        np.testing.assert_array_equal(ref.valid, out.valid)
        np.testing.assert_array_equal(ref.T_energy, out.T_energy)

    def test_multilevel_grid(self):
        sc = get_scenario("multilevel_exascale")
        mg = MultilevelParamGrid.from_params(sc.ckpt, sc.power)
        mg = MultilevelParamGrid(**{
            f: (np.linspace(120.0, 900.0, 100) if f == "mu"
                else np.broadcast_to(v, (100,)))
            for f, v in mg.fields().items()})          # > one 64-lane chunk
        ref = evaluate_multilevel_grid(mg, m_values=(1, 2, 4))
        out = evaluate_multilevel_grid(mg, m_values=(1, 2, 4),
                                       dispatch=DispatchConfig(chunk=64))
        for f in ("T_time", "m_time", "T_energy", "m_energy", "E_by_m",
                  "Tf_by_m", "energy_vs_single"):
            np.testing.assert_array_equal(getattr(ref, f), getattr(out, f),
                                          err_msg=f)

    @pytest.mark.parametrize("proc", PROCESSES,
                             ids=lambda p: p.name)
    def test_engine_auto_sampled(self, proc):
        """Grid chunking, trial blocking, and tiny memory budgets leave a
        fixed seed's auto-sampled trajectories bit-identical — for every
        failure process (device samplers with traced parameters)."""
        grid = _mixed_grid()
        kw = dict(T_base=1500.0, n_trials=8, seed=3, process=proc)
        ref = simulate_trajectories(60.0, grid, **kw)
        for cfg in (DispatchConfig(chunk=2),
                    DispatchConfig(chunk=3),
                    DispatchConfig(memory_budget_bytes=1 << 18)):
            out = simulate_trajectories(60.0, grid, dispatch=cfg, **kw)
            for name, a in _fields(ref).items():
                np.testing.assert_array_equal(a, getattr(out, name),
                                              err_msg=name)

    def test_engine_auto_sampled_bulk_device_fallback(self):
        """A process implementing only the PR-4 ``sample_gaps`` device
        hook (no traced sampler) keeps its bulk device draws: results
        must match feeding ``presample_gaps_device`` output explicitly,
        and grid chunking stays a pure knob (whole-grid sampling + per-
        chunk slicing is partition-independent)."""
        from repro.sim import presample_gaps_device

        class BulkOnly(Weibull):
            name = "bulk_only"

            def traced_sampler(self):
                raise NotImplementedError

        grid = _mixed_grid()
        proc = BulkOnly(shape=0.7)
        kw = dict(T_base=1500.0, n_trials=6, seed=9, process=proc)
        ref = simulate_trajectories(60.0, grid, **kw)
        out = simulate_trajectories(60.0, grid,
                                    dispatch=DispatchConfig(chunk=2), **kw)
        np.testing.assert_array_equal(ref.wall_time, out.wall_time)
        # the stream really is the bulk device sampler's (threefry), not
        # the host numpy fallback's (PCG64)
        from repro.sim.engine import fail_capacity_points
        caps = fail_capacity_points(60.0, grid, 1500.0, process=proc)
        gaps = presample_gaps_device(grid, 6, int(caps.max()), seed=9,
                                     process=proc)
        want = simulate_trajectories(60.0, grid, T_base=1500.0, gaps=gaps)
        np.testing.assert_array_equal(ref.wall_time, want.wall_time)

    def test_engine_auto_sampled_host_fallback(self):
        """Processes without a jax sampler chunk via host schedule slices
        — same parity contract."""
        class Odd(Exponential):
            name = "odd"

            def sample_gaps(self, key, size, mean=None):
                raise NotImplementedError

            def traced_sampler(self):
                raise NotImplementedError
        grid = _mixed_grid()
        kw = dict(T_base=1500.0, n_trials=6, seed=1, process=Odd())
        ref = simulate_trajectories(60.0, grid, **kw)
        out = simulate_trajectories(60.0, grid,
                                    dispatch=DispatchConfig(chunk=2), **kw)
        np.testing.assert_array_equal(ref.wall_time, out.wall_time)

    def test_engine_explicit_schedule(self):
        from repro.sim import presample_gaps
        grid = _mixed_grid()
        gaps = presample_gaps(grid, 6, 256, seed=0)
        kw = dict(T_base=1500.0, gaps=gaps)
        ref = simulate_trajectories(60.0, grid, **kw)
        out = simulate_trajectories(
            60.0, grid, dispatch=DispatchConfig(
                chunk=2, memory_budget_bytes=1 << 16), **kw)
        for name, a in _fields(ref).items():
            np.testing.assert_array_equal(a, getattr(out, name),
                                          err_msg=name)

    @pytest.mark.parametrize("kind", ["event", "step"])
    def test_mc_candidates(self, kind):
        grid = _mixed_grid(4)
        Ts = np.array([40.0, 60.0, 90.0])
        kw = dict(T_base=1500.0, n_trials=6, seed=2,
                  process=Weibull(shape=0.7), engine_kind=kind)
        ref = simulate_candidates(Ts, grid, **kw)
        for cfg in (DispatchConfig(chunk=2),
                    # tiny budget: grid chunking AND trial blocking engage
                    # on the auto-sampled candidate path
                    DispatchConfig(memory_budget_bytes=1 << 17)):
            out = simulate_candidates(Ts, grid, dispatch=cfg, **kw)
            np.testing.assert_array_equal(ref.wall_time, out.wall_time)
            np.testing.assert_array_equal(ref.energy, out.energy)

    def test_mc_candidates_single_point_trial_blocking(self):
        """B == 1 (candidate-axis dispatch): a small budget must stream
        the trials axis without changing the sampled results."""
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        Ts = np.linspace(40.0, 90.0, 5)
        kw = dict(T_base=1500.0, n_trials=16, seed=2,
                  process=Weibull(shape=0.7))
        ref = simulate_candidates(Ts, grid, **kw)
        out = simulate_candidates(
            Ts, grid, dispatch=DispatchConfig(memory_budget_bytes=1 << 16),
            **kw)
        np.testing.assert_array_equal(ref.wall_time, out.wall_time)

    def test_mc_periods_grid(self):
        grid = _mixed_grid(3).reshape((3,))
        periods = np.stack([np.full(3, 50.0), np.full(3, 70.0)])
        kw = dict(T_base=1500.0, n_trials=6, seed=5)
        ref = evaluate_periods_grid(grid, Weibull(shape=0.7), periods, **kw)
        out = evaluate_periods_grid(grid, Weibull(shape=0.7), periods,
                                    dispatch=DispatchConfig(chunk=2), **kw)
        for k in ref:
            np.testing.assert_array_equal(ref[k], out[k], err_msg=k)

    def test_mc_surrogate_solver(self):
        from repro.core.optimal import MCSurrogate
        kw = dict(T_base=1500.0, n_trials=32, seed=0)
        a = MCSurrogate(CK, PW, Weibull(shape=0.7), **kw).argmin("time")
        b = MCSurrogate(CK, PW, Weibull(shape=0.7),
                        dispatch=DispatchConfig(chunk=4), **kw
                        ).argmin("time")
        assert a == b    # same CRN schedules, same dispatch-invariant sums


# ---------------------------------------------------------------------------
# Sharded == single-device (run under the CI multi-device leg)
# ---------------------------------------------------------------------------

@multi_device
class TestShardedParity:
    def test_model_grid_even_and_uneven(self):
        ndev = jax.device_count()
        for n_mu in (ndev, ndev + 3):      # divisible and padded
            grid = mu_rho_grid(list(np.linspace(60, 600, n_mu)), [5.5])
            ref = evaluate_grid(grid, dispatch=DispatchConfig(shard=False))
            out = evaluate_grid(grid)
            for f in ("T_time", "T_energy", "time_ratio", "energy_ratio"):
                np.testing.assert_array_equal(
                    getattr(ref, f), getattr(out, f), err_msg=f)

    @pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
    def test_engine_auto_sampled(self, proc):
        grid = _mixed_grid(jax.device_count() + 1)   # uneven: padding
        kw = dict(T_base=1500.0, n_trials=6, seed=3, process=proc)
        ref = simulate_trajectories(60.0, grid,
                                    dispatch=DispatchConfig(shard=False),
                                    **kw)
        out = simulate_trajectories(60.0, grid, **kw)
        for name, a in _fields(ref).items():
            np.testing.assert_array_equal(a, getattr(out, name),
                                          err_msg=name)

    def test_candidate_axis_sharding_single_point_grid(self):
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        Ts = np.linspace(40.0, 90.0, jax.device_count() + 2)
        kw = dict(T_base=1500.0, n_trials=6, seed=1,
                  process=Weibull(shape=0.7))
        ref = simulate_candidates(Ts, grid,
                                  dispatch=DispatchConfig(shard=False), **kw)
        out = simulate_candidates(Ts, grid, **kw)
        np.testing.assert_array_equal(ref.wall_time, out.wall_time)

    def test_sharding_composes_with_chunking(self):
        grid = mu_rho_grid(list(np.linspace(60, 600, 7)), [2.0, 5.5, 7.0])
        ref = evaluate_grid(grid, dispatch=DispatchConfig(shard=False))
        out = evaluate_grid(
            grid, dispatch=DispatchConfig(chunk=2 * jax.device_count()))
        np.testing.assert_array_equal(ref.T_energy, out.T_energy)


class TestShardedSubprocess:
    """Sharded parity proof that runs even on a single-device host: spawn
    an 8-virtual-device interpreter (device count must be fixed before
    jax initializes) and diff sharded vs shard=False results in there."""

    SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, r"%(src)s")
import numpy as np
import jax
from repro.sim import (DispatchConfig, evaluate_grid, mu_rho_grid,
                       simulate_trajectories, ParamGrid)
from repro.core import fig12_checkpoint, EXASCALE_POWER_RHO55
from repro.core.failures import Weibull

grid = mu_rho_grid(list(np.linspace(60, 600, 11)), [5.5])   # 11: uneven
ref = evaluate_grid(grid, dispatch=DispatchConfig(shard=False))
out = evaluate_grid(grid)
model_eq = bool(np.array_equal(ref.T_energy, out.T_energy, equal_nan=True)
                and np.array_equal(ref.energy_ratio, out.energy_ratio))

base = ParamGrid.from_params(fig12_checkpoint(300.0), EXASCALE_POWER_RHO55)
mus = np.linspace(120.0, 900.0, 11)
g2 = ParamGrid(**{f: (mus if f == "mu" else np.broadcast_to(v, (11,)))
                  for f, v in base.fields().items()})
kw = dict(T_base=1500.0, n_trials=4, seed=3, process=Weibull(shape=0.7))
r2 = simulate_trajectories(60.0, g2, dispatch=DispatchConfig(shard=False),
                           **kw)
o2 = simulate_trajectories(60.0, g2, **kw)
engine_eq = bool(np.array_equal(r2.wall_time, o2.wall_time)
                 and np.array_equal(r2.energy, o2.energy))
print(json.dumps({"n_devices": jax.device_count(),
                  "model_eq": model_eq, "engine_eq": engine_eq}))
"""

    @pytest.fixture(scope="class")
    def results(self):
        out = subprocess.run(
            [sys.executable, "-c", self.SCRIPT % {"src": str(ROOT / "src")}],
            capture_output=True, text=True, timeout=900, cwd=str(ROOT))
        assert out.returncode == 0, out.stderr[-3000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    def test_sharded_matches_single_device_on_eight_devices(self, results):
        assert results["n_devices"] == 8
        assert results["model_eq"] and results["engine_eq"]


# ---------------------------------------------------------------------------
# LRU caches (bounded compiled-callable caches)
# ---------------------------------------------------------------------------

class TestLRUCaches:
    def test_lru_evicts_least_recently_used(self):
        # reprolint: disable=RPL002 (anonymous on purpose: this probes raw eviction order without polluting the global cache_stats() registry)
        lru = dsp.LRUCache(2)
        lru.put("a", 1)
        lru.put("b", 2)
        assert lru.get("a") == 1          # refresh a
        lru.put("c", 3)                   # evicts b
        assert "b" not in lru and "a" in lru and "c" in lru
        assert len(lru) == 2

    def test_device_sampler_eviction_does_not_change_results(self):
        from repro.sim import engine as eng
        from repro.sim import presample_gaps_device
        grid = ParamGrid.from_params(CK, PW).reshape((1,))
        ref = np.asarray(presample_gaps_device(grid, 2, 16, seed=7,
                                               process=Weibull(shape=0.7)))
        # Flood the cache past its cap with distinct (process, size) pairs.
        for cap in range(1, eng.DEVICE_SAMPLER_CACHE_SIZE + 4):
            presample_gaps_device(grid, 1, cap, seed=0)
        assert len(eng._DEVICE_SAMPLERS) <= eng.DEVICE_SAMPLER_CACHE_SIZE
        # The (likely evicted) original sampler recompiles to the same
        # stream: eviction is a perf knob, not a semantic one.
        again = np.asarray(presample_gaps_device(grid, 2, 16, seed=7,
                                                 process=Weibull(shape=0.7)))
        np.testing.assert_array_equal(ref, again)

    def test_dispatch_runner_cache_is_bounded(self):
        assert isinstance(dsp._RUNNERS, dsp.LRUCache)
        assert dsp._RUNNERS.maxsize == dsp.RUNNER_CACHE_SIZE


# ---------------------------------------------------------------------------
# Persistent compile cache
# ---------------------------------------------------------------------------

#: a fresh interpreter that imports repro.sim, runs one sweep and prints
#: (cache directory JAX uses, cache hits, result) as JSON.
_CACHE_PROBE = """
import json, sys
sys.path.insert(0, r'%s')
import jax
hits = []
jax.monitoring.register_event_listener(
    lambda name, **_: hits.append(1)
    if name == '/jax/compilation_cache/cache_hits' else None)
from repro.sim import evaluate_grid, mu_rho_grid
r = evaluate_grid(mu_rho_grid([60, 300], [5.5]))
print(json.dumps([jax.config.jax_compilation_cache_dir, len(hits),
                  float(r.energy_ratio[0, 0])]))
""" % (ROOT / "src")


def _cache_probe(**env_extra):
    from repro.sim import cache as c
    env = {k: v for k, v in os.environ.items() if k != c.ENV_VAR}
    env.update(env_extra, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


class TestCompileCache:
    def test_cache_helper_writes_and_reuses_entries(self, tmp_path):
        """With ``$JAX_COMPILATION_CACHE_DIR`` set, two fresh interpreters
        share that directory: the first writes entries there, the second
        hits them and gets the identical result."""
        env = dict(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        first = _cache_probe(**env)
        second = _cache_probe(**env)
        assert first[0] == second[0] == str(tmp_path)
        assert any(tmp_path.iterdir())
        assert second[1] > 0
        assert first[2] == second[2]

    def test_env_var_autoenable(self, tmp_path, monkeypatch):
        """A directory placed from outside is JAX's to read: the helper
        reports it and sets no directory of its own."""
        from repro.sim import cache as c
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(c.ENV_VAR, str(tmp_path / "cc"))
        assert c.enable_compile_cache() == str(tmp_path / "cc")
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_dir_is_in_checkout(self):
        """Without the variable, every process uses the one fixed
        directory inside the checkout (and nothing under the home)."""
        from repro.sim import cache as c
        assert c.CHECKOUT_DIR == ROOT / ".jax_cache"
        assert _cache_probe()[0] == str(c.CHECKOUT_DIR)


class TestEnvKnobGuards:
    def test_malformed_dispatch_env_vars_warn_and_fall_back(self,
                                                            monkeypatch):
        monkeypatch.setenv("REPRO_SWEEP_DEVICES", "all")
        monkeypatch.setenv("REPRO_SWEEP_CHUNK", "64k")
        monkeypatch.setenv("REPRO_SWEEP_MEMORY_MB", "2GB")
        with pytest.warns(RuntimeWarning):
            cfg = dsp.default_config()
        assert cfg.devices is None and cfg.chunk is None
        with pytest.warns(RuntimeWarning):
            assert cfg.budget() == dsp.DEFAULT_MEMORY_BUDGET
        # and the entry points still run
        grid = mu_rho_grid([60, 300], [5.5])
        r = evaluate_grid(grid)
        assert np.isfinite(np.asarray(r.T_energy)).all()
