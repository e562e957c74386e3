"""Advisor-service tests: batching/caching semantics, the quantization
tolerance contract, admission batching, and the serving CLIs.

The load-bearing guarantees (ISSUE 6 acceptance criteria):
  * batched == sequential answers, bit-identical at fixed seed;
  * cache hits serve within the documented quantization tolerance of an
    exact per-request solve (time, energy, and multilevel (T, m));
  * a burst of distinct requests is answered in ONE dispatched solve;
  * `--reduce/--no-reduce` actually toggles (the old store_true+default
    bug), and the advisor CLI smoke leg passes.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.serve import (AdvisorService, AdviceRequest, Quantization,
                         StoreTier, ThreadedAdvisor, exact_fingerprint,
                         fingerprint, quantize_request, run_open_loop,
                         synthetic_requests)
from repro.sim import cache_stats, reset_cache_stats
from repro.sim import sweep as sweep_mod

QUANT = Quantization()          # the documented defaults


def _same_advice(a, b) -> bool:
    """Bitwise equality of the served numbers (NaN == NaN)."""
    def eq(x, y):
        return x == y or (isinstance(x, float) and math.isnan(x)
                          and math.isnan(y))
    return (eq(a.period, b.period) and a.deep_every == b.deep_every
            and a.store == b.store
            and eq(a.predicted_wall, b.predicted_wall)
            and eq(a.predicted_energy, b.predicted_energy)
            and eq(a.T_time, b.T_time) and eq(a.T_energy, b.T_energy)
            and a.m_time == b.m_time and a.m_energy == b.m_energy)


def _mixed_workload(n=48, seed=7, repeat_frac=0.25):
    return synthetic_requests(n, seed=seed, two_tier_frac=0.5,
                              repeat_frac=repeat_frac)


class TestBatchingSemantics:
    def test_batched_equals_sequential_bit_identical(self):
        reqs = _mixed_workload()
        batched = AdvisorService(cache_name=None).advise_many(reqs)
        solo = AdvisorService(cache_name=None)
        for req, a in zip(reqs, batched):
            assert _same_advice(a, solo.advise(req)), req

    def test_burst_of_distinct_requests_is_one_dispatched_solve(self):
        reqs = synthetic_requests(64, seed=5, two_tier_frac=0.0)
        svc = AdvisorService(cache_name=None)
        svc.advise_many(reqs)
        assert svc.metrics()["dispatched_solves"] == 1

    def test_mixed_shapes_take_one_solve_per_shape(self):
        reqs = _mixed_workload(repeat_frac=0.0)
        assert {r.is_multilevel for r in reqs} == {False, True}
        svc = AdvisorService(cache_name=None)
        svc.advise_many(reqs)
        assert svc.metrics()["dispatched_solves"] == 2

    def test_heterogeneous_cadence_caps_batch_and_match_solo(self):
        base = synthetic_requests(6, seed=13, two_tier_frac=1.0)
        reqs = [dataclasses.replace(r, max_deep_every=cap)
                for r, cap in zip(base, (1, 2, 3, 5, 8, 12))]
        batched = AdvisorService(cache_name=None).advise_many(reqs)
        solo = AdvisorService(cache_name=None)
        for req, a in zip(reqs, batched):
            assert a.m_time <= req.max_deep_every
            assert a.m_energy <= req.max_deep_every
            assert _same_advice(a, solo.advise(req)), req

    def test_deep_every_one_recommends_deep_tier_only(self):
        req = next(r for r in synthetic_requests(32, seed=2,
                                                 two_tier_frac=1.0))
        req = dataclasses.replace(req, max_deep_every=1)
        adv = AdvisorService(cache_name=None).advise(req)
        assert adv.deep_every == 1
        assert adv.store == req.deep.name

    def test_t_base_scales_predictions_not_period(self):
        svc = AdvisorService(cache_name=None)
        req = synthetic_requests(1, seed=21)[0]
        a1 = svc.advise(dataclasses.replace(req, T_base=1.0))
        a9 = svc.advise(dataclasses.replace(req, T_base=9.0))
        assert a9.period == a1.period
        assert a9.deep_every == a1.deep_every
        assert a9.predicted_wall == pytest.approx(9.0 * a1.predicted_wall)
        assert a9.predicted_energy == pytest.approx(
            9.0 * a1.predicted_energy)


class TestFingerprintCache:
    def test_fingerprint_ignores_objective_t_base_and_names(self):
        req = synthetic_requests(1, seed=3, two_tier_frac=1.0)[0]
        fp = fingerprint(req, QUANT)
        assert fingerprint(dataclasses.replace(req, objective="time"),
                           QUANT) == fp
        assert fingerprint(dataclasses.replace(req, T_base=123.0),
                           QUANT) == fp
        renamed = dataclasses.replace(
            req, tiers=tuple(dataclasses.replace(t, name=f"x{i}")
                             for i, t in enumerate(req.tiers)))
        assert fingerprint(renamed, QUANT) == fp

    def test_fingerprint_distinguishes_cadence_cap_and_process(self):
        req = synthetic_requests(1, seed=3, two_tier_frac=1.0)[0]
        fp = fingerprint(req, QUANT)
        assert fingerprint(dataclasses.replace(req, max_deep_every=3),
                           QUANT) != fp
        assert fingerprint(dataclasses.replace(req, process="weibull",
                                               process_param=0.7),
                           QUANT) != fp

    def test_quantize_is_idempotent(self):
        for req in synthetic_requests(8, seed=4, two_tier_frac=0.5):
            qr = quantize_request(req, QUANT)
            assert quantize_request(qr, QUANT) == qr
            assert fingerprint(qr, QUANT) == fingerprint(req, QUANT)

    def test_repeat_workload_hits_and_skips_solves(self):
        reqs = _mixed_workload(repeat_frac=0.0)
        svc = AdvisorService(cache_name=None)
        first = svc.advise_many(reqs)
        solves = svc.metrics()["dispatched_solves"]
        again = svc.advise_many(reqs)
        m = svc.metrics()
        assert m["dispatched_solves"] == solves      # all hits, no solve
        assert all(a.cache_hit for a in again)
        assert not any(a.cache_hit for a in first)
        for a, b in zip(first, again):
            assert _same_advice(a, b)
        fc = m["fingerprint_cache"]
        assert fc["hits"] >= len(reqs)
        assert fc["inserts"] == fc["size"] == len(
            {fingerprint(r, svc.quant) for r in reqs})

    def test_uncertifiable_cell_falls_back_to_exact_solve(self):
        # A coarse lattice (50% steps) cannot certify the tolerance, so
        # every answer must come from the exact-parameter path and match
        # the unquantized service bit for bit.
        coarse = Quantization(rel=0.5, absolute=0.25, tol=1e-2)
        reqs = _mixed_workload(n=12, repeat_frac=0.0)
        svc = AdvisorService(quantization=coarse, cache_name=None)
        exact = AdvisorService(quantization=Quantization(rel=0.0,
                                                         absolute=0.0),
                               cache_name=None)
        for a, req in zip(svc.advise_many(reqs), reqs):
            assert a.exact and a.cert_bound == 0.0
            assert _same_advice(a, exact.advise(req)), req
        assert svc.metrics()["fallback_requests"] == len(reqs)
        # identical repeats hit the zero-width exact entries
        again = svc.advise_many(reqs)
        assert all(a.cache_hit for a in again)

    def test_eviction_changes_no_answers(self):
        reqs = synthetic_requests(10, seed=17, two_tier_frac=0.0)
        big = AdvisorService(cache_name=None)
        tiny = AdvisorService(cache_size=2, cache_name=None)
        ref = big.advise_many(reqs)
        for _ in range(2):              # thrash the 2-entry cache
            for req, a in zip(reqs, tiny.advise_many(reqs)):
                pass
        for req, want in zip(reqs, ref):
            assert _same_advice(tiny.advise(req), want)
        assert tiny.metrics()["fingerprint_cache"]["evictions"] > 0


def _objective_values(req, period, deep_every):
    """Host closed-form (time, energy) of ``req`` at a served point."""
    if req.is_multilevel:
        ck, pw = req.multilevel_params()
        p = {"C1": ck.C1, "R1": ck.R1, "D1": ck.D1, "C2": ck.C2,
             "R2": ck.R2, "D2": ck.D2, "mu": ck.mu, "q": ck.q,
             "omega": ck.omega, "P_static": pw.P_static,
             "P_cal": pw.P_cal, "P_io1": pw.P_io1, "P_io2": pw.P_io2,
             "P_down": pw.P_down}
        m = float(deep_every)
        return (float(sweep_mod.ml_time_final_batched(period, m, p,
                                                      req.T_base)),
                float(sweep_mod.ml_energy_final_batched(period, m, p,
                                                        req.T_base)))
    ck, pw = req.single_params()
    p = {"C": ck.C, "R": ck.R, "D": ck.D, "mu": ck.mu, "omega": ck.omega,
         "P_static": pw.P_static, "P_cal": pw.P_cal, "P_io": pw.P_io,
         "P_down": pw.P_down}
    return (float(sweep_mod.time_final_batched(period, p, req.T_base)),
            float(sweep_mod.energy_final_batched(period, p, req.T_base)))


class TestQuantizationTolerance:
    """The documented contract: served objective within tol of exact.

    Seeded-random sweep over the synthetic platform distribution (single
    AND two-tier, both objectives); the hypothesis-driven variant lives
    in tests/test_property.py.
    """

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_served_objective_within_documented_tolerance(self, seed):
        reqs = synthetic_requests(64, seed=seed, two_tier_frac=0.5)
        quant = AdvisorService(cache_name=None)          # default lattice
        exact = AdvisorService(quantization=Quantization(rel=0.0,
                                                         absolute=0.0),
                               cache_name=None)
        served = quant.advise_many(reqs)
        truth = exact.advise_many(reqs)
        checked = 0
        for req, a, t in zip(reqs, served, truth):
            if not (a.valid and t.valid):
                continue
            if not a.exact:
                assert a.cert_bound <= quant.quant.tol
            # both objectives, each at ITS served operating point
            sv_t, _ = _objective_values(req, a.T_time, a.m_time)
            _, sv_e = _objective_values(req, a.T_energy, a.m_energy)
            op_t, _ = _objective_values(req, t.T_time, t.m_time)
            _, op_e = _objective_values(req, t.T_energy, t.m_energy)
            slack = max(a.cert_bound, 1e-12)
            assert sv_t <= op_t * (1.0 + slack), (req, sv_t, op_t)
            assert sv_e <= op_e * (1.0 + slack), (req, sv_e, op_e)
            checked += 1
        assert checked >= len(reqs) // 2     # the sweep must have teeth

    def test_cert_bound_is_conservative_for_cell_members(self):
        # Perturb each request within its own lattice cell: the exact
        # re-solve of the perturbed platform may improve on the served
        # answer by at most cert_bound.
        rng = np.random.default_rng(0)
        reqs = synthetic_requests(24, seed=9, two_tier_frac=0.5)
        svc = AdvisorService(cache_name=None)
        exact = AdvisorService(quantization=Quantization(rel=0.0,
                                                         absolute=0.0),
                               cache_name=None)
        served = svc.advise_many(reqs)
        for req, a in zip(reqs, served):
            if not a.valid or a.exact:
                continue
            # Perturb the cell's REPRESENTATIVE by under half a lattice
            # step, so the perturbed platform provably stays in the cell.
            rep = quantize_request(req, svc.quant)
            f = 1.0 + (rng.uniform(-0.49, 0.49) * svc.quant.rel)
            pert = dataclasses.replace(
                rep, mu=rep.mu * f, T_base=req.T_base,
                tiers=tuple(dataclasses.replace(t, C=t.C * f)
                            for t in rep.tiers))
            assert fingerprint(pert, svc.quant) == fingerprint(req,
                                                               svc.quant)
            b = svc.advise(pert)
            assert b.cache_hit and _same_advice(a, b)
            t = exact.advise(pert)
            if not t.valid:
                continue
            sv_t, _ = _objective_values(pert, b.T_time, b.m_time)
            _, sv_e = _objective_values(pert, b.T_energy, b.m_energy)
            op_t, _ = _objective_values(pert, t.T_time, t.m_time)
            _, op_e = _objective_values(pert, t.T_energy, t.m_energy)
            assert sv_t <= op_t * (1.0 + a.cert_bound + 1e-12)
            assert sv_e <= op_e * (1.0 + a.cert_bound + 1e-12)


class TestThreadedAdvisor:
    def test_concurrent_submissions_match_direct_service(self):
        reqs = _mixed_workload(n=32, repeat_frac=0.3)
        want = AdvisorService(cache_name=None).advise_many(reqs)
        with ThreadedAdvisor(AdvisorService(cache_name=None),
                             batch_window_s=5e-3) as advisor:
            futs = [advisor.submit(r) for r in reqs]
            got = [f.result(timeout=60) for f in futs]
            m = advisor.metrics()
        assert m["windows"] >= 1
        assert m["requests"] == len(reqs)
        for a, b in zip(want, got):
            assert _same_advice(a, b)

    def test_zero_window_still_serves(self):
        req = synthetic_requests(1, seed=1)[0]
        with ThreadedAdvisor(AdvisorService(cache_name=None),
                             batch_window_s=0.0) as advisor:
            assert advisor.advise(req).period > 0

    def test_close_is_idempotent_and_rejects_new_work(self):
        advisor = ThreadedAdvisor(AdvisorService(cache_name=None))
        advisor.close()
        advisor.close()
        with pytest.raises(RuntimeError):
            advisor.submit(synthetic_requests(1, seed=1)[0])

    def test_invalid_knobs_rejected(self):
        with pytest.raises(ValueError):
            ThreadedAdvisor(AdvisorService(cache_name=None),
                            batch_window_s=-1.0)
        with pytest.raises(ValueError):
            ThreadedAdvisor(AdvisorService(cache_name=None), max_batch=0)


class TestLoadGenerator:
    def test_open_loop_reports_throughput_and_hits(self):
        reqs = synthetic_requests(40, seed=9, two_tier_frac=0.5,
                                  repeat_frac=0.5)
        with ThreadedAdvisor(AdvisorService(cache_name=None),
                             batch_window_s=2e-3) as advisor:
            rep = run_open_loop(advisor, reqs, rate_hz=2000.0,
                                warmup=synthetic_requests(8, seed=10))
        assert rep.n == 40 and rep.rps > 0.0
        assert rep.hit_rate > 0.0            # repeated workload must hit
        assert 0.0 <= rep.p50_ms <= rep.p99_ms <= rep.max_ms
        assert rep.windows >= 1
        assert rep.summary()["rps"] == rep.rps

    def test_synthetic_requests_deterministic_and_shaped(self):
        a = synthetic_requests(32, seed=6, two_tier_frac=0.5,
                               repeat_frac=0.25)
        b = synthetic_requests(32, seed=6, two_tier_frac=0.5,
                               repeat_frac=0.25)
        assert a == b
        assert any(r.is_multilevel for r in a)
        assert any(not r.is_multilevel for r in a)
        fps = [fingerprint(r, QUANT) for r in a]
        assert len(set(fps)) < len(fps)      # repeat_frac produced dups


class TestSchemaValidation:
    def test_rejects_bad_requests(self):
        tier = StoreTier(name="pfs", C=60.0, R=60.0, D=0.0, P_io=10.0)
        with pytest.raises(ValueError):
            AdviceRequest(mu=0.0, tiers=(tier,))
        with pytest.raises(ValueError):
            AdviceRequest(mu=100.0, tiers=())
        with pytest.raises(ValueError):
            AdviceRequest(mu=100.0, tiers=(tier, tier, tier))
        with pytest.raises(ValueError):
            AdviceRequest(mu=100.0, tiers=(tier,), objective="carbon")
        with pytest.raises(ValueError):
            AdviceRequest(mu=100.0, tiers=(tier,), T_base=-1.0)
        with pytest.raises(ValueError):
            AdviceRequest(mu=100.0, tiers=(tier,), max_deep_every=0)
        with pytest.raises(ValueError):
            StoreTier(name="bad", C=-1.0, R=0.0, D=0.0, P_io=0.0)
        with pytest.raises(ValueError):
            StoreTier(name="bad", C=1.0, R=0.0, D=0.0, P_io=0.0, q=1.5)

    def test_exact_fingerprint_zero_width(self):
        req = synthetic_requests(1, seed=1)[0]
        assert exact_fingerprint(req) != exact_fingerprint(
            dataclasses.replace(req, mu=req.mu * (1.0 + 1e-12)))


class TestCacheStatsRegistry:
    def test_named_caches_expose_counters(self):
        reset_cache_stats()
        svc = AdvisorService(cache_name="serve.fingerprints")
        reqs = synthetic_requests(8, seed=14, two_tier_frac=0.0)
        svc.advise_many(reqs)
        svc.advise_many(reqs)
        stats = cache_stats()
        assert "dispatch.runners" in stats
        assert "engine.device_samplers" in stats
        fp = stats["serve.fingerprints"]
        assert fp["hits"] > 0 and fp["inserts"] > 0
        assert fp["lookups"] == fp["hits"] + fp["misses"]
        assert svc.metrics()["caches"]["serve.fingerprints"][
            "hits"] == fp["hits"]
        reset_cache_stats()
        assert cache_stats()["serve.fingerprints"]["lookups"] == 0

    def test_runner_cache_counts_hits_across_calls(self):
        reset_cache_stats()
        from repro.sim import evaluate_grid
        from repro.sim.scenarios import mu_rho_grid
        grid = mu_rho_grid([300.0, 600.0], [2.0, 5.0])
        evaluate_grid(grid)
        evaluate_grid(grid)
        runners = cache_stats()["dispatch.runners"]
        assert runners["lookups"] >= 2
        assert runners["hits"] >= 1


class TestServeCLI:
    def test_reduce_flag_can_be_disabled(self):
        from repro.launch.serve import build_parser
        assert build_parser().parse_args([]).reduce is True
        assert build_parser().parse_args(["--reduce"]).reduce is True
        assert build_parser().parse_args(["--no-reduce"]).reduce is False

    def test_advisor_parser_defaults(self):
        from repro.launch.serve import build_advisor_parser
        args = build_advisor_parser().parse_args([])
        assert args.requests == 512 and not args.smoke
        args = build_advisor_parser().parse_args(
            ["--smoke", "--rate", "500", "--repeat-frac", "0.5"])
        assert args.smoke and args.rate == 500.0

    def test_advisor_smoke_leg_passes(self):
        from repro.launch.serve import main
        rep = main(["advisor", "--smoke"])
        assert rep.rps > 0.0 and rep.hit_rate > 0.0


class TestAdvisorBenchGate:
    def test_committed_baseline_gates_advisor_rps(self):
        import json
        from pathlib import Path
        from benchmarks.bench_sweep import CANONICAL, check_regression
        baseline = json.loads(Path(CANONICAL).read_text())
        entry = baseline["advisor_rps"]
        assert entry["speedup_warm"] >= 20.0         # acceptance floor
        assert entry["n_requests"] == 512
        assert {"rps", "p50_ms", "p99_ms"} <= set(entry)
        assert not entry.get("ungated")
        assert baseline["advisor_load_regimes"].get("ungated")
        # self-comparison passes; a 20x advisor regression trips the gate
        assert check_regression(baseline, baseline) == []
        bad = json.loads(json.dumps(baseline))
        bad["advisor_rps"]["speedup_warm"] = entry["speedup_warm"] / 20.0
        assert any("advisor_rps" in r
                   for r in check_regression(baseline, bad))
