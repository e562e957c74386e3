"""Traffic kind ``crn_solve``: back-to-back Monte-Carlo period solves.

One unit is one whole robustness solve through
``repro.sim.sweep_weibull_shapes``: for every (Weibull shape, MTBF) point
the process-optimal periods for time and for energy, found by
coarse-to-fine refinement over a candidate axis on one host-sampled
schedule shared by every candidate (common random numbers), then the
exponential-assumption periods (AlgoT, AlgoE, Young, Daly) scored on the
same schedule.  Each solve has its own seed.

The check draws, from the run's seed, one solve of the window and a few
of its points (always the point with the most failures per trajectory)
and solves them again in ``bench/reference.py``: the same schedule
stream, the same bracket, candidates and refinement rule, every
trajectory by the phase machine.  It compares the reported periods and
the means and penalties at them.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench import work


class Workload:
    def __init__(self, config: dict, mix: dict, chips: int):
        from repro.sim import DispatchConfig
        from repro.sim.dispatch import effective_devices

        self.config, self.mix, self.chips = config, mix, chips
        self.shapes = [float(k) for k in config["weibull_shapes"]]
        self.mus = [float(m) for m in config["mu_minutes"]]
        self.n_trials = int(config["trials_per_point"])
        self.n_cand = int(config["candidates"])
        self.rounds = int(config["rounds"])
        self.dispatch = DispatchConfig(precision=config["precision"])
        if effective_devices(self.dispatch) != chips:
            raise SystemExit("crn_solve: the sweep mesh does not span the "
                             "cell's chips")
        self.calls: list | None = None

    def unit(self, seed: int) -> dict:
        from repro.sim import sweep_weibull_shapes

        first = len(self.calls) if self.calls is not None else None
        r = sweep_weibull_shapes(self.shapes, self.mus,
                                 n_trials=self.n_trials, seed=seed,
                                 n_candidates=self.n_cand,
                                 rounds=self.rounds, dispatch=self.dispatch)
        B = r.grid.size
        return {"T_mc_time": r.T_mc_time.ravel(),
                "T_mc_energy": r.T_mc_energy.ravel(),
                "eval_periods": r.eval_periods.reshape(6, B),
                "wall_mc": r.wall_mc.ravel(), "energy_mc": r.energy_mc.ravel(),
                "wall_mc_se": r.wall_mc_se.ravel(),
                "energy_mc_se": r.energy_mc_se.ravel(),
                "penalties": np.stack([
                    r.time_penalty_exp.ravel(), r.energy_penalty_exp.ravel(),
                    r.time_penalty_young.ravel(), r.time_penalty_daly.ravel(),
                    r.energy_penalty_young.ravel(),
                    r.energy_penalty_daly.ravel()]),
                "bytes_moved": (sum(self.calls[first:]) if first is not None
                                and len(self.calls) > first else None)}

    def describe(self, out: dict) -> dict:
        """Monte-Carlo accuracy of one solve: the largest relative standard
        error of the mean wall time and energy at the optima."""
        return {"wall_time_se_rel_max": float(np.max(
                    out["wall_mc_se"] / out["wall_mc"])),
                "energy_se_rel_max": float(np.max(
                    out["energy_mc_se"] / out["energy_mc"]))}

    # -- bytes, from the engine calls of traced units -----------------------

    def record_engine_calls(self):
        """Count the bytes of every engine call from now on (traced runs
        only: wraps the engine's candidate entry point).  The count reads
        the failure counts of the call's result, which is already on the
        host, so it adds no device work and no wait."""
        from repro.sim import engine

        inner = engine.simulate_candidates
        self.calls = []

        def counted(*args, **kwargs):
            tb = inner(*args, **kwargs)
            self.calls.append(work.trajectory_bytes(tb.n_failures))
            return tb
        engine.simulate_candidates = counted

    def bytes_moved(self, out: dict) -> int | None:
        """Bytes of the unit's engine calls; None outside traced runs, and
        where the unit made no call through the wrapped entry point."""
        return out["bytes_moved"]

    # -- the comparison -----------------------------------------------------

    def _params(self) -> tuple[dict, np.ndarray]:
        pf = self.config["platform"]
        pts = [dict(pf, mu=mu) for _ in self.shapes for mu in self.mus]
        k = np.repeat(self.shapes, len(self.mus))
        return ref.stack(pts), k

    def sample(self, outs: list, seed: int) -> dict:
        rng = np.random.default_rng([seed, 0x5EED])
        u = int(rng.integers(len(outs)))
        p, k = self._params()
        Tt = ref.algo_t(p)
        T_base = np.maximum(30.0 * Tt, 10.0 * p["mu"])
        cv = np.maximum(1.0, ref.weibull_cv(k))
        longest = int(np.argmax(ref.expected_failures(Tt, p, T_base)
                                * cv * cv))
        n = int(self.mix["check"]["points"])
        others = rng.permutation([j for j in range(len(k)) if j != longest])
        pts = np.sort(np.append(others[:n - 1], longest)).astype(int)
        return {"unit": u, "seed": outs[u][0], "points": pts}

    def reference(self, sample: dict, dtype=np.float64) -> dict:
        """The reference solve of the sampled points, every quantity in
        ``dtype``."""
        p, k = self._params()
        p = {f: v.astype(dtype) for f, v in p.items()}
        pts = sample["points"]
        Tt, Te = ref.algo_t(p, dtype), ref.algo_e(p, dtype)
        Ty, Td = ref.young(p, dtype), ref.daly(p, dtype)
        lo0 = np.maximum((1.0 - p["omega"]) * p["C"], p["C"])
        hi0 = 2.0 * p["mu"] * (1.0 - (p["D"] + p["R"] + p["omega"] * p["C"])
                               / p["mu"])
        lo = np.maximum(lo0 * dtype(1.02), Tt / 6.0)
        hi = np.minimum(lo0 + dtype(0.75) * (hi0 - lo0), Tt * 6.0)
        T_base = np.maximum(30.0 * Tt, 10.0 * p["mu"])
        probes = lo[None, :] * (hi / lo)[None, :] ** np.linspace(
            0.0, 1.0, 9, dtype=dtype)[:, None]
        cap = ref.schedule_capacity(probes, p, T_base, k)
        gaps = ref.weibull_gaps_numpy(sample["seed"], p["mu"], k,
                                      self.n_trials, cap)[pts]
        sp = {f: v[pts] for f, v in p.items()}
        lo, hi, T_base = lo[pts], hi[pts], T_base[pts]
        S, n = len(pts), self.n_trials

        def means(xs):
            """Mean wall time and energy over trials at periods xs (M, S)."""
            M = xs.shape[0]
            lane_T = np.repeat(xs[:, :, None], n, axis=2).ravel()
            lane_p = {f: np.broadcast_to(v[None, :, None], (M, S, n)).ravel()
                      for f, v in sp.items()}
            lane_g = np.broadcast_to(gaps[None], (M,) + gaps.shape).reshape(
                M * S * n, -1)
            tb = ref.simulate(lane_T, lane_p, np.broadcast_to(
                T_base[None, :, None], (M, S, n)).ravel(), lane_g, dtype)
            if tb["truncated"].any() or tb["gaps_exhausted"].any():
                raise RuntimeError("reference solve: a trajectory did not "
                                   "finish on its schedule")
            shp = (M, S, n)
            return (tb["wall_time"].reshape(shp).mean(-1),
                    tb["energy"].reshape(shp).mean(-1))

        frac = np.linspace(0.0, 1.0, self.n_cand, dtype=dtype)[:, None]
        cols = np.arange(S)
        xs_t = lo[None, :] * (hi / lo)[None, :] ** frac
        xs_e = xs_t

        def shrink(xs, ys):
            i = np.argmin(ys, axis=0)
            lo2 = xs[np.maximum(i - 1, 0), cols]
            hi2 = xs[np.minimum(i + 1, self.n_cand - 1), cols]
            return lo2[None, :] + (hi2 - lo2)[None, :] * frac

        def score(xt, xe):
            wall_t, energy_t = means(xt)
            if xe is xt:
                return wall_t, energy_t
            return wall_t, means(xe)[1]

        for _ in range(self.rounds):
            wall_t, energy_e = score(xs_t, xs_e)
            xs_t, xs_e = shrink(xs_t, wall_t), shrink(xs_e, energy_e)
        wall_t, energy_e = score(xs_t, xs_e)
        T_mc_t = xs_t[np.argmin(wall_t, axis=0), cols]
        T_mc_e = xs_e[np.argmin(energy_e, axis=0), cols]
        cands = np.clip(np.stack([T_mc_t, T_mc_e, Tt[pts], Te[pts], Ty[pts],
                                  Td[pts]]), lo[None, :], hi[None, :])
        wall, energy = means(cands)
        return {"periods": cands,
                "means": np.concatenate([
                    wall[0], energy[1], wall[2] / wall[0],
                    energy[3] / energy[1], wall[4] / wall[0],
                    wall[5] / wall[0], energy[4] / energy[1],
                    energy[5] / energy[1]])}

    def extract(self, outs: list, sample: dict) -> dict:
        out = outs[sample["unit"]][1]
        pts = sample["points"]
        pen = out["penalties"][:, pts]
        return {"periods": out["eval_periods"][:, pts],
                "means": np.concatenate([out["wall_mc"][pts],
                                         out["energy_mc"][pts], *pen])}

    def compare(self, got: dict, want: dict) -> dict:
        return {"periods_rel": ref.rel_gap(got["periods"], want["periods"]),
                "means_rel": ref.rel_gap(got["means"], want["means"])}
