"""Traffic kind ``mc_sweep``: back-to-back Monte-Carlo sweeps of a grid.

One unit is one whole capacity-planning sweep through
``repro.sim.simulate_trajectories``: every grid point of the
configuration, its trials of failure-interrupted execution at the f64
AlgoE periods, under the configured failure process, each sweep from its
own seed.  Engine, dispatch and chunking are the program's defaults: the
in-program sampler, the capacity buckets and the engine scan do the work.

The check draws, from the run's seed, one sweep of the window and in it
``trials_per_point`` trials of every grid point plus the trajectory with
the most failures, and replays them through ``bench/reference.py`` on
the same threefry schedule.  It covers the sampler (the schedule stream),
the engine (every trajectory field) and dispatch (every result at its
point and trial, across buckets, chunks and shards).
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference as ref
from bench import work


class Workload:
    def __init__(self, config: dict, mix: dict, chips: int):
        from repro.core import Weibull
        from repro.sim import evaluate_grid, mu_rho_grid
        from repro.sim.dispatch import effective_devices

        self.config, self.mix, self.chips = config, mix, chips
        if config["failures"]["process"] != "weibull":
            raise ValueError("mc_sweep runs Weibull failures only")
        self.mus = [float(m) for m in config["mu_minutes"]]
        self.rhos = [float(r) for r in config["rho"]]
        self.grid = mu_rho_grid(self.mus, self.rhos,
                                alpha=config["platform"]["alpha"])
        self.T = np.asarray(evaluate_grid(
            self.grid, precision=config["precision"]).T_energy)
        self.shape = float(config["failures"]["shape"])
        self.process = Weibull(shape=self.shape)
        self.n_trials = int(config["trials_per_point"])
        self.T_base = float(config["job_minutes"])
        devices = effective_devices()
        if devices != chips:
            raise SystemExit(f"mc_sweep: the sweep mesh spans {devices} "
                             f"devices, the cell asks for {chips}")

    def unit(self, seed: int):
        from repro.sim import simulate_trajectories

        return simulate_trajectories(self.T, self.grid, self.T_base,
                                     n_trials=self.n_trials, seed=seed,
                                     process=self.process)

    def describe(self, out) -> dict:
        """Monte-Carlo accuracy of one sweep: the largest relative standard
        error of the mean wall time and energy over the grid."""
        n = out.wall_time.shape[-1]
        se = lambda a: (a.std(axis=-1, ddof=1) / math.sqrt(n)
                        / np.abs(a.mean(axis=-1)))
        return {"wall_time_se_rel_max": float(se(out.wall_time).max()),
                "energy_se_rel_max": float(se(out.energy).max()),
                "mean_failures": float(out.n_failures.mean())}

    def bytes_moved(self, out) -> int:
        return work.trajectory_bytes(out.n_failures)

    # -- the comparison -----------------------------------------------------

    def _params(self) -> dict:
        pf = self.config["platform"]
        pts = [ref.fig12_point(mu, rho, C=pf["C"], R=pf["R"], D=pf["D"],
                               omega=pf["omega"], alpha=pf["alpha"])
               for mu in self.mus for rho in self.rhos]
        return ref.stack(pts)

    def sample(self, outs: list, seed: int) -> dict:
        """Which sweep and which (point, trial) lanes the check replays."""
        rng = np.random.default_rng([seed, 0x5EED])
        u = int(rng.integers(len(outs)))
        unit_seed, out = outs[u]
        per = int(self.mix["check"]["trials_per_point"])
        B = self.grid.size
        pts = np.repeat(np.arange(B), per)
        trs = np.concatenate([rng.choice(self.n_trials, per, replace=False)
                              for _ in range(B)])
        nf = out.n_failures.reshape(B, self.n_trials)
        i, t = np.unravel_index(int(np.argmax(nf)), nf.shape)
        if not np.any((pts == i) & (trs == t)):
            pts, trs = np.append(pts, i), np.append(trs, t)
        return {"unit": u, "seed": unit_seed, "points": pts, "trials": trs}

    def reference(self, sample: dict, dtype=np.float64) -> dict:
        """The reference's periods and the sampled trajectories."""
        p = self._params()
        T = ref.algo_e(p, dtype)
        pts = sample["points"]
        gaps = ref.weibull_gaps_threefry(
            sample["seed"], pts, sample["trials"], p["mu"][pts],
            np.full(len(pts), self.shape),
            int(self.mix["check"]["reference_gaps"]))
        lane_p = {k: v[pts] for k, v in p.items()}
        traj = ref.simulate(T[pts], lane_p, self.T_base, gaps, dtype=dtype)
        return {"periods": T, "trajectories": traj}

    def extract(self, outs: list, sample: dict) -> dict:
        """The program's answers at the sampled lanes."""
        out = outs[sample["unit"]][1]
        B = self.grid.size
        pts, trs = sample["points"], sample["trials"]
        fields = ref.FLOAT_FIELDS + ref.COUNT_FIELDS + (
            "truncated", "gaps_exhausted")
        traj = {f: np.asarray(getattr(out, f)).reshape(
            B, self.n_trials)[pts, trs] for f in fields}
        return {"periods": self.T.ravel(), "trajectories": traj}

    def compare(self, got: dict, want: dict) -> dict:
        rel, bad = ref.trajectory_gaps(got["trajectories"],
                                       want["trajectories"])
        return {"periods_rel": ref.rel_gap(got["periods"], want["periods"]),
                "trajectory_rel": rel, "trajectory_mismatches": bad}
