"""Traffic kind ``mc_sweep_ml``: back-to-back two-level Monte-Carlo sweeps.

One unit is one whole capacity-planning sweep of two-level (buddy + PFS)
checkpointing through ``repro.sim.simulate_trajectories_ml``: every grid
point (MTBF x partner-loss probability ``q``) of the configuration, its
trials of failure-interrupted execution at the f64 energy-optimal period
and PFS cadence ``(T*, m*)``, under the configured failure process, each
sweep from its own seed.  Engine and dispatch are the program's defaults:
the in-program sampler of gaps and level-loss flags, the capacity buckets
and the two-level event scan do the work.

The check draws, from the run's seed, one sweep of the window and in it
``trials_per_point`` trials of every grid point plus the trajectory with
the most failures, and replays them through ``bench/reference_ml.py`` on
the same threefry gaps and flags.  It covers the period solve (``T*`` and
``m*``), the sampler (both streams), the engine (every trajectory field)
and dispatch (every result at its point and trial, across buckets,
chunks and shards).
"""
from __future__ import annotations

import inspect
import math

import numpy as np

from bench import reference
from bench import reference_ml as ref

#: bytes of one f64 gap and of its one-byte level-loss flag.
GAP_BYTES, FLAG_BYTES = 8, 1

#: bytes one two-level trajectory writes: five f64 fields (wall time, work
#: executed, buddy and deep I/O, downtime), four int32 counts (failures,
#: hard failures, buddy and deep checkpoints) and two one-byte flags
#: (truncated, schedule exhausted).  Energy is formed on the host.
OUTPUT_BYTES = 5 * 8 + 4 * 4 + 2 * 1


def trajectory_bytes(n_failures) -> int:
    """Bytes moved by the two-level trajectories whose failure counts are
    given: a gap and a flag per failure plus the pair its completion
    outlives, and the output fields.  The same for any engine that
    produces the same trajectories."""
    n = np.asarray(n_failures, np.int64)
    return int((GAP_BYTES + FLAG_BYTES) * (n + 1).sum()
               + OUTPUT_BYTES * n.size)


class Workload:
    def __init__(self, config: dict, mix: dict, chips: int):
        from repro.core import Weibull
        from repro.sim import (MultilevelParamGrid, evaluate_multilevel_grid,
                               simulate_trajectories_ml)
        from repro.sim.dispatch import effective_devices

        if "process" not in inspect.signature(
                simulate_trajectories_ml).parameters:
            raise SystemExit("mc_sweep_ml: this program's two-level engine "
                             "samples no failure process")
        self.config, self.mix, self.chips = config, mix, chips
        if config["failures"]["process"] != "weibull":
            raise ValueError("mc_sweep_ml runs Weibull failures only")
        self.p = ref.platform_points(config)
        shape = (len(config["mu_minutes"]), len(config["q"]))
        self.grid = MultilevelParamGrid(
            omega=self.p["omega1"].reshape(shape),
            **{k: v.reshape(shape) for k, v in self.p.items()})
        self.m_values = tuple(range(1, int(config["m_max"]) + 1))
        self.T_base = float(config["job_minutes"])
        res = evaluate_multilevel_grid(self.grid, m_values=self.m_values,
                                       precision=config["precision"])
        if not np.all(res.valid):
            raise ValueError("mc_sweep_ml: a grid point has no valid period")
        self.T = np.asarray(res.T_energy)
        self.m = np.asarray(res.m_energy)
        self.shape = float(config["failures"]["shape"])
        self.process = Weibull(shape=self.shape)
        self.n_trials = int(config["trials_per_point"])
        devices = effective_devices()
        if devices != chips:
            raise SystemExit(f"mc_sweep_ml: the sweep mesh spans {devices} "
                             f"devices, the cell asks for {chips}")
        self.lane_steps = self._lane_steps()

    def _lane_steps(self) -> int:
        """Scan steps the sweep's lanes are given (each point's capacity +
        1, times its trials), from the program's own budget rule."""
        from repro.sim.engine import fail_capacity_points_ml

        caps = fail_capacity_points_ml(self.T.ravel(), self.m.ravel(),
                                       self.grid.ravel(), self.T_base,
                                       process=self.process)
        return int(((caps + 1) * self.n_trials).sum())

    def unit(self, seed: int):
        from repro.sim import simulate_trajectories_ml

        return simulate_trajectories_ml(self.T, self.m, self.grid,
                                        self.T_base, n_trials=self.n_trials,
                                        seed=seed, process=self.process)

    def describe(self, out) -> dict:
        """Monte-Carlo accuracy and work of one sweep: the largest relative
        standard error of the mean wall time and energy over the grid,
        mean failures, the share of them that were hard, mean deep
        checkpoints, and the scan's occupancy (live lane-steps, failures +
        1 a trajectory, over the steps its lanes were given)."""
        n = out.wall_time.shape[-1]
        se = lambda a: (a.std(axis=-1, ddof=1) / math.sqrt(n)
                        / np.abs(a.mean(axis=-1)))
        fails = int(out.n_failures.sum())
        return {"wall_time_se_rel_max": float(se(out.wall_time).max()),
                "energy_se_rel_max": float(se(out.energy).max()),
                "mean_failures": float(out.n_failures.mean()),
                "hard_share": (float(out.n_hard_failures.sum()) / fails
                               if fails else 0.0),
                "mean_deep_checkpoints": float(out.n_ckpt2.mean()),
                "scan_occupancy": ((fails + out.n_failures.size)
                                   / self.lane_steps)}

    def bytes_moved(self, out) -> int:
        return trajectory_bytes(out.n_failures)

    # -- the comparison -----------------------------------------------------

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
        """The reference's ``(T*, m*)`` and the sampled trajectories."""
        T, m = ref.algo_e(self.p, self.m_values, self.T_base, dtype)
        pts, trs = sample["points"], sample["trials"]
        n = int(self.mix["check"]["reference_gaps"])
        gaps = reference.weibull_gaps_threefry(
            sample["seed"], pts, trs, self.p["mu"][pts],
            np.full(len(pts), self.shape), n)
        hard = ref.threefry_flags(sample["seed"], pts, trs,
                                  self.p["q"][pts], n)
        lane_p = {k: v[pts] for k, v in self.p.items()}
        traj = ref.simulate(T[pts], m[pts], lane_p, self.T_base, gaps, hard,
                            dtype=dtype)
        return {"periods": T, "cadences": m, "trajectories": traj}

    def extract(self, outs: list, sample: dict) -> dict:
        """The program's answers at the sampled lanes."""
        out = outs[sample["unit"]][1]
        B = self.grid.size
        pts, trs = sample["points"], sample["trials"]
        fields = ref.FLOAT_FIELDS + ref.COUNT_FIELDS + ref.FLAG_FIELDS
        traj = {f: np.asarray(getattr(out, f)).reshape(
            B, self.n_trials)[pts, trs] for f in fields}
        return {"periods": self.T.ravel(), "cadences": self.m.ravel(),
                "trajectories": traj}

    def compare(self, got: dict, want: dict) -> dict:
        rel, bad = ref.trajectory_gaps(got["trajectories"],
                                       want["trajectories"])
        return {"periods_rel": ref.periods_gap(
                    got["periods"], got["cadences"], want["periods"],
                    want["cadences"]),
                "trajectory_rel": rel, "trajectory_mismatches": bad}
