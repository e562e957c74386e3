"""Discrete-event Monte-Carlo simulator of periodic non-blocking checkpointing.

Validates the paper's closed-form expectations (``model.time_final`` /
``model.energy_final``) by direct simulation: execution alternates compute
phases (length T - C, work rate 1) and checkpoint phases (length C, work rate
omega, I/O active).  A checkpoint *commits* the state as of the beginning of
its phase — the paper's semantics: the omega*C work done concurrently with a
checkpoint is only protected by the NEXT completed checkpoint.

Failure handling: downtime D (no progress), recovery R (I/O active), rollback
to the last committed state.

Failure schedule: the simulator maintains ``next_fail`` as an *absolute*
wall-clock time, fed from a schedule of inter-failure gaps under the renewal
convention shared with the batched engine (``repro.core.failures``): gap i
runs from the end of recovery i-1 (or t = 0) to failure i.  The schedule
comes from one of

  * ``gaps=...`` — a pre-sampled gap array (the batched engine's format;
    bit-identical trajectories for *every* distribution when both consume
    the same array),
  * ``process=...`` — any :class:`repro.core.failures.FailureProcess`,
    sampled lazily from ``rng`` (the default ``Exponential`` reproduces the
    legacy ``rng.exponential(mu)`` stream bit-for-bit),
  * a replaying ``rng`` such as :class:`repro.sim.engine.ScheduledRNG`
    (kept for backward compatibility).

A schedule that runs dry before the trajectory completes would silently
simulate the tail failure-free (biased); the simulator raises instead,
mirroring the batched engine's ``gaps_exhausted`` error.  Likewise the event
budget: exceeding ``max_events`` raises rather than returning a partial
trajectory.

:func:`simulate_once_ml` is the same phase machine for two-level (buddy +
PFS) checkpointing, read from a given schedule of ``(gap, hard)`` pairs:
the plain reference of ``sim.engine.simulate_trajectories_ml``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import numpy as np

from .failures import FailureProcess, as_process
from .params import (CheckpointParams, MultilevelCheckpointParams,
                     MultilevelPowerParams, PowerParams)


@dataclasses.dataclass
class SimResult:
    wall_time: float          # == paper's T_final
    energy: float             # == paper's E_final
    n_failures: int
    work_executed: float      # == paper's T_cal
    io_time: float            # == paper's T_io
    down_time: float          # == paper's T_down
    n_checkpoints: int


class _GapSource:
    """Uniform draw interface over the three schedule flavours above."""

    def __init__(self, rng, mu: float, process: Optional[FailureProcess],
                 gaps: Optional[Sequence] = None):
        self.exhausted = False
        if gaps is not None:
            self._gaps = np.asarray(gaps, dtype=np.float64).ravel()
            self._i = 0
            self._draw = self._from_array
        elif getattr(rng, "replays_schedule", False):
            # ScheduledRNG and friends: the schedule is already materialized
            # in the rng; `scale` is ignored by contract (gaps replay
            # verbatim), and exhaustion is reported via rng.exhausted.
            self._rng = rng
            self._mu = mu
            self._draw = self._from_replaying_rng
        else:
            # iter_gaps keeps sequential semantics per process (cyclic for
            # TraceReplay, lazy i.i.d. draws otherwise — one legacy-
            # identical rng call per gap for the exponential default).
            self._iter = as_process(process).iter_gaps(rng, mean=mu)
            self._draw = self._from_process

    def _from_array(self) -> float:
        if self._i >= self._gaps.size:
            self.exhausted = True
            return math.inf
        g = float(self._gaps[self._i])
        self._i += 1
        return g

    def _from_replaying_rng(self) -> float:
        g = float(self._rng.exponential(self._mu))
        if getattr(self._rng, "exhausted", False):
            self.exhausted = True
        return g

    def _from_process(self) -> float:
        return next(self._iter)

    def __call__(self) -> float:
        return self._draw()


def simulate_once(T: float, ckpt: CheckpointParams, power: PowerParams,
                  T_base: float, rng: np.random.Generator,
                  process: Optional[FailureProcess] = None,
                  gaps: Optional[Sequence] = None,
                  max_events: Optional[int] = None) -> SimResult:
    """One trajectory of the checkpointed execution.

    ``process`` selects the inter-failure distribution (None = the paper's
    exponential, sampled from ``rng`` exactly as the legacy code did);
    ``gaps`` overrides it with a pre-sampled schedule (the parity path).
    Raises ``RuntimeError`` when the event budget or a finite failure
    schedule is exhausted before ``T_base`` work completes — a partial or
    failure-free-tail trajectory is never silently returned as complete.
    """
    C, R, D, mu, omega = ckpt.C, ckpt.R, ckpt.D, ckpt.mu, ckpt.omega
    if T <= (1.0 - omega) * C:
        raise ValueError("period too short: no work progress per period")

    wall = 0.0
    committed = 0.0        # work protected by the last completed checkpoint
    live = 0.0             # work executed since (not yet all committed)
    work_exec = 0.0        # total CPU work units executed (incl. re-exec)
    io_time = 0.0
    down_time = 0.0
    n_fail = 0
    n_ckpt = 0

    draw_gap = _GapSource(rng, mu, process, gaps)
    next_fail = draw_gap()          # absolute: first renewal starts at t=0

    # Phase machine: 'compute' (duration T - C) or 'checkpoint' (duration C).
    phase = "compute"
    phase_left = T - C
    ckpt_snapshot = 0.0    # work value being written by the in-flight ckpt

    if max_events is None:
        max_events = int(50 * (T_base / max(T - (1 - omega) * C, 1e-9)
                               + T_base / mu + 100))
    for _ in range(max_events):
        if live >= T_base - 1e-12:
            break
        rate = 1.0 if phase == "compute" else omega
        # Work left until done mid-phase?
        t_done = ((T_base - live) / rate) if rate > 0 else math.inf
        t_next = min(phase_left, t_done)

        if wall + t_next < next_fail:
            # Phase segment completes without failure.
            wall += t_next
            live += rate * t_next
            work_exec += rate * t_next
            if phase == "checkpoint":
                io_time += t_next
            phase_left -= t_next
            if live >= T_base - 1e-12:
                break
            if phase_left <= 1e-12:
                if phase == "compute":
                    phase = "checkpoint"
                    phase_left = C
                    ckpt_snapshot = live     # state at ckpt start is written
                else:
                    committed = ckpt_snapshot
                    n_ckpt += 1
                    phase = "compute"
                    phase_left = T - C
        else:
            # Failure strikes mid-phase.
            dt = next_fail - wall
            wall = next_fail
            live += rate * dt
            work_exec += rate * dt
            if phase == "checkpoint":
                io_time += dt            # partially-written ckpt I/O is wasted
            n_fail += 1
            # Downtime + recovery; the failure clock renews at recovery end
            # (no failures strike during D/R — the convention both engines
            # share, exact for memoryless processes and the documented
            # schedule semantics for all others).
            wall += D
            down_time += D
            wall += R
            io_time += R
            live = committed
            phase = "compute"
            phase_left = T - C
            next_fail = wall + draw_gap()
    else:
        raise RuntimeError(
            f"simulator exceeded its event budget ({max_events} events) "
            f"before completing T_base={T_base} work — partial trajectories "
            f"are not returned (check params, or raise max_events)")

    if draw_gap.exhausted:
        raise RuntimeError(
            "failure schedule exhausted before the trajectory completed "
            "(tail would be simulated failure-free); provide a longer gaps "
            "schedule — mirrors the batched engine's gaps_exhausted error")

    energy = (power.P_static * wall + power.P_cal * work_exec
              + power.P_io * io_time + power.P_down * down_time)
    return SimResult(wall_time=wall, energy=energy, n_failures=n_fail,
                     work_executed=work_exec, io_time=io_time,
                     down_time=down_time, n_checkpoints=n_ckpt)


def simulate(T: float, ckpt: CheckpointParams, power: PowerParams,
             T_base: float, n_trials: int = 200,
             seed: int = 0,
             process: Optional[FailureProcess] = None) -> dict:
    """Monte-Carlo estimate (mean over trials) with standard errors."""
    # reprolint: disable=RPL001 (the scalar oracle is host-only reference code; engine parity checks feed it the engine's presampled schedule via ScheduledRNG)
    rng = np.random.default_rng(seed)
    walls, energies, fails = [], [], []
    cals, ios, downs = [], [], []
    for _ in range(n_trials):
        r = simulate_once(T, ckpt, power, T_base, rng, process=process)
        walls.append(r.wall_time)
        energies.append(r.energy)
        fails.append(r.n_failures)
        cals.append(r.work_executed)
        ios.append(r.io_time)
        downs.append(r.down_time)
    walls, energies = np.asarray(walls), np.asarray(energies)

    def mean_se(x):
        x = np.asarray(x, dtype=np.float64)
        return float(x.mean()), float(x.std(ddof=1) / math.sqrt(len(x)))

    out = {}
    for k, v in (("T_final", walls), ("E_final", energies), ("T_cal", cals),
                 ("T_io", ios), ("T_down", downs), ("n_failures", fails)):
        m, se = mean_se(v)
        out[k] = m
        out[k + "_se"] = se
    return out


@dataclasses.dataclass
class MultilevelSimResult:
    wall_time: float
    energy: float
    work_executed: float
    io1_time: float           # buddy level: writes + soft recoveries
    io2_time: float           # deep level: writes + hard recoveries
    down_time: float
    n_failures: int
    n_hard_failures: int
    n_ckpt1: int              # committed buddy checkpoints
    n_ckpt2: int              # committed deep checkpoints


def simulate_once_ml(T: float, m: int, ckpt: MultilevelCheckpointParams,
                     power: MultilevelPowerParams, T_base: float,
                     gaps: Sequence, hard: Sequence,
                     max_events: Optional[int] = None) -> MultilevelSimResult:
    """One two-level trajectory on the schedule ``(gaps[i], hard[i])``.

    The semantics of :class:`~repro.core.params.MultilevelCheckpointParams`
    as a phase machine, one step per phase segment or failure.  Every
    period lasts ``T``; period ``k`` of a superperiod (``k = 0..m-1``)
    computes for ``T - C_k`` and then writes its checkpoint for ``C_k`` at
    work rate ``omega_k``: the deep level (``C2``, ``omega2``) when
    ``k == m - 1``, the buddy level (``C1``, ``omega1``) otherwise.  A
    write commits the work as of its start, at its end; a deep write
    commits both levels.  Failure ``i`` strikes ``gaps[i]`` after the end
    of the previous recovery (or t = 0).  A soft one (``hard[i]`` false)
    costs ``D1 + R1``, rolls back to the last commit of either level and
    resumes at the period after it; a hard one costs ``D2 + R2``, rolls
    back to the last deep commit and restarts the superperiod at
    ``k = 0``.  Recovery time counts as I/O of its level, a write's time
    (wasted or not) as I/O of the level it writes.

    Raises like :func:`simulate_once` when the schedule or the event
    budget runs out before ``T_base`` work completes.
    """
    m = int(m)
    C1, R1, D1 = ckpt.C1, ckpt.R1, ckpt.D1
    C2, R2, D2 = ckpt.C2, ckpt.R2, ckpt.D2
    w1, w2 = ckpt.w1, ckpt.w2
    if m < 1:
        raise ValueError("deep-checkpoint cadence m must be >= 1")
    if T < max(C1, C2) or T <= ckpt.a(m):
        raise ValueError("period too short: no work progress per period")
    gaps = [float(g) for g in np.asarray(gaps, dtype=np.float64).ravel()]
    hard = [bool(h) for h in np.asarray(hard).ravel()]
    if len(hard) < len(gaps):
        raise ValueError("fewer level-loss flags than gaps")

    wall = committed1 = committed2 = live = 0.0
    work_exec = io1 = io2 = down = 0.0
    n_fail = n_hard = n_ckpt1 = n_ckpt2 = 0
    k = 0                             # period index inside the superperiod
    cost = lambda j: C2 if j == m - 1 else C1
    drawn = 1
    exhausted = not gaps
    next_fail = gaps[0] if gaps else math.inf
    in_ckpt = False
    phase_left = T - cost(k)
    snapshot = 0.0

    if max_events is None:
        max_events = int(50 * (T_base / (T - ckpt.a(m)) * m
                               + T_base / ckpt.mu * (m + 2) + 100))
    for _ in range(max_events):
        if live >= T_base - 1e-12:
            break
        deep = k == m - 1
        rate = (w2 if deep else w1) if in_ckpt else 1.0
        t_done = ((T_base - live) / rate) if rate > 0 else math.inf
        t_next = min(phase_left, t_done)

        if wall + t_next < next_fail:
            wall += t_next
            live += rate * t_next
            work_exec += rate * t_next
            if in_ckpt:
                if deep:
                    io2 += t_next
                else:
                    io1 += t_next
            phase_left -= t_next
            if live >= T_base - 1e-12:
                break
            if phase_left <= 1e-12:
                if not in_ckpt:
                    in_ckpt = True
                    phase_left = cost(k)
                    snapshot = live
                else:
                    committed1 = snapshot
                    if deep:
                        committed2 = snapshot
                        n_ckpt2 += 1
                    else:
                        n_ckpt1 += 1
                    k = (k + 1) % m
                    in_ckpt = False
                    phase_left = T - cost(k)
        else:
            h = hard[n_fail]
            dt = next_fail - wall
            wall = next_fail
            live += rate * dt
            work_exec += rate * dt
            if in_ckpt:
                if deep:
                    io2 += dt
                else:
                    io1 += dt
            n_fail += 1
            n_hard += h
            wall += D2 if h else D1
            down += D2 if h else D1
            wall += R2 if h else R1
            if h:                     # a soft failure resumes at k
                io2 += R2
                committed1 = committed2
                k = 0
            else:
                io1 += R1
            live = committed1
            in_ckpt = False
            phase_left = T - cost(k)
            if drawn < len(gaps):
                next_fail = wall + gaps[drawn]
            else:
                exhausted = True
                next_fail = math.inf
            drawn += 1
    else:
        raise RuntimeError(
            f"simulator exceeded its event budget ({max_events} events) "
            f"before completing T_base={T_base} work")
    if exhausted:
        raise RuntimeError(
            "failure schedule exhausted before the trajectory completed "
            "(tail would be simulated failure-free); provide a longer "
            "schedule")

    energy = (power.P_static * wall + power.P_cal * work_exec
              + power.P_io1 * io1 + power.P_io2 * io2
              + power.P_down * down)
    return MultilevelSimResult(
        wall_time=wall, energy=energy, work_executed=work_exec,
        io1_time=io1, io2_time=io2, down_time=down, n_failures=n_fail,
        n_hard_failures=n_hard, n_ckpt1=n_ckpt1, n_ckpt2=n_ckpt2)
