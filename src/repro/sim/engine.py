"""Batched Monte-Carlo trajectory engine (``jax.lax.scan`` machines).

The scalar event loop of ``repro.core.simulator.simulate_once`` rewritten as
fixed-shape scans so they can be ``vmap``-ed over trials and again over
parameter batches, and jitted in float64 (under the local ``enable_x64``
context — global JAX dtype state is untouched).

Two interchangeable kernels implement the same trajectory semantics
(``engine_kind=`` selects; see docs/simulation.md "Engine architecture"):

``event`` (default)
    One scan iteration per FAILURE.  Between consecutive failures the
    trajectory is closed-form — completed periods are an integer division
    of the inter-failure gap against the period, and the committed work,
    checkpoint I/O and wasted partial segment all follow arithmetically —
    so the scan length is the failure-schedule capacity (~ E[#failures]
    x gap-cv^2), not the per-phase event count.  For heavy-tailed
    (Weibull k < 1 / log-normal) processes this is 30-100x fewer
    iterations than the step machine, which is what made the PR-3 Weibull
    path ~3x SLOWER than the scalar oracle (BENCH_sweep.json's 0.32x).

``step``
    One scan iteration per phase segment or failure, mirroring the scalar
    loop body branch-for-branch — the original machine, kept as a
    cross-check and as the bit-level twin of the scalar oracle.

Both kernels consume the same pre-sampled gap schedules and produce
identical trajectories (exactly identical — not just statistically — when
every quantity is binary-representable, e.g. the dyadic-schedule parity
tests; within ~1e-13 relative rounding noise otherwise).  One caveat: a
gap landing EXACTLY on a period boundary in exact arithmetic (``g`` an
exact multiple of ``T`` with non-dyadic values — probability zero for
continuous processes, constructible with synthetic schedules) is a
genuine tie between "checkpoint committed" and "failure first"; the
event kernel resolves it by the documented failure-wins-ties rule in
exact arithmetic, while the step kernel's float-accumulated clock falls
on whichever side its rounding lands — the two may then differ by one
period's worth of committed work for that stretch.

Scan-state layout of the STEP kernel (one trajectory; all scalars):

    wall        f64  wall-clock time
    committed   f64  work protected by the last COMPLETED checkpoint
    live        f64  work executed since the last rollback point
    work_exec   f64  total CPU work executed (incl. re-execution)
    io_time     f64  cumulative I/O-active time (ckpt writes + recoveries)
    down_time   f64  cumulative downtime
    next_fail   f64  absolute time of the next failure
    phase_left  f64  time remaining in the current phase
    snapshot    f64  work value being written by the in-flight checkpoint
    phase       i32  0 = compute (rate 1), 1 = checkpoint (rate omega)
    n_fail      i32  failures so far
    n_ckpt      i32  committed checkpoints so far
    fail_idx    i32  next index into the pre-sampled failure-gap array
    done        bool trajectory reached T_base work

One scan step processes one *event* (phase-segment completion or failure),
mirroring the scalar loop body branch-for-branch; steps after ``done`` are
no-ops.  Checkpoint-commit semantics follow the paper: a checkpoint commits
the state as of the *beginning* of its phase, so the omega*C work done
concurrently is only protected by the NEXT completed checkpoint.

Failure times are consumed from a per-trajectory array of gaps.  Feeding
the same gaps to the scalar oracle via :class:`ScheduledRNG` reproduces
trajectories bit-for-bit — the parity tests rely on this.

Schedules come from one of two samplers: :func:`presample_gaps` (host
numpy, the CRN solvers' replayable schedules) or — the default
auto-sampling path — per-(grid point, trial) folded threefry keys fed to
``FailureProcess.traced_sampler`` *inside* each dispatch chunk, so the
``(B, n_trials, capacity)`` tensor never exists on the host, never pays a
per-call host->device transfer, and is bit-identical under every way of
cutting the work: every (point, trial) pair owns its key, and each
bucket draws its lanes' schedules at the bucket's capacity, a prefix of
the lane's stream (counter-based threefry makes that prefix independent
of how far the stream is drawn).  Budgets are per-grid-point and
bucketed to powers of two (:func:`fail_capacity_points` /
:func:`step_budget_points`): mixed-mu grids are dispatched bucket by
bucket so cheap points no longer pay the most fragile point's scan
length or draw.

Every single-level jitted call routes through :mod:`repro.sim.dispatch` —
multi-device grid-axis sharding over the 1-D sweep mesh, streaming chunks
bounded by a device-memory budget, trial-axis blocking, and LRU-bounded
compiled-runner caches.  All dispatch knobs are pure performance knobs:
chunk size, shard count, memory budget, budget bucketing, and
``engine_kind`` never change a fixed seed's results
(tests/test_dispatch.py).  The bulk :func:`presample_gaps_device` sampler
(single key, whole grid) is kept for direct use and CRN-style workflows.
The two-level engine (:func:`simulate_trajectories_ml`) takes the same
path: an event kernel (one step per failure), the fused sampler with a
second threefry stream for the level-loss flags, buckets and dispatch,
and its program is ``jit_build_ml`` (docs/simulation.md).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jax import enable_x64

from ..core.failures import as_process, level_loss_flags
from . import dispatch as _dispatch
from . import precision as _precision
from .scenarios import MultilevelParamGrid, ParamGrid

COMPUTE, CHECKPOINT = 0, 1

#: ``jax.named_scope`` names of the two stages of a Monte-Carlo program:
#: the traced failure sampler and the trajectory kernel (scan or Pallas),
#: whichever implements them.  They mark the ops' HLO metadata only.
SAMPLE_SCOPE, SCAN_SCOPE = "mc.sample", "mc.scan"

#: work-completion slack, identical to the scalar simulator's epsilon.
_EPS = 1e-12


class ScheduledRNG:
    """np.random.Generator stand-in replaying a fixed gap schedule.

    ``simulate_once(..., rng=ScheduledRNG(gaps))`` consumes exactly the
    pre-sampled inter-failure gaps the batched engine was given, enabling
    trajectory-for-trajectory parity checks — for *any* distribution the
    gaps were drawn from, since the schedule replays verbatim.

    Contract: the ``scale`` argument of :meth:`exponential` is deliberately
    **ignored** — the replayed gaps are already in wall-clock units (they
    were pre-scaled when sampled), and re-scaling them here would silently
    double-apply mu.  On exhaustion the draw is ``inf`` ("no more
    failures") and :attr:`exhausted` is set; the scalar simulator raises on
    that flag, mirroring the batched engine's ``gaps_exhausted`` error.
    """

    #: marks this rng as a schedule replay for ``core.simulator`` dispatch.
    replays_schedule = True

    def __init__(self, gaps):
        self._gaps = [float(g) for g in np.asarray(gaps).ravel()]
        self._i = 0
        self.exhausted = False

    def exponential(self, scale: float = 1.0) -> float:
        if self._i >= len(self._gaps):
            self.exhausted = True
            return math.inf          # schedule exhausted: no more failures
        g = self._gaps[self._i]
        self._i += 1
        return g


@dataclasses.dataclass(frozen=True)
class TrajectoryBatch:
    """Per-trajectory outputs, shape ``grid.shape + (n_trials,)``."""

    wall_time: np.ndarray        # paper's T_final
    energy: np.ndarray           # paper's E_final
    work_executed: np.ndarray    # paper's T_cal
    io_time: np.ndarray          # paper's T_io
    down_time: np.ndarray        # paper's T_down
    n_failures: np.ndarray
    n_checkpoints: np.ndarray
    truncated: np.ndarray        # scan budget exhausted before completion
    gaps_exhausted: np.ndarray   # failure schedule ran dry (tail simulated
                                 # as failure-free -> potentially biased)


def _run_one(T, C, R, D, omega, T_base, gaps, n_steps):
    """One trajectory: scalar parameter tracers + a (F,) gap vector.

    Failure times come entirely from ``gaps`` (pre-sampled with scale mu
    outside the scan), so mu itself never enters the kernel.
    """
    f64 = gaps.dtype
    n_gaps = gaps.shape[0]

    init = (jnp.zeros((), f64),            # wall
            jnp.zeros((), f64),            # committed
            jnp.zeros((), f64),            # live
            jnp.zeros((), f64),            # work_exec
            jnp.zeros((), f64),            # io_time
            jnp.zeros((), f64),            # down_time
            gaps[0],                       # next_fail
            T - C,                         # phase_left
            jnp.zeros((), f64),            # snapshot
            jnp.zeros((), jnp.int32),      # phase = COMPUTE
            jnp.zeros((), jnp.int32),      # n_fail
            jnp.zeros((), jnp.int32),      # n_ckpt
            jnp.ones((), jnp.int32),       # fail_idx (gaps[0] consumed)
            jnp.zeros((), jnp.bool_))      # done

    def step(carry, _):
        (wall, committed, live, work_exec, io_time, down_time,
         next_fail, phase_left, snapshot, phase,
         n_fail, n_ckpt, fail_idx, done) = carry

        in_ckpt = phase == CHECKPOINT
        rate = jnp.where(in_ckpt, omega, 1.0)
        t_done = jnp.where(rate > 0.0,
                           (T_base - live) / jnp.where(rate > 0.0, rate, 1.0),
                           jnp.inf)
        t_next = jnp.minimum(phase_left, t_done)
        no_fail = wall + t_next < next_fail

        # ---- branch A: the phase segment completes without failure ----
        wall_a = wall + t_next
        live_a = live + rate * t_next
        work_a = work_exec + rate * t_next
        io_a = io_time + jnp.where(in_ckpt, t_next, 0.0)
        left_a = phase_left - t_next
        finished = live_a >= T_base - _EPS
        boundary = jnp.logical_and(~finished, left_a <= _EPS)
        start_ckpt = jnp.logical_and(boundary, ~in_ckpt)
        end_ckpt = jnp.logical_and(boundary, in_ckpt)
        phase_a = jnp.where(start_ckpt, CHECKPOINT,
                            jnp.where(end_ckpt, COMPUTE, phase))
        left_a = jnp.where(start_ckpt, C, jnp.where(end_ckpt, T - C, left_a))
        snapshot_a = jnp.where(start_ckpt, live_a, snapshot)
        committed_a = jnp.where(end_ckpt, snapshot, committed)
        n_ckpt_a = n_ckpt + end_ckpt.astype(jnp.int32)

        # ---- branch B: a failure strikes mid-segment ----
        dt = next_fail - wall
        work_b = work_exec + rate * dt
        io_b = io_time + jnp.where(in_ckpt, dt, 0.0) + R
        wall_b = next_fail + D + R
        down_b = down_time + D
        gap = jnp.where(fail_idx < n_gaps,
                        gaps[jnp.minimum(fail_idx, n_gaps - 1)], jnp.inf)
        next_fail_b = wall_b + gap

        def sel(a_val, b_val):
            return jnp.where(no_fail, a_val, b_val)

        new = (sel(wall_a, wall_b),
               sel(committed_a, committed),
               sel(live_a, committed),          # failure rolls back to commit
               sel(work_a, work_b),
               sel(io_a, io_b),
               sel(down_time, down_b),
               sel(next_fail, next_fail_b),
               sel(left_a, T - C),
               sel(snapshot_a, snapshot),
               sel(phase_a, COMPUTE).astype(jnp.int32),
               sel(n_fail, n_fail + 1).astype(jnp.int32),
               sel(n_ckpt_a, n_ckpt).astype(jnp.int32),
               sel(fail_idx, fail_idx + 1).astype(jnp.int32),
               jnp.logical_or(done, jnp.logical_and(no_fail, finished)))

        keep = lambda old, upd: jnp.where(done, old, upd)
        return tuple(keep(o, u) for o, u in zip(carry, new)), None

    final, _ = lax.scan(step, init, None, length=n_steps)
    (wall, _committed, _live, work_exec, io_time, down_time,
     _nf, _pl, _snap, _phase, n_fail, n_ckpt, fail_idx, done) = final
    return {"wall_time": wall, "work_executed": work_exec,
            "io_time": io_time, "down_time": down_time,
            "n_failures": n_fail, "n_checkpoints": n_ckpt,
            "truncated": ~done,
            # fail_idx > n_gaps means an inf gap was drawn at some point,
            # i.e. part of the trajectory ran under "no more failures".
            "gaps_exhausted": fail_idx > n_gaps}


def _run_one_event(T, C, R, D, omega, T_base, gaps, n_steps):
    """One trajectory, one scan iteration per FAILURE (the fast kernel).

    Between consecutive failures the machine is deterministic, so the whole
    inter-failure stretch collapses to closed form.  With work-per-period
    ``w = T - (1-omega)C`` and remaining work ``rem``, completion from a
    segment start (t = 0 at the end of the previous recovery, live ==
    committed, compute phase) takes

        j    = floor((rem - eps) / w)          full periods, then
        r    = rem - j*w                       work in the finishing period,
        t_in = r                 if r <= T-C   (finishes mid-compute)
               T-C + (r-(T-C))/omega otherwise (mid-checkpoint),

    i.e. ``t_fin = j*T + t_in``.  A failure at gap ``g`` wins iff
    ``t_fin >= g`` (ties go to the failure, matching the step kernel's
    strict ``wall + t_next < next_fail``); it lands in period ``k+1`` with
    ``k = #{i >= 1 : i*T < g}`` completed checkpoints, at in-period offset
    ``u = g - k*T`` (compute if ``u <= T-C``, else mid-checkpoint), from
    which the executed work, wasted checkpoint I/O and new committed value
    follow directly.  Every arithmetic expression mirrors a step-kernel
    accumulation term-for-term, so the two kernels agree exactly whenever
    the quantities involved are exactly representable (the dyadic parity
    tests) and to rounding noise otherwise — except for the
    exact-period-boundary tie described in the module docstring, where
    this kernel applies failure-wins-ties in exact arithmetic (``k*T >= g``
    leaves the boundary checkpoint uncommitted) and the step kernel's
    accumulated clock resolves the tie by its own rounding.

    The ``eps`` in ``j`` reproduces the step kernel's completion slack
    (``live >= T_base - eps``): finishing exactly at a checkpoint boundary
    does NOT count that final checkpoint.

    Step ``i`` reads gap ``i`` by loop index: a lane still running at step
    ``i`` has ``n_fail == i`` (each step either ends it, frozen from then
    on, or counts one failure), and a finished lane discards what it
    reads.  So the schedule reaches the scan as ``xs``, padded with
    ``inf`` (or cut) to ``n_steps``; under the vmaps the scan moves it to
    a capacity-major ``(n_steps, lanes)`` slab once, and each step reads
    one contiguous row instead of gathering from the whole schedule.
    """
    f64 = gaps.dtype
    n_gaps = gaps.shape[0]
    slab = jnp.pad(gaps, (0, max(n_steps - n_gaps, 0)),
                   constant_values=jnp.inf)[:n_steps]
    in_ranges = jnp.arange(n_steps, dtype=jnp.int32) < n_gaps
    Tc = T - C                          # compute-segment length
    w = T - (1.0 - omega) * C           # work committed per full period
    omega_safe = jnp.where(omega > 0.0, omega, 1.0)

    init = (jnp.zeros((), f64),         # wall
            jnp.zeros((), f64),         # committed
            jnp.zeros((), f64),         # work_exec
            jnp.zeros((), f64),         # io_time
            jnp.zeros((), f64),         # down_time
            jnp.zeros((), jnp.int32),   # n_fail
            jnp.zeros((), jnp.int32),   # n_ckpt
            jnp.zeros((), jnp.bool_),   # used_inf (schedule ran dry)
            jnp.zeros((), jnp.bool_))   # done

    def step(carry, x):
        (wall, committed, work_exec, io_time, down_time,
         n_fail, n_ckpt, used_inf, done) = carry

        # One gap per inter-failure stretch, exactly like the step kernel's
        # one-draw-per-stretch accounting (the initial draw + one per
        # failure); reading past the schedule yields inf == "no more
        # failures" and flags exhaustion.  Gap i at step i (docstring).
        g, in_range = x

        # ---- closed-form completion time from this segment start ----
        rem = T_base - committed
        j = jnp.maximum(jnp.floor((rem - _EPS) / w), 0.0)
        r = rem - j * w                 # work inside the finishing period
        rr = r - Tc                     # its checkpoint-phase share (if > 0)
        t_in = jnp.where(rr > 0.0, Tc + rr / omega_safe, r)
        t_fin = j * T + t_in
        complete = t_fin < g

        # ---- branch A: completes before the next failure ----
        wall_a = wall + t_fin
        work_a = work_exec + rem
        io_a = io_time + j * C + jnp.maximum(rr, 0.0) / omega_safe

        # ---- branch B: failure at s = g after the segment start ----
        s = jnp.where(jnp.isfinite(g), g, 0.0)
        k = jnp.floor(s / T)
        # floor(s/T) can land ON k*T (exact-boundary failure: the
        # checkpoint ending at the failure instant does NOT commit) or one
        # above it (quotient rounded up); both correct downward.
        k = jnp.where((k > 0.0) & (k * T >= s), k - 1.0, k)
        u = s - k * T                   # offset inside the failing period
        uc = u - Tc                     # its checkpoint-phase share (if > 0)
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

    final, _ = lax.scan(step, init, (slab, in_ranges), length=n_steps)
    (wall, _committed, work_exec, io_time, down_time,
     n_fail, n_ckpt, used_inf, done) = final
    return {"wall_time": wall, "work_executed": work_exec,
            "io_time": io_time, "down_time": down_time,
            "n_failures": n_fail, "n_checkpoints": n_ckpt,
            "truncated": ~done,
            "gaps_exhausted": used_inf}


#: kernel registry: engine_kind -> per-trajectory scan.
_KERNELS = {"step": _run_one, "event": _run_one_event}

#: kinds that implement the EVENT-level trajectory semantics (one
#: iteration per failure) and share the event kernel's budget algebra.
#: ``"pallas"`` is the accelerator-native port of the event kernel
#: (kernels/event_sweep.py): bit-identical to ``"event"`` under the f64
#: policy, within the policy's documented tolerance otherwise.
_EVENT_LIKE = ("event", "pallas")

#: every selectable engine kind.
_ENGINE_KINDS = ("event", "pallas", "step")


def resolve_engine_kind(engine_kind: Optional[str] = None) -> str:
    """Resolve an ``engine_kind`` argument: None defers to
    ``$REPRO_ENGINE_KIND`` (the CI pallas-interpret leg forces the
    Pallas engine this way) and then to the ``"event"`` default;
    explicit kinds pass through.  Raises on unknown kinds."""
    if engine_kind is None:
        engine_kind = os.environ.get("REPRO_ENGINE_KIND", "").strip() \
            or "event"
    if engine_kind not in _ENGINE_KINDS:
        raise ValueError(f"unknown engine_kind {engine_kind!r}; "
                         f"one of {sorted(_ENGINE_KINDS)}")
    return engine_kind


def _engine_policy(engine_kind: str, cfg, precision):
    """The PrecisionPolicy an engine dispatch runs under — only the
    Pallas kernel is policy-aware (it is the accelerator path); the scan
    kernels ARE the f64 oracle and ignore the policy by design."""
    if engine_kind != "pallas":
        return None
    return _dispatch.resolve_precision(cfg, precision)


def _kind_token(kind: str, policy) -> object:
    """Runner-cache key component for (kind, policy): plain kinds keep
    their historical string token (compile-cache continuity); the
    policy-aware pallas kind never shares a compiled runner across
    policies."""
    return kind if policy is None else (kind, policy.name)


def _grid_fn(n_steps: int, kind: str, policy=None):
    """The unjitted (grid x trials) runner of one kernel — shared by the
    plain and the candidate-axis runners.  Scan kinds double-vmap the
    per-trajectory kernel; the pallas kind hands the whole chunk to the
    blocked Pallas kernel (interpret mode off-TPU)."""
    if kind == "pallas":
        from ..kernels import event_sweep as _es
        pol = policy if policy is not None else _precision.F64

        def run_grid(T, C, R, D, omega, T_base, gaps):
            with jax.named_scope(SCAN_SCOPE):
                return _es.event_sweep(T, C, R, D, omega, T_base, gaps,
                                       n_steps=n_steps, dtype=pol.dtype,
                                       compensated=pol.compensated)
        return run_grid
    kernel = _KERNELS[kind]

    def run_grid(T, C, R, D, omega, T_base, gaps):
        def one(t, c, r, d, o, tb, g):
            with jax.named_scope(SCAN_SCOPE):
                return kernel(t, c, r, d, o, tb, g, n_steps)
        over_trials = jax.vmap(one, in_axes=(None,) * 6 + (0,))
        over_grid = jax.vmap(over_trials, in_axes=(0,) * 6 + (0,))
        return over_grid(T, C, R, D, omega, T_base, gaps)
    return run_grid


def _cand_fn(n_steps: int, kind: str, policy=None):
    """Candidate-axis runner: run the grid runner once per candidate
    period with everything else held fixed — the gap schedules are
    SHARED across candidates, never tiled or re-transferred.  Scan kinds
    vmap the candidate axis; the pallas kind serializes it with
    ``lax.map`` (one pallas_call per candidate — batching a pallas_call
    under vmap has no kernel-level batching rule to win anything)."""
    run_grid = _grid_fn(n_steps, kind, policy)

    if kind == "pallas":
        def run_cands(T2, C, R, D, omega, T_base, gaps):
            # The candidate loop itself is scan work: its op carries the
            # scope as well as the kernel calls inside it.
            with jax.named_scope(SCAN_SCOPE):
                return lax.map(
                    lambda t: run_grid(t, C, R, D, omega, T_base, gaps), T2)
        return run_cands

    def run_cands(T2, C, R, D, omega, T_base, gaps):
        return jax.vmap(run_grid, in_axes=(0,) + (None,) * 6)(
            T2, C, R, D, omega, T_base, gaps)
    return run_cands


# ---------------------------------------------------------------------------
# Budget estimation
# ---------------------------------------------------------------------------

def _expected_failures(T, grid: ParamGrid, T_base) -> np.ndarray:
    """E[#failures] from the closed-form model, clipped to be usable even
    slightly outside the model's validity range."""
    a, b = grid.a, grid.b
    denom = (T - a) * (b - T / (2.0 * grid.mu))
    with np.errstate(divide="ignore", invalid="ignore"):
        tf = np.where(denom > 1e-12, T_base * T / denom, np.inf)
    # Divergent/degenerate points: fall back to a crude geometric bound.
    tf = np.where(np.isfinite(tf) & (tf > 0), tf, 50.0 * T_base)
    return tf / grid.mu


def _process_cv_points(process, size: int) -> np.ndarray:
    """Per-raveled-grid-point gap CV (shape ``(size,)``); 1.0 where the
    process declares no spread.  Array-valued shape parameters give each
    point ITS OWN margin instead of the grid-wide worst case."""
    if process is None:
        return np.ones(size, dtype=np.float64)
    cv = np.asarray(as_process(process).ravel().gap_cv(), dtype=np.float64)
    return np.broadcast_to(cv.ravel() if cv.ndim else cv, (size,))


def _pow2(n) -> np.ndarray:
    """Elementwise next power of two (>= 1), as int64."""
    n = np.maximum(np.asarray(n), 1).astype(np.int64)
    flat = np.array([1 << (int(v) - 1).bit_length() for v in n.ravel()],
                    dtype=np.int64)
    return flat.reshape(n.shape)


def _per_point(arr, size: int) -> np.ndarray:
    """Collapse a budget estimate to one value per raveled grid point.

    Candidate-period probe stacks (shape ``(..., size)``) reduce by max
    over their leading axes; anything not aligned with the grid (scalars,
    probe vectors over a size-1 grid) collapses to the overall max.
    """
    arr = np.asarray(arr, dtype=np.float64)
    if arr.ndim >= 1 and arr.shape[-1] == size:
        if arr.ndim > 1:
            arr = arr.max(axis=tuple(range(arr.ndim - 1)))
        return arr
    return np.broadcast_to(arr.max() if arr.ndim else arr, (size,))


def fail_capacity_points(T, grid: ParamGrid, T_base,
                         process=None) -> np.ndarray:
    """Per-grid-point schedule capacity (mean + 10 sigma margin), bucketed
    to powers of two; shape ``(grid.size,)``.

    For non-exponential processes both the expected count (clustered short
    gaps inflate rollbacks, hence wall time) and the count fluctuation
    (renewal CLT: var ~ nf * cv^2) scale with the gap CV.  Power-of-two
    bucketing keeps the number of distinct compiled programs O(log) while
    letting mixed-mu grids pay only their own point's budget (the engine
    dispatches one call per bucket) instead of the grid-wide worst case.
    """
    cv = np.maximum(1.0, _process_cv_points(process, grid.size))
    nf = _expected_failures(T, grid, T_base) * cv * cv
    cap = np.ceil(nf + 10.0 * cv * np.sqrt(nf + 1.0) + 10.0)
    return _pow2(_per_point(cap, grid.size))


def default_fail_capacity(T, grid: ParamGrid, T_base,
                          process=None) -> int:
    """Grid-wide schedule capacity: the worst point's bucketed budget (the
    shared-schedule callers — CRN solvers, explicit ``gaps=`` paths)."""
    return int(np.max(fail_capacity_points(T, grid, T_base,
                                           process=process)))


def step_budget_points(T, grid: ParamGrid, T_base,
                       process=None) -> np.ndarray:
    """Per-grid-point STEP-kernel scan length (expected events with a 2x +
    fluctuation margin), bucketed to powers of two; shape ``(grid.size,)``.

    This is the budget the event kernel exists to avoid: per failure it
    pays ~2 T/(T-a) phase events of re-execution, so heavy-tailed
    processes (cv > 1) inflate it by cv^2 TWICE — once through the failure
    count and once through the margin.
    """
    cv = np.maximum(1.0, _process_cv_points(process, grid.size))
    work_per_period = np.maximum(T - grid.a, 1e-9)
    periods = T_base / work_per_period
    nf = _expected_failures(T, grid, T_base) * cv * cv
    # Each failure costs one event plus re-execution of at most one period
    # of work (2 phase events per period, +2 for the partial segments).
    per_fail = 2.0 * np.maximum(T / work_per_period, 1.0) + 4.0
    events = 2.0 * periods + 2.0 + nf * per_fail
    margin = 10.0 * cv * np.sqrt(nf + 1.0) * per_fail
    steps = np.ceil(2.0 * events + margin + 64.0)
    return _pow2(_per_point(steps, grid.size))


def default_step_budget(T, grid: ParamGrid, T_base, process=None) -> int:
    """Grid-wide step-kernel scan length: the worst point's bucketed
    budget (shared-schedule callers)."""
    return int(np.max(step_budget_points(T, grid, T_base, process=process)))


def presample_gaps(grid: ParamGrid, n_trials: int, capacity: int,
                   seed: int = 0, process=None) -> np.ndarray:
    """Inter-failure gaps, shape ``(B, n_trials, capacity)``.

    ``process`` selects the distribution (None = exponential; an
    ``Exponential()`` instance reproduces the None path bit-for-bit).  The
    process's own mean, if unset, is the grid's per-point mu; array-valued
    shape parameters broadcast over the raveled grid (``process.ravel()``
    is applied to match ``grid.ravel()``).
    """
    rng = np.random.default_rng(seed)
    mu = grid.ravel().mu[:, None, None]
    size = (grid.size, n_trials, capacity)
    if process is None:
        return rng.exponential(scale=mu, size=size)
    return np.asarray(process.ravel().sample(rng, size=size, mean=mu),
                      dtype=np.float64)


#: bound on cached compiled device samplers.  A long-lived sweep service
#: touches a new (process identity, sample size) pair per distinct grid,
#: and an unbounded dict would leak one compiled callable per pair
#: forever; the LRU evicts the least recently used sampler instead —
#: eviction only forces a recompile on the next use, never changes
#: results (tested in tests/test_dispatch.py).
DEVICE_SAMPLER_CACHE_SIZE = 32

#: compiled device samplers, keyed by (process identity, sample size).
_DEVICE_SAMPLERS = _dispatch.LRUCache(DEVICE_SAMPLER_CACHE_SIZE,
                                      name="engine.device_samplers")


def presample_gaps_device(grid: ParamGrid, n_trials: int, capacity: int,
                          seed: int = 0, process=None):
    """Inter-failure gaps sampled ON DEVICE, shape ``(B, n_trials, capacity)``.

    jax-native counterpart of :func:`presample_gaps`: threefry streams and
    the processes' inverse-CDF transforms (``FailureProcess.sample_gaps``),
    jitted, float64 — the schedule never exists on the host and no
    host->device transfer happens.  Deterministic in ``seed``; NOT the
    same stream as the numpy sampler, only the same distribution.

    Raises ``NotImplementedError`` for processes without a device sampler —
    callers fall back to :func:`presample_gaps`.
    """
    proc = as_process(process).ravel()
    flat = grid.ravel()
    size = (flat.size, int(n_trials), int(capacity))
    tok = (proc.cache_token(), size)
    fn = _DEVICE_SAMPLERS.get(tok)
    with enable_x64():
        key = jax.random.PRNGKey(int(seed))
        mean = jnp.asarray(flat.mu, dtype=jnp.float64)[:, None, None]
        if fn is None:
            fn = jax.jit(lambda k, m: proc.sample_gaps(k, size, mean=m))
            out = fn(key, mean)     # NotImplementedError escapes un-cached
            _DEVICE_SAMPLERS.put(tok, fn)
            return out
        return fn(key, mean)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _normalize_gaps(gaps, size: int):
    """Normalize a caller-supplied schedule to ``(size, n_trials, F)``.

    Accepts numpy or device (jnp) arrays; device arrays stay on device
    (the CRN solvers keep their schedules resident and reuse them across
    calls without re-transferring).
    """
    xp = jnp if isinstance(gaps, jnp.ndarray) else np
    if xp is np:
        gaps = np.asarray(gaps, dtype=np.float64)
    if gaps.ndim == 1:
        gaps = gaps[None, None, :]
    if gaps.ndim == 2:
        gaps = gaps[None, :, :]
    return xp.broadcast_to(gaps, (size, gaps.shape[-2], gaps.shape[-1]))


def _scan_len(n: int) -> int:
    """Bucket a static scan length up to a power of two: extra steps are
    no-ops for both kernels, and bucketing keeps the jit cache at O(log)
    distinct programs instead of one compile per distinct value."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def _as_f64_gaps(gaps):
    """Coerce a schedule to f64 (a device schedule built OUTSIDE an x64
    context arrives as float32 and would abort the scan with an opaque
    carry-dtype error); device arrays stay on device."""
    if isinstance(gaps, jnp.ndarray):
        if gaps.dtype == jnp.float64:
            return gaps
        with enable_x64():        # upcasting outside x64 silently truncates
            return jnp.asarray(gaps, dtype=jnp.float64)
    return np.asarray(gaps, dtype=np.float64)


def _slab_slots(kind: str, n_steps: int) -> int:
    """Gap slots per trajectory that the event scan's capacity-major slab
    adds next to the schedule (:func:`_run_one_event`); the other kinds
    read the schedule where it lies."""
    return int(n_steps) if kind == "event" else 0


def _trial_chunk(n_trials: int, capacity: int, ndev: int, cfg) -> int:
    """Trials per dispatch: all of them, unless even one grid chunk row
    per device at the full trial count would blow the memory budget —
    then the trials axis streams in blocks (an outer host loop; the MC
    reductions happen host-side on the reassembled arrays, so the block
    size never changes results)."""
    per_trial = 8 * (capacity + 32)
    budget = _dispatch.resolve(cfg).budget()
    if ndev * n_trials * per_trial <= budget:
        return n_trials
    return max(1, min(n_trials, budget // (ndev * per_trial)))


def _dispatch_explicit(T_arr, flat: ParamGrid, Tb_arr, gaps, n_steps: int,
                       kind: str, cfg, policy=None) -> dict:
    """Explicit-schedule engine dispatch over a flat grid: the grid axis
    is chunked/sharded by :mod:`.dispatch`, the trials axis streamed in
    memory-bounded blocks; returns numpy ``(B, n_trials)`` per key."""
    B = flat.size
    gaps = _as_f64_gaps(gaps)
    n_trials = int(gaps.shape[-2])
    slots = int(gaps.shape[-1]) + _slab_slots(kind, n_steps)
    ndev = _dispatch.effective_devices(cfg)
    tc = _trial_chunk(n_trials, slots, ndev, cfg)
    parts = []
    for t0 in range(0, n_trials, tc):
        g = gaps[:, t0:t0 + tc, :]
        parts.append(_dispatch.run(
            key=("explicit", int(n_steps), _kind_token(kind, policy)),
            build=_grid_fn(int(n_steps), kind, policy),
            args=(T_arr, flat.C, flat.R, flat.D, flat.omega, Tb_arr, g),
            in_axes=(0,) * 7, out_axes=0, size=B,
            per_point_bytes=8 * min(tc, n_trials) * (slots + 32),
            config=cfg))
    if len(parts) == 1:
        return parts[0]
    return {k: np.concatenate([p[k] for p in parts], axis=1)
            for k in parts[0]}


def _sampled_build(proc_fn, cap_used: int, n_steps: int, kind: str,
                   policy=None):
    """Fused sample-then-simulate chunk kernel (the auto-sampling path).

    Point ``i``/trial ``t`` draws its schedule from the folded key
    ``fold_in(fold_in(key, i), t)`` at the bucket's capacity ``cap_used``:
    a prefix of the lane's stream, the same values whatever length the
    stream is drawn to (counter-based threefry) — so bucketing, chunking,
    sharding, and trial blocking are all pure performance knobs for a
    fixed seed.  The ``(chunk, trials, cap)`` schedule tensor only ever
    exists inside this jitted call.  The pallas kind samples through the
    SAME folded keys and then hands the materialized chunk schedule to
    the blocked kernel, so its draws are bit-identical to the scan
    kinds'.
    """
    if kind == "pallas":
        run_grid = _grid_fn(n_steps, kind, policy)

        def build(T, C, R, D, omega, Tb, mean, idx, t_idx, key, *params):
            def sample_point(m, i, *pp):
                kp = jax.random.fold_in(key, i)

                def sample_trial(ti):
                    return proc_fn(jax.random.fold_in(kp, ti),
                                   (cap_used,), m, pp)
                return jax.vmap(sample_trial)(t_idx)
            # One point per loop iteration: the loop writes the f64
            # schedule out before the kernel's cast and relayout.  Fused
            # into them (under vmap), the emulated-f64 sampler takes the
            # TPU compiler minutes instead of seconds.
            with jax.named_scope(SAMPLE_SCOPE):
                gaps = lax.map(lambda a: sample_point(*a),
                               (mean, idx) + tuple(params))
            return run_grid(T, C, R, D, omega, Tb, gaps)
        return build
    kernel = _KERNELS[kind]

    def build(T, C, R, D, omega, Tb, mean, idx, t_idx, key, *params):
        def per_point(t, c, r, d, o, tb, m, i, *pp):
            with jax.named_scope(SAMPLE_SCOPE):
                kp = jax.random.fold_in(key, i)

            def per_trial(ti):
                with jax.named_scope(SAMPLE_SCOPE):
                    g = proc_fn(jax.random.fold_in(kp, ti), (cap_used,),
                                m, pp)
                with jax.named_scope(SCAN_SCOPE):
                    return kernel(t, c, r, d, o, tb, g, n_steps)
            return jax.vmap(per_trial)(t_idx)
        return jax.vmap(per_point)(T, C, R, D, omega, Tb, mean, idx,
                                   *params)
    return build


def _bulk_schedule(flat: ParamGrid, n_trials: int, capacity: int,
                   seed: int, process):
    """Whole-grid auto-sampled schedule for processes WITHOUT a traced
    sampler: bulk device sampling (``FailureProcess.sample_gaps``) when
    the process has it, host numpy otherwise — the compatibility tiers
    below the fused pointwise path."""
    try:
        return presample_gaps_device(flat, n_trials, capacity, seed=seed,
                                     process=process)
    except NotImplementedError:
        return presample_gaps(flat, n_trials, capacity, seed=seed,
                              process=process)


def _sampler_inputs(proc, flat: ParamGrid, seed: int):
    """(token, per-point parameter arrays, sampler fn, per-point means,
    global indices, base key) of the pointwise auto-sampling contract."""
    token, params, fn = proc.traced_sampler()
    size = flat.size
    mean_arr = np.broadcast_to(
        np.asarray(proc.resolve_mean(flat.mu), dtype=np.float64), (size,))
    params_b = tuple(np.broadcast_to(np.asarray(p, dtype=np.float64),
                                     (size,)) for p in params)
    idx_all = np.arange(size, dtype=np.uint32)
    with enable_x64():
        key = jax.random.PRNGKey(int(seed))
    return token, params_b, fn, mean_arr, idx_all, key


def _assemble_batch(out: dict, grid: ParamGrid, n_trials: int,
                    lead: tuple = ()) -> TrajectoryBatch:
    """Reshape flat engine outputs to ``lead + grid.shape + (n_trials,)``
    and attach the energy integral (``lead`` is the candidate axis of
    :func:`simulate_candidates`)."""
    shp = lead + grid.shape + (n_trials,)
    bc = lambda x: x.reshape((1,) * len(lead) + grid.shape + (1,))
    wall = out["wall_time"].reshape(shp)
    work = out["work_executed"].reshape(shp)
    io = out["io_time"].reshape(shp)
    down = out["down_time"].reshape(shp)
    energy = (bc(grid.P_static) * wall + bc(grid.P_cal) * work
              + bc(grid.P_io) * io + bc(grid.P_down) * down)
    return TrajectoryBatch(
        wall_time=wall, energy=energy, work_executed=work, io_time=io,
        down_time=down,
        n_failures=out["n_failures"].reshape(shp),
        n_checkpoints=out["n_checkpoints"].reshape(shp),
        truncated=out["truncated"].reshape(shp),
        gaps_exhausted=out["gaps_exhausted"].reshape(shp))


@functools.partial(jax.profiler.annotate_function,
                   name="repro.mc.trajectories")
def simulate_trajectories(T, grid: ParamGrid, T_base: float = 1.0,
                          n_trials: int = 200, seed: int = 0,
                          gaps: Optional[np.ndarray] = None,
                          n_steps: Optional[int] = None,
                          process=None,
                          engine_kind: Optional[str] = None,
                          dispatch=None,
                          precision=None) -> TrajectoryBatch:
    """Simulate every (grid point x trial) trajectory in a few jitted calls.

    ``T`` broadcasts against ``grid.shape``.  ``gaps`` (grid.size, n_trials,
    F) overrides the pre-sampled failure schedule — pass the same schedule to
    the scalar oracle via :class:`ScheduledRNG` (or ``simulate_once(gaps=)``)
    for parity checks.  ``process`` (a
    :class:`repro.core.failures.FailureProcess`) selects the inter-failure
    distribution when the schedule is auto-sampled — on device via the
    process's jax sampler when it has one; the scans themselves are
    distribution-agnostic (they only consume gaps).

    ``engine_kind`` selects the kernel: ``"event"`` (default, one scan
    iteration per failure — the fast path), ``"pallas"`` (the
    accelerator-native Pallas port of the event kernel —
    ``kernels/event_sweep.py``; interpret mode off-TPU, precision per
    the resolved :class:`~repro.sim.precision.PrecisionPolicy`
    ``precision``, bit-identical to ``"event"`` under the f64 policy),
    or ``"step"`` (one iteration per phase event — the scalar oracle's
    bit-level twin, kept as a cross-check).  None defers to
    ``$REPRO_ENGINE_KIND`` and then ``"event"``.  When the schedule is
    auto-sampled, grid points are dispatched in power-of-two budget
    buckets so mixed-mu grids don't pay the worst point's scan length
    everywhere.

    Every jitted call routes through :mod:`repro.sim.dispatch`
    (``dispatch`` is its :class:`~repro.sim.dispatch.DispatchConfig`; None
    = environment defaults): the grid axis is sharded across the local
    devices and chunked to a device-memory budget, and the trials axis
    streams in memory-bounded blocks.  Auto-sampled schedules are drawn
    inside each chunk from per-(grid point, trial) folded keys at the
    bucket's capacity, a prefix of the lane's stream, so sharding/
    chunking/budget knobs — like the budget-bucketing knobs above —
    never change a fixed seed's results.
    """
    engine_kind = resolve_engine_kind(engine_kind)
    flat = grid.ravel()
    T_arr = np.broadcast_to(np.asarray(T, dtype=np.float64),
                            grid.shape).ravel()
    Tb_arr = np.broadcast_to(np.asarray(T_base, dtype=np.float64),
                             grid.shape).ravel()
    if np.any(T_arr <= (1.0 - flat.omega) * flat.C):
        raise ValueError("period too short: no work progress per period")
    cfg = _dispatch.resolve(dispatch)
    pol = _engine_policy(engine_kind, cfg, precision)

    if gaps is not None:
        # Shared-schedule path (parity / CRN): one budget, grid chunked.
        gaps = _normalize_gaps(gaps, flat.size)
        n_trials = int(gaps.shape[-2])
        if n_steps is None:
            # The event kernel executes (#failures + 1 completion) steps,
            # and a schedule of F gaps admits at most F failures.
            n_steps = (_scan_len(gaps.shape[-1]) + 1
                       if engine_kind in _EVENT_LIKE else
                       default_step_budget(T_arr, flat, Tb_arr,
                                           process=process))
        else:
            n_steps = _scan_len(n_steps)
        out = _dispatch_explicit(T_arr, flat, Tb_arr, gaps, int(n_steps),
                                 engine_kind, cfg, policy=pol)
        return _assemble_batch(out, grid, n_trials)

    # Auto-sampled path: per-point budgets, one dispatch per pow2 bucket.
    # Point i / trial t samples its schedule from the folded key
    # fold_in(fold_in(PRNGKey(seed), i), t) at its bucket's capacity: a
    # prefix of that key's stream, whose values do not depend on how far
    # the stream is drawn — so n_steps, engine_kind, bucket membership,
    # chunk size, shard count, and memory budget never change the sampled
    # failure times.
    caps = fail_capacity_points(T_arr, flat, Tb_arr, process=process)
    if n_steps is not None:
        budgets = np.full(flat.size, _scan_len(n_steps), dtype=np.int64)
    elif engine_kind in _EVENT_LIKE:
        budgets = caps + 1
    else:
        budgets = step_budget_points(T_arr, flat, Tb_arr, process=process)
    proc = as_process(process).ravel()
    try:
        token, params_b, proc_fn, mean_arr, idx_all, key = \
            _sampler_inputs(proc, flat, seed)
        g_full = None
    except NotImplementedError:
        # Processes without a traced-parameter sampler fall back to ONE
        # full-grid schedule at the max capacity, sliced per bucket (and
        # per chunk by the dispatcher) — the same partition-independent
        # contract as the fused path.  Prefer the bulk device sampler
        # (``sample_gaps`` — the PR-4 extension point custom processes
        # may already implement) so their draws stay on device; host
        # numpy is the last-resort gate.  Note the bulk tensor is
        # grid-wide, so the memory-bounded-chunking promise only holds
        # for processes with a traced sampler.
        g_full = _bulk_schedule(flat, n_trials, int(np.max(caps)), seed,
                                process)

    ndev = _dispatch.effective_devices(cfg)
    acc: dict = {}
    for b in np.unique(budgets):
        idx = np.nonzero(budgets == b)[0]
        sub = ParamGrid(**{f: v[idx] for f, v in flat.fields().items()})
        cap = int(np.max(caps[idx]))
        if g_full is not None:
            with enable_x64():   # gathering a f64 device array needs x64
                g = g_full[idx, :, :cap]
            out = _dispatch_explicit(T_arr[idx], sub, Tb_arr[idx], g,
                                     int(b), engine_kind, cfg, policy=pol)
            _scatter(acc, out, flat.size, n_trials, idx, slice(None))
            continue
        slots = cap + _slab_slots(engine_kind, b)
        tc = _trial_chunk(n_trials, slots, ndev, cfg)
        for t0 in range(0, n_trials, tc):
            t_idx = np.arange(t0, min(t0 + tc, n_trials), dtype=np.uint32)
            out = _dispatch.run(
                key=("sampled", token, cap, int(b),
                     _kind_token(engine_kind, pol), len(params_b)),
                build=_sampled_build(proc_fn, cap, int(b), engine_kind,
                                     policy=pol),
                args=(T_arr[idx], sub.C, sub.R, sub.D, sub.omega,
                      Tb_arr[idx], mean_arr[idx], idx_all[idx], t_idx,
                      key) + tuple(p[idx] for p in params_b),
                in_axes=(0,) * 8 + (None, None) + (0,) * len(params_b),
                out_axes=0, size=len(idx),
                per_point_bytes=8 * len(t_idx) * (slots + 32),
                config=cfg)
            _scatter(acc, out, flat.size, n_trials, idx,
                     slice(t0, t0 + len(t_idx)))
    return _assemble_batch(acc, grid, n_trials)


def _scatter(acc: dict, out: dict, size: int, n_trials: int, idx,
             t_slice) -> None:
    """Write one (bucket x trial-block) result into the full-grid
    accumulator (allocating it on first use)."""
    for k, v in out.items():
        if k not in acc:
            acc[k] = np.empty((size, n_trials), dtype=v.dtype)
        acc[k][idx, t_slice] = v


def _cand_sampled_build(proc_fn, cap_sample: int, n_steps: int, kind: str,
                        policy=None):
    """Fused sample-then-candidate-vmap chunk kernel: the schedule is
    drawn once per chunk from the pointwise folded keys and SHARED across
    the candidate axis (``in_axes=None``) — CRN by construction, never
    tiled, and partition-independent like :func:`_sampled_build`.  The
    pallas kind serializes the candidate axis with ``lax.map``
    (see :func:`_cand_fn`); the schedule is still drawn once."""
    run_grid = _grid_fn(n_steps, kind, policy)

    def build(T2, C, R, D, omega, Tb, mean, idx, t_idx, key, *params):
        def sample_point(m, i, *pp):
            kp = jax.random.fold_in(key, i)

            def sample_trial(ti):
                return proc_fn(jax.random.fold_in(kp, ti), (cap_sample,),
                               m, pp)
            return jax.vmap(sample_trial)(t_idx)
        with jax.named_scope(SAMPLE_SCOPE):
            gaps = jax.vmap(sample_point)(mean, idx, *params)
        if kind == "pallas":
            with jax.named_scope(SCAN_SCOPE):
                return lax.map(
                    lambda t: run_grid(t, C, R, D, omega, Tb, gaps), T2)
        return jax.vmap(run_grid, in_axes=(0,) + (None,) * 6)(
            T2, C, R, D, omega, Tb, gaps)
    return build


def _cand_axis(M: int, B: int) -> str:
    """Which axis the candidate dispatch shards/chunks over: the grid
    axis normally; the candidate axis for single-point grids (the
    MCSurrogate shape, where the grid axis has nothing to split)."""
    return "cand" if B == 1 and M > 1 else "grid"


@functools.partial(jax.profiler.annotate_function,
                   name="repro.mc.candidates")
def simulate_candidates(T_cand, grid: ParamGrid, T_base: float = 1.0,
                        n_trials: int = 200, seed: int = 0,
                        gaps: Optional[np.ndarray] = None,
                        n_steps: Optional[int] = None, process=None,
                        engine_kind: Optional[str] = None,
                        dispatch=None,
                        precision=None) -> TrajectoryBatch:
    """Simulate M candidate periods against ONE shared set of failure
    schedules (the CRN solvers' hot path).

    ``T_cand`` has shape ``(M,) + grid.shape`` (or ``(M,)``, one period per
    candidate for the whole grid).  The candidate axis is a ``vmap`` with
    ``in_axes=None`` on the schedules and parameters — the big
    ``(B, n_trials, capacity)`` gap tensor is shared across candidates,
    never tiled, materialized M times, or re-transferred.  Outputs carry a
    leading ``(M,)`` axis over ``grid.shape + (n_trials,)``.

    With ``gaps=None`` one schedule set is auto-sampled (pointwise folded
    keys, device sampler when available) and shared by every candidate —
    common random numbers by construction.  Calls route through
    :mod:`repro.sim.dispatch` (sharding + memory-bounded chunking over
    the grid axis — or over the candidate axis for single-point grids,
    where the schedules are replicated instead of split); the dispatch
    knobs never change a fixed seed's results.
    """
    engine_kind = resolve_engine_kind(engine_kind)
    flat = grid.ravel()
    T2 = np.asarray(T_cand, dtype=np.float64)
    M = T2.shape[0]
    if T2.ndim == 1:
        T2 = T2.reshape((M,) + (1,) * max(len(grid.shape), 1))
    T2 = np.broadcast_to(T2, (M,) + grid.shape).reshape(M, flat.size)
    Tb_arr = np.broadcast_to(np.asarray(T_base, dtype=np.float64),
                             grid.shape).ravel()
    if np.any(T2 <= (1.0 - flat.omega) * flat.C):
        raise ValueError("period too short: no work progress per period")
    cfg = _dispatch.resolve(dispatch)
    pol = _engine_policy(engine_kind, cfg, precision)
    B = flat.size
    axis = _cand_axis(M, B)

    if gaps is None:
        cap = default_fail_capacity(T2, flat, Tb_arr, process=process)
        if n_steps is None:
            ns = (_scan_len(cap) + 1 if engine_kind in _EVENT_LIKE else
                  default_step_budget(T2, flat, Tb_arr, process=process))
        else:
            ns = _scan_len(n_steps)
        proc = as_process(process).ravel()
        try:
            token, params_b, proc_fn, mean_arr, idx_all, key = \
                _sampler_inputs(proc, flat, seed)
        except NotImplementedError:
            # sample_gaps-only processes keep their bulk device draws
            # (PR-4 contract); host numpy is the last-resort gate.
            gaps = _bulk_schedule(flat, n_trials, cap, seed, process)
        else:
            out = _dispatch_cands(
                ("cand_sampled", token, cap, int(ns),
                 _kind_token(engine_kind, pol), len(params_b)),
                _cand_sampled_build(proc_fn, cap, int(ns), engine_kind,
                                    policy=pol),
                T2, flat, Tb_arr, axis, cfg, n_trials,
                cap + _slab_slots(engine_kind, ns),
                sampler_args=(mean_arr, idx_all, key, params_b))
            return _assemble_batch(out, grid, n_trials, lead=(M,))

    gaps = _normalize_gaps(gaps, flat.size)
    n_trials = int(gaps.shape[-2])
    if n_steps is None:
        n_steps = (_scan_len(gaps.shape[-1]) + 1
                   if engine_kind in _EVENT_LIKE else
                   default_step_budget(T2, flat, Tb_arr, process=process))
    else:
        n_steps = _scan_len(n_steps)
    out = _dispatch_cands(
        ("cand_explicit", int(n_steps), _kind_token(engine_kind, pol)),
        _cand_fn(int(n_steps), engine_kind, policy=pol),
        T2, flat, Tb_arr, axis, cfg, n_trials,
        int(gaps.shape[-1]) + _slab_slots(engine_kind, n_steps), gaps=gaps)
    return _assemble_batch(out, grid, n_trials, lead=(M,))


def _dispatch_cands(key, build, T2, flat: ParamGrid, Tb_arr, axis: str,
                    cfg, n_trials: int, slots: int, gaps=None,
                    sampler_args=None) -> dict:
    """Route a candidate-vmap runner through the dispatcher.

    ``axis="grid"`` shards/chunks the grid axis (candidate axis rides
    whole, schedules split with their grid points); ``axis="cand"``
    shards/chunks the candidate axis (schedules replicated — the B == 1
    solver shape).  The trials axis streams in memory-bounded blocks on
    both schedule paths (explicit schedules are sliced; auto-sampled
    blocks re-derive their per-(point, trial) folded keys, so blocking
    is bit-exact).  ``slots`` is the gap slots a trajectory holds on
    device: its schedule's capacity plus :func:`_slab_slots`.
    """
    M, B = T2.shape
    ndev = _dispatch.effective_devices(cfg)
    grid_args = (flat.C, flat.R, flat.D, flat.omega, Tb_arr)
    sampled = sampler_args is not None
    if not sampled:
        gaps = _as_f64_gaps(gaps)
    # Trials stream in memory-bounded blocks on BOTH schedule paths.  On
    # the candidate axis the (B, trials-block, slots) schedule is
    # replicated per device (ndev-independent, hence the 1), so the
    # block length is what bounds it; on the grid axis each point owns
    # its schedule slice plus M candidates' worth of live carries.
    tc = _trial_chunk(n_trials,
                      B * slots if axis == "cand" else slots + 32 * M,
                      1 if axis == "cand" else ndev, cfg)
    parts = []
    for t0 in range(0, n_trials, tc):
        t1 = min(t0 + tc, n_trials)
        if sampled:
            mean_arr, idx_all, base_key, params_b = sampler_args
            t_idx = np.arange(t0, t1, dtype=np.uint32)
            args = (T2,) + grid_args + (mean_arr, idx_all, t_idx,
                                        base_key) + tuple(params_b)
            cand_axes = (0,) + (None,) * (len(args) - 1)
            grid_axes = ((1,) + (0,) * 5 + (0, 0, None, None)
                         + (0,) * len(params_b))
        else:
            args = (T2,) + grid_args + (gaps[:, t0:t1, :],)
            cand_axes = (0,) + (None,) * 6
            grid_axes = (1,) + (0,) * 6
        if axis == "cand":
            out = _dispatch.run(
                key=key + ("cand",), build=build, args=args,
                in_axes=cand_axes, out_axes=0, size=M,
                per_point_bytes=8 * B * (t1 - t0) * 48, config=cfg)
        else:
            out = _dispatch.run(
                key=key + ("grid",), build=build, args=args,
                in_axes=grid_axes, out_axes=1, size=B,
                per_point_bytes=8 * (t1 - t0) * (slots + 32 * M),
                config=cfg)
        parts.append(out)
    if len(parts) == 1:
        return parts[0]
    return {k: np.concatenate([p[k] for p in parts], axis=-1)
            for k in parts[0]}


# ---------------------------------------------------------------------------
# Two-level (buddy + PFS) checkpointing: the event kernel
# ---------------------------------------------------------------------------
#
# The superperiod structure: periods 0..m-2 end with a buddy checkpoint
# (cost C1, commits level 1), period m-1 with a deep checkpoint (cost C2,
# commits BOTH levels).  Each failure carries a "hard" flag (buddy copy
# lost, probability q): a soft failure rolls back to the last committed
# level-1 state and resumes the period schedule where that commit left it;
# a hard failure rolls back to the last deep commit and restarts the
# superperiod at period 0 (re-executing the intermediate buddy checkpoints
# on the way — their I/O is naturally re-counted).  The plain reference
# is ``core.simulator.simulate_once_ml``, a phase machine of the same
# semantics.
#
# With m = 1 and degenerate levels (C1=C2, R1=R2, D1=D2) every arithmetic
# expression of the kernel reduces to the single-level ``_run_one_event``
# operation for operation: the two engines agree bit for bit on a dyadic
# schedule, where every operation is exact, and to rounding otherwise (XLA
# may contract multiply-adds differently in two programs).

@dataclasses.dataclass(frozen=True)
class MultilevelTrajectoryBatch:
    """Per-trajectory outputs, shape ``grid.shape + (n_trials,)``."""

    wall_time: np.ndarray
    energy: np.ndarray
    work_executed: np.ndarray
    io1_time: np.ndarray         # buddy-level I/O (writes + soft recoveries)
    io2_time: np.ndarray         # deep-level I/O (writes + hard recoveries)
    down_time: np.ndarray
    n_failures: np.ndarray
    n_hard_failures: np.ndarray
    n_ckpt1: np.ndarray          # committed buddy checkpoints
    n_ckpt2: np.ndarray          # committed deep checkpoints
    truncated: np.ndarray
    gaps_exhausted: np.ndarray


def _run_one_ml_event(T, m, C1, C2, R1, R2, D1, D2, omega1, omega2, T_base,
                      gaps, hard, n_steps):
    """One two-level trajectory, one scan iteration per FAILURE.

    ``hard[i]`` is the level-loss flag of failure ``i``.  A stretch starts
    after a recovery (or at t = 0) in period ``k0`` of the superperiod,
    with ``live == committed1``; from there the machine is deterministic.
    Every period lasts ``T``; period ``j`` (``j`` counted in the
    superperiod) writes the deep level if ``j == m - 1`` and the buddy
    level otherwise, does work ``w_j = T - (1 - omega_j) C_j`` and commits
    the work as of its checkpoint's start.  So the work of the first ``n``
    whole periods of the stretch is ``(n - nd) w1 + nd w2``, with
    ``nd = floor((k0 + n) / m)`` deep ones among them, and:

    * completion: measured from the superperiod's start, ``Q`` whole
      superperiods (``S = (m-1) w1 + w2`` each) and then ``t <= m - 1``
      buddy periods fit in ``rem - eps + k0 w1``, so ``n = Q m + t - k0``
      whole periods come before the finishing one, which is period
      ``t`` of its superperiod (``eps`` as in ``_run_one_event``);
    * a failure at ``s = g`` lands in whole period ``kf = floor(s / T)``
      of the stretch (failure wins ties, as in ``_run_one_event``), which
      is period ``p = (k0 + kf) mod m``; the last commit of either level
      is that of period ``kf - 1``, the last deep one that of period
      ``Q m - 1 - k0`` with ``Q = floor((k0 + kf) / m)`` (none if
      ``Q == 0``); a soft failure resumes at ``p``, a hard one at 0.

    Step ``i`` reads gap ``i`` and flag ``i`` by loop index, as in
    ``_run_one_event``: the schedule and the flags reach the scan as
    ``xs``, padded with ``inf`` and ``False`` (or cut) to ``n_steps``.
    """
    f64 = gaps.dtype
    n_gaps = gaps.shape[0]
    pad = (0, max(n_steps - n_gaps, 0))
    slab = jnp.pad(gaps, pad, constant_values=jnp.inf)[:n_steps]
    flags = jnp.pad(hard, pad, constant_values=False)[:n_steps]
    in_ranges = jnp.arange(n_steps, dtype=jnp.int32) < n_gaps
    mf = m.astype(f64)
    w1 = T - (1.0 - omega1) * C1        # work of a buddy period
    w2 = T - (1.0 - omega2) * C2        # work of a deep period
    S = (mf - 1.0) * w1 + w2            # work of a superperiod

    def period(deep):
        """(cost, compute length, overlap rate, safe rate) of a period."""
        C = jnp.where(deep, C2, C1)
        omega = jnp.where(deep, omega2, omega1)
        return C, T - C, omega, jnp.where(omega > 0.0, omega, 1.0)

    def work_of(nb, nd):
        return nb * w1 + nd * w2

    init = (jnp.zeros((), f64),         # wall
            jnp.zeros((), f64),         # committed1
            jnp.zeros((), f64),         # committed2
            jnp.zeros((), f64),         # work_exec
            jnp.zeros((), f64),         # io1_time
            jnp.zeros((), f64),         # io2_time
            jnp.zeros((), f64),         # down_time
            jnp.zeros((), jnp.int32),   # k: period of the stretch's start
            jnp.zeros((), jnp.int32),   # n_fail
            jnp.zeros((), jnp.int32),   # n_hard
            jnp.zeros((), jnp.int32),   # n_ckpt1
            jnp.zeros((), jnp.int32),   # n_ckpt2
            jnp.zeros((), jnp.bool_),   # used_inf (schedule ran dry)
            jnp.zeros((), jnp.bool_))   # done

    def step(carry, x):
        (wall, committed1, committed2, work_exec, io1_time, io2_time,
         down_time, k, n_fail, n_hard, n_ckpt1, n_ckpt2, used_inf,
         done) = carry
        g, h, in_range = x
        k0 = k.astype(f64)

        # ---- closed-form completion from this stretch's start ----
        rem = T_base - committed1
        Y = (rem - _EPS) + k0 * w1
        Q = jnp.maximum(jnp.floor(Y / S), 0.0)
        t = jnp.clip(jnp.floor((Y - Q * S) / w1), 0.0, mf - 1.0)
        n = Q * mf + t - k0             # whole periods before the last
        nb = n - Q
        r = rem - work_of(nb, Q)        # work inside the finishing period
        deep_f = t == mf - 1.0
        _, Tc_f, _, om_f = period(deep_f)
        rr = r - Tc_f                   # its checkpoint-phase share (if > 0)
        t_in = jnp.where(rr > 0.0, Tc_f + rr / om_f, r)
        t_fin = n * T + t_in
        complete = t_fin < g

        # ---- branch A: completes before the next failure ----
        wall_a = wall + t_fin
        work_a = work_exec + rem
        tail = jnp.maximum(rr, 0.0) / om_f
        io1_a = io1_time + nb * C1 + jnp.where(deep_f, 0.0, tail)
        io2_a = io2_time + Q * C2 + jnp.where(deep_f, tail, 0.0)

        # ---- branch B: failure at s = g after the stretch's start ----
        s = jnp.where(jnp.isfinite(g), g, 0.0)
        kf = jnp.floor(s / T)
        # floor(s/T) can land ON kf*T (exact-boundary failure: the
        # checkpoint ending at the failure instant does NOT commit) or one
        # above it (quotient rounded up); both correct downward.
        kf = jnp.where((kf > 0.0) & (kf * T >= s), kf - 1.0, kf)
        a = k0 + kf                     # the failing period, from k = 0
        Qb = jnp.floor(a / mf)
        p = a - Qb * mf
        deep_b = p == mf - 1.0
        _, Tc_b, om_b, _ = period(deep_b)
        u = s - kf * T                  # offset inside the failing period
        uc = u - Tc_b                   # its checkpoint-phase share (if > 0)
        nbb = kf - Qb
        work_b = work_exec + work_of(nbb, Qb) + jnp.where(
            uc > 0.0, Tc_b + om_b * uc, u)
        wasted = jnp.maximum(uc, 0.0)
        io1_b = (io1_time + nbb * C1 + jnp.where(deep_b, 0.0, wasted)
                 + jnp.where(h, 0.0, R1))
        io2_b = (io2_time + Qb * C2 + jnp.where(deep_b, wasted, 0.0)
                 + jnp.where(h, R2, 0.0))
        D = jnp.where(h, D2, D1)
        wall_b = (wall + s) + D + jnp.where(h, R2, R1)
        # Last commit of either level: period kf - 1 of the stretch.
        Ql = jnp.floor((a - 1.0) / mf)
        _, Tc_l, _, _ = period(a - 1.0 - Ql * mf == mf - 1.0)
        last1 = jnp.where(kf >= 1.0, committed1
                          + work_of(kf - 1.0 - Ql, Ql) + Tc_l, committed1)
        # Last deep commit: period Qb m - 1 - k0 of the stretch.
        last2 = jnp.where(Qb >= 1.0, committed1
                          + work_of(Qb * (mf - 1.0) - k0, Qb - 1.0)
                          + (T - C2), committed2)
        committed1_b = jnp.where(h, last2, last1)
        k_b = jnp.where(h, 0, p.astype(jnp.int32))

        def sel(a_val, b_val):
            return jnp.where(complete, a_val, b_val)

        hard_b = n_hard + h.astype(jnp.int32)
        new = (sel(wall_a, wall_b),
               sel(committed1, committed1_b),
               sel(committed2, last2),
               sel(work_a, work_b),
               sel(io1_a, io1_b),
               sel(io2_a, io2_b),
               sel(down_time, down_time + D),
               sel(k, k_b).astype(jnp.int32),
               sel(n_fail, n_fail + 1).astype(jnp.int32),
               sel(n_hard, hard_b).astype(jnp.int32),
               (n_ckpt1 + sel(nb, nbb).astype(jnp.int32)).astype(jnp.int32),
               (n_ckpt2 + sel(Q, Qb).astype(jnp.int32)).astype(jnp.int32),
               jnp.logical_or(used_inf, ~in_range),
               jnp.logical_or(done, complete))

        keep = lambda old, upd: jnp.where(done, old, upd)
        return tuple(keep(o, u) for o, u in zip(carry, new)), None

    final, _ = lax.scan(step, init, (slab, flags, in_ranges), length=n_steps)
    (wall, _c1, _c2, work_exec, io1_time, io2_time, down_time, _k,
     n_fail, n_hard, n_ckpt1, n_ckpt2, used_inf, done) = final
    return {"wall_time": wall, "work_executed": work_exec,
            "io1_time": io1_time, "io2_time": io2_time,
            "down_time": down_time, "n_failures": n_fail,
            "n_hard_failures": n_hard, "n_ckpt1": n_ckpt1,
            "n_ckpt2": n_ckpt2, "truncated": ~done,
            "gaps_exhausted": used_inf}


#: per-point parameters of the two-level kernel, in its argument order.
_ML_PARAMS = ("C1", "C2", "R1", "R2", "D1", "D2", "omega1", "omega2")

#: the two-level kernel's outputs by dtype, in their packed order.
_ML_FLOATS = ("wall_time", "work_executed", "io1_time", "io2_time",
              "down_time")
_ML_COUNTS = ("n_failures", "n_hard_failures", "n_ckpt1", "n_ckpt2")
_ML_FLAGS = ("truncated", "gaps_exhausted")


def _pack_ml(out: dict) -> tuple:
    """The kernel's eleven fields as one f64 and one int32 array, fields
    leading (the grid axis is axis 1), so a chunk comes back to the host
    in two transfers and not eleven."""
    return (jnp.stack([out[f] for f in _ML_FLOATS]),
            jnp.stack([out[f] for f in _ML_COUNTS]
                      + [out[f].astype(jnp.int32) for f in _ML_FLAGS]))


def _unpack_ml(packed) -> dict:
    """The host side of :func:`_pack_ml`: the fields by name."""
    floats, ints = packed
    out = dict(zip(_ML_FLOATS, floats))
    out.update(zip(_ML_COUNTS, ints))
    out.update((f, ints[len(_ML_COUNTS) + j] != 0)
               for j, f in enumerate(_ML_FLAGS))
    return out


def _grid_fn_ml(n_steps: int):
    """The explicit-schedule (grid x trials) runner of the two-level
    kernel."""
    def run_grid_ml(T, m, C1, C2, R1, R2, D1, D2, omega1, omega2, T_base,
                    gaps, hard):
        def one(*a):
            with jax.named_scope(SCAN_SCOPE):
                return _run_one_ml_event(*a, n_steps)
        over_trials = jax.vmap(one, in_axes=(None,) * 11 + (0, 0))
        over_grid = jax.vmap(over_trials, in_axes=(0,) * 13)
        return _pack_ml(over_grid(T, m, C1, C2, R1, R2, D1, D2, omega1,
                                  omega2, T_base, gaps, hard))
    return run_grid_ml


def _sampled_build_ml(proc_fn, cap_used: int, n_steps: int):
    """Fused sample-then-simulate chunk kernel of the two-level engine.

    Lane (point ``i``, trial ``t``) owns the folded key ``fold_in(fold_in(
    key, i), t)``: its gaps are the process's traced draw from that key,
    as in :func:`_sampled_build`, and its level-loss flags the second
    stream :func:`repro.core.failures.level_loss_flags` of the same key.
    Both are drawn at the bucket's capacity ``cap_used``, a prefix of each
    stream (counter-based threefry: the same values whatever length the
    stream is drawn to).  The jitted program is ``jit_build_ml``;
    it returns its outputs packed by :func:`_pack_ml`.
    """
    def build_ml(T, m, C1, C2, R1, R2, D1, D2, omega1, omega2, Tb, q, mean,
                 idx, t_idx, key, *params):
        def per_point(t, mm, c1, c2, r1, r2, d1, d2, o1, o2, tb, qq, mu, i,
                      *pp):
            with jax.named_scope(SAMPLE_SCOPE):
                kp = jax.random.fold_in(key, i)

            def per_trial(ti):
                with jax.named_scope(SAMPLE_SCOPE):
                    kl = jax.random.fold_in(kp, ti)
                    g = proc_fn(kl, (cap_used,), mu, pp)
                    h = level_loss_flags(kl, (cap_used,), qq)
                with jax.named_scope(SCAN_SCOPE):
                    return _run_one_ml_event(
                        t, mm, c1, c2, r1, r2, d1, d2, o1, o2, tb, g, h,
                        n_steps)
            return jax.vmap(per_trial)(t_idx)
        return _pack_ml(jax.vmap(per_point)(
            T, m, C1, C2, R1, R2, D1, D2, omega1, omega2, Tb, q, mean, idx,
            *params))
    return build_ml


def _expected_failures_ml(T, m, grid: MultilevelParamGrid,
                          T_base) -> np.ndarray:
    """E[#failures] from the two-level closed form, clipped like the
    single-level estimator."""
    a, b, mu_m = grid.a(m), grid.b(m), grid.mu_eff(m)
    denom = (T - a) * (b - T / (2.0 * mu_m))
    with np.errstate(divide="ignore", invalid="ignore"):
        tf = np.where(denom > 1e-12, T_base * T / denom, np.inf)
    tf = np.where(np.isfinite(tf) & (tf > 0), tf, 50.0 * T_base)
    return tf / grid.mu


def fail_capacity_points_ml(T, m, grid: MultilevelParamGrid, T_base,
                            process=None) -> np.ndarray:
    """Per-grid-point schedule capacity of the two-level engine (mean +
    10 sigma of the two-level failure count, inflated by the gap CV as in
    :func:`fail_capacity_points`), bucketed to powers of two; shape
    ``(grid.size,)``.  The event kernel takes one step per failure, so a
    point's scan length is its capacity + 1."""
    cv = np.maximum(1.0, _process_cv_points(process, grid.size))
    nf = _expected_failures_ml(T, m, grid, T_base) * cv * cv
    cap = np.ceil(nf + 10.0 * cv * np.sqrt(nf + 1.0) + 10.0)
    return _pow2(_per_point(cap, grid.size))


def _broadcast_schedule(arr, size, dtype):
    arr = np.asarray(arr, dtype=dtype)
    if arr.ndim == 1:
        arr = arr[None, None, :]
    if arr.ndim == 2:
        arr = arr[None, :, :]
    return np.broadcast_to(arr, (size, arr.shape[-2], arr.shape[-1]))


def _assemble_batch_ml(out: dict, grid: MultilevelParamGrid,
                       n_trials: int) -> MultilevelTrajectoryBatch:
    """Reshape flat two-level outputs to ``grid.shape + (n_trials,)`` and
    attach the energy integral."""
    shp = grid.shape + (n_trials,)
    bc = lambda x: x.reshape(grid.shape + (1,))
    wall = out["wall_time"].reshape(shp)
    work = out["work_executed"].reshape(shp)
    io1 = out["io1_time"].reshape(shp)
    io2 = out["io2_time"].reshape(shp)
    down = out["down_time"].reshape(shp)
    energy = (bc(grid.P_static) * wall + bc(grid.P_cal) * work
              + bc(grid.P_io1) * io1 + bc(grid.P_io2) * io2
              + bc(grid.P_down) * down)
    return MultilevelTrajectoryBatch(
        wall_time=wall, energy=energy, work_executed=work,
        io1_time=io1, io2_time=io2, down_time=down,
        n_failures=out["n_failures"].reshape(shp),
        n_hard_failures=out["n_hard_failures"].reshape(shp),
        n_ckpt1=out["n_ckpt1"].reshape(shp),
        n_ckpt2=out["n_ckpt2"].reshape(shp),
        truncated=out["truncated"].reshape(shp),
        gaps_exhausted=out["gaps_exhausted"].reshape(shp))


@functools.partial(jax.profiler.annotate_function,
                   name="repro.mc.trajectories_ml")
def simulate_trajectories_ml(T, m, grid: MultilevelParamGrid,
                             T_base: float = 1.0, n_trials: int = 200,
                             seed: int = 0,
                             gaps: Optional[np.ndarray] = None,
                             hard: Optional[np.ndarray] = None,
                             n_steps: Optional[int] = None,
                             process=None,
                             dispatch=None) -> MultilevelTrajectoryBatch:
    """Simulate every two-level (grid point x trial) trajectory.

    ``T`` and ``m`` broadcast against ``grid.shape``.  Without a schedule
    the failures are sampled inside the program, as in
    :func:`simulate_trajectories`: lane (point ``i``, trial ``t``) draws
    its gaps from ``process`` (None = exponential at the grid's mu) and
    its level-loss flags (:func:`repro.core.failures.level_loss_flags`,
    Bernoulli of the point's ``q``) from the folded key
    ``fold_in(fold_in(PRNGKey(seed), i), t)``, at its bucket's capacity
    (a prefix of each stream).
    Points are dispatched in power-of-two capacity buckets through
    :mod:`repro.sim.dispatch` (``dispatch`` is its config), so buckets,
    chunks, trial blocks and devices never change a fixed seed's results.
    ``gaps`` and ``hard`` (both, ``(grid.size, n_trials, F)`` or
    broadcastable) replace the sampled schedule; feed the same schedule to
    :func:`repro.core.simulator.simulate_once_ml` for parity checks.
    """
    flat = grid.ravel()
    T_arr = np.broadcast_to(np.asarray(T, dtype=np.float64),
                            grid.shape).ravel()
    m_arr = np.broadcast_to(np.asarray(m, dtype=np.int32),
                            grid.shape).ravel()
    Tb_arr = np.broadcast_to(np.asarray(T_base, dtype=np.float64),
                             grid.shape).ravel()
    if np.any(m_arr < 1):
        raise ValueError("deep-checkpoint cadence m must be >= 1")
    if np.any(T_arr < np.maximum(flat.C1, flat.C2)):
        raise ValueError("period too short: T must cover the checkpoint")
    if np.any(T_arr <= flat.a(m_arr)) or np.any(
            (m_arr > 1) & (T_arr <= (1.0 - flat.omega1) * flat.C1)):
        raise ValueError("period too short: no work progress per period")
    if (gaps is None) != (hard is None):
        raise ValueError("pass both gaps and hard flags, or neither")
    cfg = _dispatch.resolve(dispatch)
    ndev = _dispatch.effective_devices(cfg)
    per_point = (T_arr, m_arr) + tuple(getattr(flat, f) for f in _ML_PARAMS)

    if gaps is not None:
        gaps = _broadcast_schedule(gaps, flat.size, np.float64)
        hard = _broadcast_schedule(hard, flat.size, np.bool_)
        if gaps.shape != hard.shape:
            raise ValueError(f"gaps {gaps.shape} and hard flags "
                             f"{hard.shape} schedules disagree")
        n_trials = gaps.shape[-2]
        # One step per failure and one for the completion.
        ns = (_scan_len(gaps.shape[-1]) + 1 if n_steps is None
              else _scan_len(n_steps))
        slots = gaps.shape[-1] + ns
        tc = _trial_chunk(n_trials, slots, ndev, cfg)
        acc: dict = {}
        for t0 in range(0, n_trials, tc):
            t1 = min(t0 + tc, n_trials)
            out = _dispatch.run(
                key=("explicit_ml", ns), build=_grid_fn_ml(ns),
                args=per_point + (Tb_arr, gaps[:, t0:t1], hard[:, t0:t1]),
                in_axes=(0,) * 13, out_axes=1, size=flat.size,
                per_point_bytes=8 * (t1 - t0) * (slots + 32), config=cfg)
            _scatter(acc, _unpack_ml(out), flat.size, n_trials,
                     np.arange(flat.size), slice(t0, t1))
        return _assemble_batch_ml(acc, grid, n_trials)

    caps = fail_capacity_points_ml(T_arr, m_arr, flat, Tb_arr,
                                   process=process)
    budgets = (caps + 1 if n_steps is None else
               np.full(flat.size, _scan_len(n_steps), dtype=np.int64))
    token, params_b, proc_fn, mean_arr, idx_all, key = _sampler_inputs(
        as_process(process).ravel(), flat, seed)
    # Every bucket and trial block is launched before any is fetched, so
    # the device runs the next while the host copies one back.
    pending = []
    for b in np.unique(budgets):
        idx = np.nonzero(budgets == b)[0]
        cap = int(np.max(caps[idx]))
        # f64-sized slots a trajectory holds on device: its gaps and the
        # flag stream's uniforms, and the scan's two slabs.
        slots = 2 * cap + 2 * int(b)
        tc = _trial_chunk(n_trials, slots, ndev, cfg)
        for t0 in range(0, n_trials, tc):
            t_idx = np.arange(t0, min(t0 + tc, n_trials), dtype=np.uint32)
            finish = _dispatch.launch(
                key=("sampled_ml", token, cap, int(b), len(params_b)),
                build=_sampled_build_ml(proc_fn, cap, int(b)),
                args=tuple(x[idx] for x in per_point)
                + (Tb_arr[idx], flat.q[idx], mean_arr[idx], idx_all[idx],
                   t_idx, key) + tuple(p[idx] for p in params_b),
                in_axes=(0,) * 14 + (None, None) + (0,) * len(params_b),
                out_axes=1, size=len(idx),
                per_point_bytes=8 * len(t_idx) * (slots + 32), config=cfg)
            pending.append((finish, idx, slice(t0, t0 + len(t_idx))))
    acc = {}
    for finish, idx, t_slice in pending:
        _scatter(acc, _unpack_ml(finish()), flat.size, n_trials, idx,
                 t_slice)
    return _assemble_batch_ml(acc, grid, n_trials)


def simulate_grid_ml(T, m, grid: MultilevelParamGrid, T_base: float = 1.0,
                     n_trials: int = 200, seed: int = 0,
                     gaps: Optional[np.ndarray] = None,
                     hard: Optional[np.ndarray] = None,
                     n_steps: Optional[int] = None, process=None,
                     dispatch=None) -> dict:
    """Mean/SE summaries of the two-level Monte-Carlo (validates the
    multilevel closed forms; raises on truncation/schedule exhaustion)."""
    tb = simulate_trajectories_ml(T, m, grid, T_base, n_trials=n_trials,
                                  seed=seed, gaps=gaps, hard=hard,
                                  n_steps=n_steps, process=process,
                                  dispatch=dispatch)
    if np.any(tb.truncated):
        raise RuntimeError(
            f"{int(tb.truncated.sum())} trajectories exceeded the scan "
            f"budget; pass a larger n_steps (check params)")
    if np.any(tb.gaps_exhausted):
        raise RuntimeError(
            f"{int(tb.gaps_exhausted.sum())} trajectories exhausted their "
            f"failure schedule (tail simulated failure-free); pass gaps/"
            f"hard arrays with larger capacity")
    out = {}
    n = tb.wall_time.shape[-1]
    for key, arr in (("T_final", tb.wall_time), ("E_final", tb.energy),
                     ("T_cal", tb.work_executed), ("T_io1", tb.io1_time),
                     ("T_io2", tb.io2_time), ("T_down", tb.down_time),
                     ("n_failures", tb.n_failures.astype(np.float64)),
                     ("n_hard", tb.n_hard_failures.astype(np.float64))):
        out[key] = arr.mean(axis=-1)
        out[key + "_se"] = arr.std(axis=-1, ddof=1) / math.sqrt(n)
    return out


def simulate_grid(T, grid: ParamGrid, T_base: float = 1.0,
                  n_trials: int = 200, seed: int = 0,
                  gaps: Optional[np.ndarray] = None,
                  n_steps: Optional[int] = None,
                  process=None) -> dict:
    """Batched analogue of ``core.simulator.simulate``: mean/SE summaries.

    Returns a dict of arrays of ``grid.shape`` with the same keys as the
    scalar ``simulate`` ("T_final", "T_final_se", "E_final", ...).
    """
    tb = simulate_trajectories(T, grid, T_base, n_trials=n_trials, seed=seed,
                               gaps=gaps, n_steps=n_steps, process=process)
    if np.any(tb.truncated):
        raise RuntimeError(
            f"{int(tb.truncated.sum())} trajectories exceeded the scan "
            f"budget; pass a larger n_steps (check params)")
    if np.any(tb.gaps_exhausted):
        raise RuntimeError(
            f"{int(tb.gaps_exhausted.sum())} trajectories exhausted their "
            f"failure schedule (tail simulated failure-free); pass a gaps "
            f"array with larger capacity")
    out = {}
    n = tb.wall_time.shape[-1]
    for key, arr in (("T_final", tb.wall_time), ("E_final", tb.energy),
                     ("T_cal", tb.work_executed), ("T_io", tb.io_time),
                     ("T_down", tb.down_time),
                     ("n_failures", tb.n_failures.astype(np.float64))):
        out[key] = arr.mean(axis=-1)
        out[key + "_se"] = arr.std(axis=-1, ddof=1) / math.sqrt(n)
    return out
