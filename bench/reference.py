"""Plain reference of periodic non-blocking checkpointing under failures.

Written from the paper's semantics (arXiv:1310.8456, §2-3) in numpy alone;
it imports nothing of the program under test.

* Periods: AlgoT, ``T = sqrt(2 a b mu)``, and AlgoE, the minimum root of
  the quadratic ``K(T) E'(T)`` (coefficients as corrected for any alpha),
  both clipped to the valid bracket; Young and Daly for the solve.
* Trajectories: the phase machine, one step per phase segment or failure.
  Execution alternates compute phases (``T - C``, work rate 1) and
  checkpoint phases (``C``, work rate omega, I/O active); a checkpoint
  commits the work as of its start.  A failure costs downtime ``D`` and
  recovery ``R``, rolls back to the last commit, and the failure clock
  renews at the end of recovery.  Lanes step in lockstep; a finished lane
  stops changing.
* Failure schedules: Weibull gaps of mean ``mu``, drawn from a seed in the
  documented stream of each path (threefry keys folded per point and
  trial for the in-program sampler; numpy's PCG64 for the host sampler).

Every routine takes ``dtype``: float64 is the reference, float32 is the
control that a comparison has to reject.
"""
from __future__ import annotations

import math

import numpy as np

#: work-completion and phase-boundary slack of the paper's simulator.
EPS = 1e-12

#: fields of one trajectory that the comparison reads.
FLOAT_FIELDS = ("wall_time", "energy", "work_executed", "io_time",
                "down_time")
COUNT_FIELDS = ("n_failures", "n_checkpoints")


# ---------------------------------------------------------------------------
# Platform parameters
# ---------------------------------------------------------------------------

def fig12_point(mu: float, rho: float, C: float = 10.0, R: float = 10.0,
                D: float = 1.0, omega: float = 0.5,
                alpha: float = 1.0) -> dict:
    """Figure 1-2 parameters: powers from rho at P_static = 1,
    ``beta = rho (1 + alpha) - 1``, no power while down."""
    return {"C": C, "R": R, "D": D, "mu": mu, "omega": omega,
            "P_static": 1.0, "P_cal": alpha,
            "P_io": rho * (1.0 + alpha) - 1.0, "P_down": 0.0}


def stack(points: list[dict]) -> dict:
    """List of parameter dicts -> dict of float64 arrays."""
    return {k: np.array([p[k] for p in points], np.float64)
            for k in points[0]}


def _ab(p):
    a = (1.0 - p["omega"]) * p["C"]
    b = 1.0 - (p["D"] + p["R"] + p["omega"] * p["C"]) / p["mu"]
    return a, b


def bracket(p, dtype=np.float64):
    """Valid open period interval ``(max(a, C), 2 mu b)``, shrunk by 1e-9
    of its span (and 1e-12) on the inside."""
    p = {k: np.asarray(v, dtype) for k, v in p.items()}
    a, b = _ab(p)
    lo0 = np.maximum(a, p["C"])
    hi0 = 2.0 * p["mu"] * b
    span = hi0 - lo0
    return lo0 + dtype(1e-9) * span + dtype(1e-12), hi0 - dtype(1e-9) * span


def algo_t(p, dtype=np.float64):
    """AlgoT: ``sqrt(2 a b mu)`` clipped to the bracket."""
    p = {k: np.asarray(v, dtype) for k, v in p.items()}
    a, b = _ab(p)
    lo, hi = bracket(p, dtype)
    return np.clip(np.sqrt(2.0 * a * b * p["mu"]), lo, hi)


def algo_e(p, dtype=np.float64):
    """AlgoE: the root of ``K E'`` where it is a minimum, in the bracket.

    With ``alpha, beta, gamma`` the compute, I/O and down powers over the
    static power, ``P = alpha omega C + beta R + gamma D`` and
    ``Q = (beta - alpha (1 - omega)) C^2``:

        c2 = 1/(2mu) + P/(2mu^2) + alpha b/(2mu) + (alpha a - beta C)/(4mu^2)
        c1 = (beta C - alpha a) b/mu + Q/(2mu^2)
        c0 = -a b (P + mu)/mu - beta C b^2 - Q (b/(2mu) + a/(4mu^2))

    Raises where no root is a minimum inside the bracket: the configured
    grids never take the numeric fallback.
    """
    p = {k: np.asarray(v, dtype) for k, v in p.items()}
    a, b = _ab(p)
    C, mu, omega = p["C"], p["mu"], p["omega"]
    al = p["P_cal"] / p["P_static"]
    be = p["P_io"] / p["P_static"]
    ga = p["P_down"] / p["P_static"]
    P = al * omega * C + be * p["R"] + ga * p["D"]
    Q = (be - al * (1.0 - omega)) * C**2
    c2 = (1.0 / (2.0 * mu) + P / (2.0 * mu**2) + al * b / (2.0 * mu)
          + (al * a - be * C) / (4.0 * mu**2))
    c1 = (be * C - al * a) * b / mu + Q / (2.0 * mu**2)
    c0 = (-a * b * (P + mu) / mu - be * C * b**2
          - Q * (b / (2.0 * mu) + a / (4.0 * mu**2)))
    sq = np.sqrt(c1**2 - 4.0 * c2 * c0)
    lo, hi = bracket(p, dtype)
    roots = np.stack([(-c1 - sq) / (2.0 * c2), (-c1 + sq) / (2.0 * c2)])
    ok = (roots > lo) & (roots < hi) & (2.0 * c2 * roots + c1 > 0.0)
    if not ok.any(axis=0).all():
        raise ValueError("AlgoE: no minimum root inside the bracket")
    return np.where(ok[0], roots[0], roots[1])


def young(p, dtype=np.float64):
    p = {k: np.asarray(v, dtype) for k, v in p.items()}
    return np.sqrt(2.0 * p["C"] * p["mu"]) + p["C"]


def daly(p, dtype=np.float64):
    p = {k: np.asarray(v, dtype) for k, v in p.items()}
    return np.sqrt(2.0 * p["C"] * (p["mu"] + p["D"] + p["R"])) + p["C"]


# ---------------------------------------------------------------------------
# Failure schedules
# ---------------------------------------------------------------------------

def weibull_inv_gamma(k):
    """``1 / Gamma(1 + 1/k)``: the Weibull scale per unit mean."""
    return 1.0 / np.vectorize(math.gamma, otypes=[np.float64])(
        1.0 + 1.0 / np.asarray(k, np.float64))


def weibull_cv(k):
    g = np.vectorize(math.gamma, otypes=[np.float64])
    k = np.asarray(k, np.float64)
    return np.sqrt(np.maximum(g(1.0 + 2.0 / k) / g(1.0 + 1.0 / k) ** 2
                              - 1.0, 0.0))


def threefry_unit_exponentials(seed: int, points, trials, n: int):
    """Standard exponentials ``E[j]`` of lane ``(point, trial)``: the
    first ``n`` of the stream ``exponential(fold_in(fold_in(
    PRNGKey(seed), point), trial))`` in float64 (JAX's counter-based
    threefry, partitionable: a stream's prefix does not depend on its
    length).  Drawn on the host CPU; shape ``(lanes, n)``."""
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        key = jax.random.PRNGKey(int(seed))

        def one(i, t):
            k = jax.random.fold_in(jax.random.fold_in(key, i), t)
            return jax.random.exponential(k, (n,), dtype=jnp.float64)
        pts = jax.device_put(np.asarray(points, np.uint32), cpu)
        trs = jax.device_put(np.asarray(trials, np.uint32), cpu)
        return np.asarray(jax.jit(jax.vmap(one))(pts, trs), np.float64)


def weibull_gaps_threefry(seed: int, points, trials, mu, k, n: int):
    """Weibull(k) gaps of mean ``mu`` per lane: ``mu / Gamma(1 + 1/k) *
    E^(1/k)`` over the threefry exponentials above; ``(lanes, n)``."""
    e = threefry_unit_exponentials(seed, points, trials, n)
    mu = np.asarray(mu, np.float64)[:, None]
    k = np.asarray(k, np.float64)[:, None]
    return mu * weibull_inv_gamma(k) * e ** (1.0 / k)


def weibull_gaps_numpy(seed: int, mu, k, n_trials: int, capacity: int):
    """Host-sampled schedule of the CRN solvers: numpy's PCG64 stream
    ``default_rng(seed).weibull(k, size=(B, n_trials, capacity))`` scaled
    per point to mean ``mu``; shape ``(B, n_trials, capacity)``."""
    rng = np.random.default_rng(seed)
    mu = np.asarray(mu, np.float64)[:, None, None]
    k = np.asarray(k, np.float64)[:, None, None]
    scale = mu / np.vectorize(math.gamma, otypes=[np.float64])(1.0 + 1.0 / k)
    return scale * rng.weibull(k, size=(mu.shape[0], n_trials, capacity))


def expected_failures(T, p, T_base):
    """Closed-form E[#failures] at period ``T`` (the schedule budget of the
    CRN solvers), ``T_base T / ((T - a)(b - T/(2 mu))) / mu``; a point
    outside the model's range counts ``50 T_base / mu``."""
    a, b = _ab(p)
    denom = (T - a) * (b - T / (2.0 * p["mu"]))
    with np.errstate(divide="ignore", invalid="ignore"):
        tf = np.where(denom > 1e-12, T_base * T / denom, np.inf)
    tf = np.where(np.isfinite(tf) & (tf > 0), tf, 50.0 * T_base)
    return tf / p["mu"]


def schedule_capacity(T, p, T_base, k) -> int:
    """Gaps per trajectory of a shared CRN schedule: the mean failure count
    inflated by the gap CV squared plus ten standard deviations, to the
    next power of two, worst case over ``T``'s leading axes and points."""
    cv = np.maximum(1.0, weibull_cv(k))
    nf = expected_failures(T, p, T_base) * cv * cv
    cap = np.ceil(nf + 10.0 * cv * np.sqrt(nf + 1.0) + 10.0)
    return 1 << (max(int(np.max(cap)), 1) - 1).bit_length()


# ---------------------------------------------------------------------------
# Trajectories
# ---------------------------------------------------------------------------

def simulate(T, p, T_base, gaps, dtype=np.float64,
             max_steps: int = 10**6) -> dict:
    """Phase-machine trajectories, one per lane.

    ``T``, ``T_base`` and every entry of ``p`` are ``(lanes,)`` arrays (or
    scalars); ``gaps`` is ``(lanes, F)``.  Returns a dict of ``(lanes,)``
    arrays: the float fields, the counts, ``truncated`` (the step budget
    ran out) and ``gaps_exhausted`` (a lane needed more than ``F`` gaps).
    """
    gaps = np.asarray(gaps, dtype)
    L, F = gaps.shape
    full = lambda v: np.broadcast_to(np.asarray(v, dtype), (L,)).copy()
    T, T_base = full(T), full(T_base)
    C, R, D, omega = (full(p[k]) for k in ("C", "R", "D", "omega"))
    zero = lambda: np.zeros(L, dtype)
    wall, committed, live, work, io, down, snap = (zero() for _ in range(7))
    n_fail = np.zeros(L, np.int64)
    n_ckpt = np.zeros(L, np.int64)
    in_ckpt = np.zeros(L, bool)
    left = T - C
    next_fail = gaps[:, 0].copy()
    idx = np.ones(L, np.int64)
    exhausted = np.zeros(L, bool)
    done = live >= T_base - dtype(EPS)
    rows = np.arange(L)
    for _ in range(max_steps):
        if done.all():
            break
        rate = np.where(in_ckpt, omega, dtype(1.0))
        t_next = np.minimum(left, (T_base - live) / rate)
        seg = ~done & (wall + t_next < next_fail)
        hit = ~done & ~seg

        # A phase segment completes before the next failure.
        wall = np.where(seg, wall + t_next, wall)
        live_a = live + rate * t_next
        work = np.where(seg, work + rate * t_next, work)
        io = np.where(seg & in_ckpt, io + t_next, io)
        left_a = left - t_next
        fin = seg & (live_a >= T_base - dtype(EPS))
        turn = seg & ~fin & (left_a <= dtype(EPS))
        start = turn & ~in_ckpt
        end = turn & in_ckpt
        snap = np.where(start, live_a, snap)
        committed = np.where(end, snap, committed)
        n_ckpt = n_ckpt + end
        left = np.where(seg, np.where(start, C, np.where(end, T - C,
                                                          left_a)), left)
        in_ckpt = np.where(start, True, np.where(end, False, in_ckpt))
        live = np.where(seg, live_a, live)
        done = done | fin

        # A failure strikes inside the segment.
        dt = next_fail - wall
        work = np.where(hit, work + rate * dt, work)
        io = np.where(hit & in_ckpt, io + dt, io)
        wall_b = next_fail + D + R
        wall = np.where(hit, wall_b, wall)
        down = np.where(hit, down + D, down)
        io = np.where(hit, io + R, io)
        n_fail = n_fail + hit
        live = np.where(hit, committed, live)
        in_ckpt = np.where(hit, False, in_ckpt)
        left = np.where(hit, T - C, left)
        ran_dry = hit & (idx >= F)
        exhausted = exhausted | ran_dry
        g = np.where(ran_dry, dtype(np.inf),
                     gaps[rows, np.minimum(idx, F - 1)])
        next_fail = np.where(hit, wall_b + g, next_fail)
        idx = idx + hit
    P = {k: full(p[k]) for k in ("P_static", "P_cal", "P_io", "P_down")}
    energy = (P["P_static"] * wall + P["P_cal"] * work + P["P_io"] * io
              + P["P_down"] * down)
    return {"wall_time": wall, "energy": energy, "work_executed": work,
            "io_time": io, "down_time": down, "n_failures": n_fail,
            "n_checkpoints": n_ckpt, "truncated": ~done,
            "gaps_exhausted": exhausted}


# ---------------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------------

def rel_gap(got, want) -> float:
    """Largest ``|got - want| / |want|`` (0 where both are 0; inf where
    only the reference is 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.abs(got - want)
    den = np.abs(want)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(diff == 0.0, 0.0, diff / den)
    r = np.where(np.isnan(r), np.inf, r)
    return float(r.max()) if r.size else 0.0


def trajectory_gaps(got: dict, want: dict) -> tuple[float, int]:
    """(largest relative gap over the float fields, number of lanes whose
    counts or flags differ or that the reference could not finish)."""
    rel = max(rel_gap(got[f], want[f]) for f in FLOAT_FIELDS)
    bad = np.zeros(np.shape(want["wall_time"]), bool)
    for f in COUNT_FIELDS:
        bad |= np.asarray(got[f]) != np.asarray(want[f])
    for f in ("truncated", "gaps_exhausted"):
        bad |= np.asarray(got[f], bool) | np.asarray(want[f], bool)
    return rel, int(bad.sum())
