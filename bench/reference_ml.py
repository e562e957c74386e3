"""Plain reference of two-level (buddy + PFS) checkpointing under failures.

Written from the two-level model's semantics in numpy alone; it imports
nothing of the program under test (the single-level reference's
threefry replay and comparison helpers are the benchmark's own).

* Periods: the two-level AlgoE.  For each cadence ``m`` the expected
  energy of a job of ``T_base`` work at period ``T`` is

      E(T) = P_cal T_base + P_static Tf + (Tf / mu) (W0 + W1 T + Wm / T)
             + J T_base / (T - a),   Tf = T_base T / ((T - a)(b - T / (2 mu_m)))

  and ``K(T) E'(T)``, with ``K = (T - a)^2 (b - T/(2 mu_m))^2 / (P_static
  T_base)``, is a quadratic in ``T`` whose coefficients follow from
  ``N(T) = P_static T + (W0 T + W1 T^2 + Wm) / mu`` and ``g(T) = (T -
  a)(b - T/(2 mu_m))`` as ``(N' g - N g' - J (b - T/(2 mu_m))^2) /
  P_static``.  Its minimum root inside the valid bracket is ``T*_m``;
  ``(T*, m*)`` is the pair of least energy over the candidate cadences.
* Trajectories: the phase machine, one step per phase segment or failure.
  Every period lasts ``T``; period ``k`` of a superperiod computes for
  ``T - C_k`` and writes level 2 (``C2``, rate ``omega2``) if ``k == m -
  1``, level 1 (``C1``, ``omega1``) otherwise; a write commits the work
  as of its start, a deep write both levels.  Failure ``i`` strikes gap
  ``i`` after the previous recovery.  A soft one costs ``D1 + R1``, rolls
  back to the last commit of either level and resumes at the period after
  it; a hard one (flag ``i``) costs ``D2 + R2``, rolls back to the last
  deep commit and restarts the superperiod.  Recovery counts as I/O of
  its level, a write's time as I/O of the level it writes.
* Failure schedules: Weibull gaps from the threefry stream of each lane
  (``bench/reference.py``) and the flags from a second stream of the same
  lane key: ``uniform(fold_in(lane_key, 1), n, float64) < q``.

Every routine takes ``dtype``: float64 is the reference, float32 is the
control that a comparison has to reject.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref

#: work-completion and phase-boundary slack of the phase machine.
EPS = 1e-12

#: fields of one trajectory that the comparison reads.
FLOAT_FIELDS = ("wall_time", "energy", "work_executed", "io1_time",
                "io2_time", "down_time")
COUNT_FIELDS = ("n_failures", "n_hard_failures", "n_ckpt1", "n_ckpt2")
FLAG_FIELDS = ("truncated", "gaps_exhausted")

#: ``fold_in`` tag of the flag stream within a lane's key.
FLAG_STREAM = 1


# ---------------------------------------------------------------------------
# Platform parameters
# ---------------------------------------------------------------------------

def platform_points(config: dict) -> dict:
    """Per-point float64 parameters of a two-level configuration, MTBF-
    major over ``mu_minutes`` x ``q``."""
    pf = config["platform"]
    mus = np.asarray(config["mu_minutes"], np.float64)
    qs = np.asarray(config["q"], np.float64)
    mu, q = (a.ravel() for a in np.meshgrid(mus, qs, indexing="ij"))
    C2, R2 = float(pf["C2"]), float(pf["R2"])
    C1 = float(pf["buddy_ratio"]) * C2
    full = lambda v: np.full(mu.size, float(v))
    return {"C1": full(C1), "R1": full(float(pf["buddy_ratio"]) * R2),
            "D1": full(pf["D1"]), "C2": full(C2), "R2": full(R2),
            "D2": full(pf["D2"]), "omega1": full(pf["omega"]),
            "omega2": full(pf["omega"]), "mu": mu, "q": q,
            "P_static": full(pf["P_static"]), "P_cal": full(pf["P_cal"]),
            "P_io1": full(pf["P_io1"]), "P_io2": full(pf["P_io2"]),
            "P_down": full(pf["P_down"])}


def _terms(p, m):
    """(a, b, mu_m, W0, W1, Wm, J) of cadence ``m`` (array)."""
    C1, C2, w1, w2, q = p["C1"], p["C2"], p["omega1"], p["omega2"], p["q"]
    a = ((m - 1) * (1 - w1) * C1 + (1 - w2) * C2) / m
    Cw = ((m - 1) * w1 * C1 + w2 * C2) / m
    soft = p["D1"] + p["R1"] + Cw
    hard = p["D2"] + p["R2"] + w2 * C2
    b = 1 - (soft + q * (hard - soft)) / p["mu"]
    mu_m = p["mu"] / (1 + q * (m - 1))
    S2 = ((m - 1) * C1**2 + C2**2) / m
    S2w = ((m - 1) * w1 * C1**2 + w2 * C2**2) / m
    Pc, P1, P2, Pd = p["P_cal"], p["P_io1"], p["P_io2"], p["P_down"]
    W0 = (Pc * (Cw + q * (w2 * C2 - Cw - (m - 1) * (1 - w1) * C1 / 2))
          + P1 * ((1 - q) * p["R1"] + q * (m - 1) * C1 / 2)
          + P2 * q * p["R2"] + Pd * (p["D1"] + q * (p["D2"] - p["D1"])))
    W1 = Pc * (1 + q * (m - 1)) / 2
    Wm = Pc * (S2w - S2) / 2 + P1 * (m - 1) * C1**2 / (2 * m) \
        + P2 * C2**2 / (2 * m)
    J = P1 * (m - 1) * C1 / m + P2 * C2 / m
    return a, b, mu_m, W0, W1, Wm, J


def energy(T, m, p, T_base):
    """Expected energy of the two-level scheme at ``(T, m)``."""
    a, b, mu_m, W0, W1, Wm, J = _terms(p, m)
    Tf = T_base * T / ((T - a) * (b - T / (2 * mu_m)))
    return (p["P_cal"] * T_base + p["P_static"] * Tf
            + Tf / p["mu"] * (W0 + W1 * T + Wm / T) + J * T_base / (T - a))


def algo_e(p, m_values, T_base, dtype=np.float64):
    """``(T*, m*)`` per point: the AlgoE root of each cadence in
    ``m_values`` and the cadence of least energy.  Raises where no
    cadence has a minimum root inside its bracket."""
    p = {k: np.asarray(v, dtype) for k, v in p.items()}
    T_base = dtype(T_base)
    ms = np.asarray(m_values, dtype)[:, None]
    a, b, mu_m, W0, W1, Wm, J = _terms(p, ms)
    mu, Ps = p["mu"], p["P_static"]
    n2, n1, n0 = W1 / mu, Ps + W0 / mu, Wm / mu
    g2, g1, g0 = -1 / (2 * mu_m), b + a / (2 * mu_m), -a * b
    c2 = (n2 * g1 - n1 * g2 - J / (4 * mu_m**2)) / Ps
    c1 = (2 * (n2 * g0 - n0 * g2) + J * b / mu_m) / Ps
    c0 = (n1 * g0 - n0 * g1 - J * b**2) / Ps
    lo0 = np.maximum(np.maximum(a, p["C1"]), p["C2"])
    hi0 = 2 * mu_m * b
    valid = hi0 > lo0 * (1 + dtype(1e-9))
    span = hi0 - lo0
    lo = lo0 + dtype(1e-9) * span + dtype(1e-12)
    hi = hi0 - dtype(1e-9) * span
    with np.errstate(invalid="ignore"):
        sq = np.sqrt(c1**2 - 4 * c2 * c0)
        roots = np.stack([(-c1 - sq) / (2 * c2), (-c1 + sq) / (2 * c2)])
        ok = valid & (roots > lo) & (roots < hi) & (2 * c2 * roots + c1 > 0)
    T_m = np.where(ok[0], roots[0], roots[1])
    has = ok.any(axis=0)
    if not has.any(axis=0).all():
        raise ValueError("AlgoE: no cadence has a minimum root in range")
    with np.errstate(invalid="ignore", divide="ignore"):
        E = np.where(has, energy(T_m, ms, p, T_base), np.inf)
    i = np.argmin(E, axis=0)
    cols = np.arange(E.shape[1])
    return T_m[i, cols], np.asarray(m_values)[i]


# ---------------------------------------------------------------------------
# Failure schedules
# ---------------------------------------------------------------------------

def threefry_flags(seed: int, points, trials, q, n: int):
    """Level-loss flags of lane ``(point, trial)``: the first ``n`` of
    ``uniform(fold_in(fold_in(fold_in(PRNGKey(seed), point), trial),
    FLAG_STREAM), float64) < q``, drawn on the host CPU; ``(lanes, n)``."""
    import jax
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    with jax.enable_x64(True), jax.default_device(cpu):
        key = jax.random.PRNGKey(int(seed))

        def one(i, t):
            k = jax.random.fold_in(jax.random.fold_in(key, i), t)
            return jax.random.uniform(jax.random.fold_in(k, FLAG_STREAM),
                                      (n,), dtype=jnp.float64)
        pts = jax.device_put(np.asarray(points, np.uint32), cpu)
        trs = jax.device_put(np.asarray(trials, np.uint32), cpu)
        u = np.asarray(jax.jit(jax.vmap(one))(pts, trs), np.float64)
    return u < np.asarray(q, np.float64)[:, None]


# ---------------------------------------------------------------------------
# Trajectories
# ---------------------------------------------------------------------------

def simulate(T, m, p, T_base, gaps, hard, dtype=np.float64,
             max_steps: int = 10**6) -> dict:
    """Two-level phase-machine trajectories, one per lane.

    ``T``, ``m``, ``T_base`` and every entry of ``p`` are ``(lanes,)``
    arrays (or scalars); ``gaps`` and ``hard`` are ``(lanes, F)``.  Returns
    a dict of ``(lanes,)`` arrays: the float fields, the counts,
    ``truncated`` (the step budget ran out) and ``gaps_exhausted`` (a lane
    needed more than ``F`` gaps).
    """
    gaps = np.asarray(gaps, dtype)
    hard = np.asarray(hard, bool)
    L, F = gaps.shape
    full = lambda v: np.broadcast_to(np.asarray(v, dtype), (L,)).copy()
    T, T_base = full(T), full(T_base)
    m = np.broadcast_to(np.asarray(m, np.int64), (L,)).copy()
    C1, C2, R1, R2, D1, D2, w1, w2 = (full(p[k]) for k in (
        "C1", "C2", "R1", "R2", "D1", "D2", "omega1", "omega2"))
    zero = lambda: np.zeros(L, dtype)
    wall, c1, c2, live, work, io1, io2, down, snap = (
        zero() for _ in range(9))
    ints = lambda: np.zeros(L, np.int64)
    n_fail, n_hard, n_ck1, n_ck2, k, resume = (ints() for _ in range(6))
    in_ckpt = np.zeros(L, bool)
    cost = lambda kk: np.where(kk == m - 1, C2, C1)
    left = T - cost(k)
    next_fail = gaps[:, 0].copy()
    idx = np.ones(L, np.int64)
    exhausted = np.zeros(L, bool)
    done = live >= T_base - dtype(EPS)
    rows = np.arange(L)
    one = dtype(1.0)
    for _ in range(max_steps):
        if done.all():
            break
        deep = k == m - 1
        rate = np.where(in_ckpt, np.where(deep, w2, w1), one)
        with np.errstate(divide="ignore"):
            t_done = np.where(rate > 0, (T_base - live) / rate, np.inf)
        t_next = np.minimum(left, t_done)
        seg = ~done & (wall + t_next < next_fail)
        hit = ~done & ~seg

        # A phase segment completes before the next failure.
        wall = np.where(seg, wall + t_next, wall)
        live_a = live + rate * t_next
        work = np.where(seg, work + rate * t_next, work)
        io1 = np.where(seg & in_ckpt & ~deep, io1 + t_next, io1)
        io2 = np.where(seg & in_ckpt & deep, io2 + t_next, io2)
        left_a = left - t_next
        fin = seg & (live_a >= T_base - dtype(EPS))
        turn = seg & ~fin & (left_a <= dtype(EPS))
        start = turn & ~in_ckpt
        end = turn & in_ckpt
        snap = np.where(start, live_a, snap)
        c1 = np.where(end, snap, c1)
        c2 = np.where(end & deep, snap, c2)
        n_ck1 = n_ck1 + (end & ~deep)
        n_ck2 = n_ck2 + (end & deep)
        k_next = np.where(end, (k + 1) % m, k)
        resume = np.where(end, k_next, resume)
        k = k_next
        left = np.where(seg, np.where(start, cost(k), np.where(
            end, T - cost(k), left_a)), left)
        in_ckpt = np.where(start, True, np.where(end, False, in_ckpt))
        live = np.where(seg, live_a, live)
        done = done | fin

        # A failure strikes inside the segment.
        h = hard[rows, np.minimum(n_fail, F - 1)]
        dt = next_fail - wall
        work = np.where(hit, work + rate * dt, work)
        io1 = np.where(hit & in_ckpt & ~deep, io1 + dt, io1)
        io2 = np.where(hit & in_ckpt & deep, io2 + dt, io2)
        D = np.where(h, D2, D1)
        R = np.where(h, R2, R1)
        wall_b = next_fail + D + R
        wall = np.where(hit, wall_b, wall)
        down = np.where(hit, down + D, down)
        io1 = np.where(hit & ~h, io1 + R1, io1)
        io2 = np.where(hit & h, io2 + R2, io2)
        n_fail = n_fail + hit
        n_hard = n_hard + (hit & h)
        c1 = np.where(hit & h, c2, c1)
        k = np.where(hit, np.where(h, 0, resume), k)
        resume = np.where(hit, k, resume)
        live = np.where(hit, c1, live)
        in_ckpt = np.where(hit, False, in_ckpt)
        left = np.where(hit, T - cost(k), left)
        ran_dry = hit & (idx >= F)
        exhausted = exhausted | ran_dry
        g = np.where(ran_dry, dtype(np.inf),
                     gaps[rows, np.minimum(idx, F - 1)])
        next_fail = np.where(hit, wall_b + g, next_fail)
        idx = idx + hit
    P = {kk: full(p[kk]) for kk in ("P_static", "P_cal", "P_io1", "P_io2",
                                    "P_down")}
    e = (P["P_static"] * wall + P["P_cal"] * work + P["P_io1"] * io1
         + P["P_io2"] * io2 + P["P_down"] * down)
    return {"wall_time": wall, "energy": e, "work_executed": work,
            "io1_time": io1, "io2_time": io2, "down_time": down,
            "n_failures": n_fail, "n_hard_failures": n_hard,
            "n_ckpt1": n_ck1, "n_ckpt2": n_ck2, "truncated": ~done,
            "gaps_exhausted": exhausted}


# ---------------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------------

def periods_gap(got_T, got_m, want_T, want_m) -> float:
    """Largest relative gap of ``T*``; inf where any ``m*`` differs."""
    if not np.array_equal(np.asarray(got_m), np.asarray(want_m)):
        return float("inf")
    return ref.rel_gap(got_T, want_T)


def trajectory_gaps(got: dict, want: dict) -> tuple[float, int]:
    """(largest relative gap over the float fields, number of lanes whose
    counts or flags differ or that the reference could not finish)."""
    rel = max(ref.rel_gap(got[f], want[f]) for f in FLOAT_FIELDS)
    bad = np.zeros(np.shape(want["wall_time"]), bool)
    for f in COUNT_FIELDS:
        bad |= np.asarray(got[f]) != np.asarray(want[f])
    for f in FLAG_FIELDS:
        bad |= np.asarray(got[f], bool) | np.asarray(want[f], bool)
    return rel, int(bad.sum())
