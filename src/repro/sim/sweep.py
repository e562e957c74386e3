"""Batched closed-form model + period solvers over a :class:`ParamGrid`.

Vectorized (leading-batch-axes) counterparts of ``core.model`` and
``core.optimal``: the §3.1/§3.2 expectations, the golden-section minimizer,
the AlgoT closed form, the AlgoE quadratic root (corrected coefficients from
``optimal.derived_coefficients``, vectorized), and the Young/Daly/MSK
baselines — all evaluated for a whole grid in a few jitted float64 calls.

Root-selection semantics match the fixed scalar solver: E' = Q/K with K > 0
on the valid interval, so the energy *minimum* is the root of the quadratic
Q where Q' > 0; any point where that root is missing, complex, or outside
the bracket — or where its energy is beaten by the batched golden-section
argmin — falls back to the numeric result elementwise.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jax import enable_x64

from ..core.params import PowerParams
from . import dispatch as _dispatch
from . import precision as _precision
from . import scenarios
from .scenarios import MultilevelParamGrid, ParamGrid

_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0

#: conservative device-memory estimate per grid point of the closed-form
#: model sweep (the stacked golden-section state plus its elementwise
#: temporaries measure ~0.5 KiB/point; 8x headroom keeps the chunker's
#: budget promise honest).  Feeds dispatch.chunk_plan.
_MODEL_BYTES_PER_POINT = 4096

#: per-(grid point, candidate cadence) estimate for the multilevel sweep
#: (same stacked loops, per m plus the by_m output block).
_ML_BYTES_PER_POINT_M = 2048

#: every model-sweep dispatch shape is padded to a multiple of this lane
#: count.  XLA:CPU contracts the dense elementwise math differently at
#: small/ragged batch extents (unrolling + scalar remainder lanes flip
#: ~1-ulp roundings), so a fixed quantum is what makes chunk size, shard
#: count, and memory budget bit-exact no-ops for the model paths.
_MODEL_PAD_QUANTUM = 64

# p: dict of broadcastable jnp float64 arrays with the ParamGrid field names.


def _ab(p):
    a = (1.0 - p["omega"]) * p["C"]
    b = 1.0 - (p["D"] + p["R"] + p["omega"] * p["C"]) / p["mu"]
    return a, b


def time_final_batched(T, p, T_base=1.0):
    """§3.1: T_final = T_base * T / ((T-a)(b - T/2mu)), elementwise."""
    a, b = _ab(p)
    return T_base * T / ((T - a) * (b - T / (2.0 * p["mu"])))


def _re_exec(T, p):
    C, omega = p["C"], p["omega"]
    return (omega * C + (T**2 - C**2) / (2.0 * T)
            + omega * C**2 / (2.0 * T))


def _io_per_failure(T, p):
    return p["R"] + p["C"]**2 / (2.0 * T)


def energy_final_batched(T, p, T_base=1.0):
    """§3.2: E_final = T_cal P_cal + T_io P_io + T_down P_down + Tf P_static."""
    C, omega = p["C"], p["omega"]
    Tf = time_final_batched(T, p, T_base)
    nf = Tf / p["mu"]
    T_cal = T_base + nf * _re_exec(T, p)
    T_io = T_base * C / (T - (1.0 - omega) * C) + nf * _io_per_failure(T, p)
    T_down = nf * p["D"]
    # Policy-aware sum: the plain left-associated chain under the f64
    # oracle (bit-identical to inlining the +s), Neumaier-compensated
    # under a reduced-precision policy (sim/precision.py).
    return _precision.psum((T_cal * p["P_cal"], T_io * p["P_io"],
                            T_down * p["P_down"], Tf * p["P_static"]))


def _bracket(p):
    """Shrunk (lo, hi) per grid point, mirroring ``optimal._bracket``.

    Degenerate points (hi0 <= lo0) get a harmless placeholder bracket; the
    caller masks them out via ``valid``.
    """
    a, b = _ab(p)
    lo0 = jnp.maximum(a, p["C"])
    hi0 = 2.0 * p["mu"] * b
    valid = hi0 > lo0 * (1.0 + 1e-9)
    hi0 = jnp.where(valid, hi0, 2.0 * lo0 + 1.0)
    span = hi0 - lo0
    return lo0 + 1e-9 * span + 1e-12, hi0 - 1e-9 * span, valid


def golden_section_batched(f: Callable, lo, hi, iters: int = 40):
    """Elementwise golden-section argmin of ``f`` on [lo, hi].

    Branchless (``jnp.where``) form of ``optimal.golden_section`` carrying
    the two interior function values, so each iteration costs ONE batched
    evaluation of ``f`` — the loop is sequential, so per-step cost is what
    dominates on small grids.
    """
    a, b = lo, hi
    c = b - _GOLDEN * (b - a)
    d = a + _GOLDEN * (b - a)
    fc, fd = f(c), f(d)

    def body(_, st):
        a, b, c, d, fc, fd = st
        left = fc < fd
        a2 = jnp.where(left, a, c)
        b2 = jnp.where(left, d, b)
        new = jnp.where(left, b2 - _GOLDEN * (b2 - a2),
                        a2 + _GOLDEN * (b2 - a2))
        fnew = f(new)
        c2 = jnp.where(left, new, d)
        fc2 = jnp.where(left, fnew, fd)
        d2 = jnp.where(left, c, new)
        fd2 = jnp.where(left, fc, fnew)
        return (a2, b2, c2, d2, fc2, fd2)

    a, b, _, _, _, _ = lax.fori_loop(0, iters, body, (a, b, c, d, fc, fd))
    return 0.5 * (a + b)


# ---------------------------------------------------------------------------
# Period solvers
# ---------------------------------------------------------------------------

def _t_opt_time_from(p, t_num):
    """AlgoT closed form, falling back to the supplied numeric argmin."""
    a, b = _ab(p)
    lo, hi, _ = _bracket(p)
    val = 2.0 * a * b * p["mu"]
    t_closed = jnp.clip(jnp.sqrt(jnp.maximum(val, 0.0)), lo, hi)
    return jnp.where(val > 0.0, t_closed, t_num)


def t_opt_time_batched(p, T_base=1.0):
    """AlgoT, Eq. (1) closed form; numeric fallback where it degenerates.

    Degenerate grid points (no valid period: the scalar solver raises)
    return NaN — the elementwise analogue of that error.
    """
    lo, hi, valid = _bracket(p)
    t_num = golden_section_batched(
        lambda t: time_final_batched(t, p, T_base), lo, hi)
    return jnp.where(valid, _t_opt_time_from(p, t_num), jnp.nan)


def _energy_quadratic(p):
    """Vectorized corrected coefficients (``optimal.derived_coefficients``)."""
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
    return c2, c1, c0


def _t_opt_energy_from(p, T_base, t_num):
    """AlgoE quadratic root, guarded by the supplied numeric argmin."""
    lo, hi, _ = _bracket(p)
    c2, c1, c0 = _energy_quadratic(p)

    disc = c1**2 - 4.0 * c2 * c0
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    safe_c2 = jnp.where(jnp.abs(c2) > 1e-300, c2, 1.0)
    r1 = (-c1 - sq) / (2.0 * safe_c2)
    r2 = (-c1 + sq) / (2.0 * safe_c2)
    safe_c1 = jnp.where(jnp.abs(c1) > 1e-300, c1, 1.0)
    rlin = -c0 / safe_c1

    def is_min_root(r):
        # E'' sign at a root of E' equals the sign of Q' (K > 0 in-bracket).
        return ((disc >= 0.0) & (jnp.abs(c2) > 1e-300)
                & (r > lo) & (r < hi) & (2.0 * c2 * r + c1 > 0.0))

    lin_ok = (jnp.abs(c2) <= 1e-300) & (jnp.abs(c1) > 1e-300) \
        & (rlin > lo) & (rlin < hi) & (c1 > 0.0)

    t_root = jnp.where(is_min_root(r1), r1,
                       jnp.where(is_min_root(r2), r2,
                                 jnp.where(lin_ok, rlin, t_num)))
    # Safeguard: never return a root whose energy loses to the numeric argmin.
    e_root = energy_final_batched(t_root, p, T_base)
    e_num = energy_final_batched(t_num, p, T_base)
    return jnp.where(e_root <= e_num * (1.0 + 1e-9), t_root, t_num)


def t_opt_energy_batched(p, T_base=1.0):
    """AlgoE: minimum-branch quadratic root, numeric fallback elementwise.

    Degenerate grid points (no valid period) return NaN.
    """
    lo, hi, valid = _bracket(p)
    t_num = golden_section_batched(
        lambda t: energy_final_batched(t, p, T_base), lo, hi)
    return jnp.where(valid, _t_opt_energy_from(p, T_base, t_num), jnp.nan)


def t_young_batched(p):
    return jnp.sqrt(2.0 * p["C"] * p["mu"]) + p["C"]


def t_daly_batched(p):
    return jnp.sqrt(2.0 * p["C"] * (p["mu"] + p["D"] + p["R"])) + p["C"]


def _msk_energy(T, p0, T_base=1.0):
    """MSK objective on the omega=0 parameter set (paper §3.2 side note)."""
    C, R = p0["C"], p0["R"]
    Tf = time_final_batched(T, p0, T_base)
    nf = Tf / p0["mu"]
    T_cal = T_base + nf * (T - 2.0 * C) / 2.0
    T_io = T_base * C / (T - C) + nf * (R + C)
    T_down = nf * p0["D"]
    return _precision.psum((T_cal * p0["P_cal"], T_io * p0["P_io"],
                            T_down * p0["P_down"], Tf * p0["P_static"]))


def _msk_setup(p):
    """(omega=0 params, lo, hi, valid) for the MSK numeric argmin."""
    p0 = dict(p)
    p0["omega"] = jnp.zeros_like(p["omega"])
    lo, hi, valid = _bracket(p0)
    return p0, jnp.maximum(lo, 2.0 * p0["C"] + 1e-12), hi, valid


def t_msk_energy_batched(p, T_base=1.0):
    """MSK energy-optimal period; degenerate points return NaN."""
    p0, lo, hi, valid = _msk_setup(p)
    t = golden_section_batched(lambda t: _msk_energy(t, p0, T_base), lo, hi)
    return jnp.where(valid, t, jnp.nan)


# ---------------------------------------------------------------------------
# Grid evaluation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GridResult:
    """Periods/ratios for a whole grid; arrays of ``grid.shape``.

    Degenerate points (``~valid``: C of the order of the MTBF, no usable
    period) carry T_time = T_energy = C and ratios of exactly 1.0, matching
    the scalar ``tradeoff.evaluate`` convention; their Tf_*/E_* are NaN.
    """

    grid: ParamGrid
    T_base: float
    T_time: np.ndarray           # AlgoT period
    T_energy: np.ndarray         # AlgoE period
    T_young: np.ndarray
    T_daly: np.ndarray
    T_msk: np.ndarray
    Tf_time: np.ndarray          # T_final at the AlgoT period
    Tf_energy: np.ndarray        # T_final at the AlgoE period
    E_time: np.ndarray           # E_final at the AlgoT period
    E_energy: np.ndarray         # E_final at the AlgoE period
    time_ratio: np.ndarray       # Tf_energy / Tf_time  (>= 1, "loss")
    energy_ratio: np.ndarray     # E_time / E_energy    (>= 1, "gain")
    valid: np.ndarray

    @property
    def energy_saving(self) -> np.ndarray:
        return 1.0 - 1.0 / self.energy_ratio

    @property
    def time_overhead(self) -> np.ndarray:
        return self.time_ratio - 1.0


_FIELD_ORDER = ("C", "R", "D", "mu", "omega",
                "P_static", "P_cal", "P_io", "P_down")
_OUT_ORDER = ("T_time", "T_energy", "T_young", "T_daly", "T_msk",
              "Tf_time", "Tf_energy", "E_time", "E_energy",
              "time_ratio", "energy_ratio", "valid")


def _evaluate_core(P, T_base):
    # P is one stacked (9, N) array — a single host->device transfer and a
    # single dispatch beat nine tiny ones on small grids.  Jitted (and
    # sharded/chunked) by the dispatch layer, not here.
    p = dict(zip(_FIELD_ORDER, P))
    lo, hi, valid = _bracket(p)
    p0, lo_m, hi_m, _ = _msk_setup(p)

    # The three numeric argmins (AlgoT fallback, AlgoE guard, MSK) share ONE
    # golden-section loop over a stacked leading axis: the loop is sequential
    # and dispatch-bound on small grids, so fusing it is a ~3x win there.
    sel = jnp.arange(3, dtype=jnp.int32).reshape((3,) + (1,) * lo.ndim)

    def objective(t):
        return jnp.where(sel == 0, time_final_batched(t, p, T_base),
                         jnp.where(sel == 1,
                                   energy_final_batched(t, p, T_base),
                                   _msk_energy(t, p0, T_base)))

    t_num = golden_section_batched(objective,
                                   jnp.stack([lo, lo, lo_m]),
                                   jnp.stack([hi, hi, hi_m]))
    Tt = _t_opt_time_from(p, t_num[0])
    Te = _t_opt_energy_from(p, T_base, t_num[1])
    Ty = t_young_batched(p)
    Td = t_daly_batched(p)
    Tm = t_num[2]
    Tf_t = time_final_batched(Tt, p, T_base)
    Tf_e = time_final_batched(Te, p, T_base)
    E_t = energy_final_batched(Tt, p, T_base)
    E_e = energy_final_batched(Te, p, T_base)
    nan = jnp.full_like(Tt, jnp.nan)
    C = p["C"]
    one = jnp.ones_like(Tt)
    return jnp.stack([jnp.where(valid, Tt, C),
                      jnp.where(valid, Te, C),
                      Ty, Td,
                      jnp.where(valid, Tm, C),
                      jnp.where(valid, Tf_t, nan),
                      jnp.where(valid, Tf_e, nan),
                      jnp.where(valid, E_t, nan),
                      jnp.where(valid, E_e, nan),
                      jnp.where(valid, Tf_e / Tf_t, one),
                      jnp.where(valid, E_t / E_e, one),
                      valid.astype(C.dtype)])


def _policy_build(core, policy):
    """Policy-routed variant of a stacked model core: inputs cast to the
    policy's compute dtype, the trace runs under the policy context (so
    the energy-term sums go through ``precision.psum`` compensated), and
    outputs are cast back to f64 for the host-side layers.  Only built
    for non-exact policies — the f64 oracle keeps the original build and
    its bit-identical compiled program."""
    def build(*args):
        with _precision.trace_policy(policy):
            out = core(*(policy.cast(a) for a in args))
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float64), out)
    return build


def _policy_key(key: tuple, policy) -> tuple:
    """Runner-cache key for a policy-routed build: the f64 oracle keeps
    its historical key; other policies never share a compiled program."""
    return key if policy is None or policy.exact else key + (policy.name,)


def evaluate_grid(grid: ParamGrid, T_base: float = 1.0,
                  dispatch=None, precision=None) -> GridResult:
    """Periods + time/energy ratios for every grid point.

    Routed through :mod:`repro.sim.dispatch`: the grid axis is sharded
    across the local devices and chunked to the configured device-memory
    budget (``dispatch`` is a :class:`~repro.sim.dispatch.DispatchConfig`;
    None = environment defaults), so a 10^6-point dense grid streams in
    bounded memory.  The computation is elementwise per grid point —
    chunk size and shard count are bit-exact no-ops on the results.

    ``precision`` selects the :class:`~repro.sim.precision
    .PrecisionPolicy` (a policy, a name, or None = config/env/backend
    default — f64 on CPU): the f64 oracle path is untouched; a reduced-
    precision policy computes in its dtype with compensated energy sums
    and lands within the policy's documented tolerance of the oracle
    (tests/test_pallas_engine.py parity gates).
    """
    pol = _dispatch.resolve_precision(dispatch, precision)
    flat = grid.ravel()
    P = np.stack([getattr(flat, f) for f in _FIELD_ORDER])
    raw = _dispatch.run(
        key=_policy_key(("evaluate_core",), pol),
        build=(_evaluate_core if pol.exact
               else _policy_build(_evaluate_core, pol)),
        args=(P, np.float64(T_base)), in_axes=(1, None), out_axes=1,
        size=flat.size, per_point_bytes=_MODEL_BYTES_PER_POINT,
        config=dispatch, quantum=_MODEL_PAD_QUANTUM)
    out = {k: raw[i].reshape(grid.shape) for i, k in enumerate(_OUT_ORDER)}
    out["valid"] = out["valid"] > 0.5
    return GridResult(grid=grid, T_base=float(T_base), **out)


# ---------------------------------------------------------------------------
# Multilevel (buddy + PFS) batched model + joint (T, m) solvers
# ---------------------------------------------------------------------------
#
# p: dict of broadcastable jnp float64 arrays with MultilevelParamGrid field
# names; m: a float array broadcasting against them (the solvers put the
# candidate cadences on a leading axis and argmin over it).

def _where(cond, a, b):
    """Namespace-dispatching ``where``: jnp only when an operand is a jax
    value (traced or device), numpy otherwise.  The ``ml_*_batched``
    entry points are also called EAGERLY on host scalars (the serve
    layer's certificate sweeps); an unconditional ``jnp.where`` there
    would pull the whole expression onto the jax eager path and compile
    one tiny program per arithmetic op (caught by the sanitizer tier's
    recompile budget)."""
    import jax
    if any(isinstance(x, jax.Array) for x in (cond, a, b)):
        return jnp.where(cond, a, b)
    return np.where(cond, a, b)


def _ml_omega_terms(p, m):
    """(w1, w2, Cw, S2, S2w): per-level overlap aggregates.

    Where the two overlap factors coincide the shared-omega expressions
    are evaluated verbatim (bit-for-bit with both the pre-async batched
    forms and the scalar ``MultilevelCheckpointParams`` branches).
    ``omega1``/``omega2`` fall back to the shared ``omega`` when a plain
    param dict omits them (the public ``ml_*_batched`` entry points
    accept both spellings).
    """
    C1, C2 = p["C1"], p["C2"]
    w1 = p.get("omega1", p["omega"])
    w2 = p.get("omega2", p["omega"])
    shared = w1 == w2
    Cb = ((m - 1.0) * C1 + C2) / m
    S2 = ((m - 1.0) * C1**2 + C2**2) / m
    Cw = _where(shared, w1 * Cb,
                ((m - 1.0) * w1 * C1 + w2 * C2) / m)
    S2w = _where(shared, w1 * S2,
                 ((m - 1.0) * w1 * C1**2 + w2 * C2**2) / m)
    return w1, w2, Cw, S2, S2w


def _ml_derived(p, m):
    """(C_mean, a_m, b_m, mu_m) of the multilevel §3.1 analogue."""
    Cb = ((m - 1.0) * p["C1"] + p["C2"]) / m
    w1, w2, Cw, _, _ = _ml_omega_terms(p, m)
    a = _where(w1 == w2, (1.0 - w1) * Cb,
               ((m - 1.0) * (1.0 - w1) * p["C1"]
                + (1.0 - w2) * p["C2"]) / m)
    soft = p["D1"] + p["R1"] + Cw
    hard = p["D2"] + p["R2"] + w2 * p["C2"]
    b = 1.0 - (soft + p["q"] * (hard - soft)) / p["mu"]
    mu_m = p["mu"] / (1.0 + p["q"] * (m - 1.0))
    return Cb, a, b, mu_m


def ml_time_final_batched(T, m, p, T_base=1.0):
    """Two-level expected makespan, elementwise (period T, deep every m)."""
    _, a, b, mu_m = _ml_derived(p, m)
    return T_base * T / ((T - a) * (b - T / (2.0 * mu_m)))


def ml_energy_final_batched(T, m, p, T_base=1.0):
    """Two-level E_final with per-level I/O powers, elementwise."""
    C1, R1, D1 = p["C1"], p["R1"], p["D1"]
    C2, R2, D2 = p["C2"], p["R2"], p["D2"]
    q = p["q"]
    Cb, a, b, mu_m = _ml_derived(p, m)
    w1, w2, Cw, S2, S2w = _ml_omega_terms(p, m)

    Tf = T_base * T / ((T - a) * (b - T / (2.0 * mu_m)))
    nf = Tf / p["mu"]
    Ew = (T**2 - S2) / (2.0 * T) + S2w / (2.0 * T)
    w_soft = Cw + Ew
    w_hard = w2 * C2 + (m - 1.0) * (T - (1.0 - w1) * C1) / 2.0 + Ew
    T_cal = T_base + nf * (w_soft + q * (w_hard - w_soft))

    ck_io1 = T_base * ((m - 1.0) * C1 / m) / (T - a)
    ck_io2 = T_base * (C2 / m) / (T - a)
    io1_pf = ((m - 1.0) / m) * C1**2 / (2.0 * T) + (1.0 - q) * R1 \
        + q * (m - 1.0) * C1 / 2.0
    io2_pf = C2**2 / (2.0 * m * T) + q * R2
    T_down = nf * (D1 + q * (D2 - D1))
    return _precision.psum((T_cal * p["P_cal"],
                            (ck_io1 + nf * io1_pf) * p["P_io1"],
                            (ck_io2 + nf * io2_pf) * p["P_io2"],
                            T_down * p["P_down"], Tf * p["P_static"]))


def _ml_bracket(p, m):
    """Shrunk (lo, hi, valid) per (m, grid point)."""
    _, a, b, mu_m = _ml_derived(p, m)
    lo0 = jnp.maximum(jnp.maximum(a, p["C1"]), p["C2"])
    hi0 = 2.0 * mu_m * b
    valid = hi0 > lo0 * (1.0 + 1e-9)
    hi0 = jnp.where(valid, hi0, 2.0 * lo0 + 1.0)
    span = hi0 - lo0
    return lo0 + 1e-9 * span + 1e-12, hi0 - 1e-9 * span, valid


def _ml_energy_prime_batched(T, m, p, T_base=1.0):
    """Analytic two-level dE/dT (W normal form, mirrors core.model)."""
    C1, C2 = p["C1"], p["C2"]
    q = p["q"]
    Pc, P1, P2, Pd = p["P_cal"], p["P_io1"], p["P_io2"], p["P_down"]
    Cb, a, b, mu_m = _ml_derived(p, m)
    w1, w2, Cw, S2, S2w = _ml_omega_terms(p, m)

    W0 = (Pc * (Cw + q * (w2 * C2 - Cw
                          - (m - 1.0) * (1.0 - w1) * C1 / 2.0))
          + P1 * ((1.0 - q) * p["R1"] + q * (m - 1.0) * C1 / 2.0)
          + P2 * q * p["R2"]
          + Pd * (p["D1"] + q * (p["D2"] - p["D1"])))
    W1 = Pc * (1.0 + q * (m - 1.0)) / 2.0
    Wm = (Pc * (S2w - S2) / 2.0
          + P1 * (m - 1.0) * C1**2 / (2.0 * m)
          + P2 * C2**2 / (2.0 * m))
    J = P1 * (m - 1.0) * C1 / m + P2 * C2 / m

    Tf = T_base * T / ((T - a) * (b - T / (2.0 * mu_m)))
    Tfp = T_base * (-a * b + T**2 / (2.0 * mu_m)) \
        / ((T - a) ** 2 * (b - T / (2.0 * mu_m)) ** 2)
    W = W0 + W1 * T + Wm / T
    Wp = W1 - Wm / T**2
    return (p["P_static"] * Tfp + Tfp / p["mu"] * W + Tf / p["mu"] * Wp
            - J * T_base / (T - a) ** 2)


def _ml_quadratic(p, m, lo, hi, T_base):
    """(c2, c1, c0, quad_ok) of Q_m = K_m * E' by 3-point Newton
    interpolation of the analytic product + vectorized 4th-point check."""
    _, a, b, mu_m = _ml_derived(p, m)

    def Q(t):
        K = (t - a) ** 2 * (b - t / (2.0 * mu_m)) ** 2 \
            / (p["P_static"] * T_base)
        return K * _ml_energy_prime_batched(t, m, p, T_base)

    span = hi - lo
    t1, t2, t3 = lo + 0.2 * span, lo + 0.45 * span, lo + 0.7 * span
    q1, q2, q3 = Q(t1), Q(t2), Q(t3)
    d1 = (q2 - q1) / (t2 - t1)
    d2 = (q3 - q2) / (t3 - t2)
    c2 = (d2 - d1) / (t3 - t1)
    c1 = d1 - c2 * (t1 + t2)
    c0 = q1 - t1 * (d1 - c2 * t2)

    t4 = lo + 0.9 * span
    q4 = Q(t4)
    q4_poly = c2 * t4**2 + c1 * t4 + c0
    scale = jnp.maximum(jnp.maximum(jnp.abs(q4), jnp.abs(q4_poly)),
                        jnp.maximum(jnp.abs(c0), 1e-300))
    quad_ok = jnp.abs(q4 - q4_poly) <= 1e-6 * scale
    return c2, c1, c0, quad_ok


def _t_opt_time_ml_from(p, m, t_num):
    """Per-m AlgoT closed form, numeric fallback where it degenerates."""
    _, a, b, mu_m = _ml_derived(p, m)
    lo, hi, _ = _ml_bracket(p, m)
    val = 2.0 * a * b * mu_m
    t_closed = jnp.clip(jnp.sqrt(jnp.maximum(val, 0.0)), lo, hi)
    return jnp.where(val > 0.0, t_closed, t_num)


def _t_opt_energy_ml_from(p, m, T_base, t_num):
    """Per-m AlgoE quadratic root with the scalar solver's guard semantics."""
    lo, hi, _ = _ml_bracket(p, m)
    c2, c1, c0, quad_ok = _ml_quadratic(p, m, lo, hi, T_base)

    disc = c1**2 - 4.0 * c2 * c0
    sq = jnp.sqrt(jnp.maximum(disc, 0.0))
    safe_c2 = jnp.where(jnp.abs(c2) > 1e-300, c2, 1.0)
    r1 = (-c1 - sq) / (2.0 * safe_c2)
    r2 = (-c1 + sq) / (2.0 * safe_c2)
    safe_c1 = jnp.where(jnp.abs(c1) > 1e-300, c1, 1.0)
    rlin = -c0 / safe_c1

    def is_min_root(r):
        return (quad_ok & (disc >= 0.0) & (jnp.abs(c2) > 1e-300)
                & (r > lo) & (r < hi) & (2.0 * c2 * r + c1 > 0.0))

    lin_ok = quad_ok & (jnp.abs(c2) <= 1e-300) & (jnp.abs(c1) > 1e-300) \
        & (rlin > lo) & (rlin < hi) & (c1 > 0.0)

    t_root = jnp.where(is_min_root(r1), r1,
                       jnp.where(is_min_root(r2), r2,
                                 jnp.where(lin_ok, rlin, t_num)))
    e_root = ml_energy_final_batched(t_root, m, p, T_base)
    e_num = ml_energy_final_batched(t_num, m, p, T_base)
    return jnp.where(e_root <= e_num * (1.0 + 1e-9), t_root, t_num)


@dataclasses.dataclass(frozen=True)
class MultilevelGridResult:
    """Jointly optimal (T, m) per grid point, plus per-m curves.

    Scalar-per-point arrays have ``grid.shape``; the ``*_by_m`` arrays carry
    a leading axis over ``m_values``.  Degenerate points (no valid period at
    any m) follow the ``GridResult`` convention: periods C2, m 1, ratios
    exactly 1.0, Tf/E NaN.
    """

    grid: MultilevelParamGrid
    m_values: tuple
    T_base: float
    T_time: np.ndarray           # AlgoT period
    m_time: np.ndarray           # AlgoT deep-checkpoint cadence (int)
    T_energy: np.ndarray         # AlgoE period
    m_energy: np.ndarray         # (int)
    Tf_time: np.ndarray
    Tf_energy: np.ndarray
    E_time: np.ndarray
    E_energy: np.ndarray
    time_ratio: np.ndarray       # Tf_energy / Tf_time  (>= 1, "loss")
    energy_ratio: np.ndarray     # E_time / E_energy    (>= 1, "gain")
    time_vs_single: np.ndarray   # Tf(AlgoT, 2-level) / Tf(AlgoT, PFS-only)
    energy_vs_single: np.ndarray  # E(AlgoE, 2-level) / E(AlgoE, PFS-only)
    T_time_by_m: np.ndarray      # (M,) + grid.shape
    Tf_by_m: np.ndarray
    T_energy_by_m: np.ndarray
    E_by_m: np.ndarray
    valid_by_m: np.ndarray
    valid: np.ndarray

    @property
    def energy_saving(self) -> np.ndarray:
        return 1.0 - 1.0 / self.energy_ratio

    @property
    def time_overhead(self) -> np.ndarray:
        return self.time_ratio - 1.0

    def point_at(self, idx):
        """Scalar :class:`core.tradeoff.MultilevelTradeoffPoint` view."""
        from ..core.tradeoff import MultilevelTradeoffPoint
        return MultilevelTradeoffPoint(
            ckpt=self.grid.ckpt_at(idx), power=self.grid.power_at(idx),
            T_time=float(self.T_time[idx]), m_time=int(self.m_time[idx]),
            T_energy=float(self.T_energy[idx]),
            m_energy=int(self.m_energy[idx]),
            time_ratio=float(self.time_ratio[idx]),
            energy_ratio=float(self.energy_ratio[idx]),
            time_vs_single=float(self.time_vs_single[idx]),
            energy_vs_single=float(self.energy_vs_single[idx]))


_ML_FIELD_ORDER = ("C1", "R1", "D1", "C2", "R2", "D2", "mu", "omega", "q",
                   "P_static", "P_cal", "P_io1", "P_io2", "P_down",
                   "omega1", "omega2")
_ML_OUT_ORDER = ("T_time", "m_time", "T_energy", "m_energy",
                 "Tf_time", "Tf_energy", "E_time", "E_energy",
                 "time_ratio", "energy_ratio",
                 "time_vs_single", "energy_vs_single", "valid")


def _evaluate_ml_core(P, T_base, m_values, m_max=None):
    # P: one stacked (16, N) array; m_values: static tuple of cadences
    # (closed over by the dispatch build — one compiled program per
    # distinct tuple, exactly like the old static_argnums jit).
    # m_max: optional traced (N,) per-point cadence cap — candidates with
    # mv > m_max are masked invalid for that point, so heterogeneous
    # cadence budgets (the advisor's admission batches) share ONE
    # compiled program over the union candidate set.
    p = dict(zip(_ML_FIELD_ORDER, P))
    mv = jnp.asarray(m_values, P.dtype).reshape((-1, 1))     # (M, 1)
    lo, hi, valid_m = _ml_bracket(p, mv)                     # (M, N)
    if m_max is not None:
        valid_m = valid_m & (mv <= m_max[None, :])

    # The per-m time and energy numeric argmins share ONE golden-section
    # loop over a stacked leading axis (same dispatch-bound rationale as
    # the single-level _evaluate_core).
    sel = jnp.arange(2, dtype=jnp.int32).reshape((2, 1, 1))

    def objective(t):
        return jnp.where(sel == 0, ml_time_final_batched(t, mv, p, T_base),
                         ml_energy_final_batched(t, mv, p, T_base))

    t_num = golden_section_batched(objective,
                                   jnp.broadcast_to(lo, (2,) + lo.shape),
                                   jnp.broadcast_to(hi, (2,) + hi.shape))
    Tt_m = _t_opt_time_ml_from(p, mv, t_num[0])              # (M, N)
    Te_m = _t_opt_energy_ml_from(p, mv, T_base, t_num[1])
    Tf_m = ml_time_final_batched(Tt_m, mv, p, T_base)
    E_m = ml_energy_final_batched(Te_m, mv, p, T_base)

    inf = jnp.inf
    i_t = jnp.argmin(jnp.where(valid_m, Tf_m, inf), axis=0)  # (N,)
    i_e = jnp.argmin(jnp.where(valid_m, E_m, inf), axis=0)
    take = lambda arr, i: jnp.take_along_axis(arr, i[None, :], axis=0)[0]
    m_arr = jnp.asarray(m_values, P.dtype)
    T_time, m_time = take(Tt_m, i_t), m_arr[i_t]
    T_energy, m_energy = take(Te_m, i_e), m_arr[i_e]
    Tf_time, E_energy = take(Tf_m, i_t), take(E_m, i_e)
    # Cross metrics at the jointly-optimal operating points.
    Tf_energy = ml_time_final_batched(T_energy, m_energy, p, T_base)
    E_time = ml_energy_final_batched(T_time, m_time, p, T_base)

    # PFS-only single-level comparator on the same grid (C2/R2/D2/P_io2,
    # at the deep level's overlap factor — mirrors grid.single_level()).
    p_sl = {"C": p["C2"], "R": p["R2"], "D": p["D2"], "mu": p["mu"],
            "omega": p["omega2"], "P_static": p["P_static"],
            "P_cal": p["P_cal"], "P_io": p["P_io2"], "P_down": p["P_down"]}
    lo_s, hi_s, valid_s = _bracket(p_sl)
    sel_s = jnp.arange(2, dtype=jnp.int32).reshape((2, 1))

    def objective_s(t):
        return jnp.where(sel_s == 0, time_final_batched(t, p_sl, T_base),
                         energy_final_batched(t, p_sl, T_base))

    t_num_s = golden_section_batched(objective_s,
                                     jnp.stack([lo_s, lo_s]),
                                     jnp.stack([hi_s, hi_s]))
    Tt_s = _t_opt_time_from(p_sl, t_num_s[0])
    Te_s = _t_opt_energy_from(p_sl, T_base, t_num_s[1])
    Tf_s = time_final_batched(Tt_s, p_sl, T_base)
    E_s = energy_final_batched(Te_s, p_sl, T_base)

    valid = jnp.any(valid_m, axis=0)
    nan = jnp.full_like(T_time, jnp.nan)
    one = jnp.ones_like(T_time)
    C2 = p["C2"]
    scalars = jnp.stack([
        jnp.where(valid, T_time, C2),
        jnp.where(valid, m_time, 1.0),
        jnp.where(valid, T_energy, C2),
        jnp.where(valid, m_energy, 1.0),
        jnp.where(valid, Tf_time, nan),
        jnp.where(valid, Tf_energy, nan),
        jnp.where(valid, E_time, nan),
        jnp.where(valid, E_energy, nan),
        jnp.where(valid, Tf_energy / Tf_time, one),
        jnp.where(valid, E_time / E_energy, one),
        # vs-single ratios are meaningless when the PFS-only comparator has
        # no valid period at all (exactly the regime where the buddy level
        # rescues an otherwise infeasible platform): report NaN there.
        jnp.where(valid, jnp.where(valid_s, Tf_time / Tf_s, nan), one),
        jnp.where(valid, jnp.where(valid_s, E_energy / E_s, nan), one),
        valid.astype(C2.dtype)])
    by_m = jnp.stack([Tt_m, jnp.where(valid_m, Tf_m, jnp.nan),
                      Te_m, jnp.where(valid_m, E_m, jnp.nan),
                      valid_m.astype(C2.dtype)])
    return scalars, by_m


def evaluate_multilevel_grid(grid: MultilevelParamGrid,
                             m_values: Sequence[int] = tuple(range(1, 13)),
                             T_base: float = 1.0,
                             dispatch=None, m_max=None,
                             precision=None) -> MultilevelGridResult:
    """Jointly optimal (T, m) + ratios for every grid point.

    ``m_values`` is the candidate set of deep-checkpoint cadences (static:
    one compiled program per distinct tuple).  The grid axis routes
    through :mod:`repro.sim.dispatch` (sharding + memory-bounded
    chunking; ``dispatch`` is its config, None = environment defaults).

    ``m_max`` (optional) caps the cadence PER GRID POINT: an integer array
    broadcastable to ``grid.shape``; candidates ``m > m_max[point]`` are
    masked invalid for that point only.  This is the heterogeneous-request
    assembly hook: requests with different cadence budgets batch into one
    call over the union candidate set instead of one compiled program per
    distinct budget.  ``m_max=None`` keeps the unmasked program and its
    results bit-for-bit.

    ``precision`` routes the sweep through a
    :class:`~repro.sim.precision.PrecisionPolicy` exactly like
    :func:`evaluate_grid` (f64 oracle untouched; reduced-precision
    within documented tolerance).
    """
    pol = _dispatch.resolve_precision(dispatch, precision)
    m_values = tuple(int(m) for m in m_values)
    if not m_values or min(m_values) < 1:
        raise ValueError(f"m_values must be positive ints, got {m_values}")
    flat = grid.ravel()
    P = np.stack([getattr(flat, f) for f in _ML_FIELD_ORDER])
    if m_max is None:
        core = lambda P_, tb: _evaluate_ml_core(P_, tb, m_values)
        scalars, by_m = _dispatch.run(
            key=_policy_key(("evaluate_ml_core", m_values), pol),
            build=core if pol.exact else _policy_build(core, pol),
            args=(P, np.float64(T_base)), in_axes=(1, None), out_axes=(1, 2),
            size=flat.size,
            per_point_bytes=_ML_BYTES_PER_POINT_M * len(m_values),
            config=dispatch, quantum=_MODEL_PAD_QUANTUM)
    else:
        mm = np.broadcast_to(np.asarray(m_max, dtype=np.float64),
                             grid.shape).ravel()
        core = lambda P_, tb, mm_: _evaluate_ml_core(P_, tb, m_values, mm_)
        scalars, by_m = _dispatch.run(
            key=_policy_key(("evaluate_ml_core_masked", m_values), pol),
            build=core if pol.exact else _policy_build(core, pol),
            args=(P, np.float64(T_base), mm), in_axes=(1, None, 0),
            out_axes=(1, 2), size=flat.size,
            per_point_bytes=_ML_BYTES_PER_POINT_M * len(m_values),
            config=dispatch, quantum=_MODEL_PAD_QUANTUM)
    out = {k: scalars[i].reshape(grid.shape)
           for i, k in enumerate(_ML_OUT_ORDER)}
    out["valid"] = out["valid"] > 0.5
    out["m_time"] = np.where(out["valid"], out["m_time"], 1).astype(np.int64)
    out["m_energy"] = np.where(out["valid"], out["m_energy"],
                               1).astype(np.int64)
    M = len(m_values)
    shp = (M,) + grid.shape
    return MultilevelGridResult(
        grid=grid, m_values=m_values, T_base=float(T_base),
        T_time_by_m=by_m[0].reshape(shp), Tf_by_m=by_m[1].reshape(shp),
        T_energy_by_m=by_m[2].reshape(shp), E_by_m=by_m[3].reshape(shp),
        valid_by_m=by_m[4].reshape(shp) > 0.5, **out)


# ---------------------------------------------------------------------------
# Robustness: exponential-assumption periods under realistic failures
# ---------------------------------------------------------------------------
#
# No closed form exists for non-exponential processes, so the grid solver is
# Monte-Carlo: one pre-sampled schedule set per grid point (common random
# numbers) is parked on device once and reused for every candidate period,
# the argmin is localized by batched coarse-to-fine refinement (one
# candidate-vmapped engine call scores ALL candidates for every grid point
# at once; the big gap arrays are shared via in_axes=None, never tiled),
# and every reported period — the process optimum, the
# exponential-closed-form AlgoT/AlgoE, Young, Daly — is evaluated on the
# *same* schedules so the penalties are CRN-paired.

@dataclasses.dataclass(frozen=True)
class RobustnessResult:
    """Per-grid-point periods and CRN penalties; arrays of ``grid.shape``.

    ``*_penalty_*`` are ratios >= ~1: wall time (or energy) at the
    exponential-assumption period divided by its value at the MC
    process-optimal period, under the non-exponential process.
    """

    grid: ParamGrid
    process: object                # FailureProcess
    T_base: np.ndarray             # per-point simulated work (grid.shape)
    n_trials: int
    T_exp_time: np.ndarray         # AlgoT closed form (exponential model)
    T_exp_energy: np.ndarray       # AlgoE quadratic root
    T_young: np.ndarray
    T_daly: np.ndarray
    T_mc_time: np.ndarray          # process-optimal (MC surrogate)
    T_mc_energy: np.ndarray
    eval_periods: np.ndarray       # (6,) + grid.shape: the periods actually
                                   # scored, order [mc_t, mc_e, algoT,
                                   # algoE, young, daly] (clipped into the
                                   # safe range) — feed to
                                   # evaluate_periods_grid for independent-
                                   # seed validation
    wall_mc: np.ndarray            # E[T_final] at T_mc_time
    energy_mc: np.ndarray          # E[E_final] at T_mc_energy
    wall_mc_se: np.ndarray
    energy_mc_se: np.ndarray
    time_penalty_exp: np.ndarray
    energy_penalty_exp: np.ndarray
    time_penalty_young: np.ndarray
    time_penalty_daly: np.ndarray
    energy_penalty_young: np.ndarray
    energy_penalty_daly: np.ndarray
    valid: np.ndarray


def _flat_tbase(T_base, grid: ParamGrid) -> np.ndarray:
    """Per-point T_base as a flat (grid.size,) array, accepting a scalar,
    an already-flat vector, or a grid-shaped array."""
    arr = np.asarray(T_base, dtype=np.float64)
    if arr.shape == grid.shape:
        return arr.ravel().copy()
    return np.broadcast_to(arr, (grid.size,)).copy()


def _mc_eval(T_cand, flat: ParamGrid, T_base, gaps, n_steps=None,
             engine_kind: Optional[str] = None, dispatch=None):
    """Engine means over trials for candidate periods ``T_cand`` of shape
    ``(M, B)`` against the flat grid (B,), in ONE candidate-vmapped engine
    call (the gap schedules — the big arrays — are shared across the
    candidate axis via ``in_axes=None``, never tiled or re-transferred)."""
    from . import engine as _engine
    T_cand = np.atleast_2d(np.asarray(T_cand, dtype=np.float64))
    tb = _engine.simulate_candidates(T_cand, flat, T_base, gaps=gaps,
                                     n_steps=n_steps,
                                     engine_kind=engine_kind,
                                     dispatch=dispatch)
    if tb.truncated.any():
        raise RuntimeError("robustness sweep: scan budget exceeded — "
                           "candidate period too close to a bracket "
                           "edge")
    if tb.gaps_exhausted.any():
        raise RuntimeError("robustness sweep: failure schedule "
                           "exhausted — increase n_trials capacity "
                           "margins")
    n = tb.wall_time.shape[-1]
    se = lambda a: a.std(axis=-1, ddof=1) / math.sqrt(n)
    return (tb.wall_time.mean(axis=-1), tb.energy.mean(axis=-1),
            se(tb.wall_time), se(tb.energy))


@functools.partial(jax.profiler.annotate_function,
                   name="repro.robust.solve")
def evaluate_robustness_grid(grid: ParamGrid, process,
                             T_base: Optional[float] = None,
                             n_trials: int = 160, seed: int = 0,
                             n_candidates: int = 13, rounds: int = 3,
                             engine_kind: Optional[str] = None,
                             dispatch=None) -> RobustnessResult:
    """MC robustness evaluation of a whole grid under ``process``.

    Each refinement round scores ``n_candidates`` periods in one
    candidate-vmapped engine call (every candidate x grid point at once);
    a final pass scores the six reported periods (MC-time, MC-energy,
    AlgoT, AlgoE, Young, Daly) on the same CRN schedules, which are
    host-sampled once (replayable) and then device-resident for every
    call.  Use :func:`evaluate_periods_grid` with a different ``seed`` to
    re-validate the reported optima on independent randomness (the
    benchmark's 2% gate).
    """
    from ..core.failures import as_process
    from . import engine as _engine
    process = as_process(process)
    engine_kind = _engine.resolve_engine_kind(engine_kind)
    flat = grid.ravel()
    B = flat.size
    with jax.profiler.TraceAnnotation("repro.robust.closed_forms"):
        res = evaluate_grid(grid, T_base=1.0, dispatch=dispatch)
        if not res.valid.all():
            raise ValueError("robustness sweep: grid contains degenerate "
                             "points (no valid period); filter them first")
        Tt = np.asarray(res.T_time, dtype=np.float64).ravel()
        Te = np.asarray(res.T_energy, dtype=np.float64).ravel()
        Ty = np.asarray(res.T_young, dtype=np.float64).ravel()
        Td = np.asarray(res.T_daly, dtype=np.float64).ravel()
        lo0, hi0 = flat.period_bounds()

    # Search well clear of the bracket edges, where E[T_final] (and with it
    # the scan/schedule budgets) diverges; the optimum sits near the
    # exponential T* for every renewal process with the same mean.
    lo = np.maximum(lo0 * 1.02, Tt / 6.0)
    hi = np.minimum(lo0 + 0.75 * (hi0 - lo0), Tt * 6.0)
    if T_base is None:
        # Per grid point: enough periods and failures to average over.
        T_base = np.maximum(30.0 * Tt, 10.0 * flat.mu)
    T_base = _flat_tbase(T_base, grid)
    probes = lo[None, :] * (hi / lo)[None, :] ** np.linspace(
        0.0, 1.0, 9)[:, None]
    cap = _engine.default_fail_capacity(probes, flat, T_base,
                                       process=process)
    n_steps = (None if engine_kind in _engine._EVENT_LIKE else
               _engine.default_step_budget(probes, flat, T_base,
                                           process=process))
    with jax.profiler.TraceAnnotation("repro.robust.schedule"):
        gaps = _engine.presample_gaps(flat, n_trials, cap, seed=seed,
                                      process=process)
        with enable_x64():
            # device-resident once, reused below
            gaps = jnp.asarray(gaps, dtype=jnp.float64)

    # Coarse-to-fine localization of both argmins (batched over the grid).
    frac = np.linspace(0.0, 1.0, n_candidates)[:, None]
    xs_t = lo[None, :] * (hi / lo)[None, :] ** frac     # geometric first pass
    xs_e = xs_t

    def shrink(xs, ys):
        i = np.argmin(ys, axis=0)
        lo2 = xs[np.maximum(i - 1, 0), np.arange(B)]
        hi2 = xs[np.minimum(i + 1, n_candidates - 1), np.arange(B)]
        return lo2[None, :] + (hi2 - lo2)[None, :] * frac

    def score(xs_time, xs_energy):
        # One engine pass returns BOTH objectives, so identical candidate
        # sets (the shared first round) are simulated only once.
        wall_t, energy_t, _, _ = _mc_eval(xs_time, flat, T_base, gaps,
                                          n_steps, engine_kind, dispatch)
        if xs_energy is xs_time:
            return wall_t, energy_t
        _, energy_e, _, _ = _mc_eval(xs_energy, flat, T_base, gaps, n_steps,
                                     engine_kind, dispatch)
        return wall_t, energy_e

    for _ in range(rounds):
        wall_t, energy_e = score(xs_t, xs_e)
        xs_t = shrink(xs_t, wall_t)
        xs_e = shrink(xs_e, energy_e)
    wall_t, energy_e = score(xs_t, xs_e)
    T_mc_t = xs_t[np.argmin(wall_t, axis=0), np.arange(B)]
    T_mc_e = xs_e[np.argmin(energy_e, axis=0), np.arange(B)]

    # Score all six reported periods on the same schedules (CRN-paired).
    cands = np.clip(np.stack([T_mc_t, T_mc_e, Tt, Te, Ty, Td]),
                    lo[None, :], hi[None, :])
    wall, energy, wall_se, energy_se = _mc_eval(cands, flat, T_base, gaps,
                                                n_steps, engine_kind,
                                                dispatch)
    shp = grid.shape
    r = lambda a: np.asarray(a, dtype=np.float64).reshape(shp)
    return RobustnessResult(
        grid=grid, process=process, T_base=r(T_base),
        n_trials=int(n_trials),
        T_exp_time=r(Tt), T_exp_energy=r(Te), T_young=r(Ty), T_daly=r(Td),
        T_mc_time=r(T_mc_t), T_mc_energy=r(T_mc_e),
        eval_periods=cands.reshape((6,) + shp),
        wall_mc=r(wall[0]), energy_mc=r(energy[1]),
        wall_mc_se=r(wall_se[0]), energy_mc_se=r(energy_se[1]),
        time_penalty_exp=r(wall[2] / wall[0]),
        energy_penalty_exp=r(energy[3] / energy[1]),
        time_penalty_young=r(wall[4] / wall[0]),
        time_penalty_daly=r(wall[5] / wall[0]),
        energy_penalty_young=r(energy[4] / energy[1]),
        energy_penalty_daly=r(energy[5] / energy[1]),
        valid=np.asarray(res.valid).copy())


def evaluate_periods_grid(grid: ParamGrid, process, periods,
                          T_base, n_trials: int = 160, seed: int = 0,
                          engine_kind: Optional[str] = None, dispatch=None):
    """MC means at given candidate periods under ``process`` (CRN-shared
    across candidates, independent across seeds).

    ``periods`` has shape ``(M,) + grid.shape``; returns a dict of
    ``wall`` / ``energy`` (+ ``_se``) arrays of the same shape.  This is the
    independent-validation entry: score ``RobustnessResult.eval_periods``
    with a fresh ``seed`` and compare the derived penalties.
    """
    from ..core.failures import as_process
    from . import engine as _engine
    process = as_process(process)
    engine_kind = _engine.resolve_engine_kind(engine_kind)
    flat = grid.ravel()
    B = flat.size
    P = np.asarray(periods, dtype=np.float64).reshape((-1, B))
    T_base = _flat_tbase(T_base, grid)
    cap = _engine.default_fail_capacity(P, flat, T_base, process=process)
    n_steps = (None if engine_kind in _engine._EVENT_LIKE else
               _engine.default_step_budget(P, flat, T_base,
                                           process=process))
    gaps = _engine.presample_gaps(flat, n_trials, cap, seed=seed,
                                  process=process)
    wall, energy, wall_se, energy_se = _mc_eval(P, flat, T_base, gaps,
                                                n_steps, engine_kind,
                                                dispatch)
    shp = (P.shape[0],) + grid.shape
    return {"wall": wall.reshape(shp), "energy": energy.reshape(shp),
            "wall_se": wall_se.reshape(shp),
            "energy_se": energy_se.reshape(shp)}


def sweep_weibull_shapes(shapes: Sequence[float], mu_minutes: Sequence[float],
                         base: str = "exascale_rho55",
                         **kwargs) -> RobustnessResult:
    """Weibull shape x exascale-platform MTBF robustness sweep (the
    fig5 benchmark's entry point)."""
    grid, process = scenarios.robustness_grid(shapes, mu_minutes, base=base)
    return evaluate_robustness_grid(grid, process, **kwargs)


# ---------------------------------------------------------------------------
# Figure-level conveniences
# ---------------------------------------------------------------------------

def sweep_rho_grid(rhos: Sequence[float], mu_minutes: float,
                   alpha: float = 1.0) -> GridResult:
    """Figure 1: rho swept at one MTBF (grid shape ``(1, len(rhos))``)."""
    return evaluate_grid(scenarios.mu_rho_grid([mu_minutes], rhos, alpha))


def sweep_mu_rho_grid(mus: Sequence[float], rhos: Sequence[float],
                      alpha: float = 1.0) -> GridResult:
    """Figure 2: the (mu x rho) ratio surfaces in one call."""
    return evaluate_grid(scenarios.mu_rho_grid(mus, rhos, alpha))


def sweep_nodes_grid(n_nodes: Sequence[float],
                     power: PowerParams) -> GridResult:
    """Figure 3: scalability in N at one power scenario."""
    return evaluate_grid(scenarios.nodes_grid(n_nodes, power))
