"""Failure-process abstraction: renewal processes of i.i.d. inter-failure gaps.

The paper (and the seed code) hardwires exponential (memoryless) failures,
but field studies of HPC failure logs consistently fit Weibull with shape
< 1 (infant-mortality clustering) and sometimes log-normal.  This module
makes the inter-failure distribution a first-class object that every
simulation layer consumes:

  * :class:`Exponential` — the paper's Poisson process (the default
    everywhere; reproduces the legacy sampling stream *bit-for-bit*).
  * :class:`Weibull` — shape ``k`` (k < 1: decreasing hazard, clustered
    failures; k = 1 is exponential; k > 1: wear-out).
  * :class:`LogNormal` — multiplicative-error gap model (non-monotone
    hazard).
  * :class:`TraceReplay` — replay of an empirical gap log (cyclically, from
    a random per-trajectory phase), preserving the trace's autocorrelation.

Semantics shared with both simulators (the *renewal convention*): gap ``i``
is the time from the end of recovery ``i-1`` (or from t = 0) to failure
``i`` — the machine's clock of the failure process restarts when it comes
back up.  A pre-sampled gap array therefore defines an absolute-time
failure schedule once the recovery ends are known, and the same array fed
to the scalar oracle (:func:`repro.core.simulator.simulate_once` with
``gaps=...``) and the batched engine produces bit-identical trajectories
for *every* distribution.

Parameterization: every process targets a mean gap ``mu`` (the platform
MTBF).  Constructors accept ``mu=None``, in which case the caller (the
engine / the scalar simulator) supplies the mean at sampling time — this is
how one process instance serves a whole :class:`~repro.sim.scenarios.ParamGrid`
of MTBFs.  Shape parameters may be *arrays* broadcasting against the grid's
leading axes (batched sampling over distribution-parameter grids); use
:meth:`FailureProcess.ravel` next to ``ParamGrid.ravel``.

Two sampling backends share the same distributions:

  * :meth:`FailureProcess.sample` — host numpy, from an
    ``np.random.Generator`` (the legacy streams; the CRN solvers pre-sample
    here so one schedule set can be replayed for every candidate period).
  * :meth:`FailureProcess.sample_gaps` — jax-native inverse-CDF sampling
    from a threefry key, device-resident end to end.  The batched engine's
    default path; erases the host presample tensors and their per-call
    host->device transfers.  The two backends draw from the same
    distribution but NOT the same stream (threefry vs PCG64).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Union

import numpy as np

ArrayLike = Union[float, np.ndarray]


def _lead(x: ArrayLike, size: tuple) -> np.ndarray:
    """Align an array-valued parameter with the *leading* axes of ``size``.

    A ``(B,)`` parameter sampled at ``size=(B, n_trials, capacity)`` becomes
    ``(B, 1, 1)`` so numpy broadcasting pairs grid points with their own
    parameter instead of the trailing-axis default.
    """
    x = np.asarray(x, dtype=np.float64)
    if x.ndim == 0 or size is None:
        return x
    extra = len(size) - x.ndim
    if extra < 0:
        raise ValueError(f"parameter of shape {x.shape} cannot broadcast "
                         f"against sample size {size}")
    return x.reshape(x.shape + (1,) * extra)


def _param_token(x) -> tuple:
    """Hashable identity of a (possibly array-valued) parameter — used to
    key jit caches of the device samplers."""
    if x is None:
        return (None,)
    arr = np.asarray(x, dtype=np.float64)
    return (arr.shape, arr.tobytes())


def _lead_j(x, size: tuple):
    """jnp counterpart of :func:`_lead` (accepts traced arrays)."""
    import jax.numpy as jnp
    x = jnp.asarray(x, dtype=jnp.float64)
    if x.ndim == 0 or size is None:
        return x
    extra = len(size) - x.ndim
    if extra < 0:
        raise ValueError(f"parameter of shape {x.shape} cannot broadcast "
                         f"against sample size {size}")
    return x.reshape(x.shape + (1,) * extra)


class FailureProcess:
    """A renewal process of i.i.d. inter-failure gaps (see module docstring).

    Subclasses implement :meth:`sample` (and usually :meth:`hazard`); the
    base class provides mean resolution and the variance scaling the engine
    uses to size pre-sampled schedules.
    """

    name: str = "process"
    #: declared mean gap, or None when the caller supplies it per sample.
    mu: Optional[ArrayLike] = None

    # -- mean plumbing -------------------------------------------------------
    def resolve_mean(self, mean: Optional[ArrayLike] = None) -> np.ndarray:
        """The mean gap to sample at: the caller's ``mean`` unless the
        process pins its own ``mu``."""
        m = self.mu if self.mu is not None else mean
        if m is None:
            raise ValueError(f"{self.name}: no mean gap — construct with "
                             f"mu=... or pass mean= when sampling")
        return np.asarray(m, dtype=np.float64)

    def gap_cv(self) -> ArrayLike:
        """Coefficient of variation (std/mean) of one gap — sizes the
        pre-sampled schedule capacity; 1.0 for exponential."""
        return 1.0

    # -- sampling / hazard ---------------------------------------------------
    def sample(self, rng: np.random.Generator, size=None,
               mean: Optional[ArrayLike] = None):
        """Draw inter-failure gaps of the given shape (mean ``mean``)."""
        raise NotImplementedError

    def sample_gaps(self, key, size: tuple,
                    mean: Optional[ArrayLike] = None):
        """jax-native gap sampler: draw ``size`` inter-failure gaps on device
        from threefry ``key`` (inverse-CDF / standard-normal transforms; no
        host round-trip).  Distribution parameters are baked in as
        constants; ``mean`` may be a traced array (one mean per grid
        point, ``_lead``-aligned by the caller or broadcastable).

        The engine's auto-sampling ladder: :meth:`traced_sampler` (the
        fused per-(point, trial) dispatch path) first, then this bulk
        sampler (one whole-grid device draw), then host numpy.
        Subclasses without any jax sampler inherit this
        ``NotImplementedError`` and the engine falls back to host numpy
        sampling — new processes work immediately, just without the
        on-device fast paths.
        """
        raise NotImplementedError(f"{self.name}: no device sampler")

    def cache_token(self) -> tuple:
        """Hashable identity of the process (class + parameters) — keys the
        engine's jit cache of compiled device samplers."""
        return (type(self).__name__, _param_token(self.mu))

    def traced_sampler(self):
        """``(token, params, fn)``: the device sampler with every
        distribution parameter TRACED instead of baked as a constant.

        ``params`` is a tuple of per-grid-point parameter arrays (each
        broadcastable against the raveled grid) and ``fn(key, size, mean,
        params)`` draws ``size`` gaps on device where ``mean`` and every
        element of ``params`` may be traced scalars (the engine vmaps
        ``fn`` over grid points).  Because the parameter values enter as
        arguments, one compiled program serves every chunk/shard slice of
        a grid — this is what makes the dispatch layer's chunking free of
        per-chunk recompiles for array-parameterized processes.

        ``token`` is the hashable identity of the *static* part of the
        sampler (class + non-array configuration) — the jit cache key.
        Subclasses without a jax sampler inherit this
        ``NotImplementedError`` and the engine falls back to host numpy
        sampling.
        """
        raise NotImplementedError(f"{self.name}: no device sampler")

    def hazard(self, t: ArrayLike, mean: Optional[ArrayLike] = None):
        """Instantaneous failure rate h(t) at gap-age ``t``."""
        raise NotImplementedError(f"{self.name}: no analytic hazard")

    def _device_mean(self, mean, size):
        """``resolve_mean`` for the device samplers: keeps traced (jnp)
        means intact instead of forcing them through numpy."""
        m = self.mu if self.mu is not None else mean
        if m is None:
            raise ValueError(f"{self.name}: no mean gap — construct with "
                             f"mu=... or pass mean= when sampling")
        return _lead_j(m, size)

    def ravel(self) -> "FailureProcess":
        """Flatten array-valued shape parameters (``ParamGrid.ravel``'s
        counterpart); the default has none."""
        return self

    def iter_gaps(self, rng: np.random.Generator,
                  mean: Optional[ArrayLike] = None):
        """Infinite iterator of gaps for ONE trajectory (the scalar
        simulator's lazy draw path).

        The default yields i.i.d. draws — correct for every i.i.d.-renewal
        process; :class:`TraceReplay` overrides it to keep its cyclic
        ordering.  For the exponential default each ``next()`` performs
        exactly one ``rng.exponential(scale=mean)`` call, preserving the
        legacy stream bit-for-bit.
        """
        while True:
            yield float(self.sample(rng, mean=mean))

    @property
    def is_exponential(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class Exponential(FailureProcess):
    """The paper's Poisson process: constant hazard 1/mu.

    ``sample`` forwards to ``rng.exponential(scale=mean, size=size)`` —
    the *exact* call the legacy code made — so an ``Exponential()`` instance
    reproduces today's sampling streams bit-for-bit (parity-tested).
    """

    mu: Optional[ArrayLike] = None
    name: str = "exponential"

    def sample(self, rng, size=None, mean=None):
        return rng.exponential(scale=_lead(self.resolve_mean(mean), size),
                               size=size)

    def sample_gaps(self, key, size, mean=None):
        import jax
        import jax.numpy as jnp
        m = self._device_mean(mean, size)
        return m * jax.random.exponential(key, size, dtype=jnp.float64)

    def traced_sampler(self):
        import jax
        import jax.numpy as jnp

        def fn(key, size, mean, params):
            return mean * jax.random.exponential(key, size,
                                                 dtype=jnp.float64)
        return ("exponential",), (), fn

    def ravel(self) -> "Exponential":
        return dataclasses.replace(
            self, mu=None if self.mu is None else np.ravel(self.mu))

    def hazard(self, t, mean=None):
        return np.broadcast_to(1.0 / self.resolve_mean(mean),
                               np.shape(t)).astype(np.float64)

    @property
    def is_exponential(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class Weibull(FailureProcess):
    """Weibull(shape k, scale lam) gaps with mean ``lam * Gamma(1 + 1/k)``.

    ``shape`` may be an array (one k per grid point).  The scale is derived
    from the target mean, so a Weibull process at a platform's MTBF is
    directly comparable to the exponential model at the same mu.
    """

    shape: ArrayLike = 0.7
    mu: Optional[ArrayLike] = None
    name: str = "weibull"

    def __post_init__(self):
        if np.any(np.asarray(self.shape) <= 0):
            raise ValueError(f"Weibull shape must be > 0, got {self.shape}")

    def _scale(self, mean, size=None):
        k = _lead(self.shape, size)
        return _lead(self.resolve_mean(mean), size) / _gamma1p(1.0 / k), k

    def sample(self, rng, size=None, mean=None):
        lam, k = self._scale(mean, size)
        return lam * rng.weibull(k, size=size)

    def sample_gaps(self, key, size, mean=None):
        # Inverse CDF through the standard exponential: X = lam * E^(1/k)
        # with E ~ Exp(1) (so -log U never sees U == 0).
        import jax
        import jax.numpy as jnp
        k = _lead_j(self.shape, size)
        lam = self._device_mean(mean, size) / _lead_j(
            _gamma1p(1.0 / np.asarray(self.shape, dtype=np.float64)), size)
        e = jax.random.exponential(key, size, dtype=jnp.float64)
        return lam * e ** (1.0 / k)

    def cache_token(self):
        return (type(self).__name__, _param_token(self.shape),
                _param_token(self.mu))

    def traced_sampler(self):
        import jax
        import jax.numpy as jnp
        k = np.asarray(self.shape, dtype=np.float64)
        inv_gamma = 1.0 / np.asarray(_gamma1p(1.0 / k), dtype=np.float64)

        def fn(key, size, mean, params):
            kk, ig = params
            e = jax.random.exponential(key, size, dtype=jnp.float64)
            return mean * ig * e ** (1.0 / kk)
        return ("weibull",), (k, inv_gamma), fn

    def gap_cv(self):
        k = np.asarray(self.shape, dtype=np.float64)
        g1 = _gamma1p(1.0 / k)
        g2 = _gamma1p(2.0 / k)
        return np.sqrt(np.maximum(g2 / g1**2 - 1.0, 0.0))

    def hazard(self, t, mean=None):
        lam, k = self._scale(mean)
        t = np.asarray(t, dtype=np.float64)
        return (k / lam) * (t / lam) ** (k - 1.0)

    def ravel(self) -> "Weibull":
        return dataclasses.replace(
            self, shape=np.ravel(self.shape),
            mu=None if self.mu is None else np.ravel(self.mu))


@dataclasses.dataclass(frozen=True)
class LogNormal(FailureProcess):
    """Log-normal gaps: exp(N(m, sigma^2)) with m chosen so the mean is mu.

    ``sigma`` is the shape parameter (log-space std); the hazard rises then
    falls — a common fit for repair-induced failure clustering.
    """

    sigma: ArrayLike = 1.0
    mu: Optional[ArrayLike] = None
    name: str = "lognormal"

    def __post_init__(self):
        if np.any(np.asarray(self.sigma) <= 0):
            raise ValueError(f"LogNormal sigma must be > 0, got {self.sigma}")

    def sample(self, rng, size=None, mean=None):
        s = _lead(self.sigma, size)
        m = np.log(_lead(self.resolve_mean(mean), size)) - 0.5 * s * s
        return rng.lognormal(mean=m, sigma=s, size=size)

    def sample_gaps(self, key, size, mean=None):
        import jax
        import jax.numpy as jnp
        s = _lead_j(self.sigma, size)
        m = jnp.log(self._device_mean(mean, size)) - 0.5 * s * s
        z = jax.random.normal(key, size, dtype=jnp.float64)
        return jnp.exp(m + s * z)

    def cache_token(self):
        return (type(self).__name__, _param_token(self.sigma),
                _param_token(self.mu))

    def traced_sampler(self):
        import jax
        import jax.numpy as jnp
        sigma = np.asarray(self.sigma, dtype=np.float64)

        def fn(key, size, mean, params):
            (s,) = params
            z = jax.random.normal(key, size, dtype=jnp.float64)
            return jnp.exp(jnp.log(mean) - 0.5 * s * s + s * z)
        return ("lognormal",), (sigma,), fn

    def gap_cv(self):
        s = np.asarray(self.sigma, dtype=np.float64)
        return np.sqrt(np.expm1(s * s))

    def hazard(self, t, mean=None):
        s = np.asarray(self.sigma, dtype=np.float64)
        m = np.log(self.resolve_mean(mean)) - 0.5 * s * s
        t = np.asarray(t, dtype=np.float64)
        z = (np.log(t) - m) / s
        pdf = np.exp(-0.5 * z * z) / (t * s * math.sqrt(2.0 * math.pi))
        sf = 0.5 * _erfc(z / math.sqrt(2.0))
        return pdf / sf

    def ravel(self) -> "LogNormal":
        return dataclasses.replace(
            self, sigma=np.ravel(self.sigma),
            mu=None if self.mu is None else np.ravel(self.mu))


@dataclasses.dataclass(frozen=True)
class TraceReplay(FailureProcess):
    """Replay an empirical inter-failure gap log.

    Each trajectory replays the trace *cyclically from a uniformly random
    starting offset*, so trajectories differ in phase but preserve the
    trace's gap ordering (and hence its clustering / autocorrelation —
    exactly what i.i.d. resampling would destroy).  When the caller
    supplies a target mean (a grid's mu), gaps are rescaled by
    ``mean / trace_mean``; construct with ``rescale=False`` to forbid that
    and always replay the raw trace.
    """

    gaps: tuple = ()
    rescale: bool = True
    name: str = "trace"

    def __post_init__(self):
        g = np.asarray(self.gaps, dtype=np.float64).ravel()
        if g.size == 0:
            raise ValueError("TraceReplay needs at least one gap")
        if np.any(g <= 0) or not np.all(np.isfinite(g)):
            raise ValueError("trace gaps must be finite and > 0")
        object.__setattr__(self, "gaps", tuple(float(x) for x in g))

    @property
    def mu(self):  # type: ignore[override]
        return float(np.mean(self.gaps))

    def resolve_mean(self, mean=None):
        if mean is None or not self.rescale:
            return np.asarray(self.mu, dtype=np.float64)
        return np.asarray(mean, dtype=np.float64)

    def gap_cv(self):
        g = np.asarray(self.gaps)
        return float(g.std() / g.mean()) if g.size > 1 else 1.0

    def sample(self, rng, size=None, mean=None):
        trace = np.asarray(self.gaps, dtype=np.float64)
        n = trace.size
        if size is None:
            # A single draw cannot carry the trace's ordering — use
            # iter_gaps for sequential scalar draws (the simulator does).
            return float(trace[int(rng.integers(n))]) \
                * float(self.resolve_mean(mean) / self.mu)
        size = tuple(size)
        start = rng.integers(n, size=size[:-1] + (1,))
        idx = (start + np.arange(size[-1])) % n
        out = trace[idx] * (_lead(self.resolve_mean(mean), size) / self.mu)
        return np.broadcast_to(out, size).copy()

    def sample_gaps(self, key, size, mean=None):
        """Device replay: one uniform starting offset per leading index
        (trajectory), then a cyclic gather — mirrors :meth:`sample`."""
        import jax
        import jax.numpy as jnp
        trace = jnp.asarray(self.gaps, dtype=jnp.float64)
        n = len(self.gaps)
        start = jax.random.randint(key, size[:-1] + (1,), 0, n)
        idx = (start + jnp.arange(size[-1], dtype=start.dtype)) % n
        scale = (_lead_j(mean, size) / self.mu
                 if (mean is not None and self.rescale) else 1.0)
        return jnp.broadcast_to(trace[idx] * scale, size)

    def cache_token(self):
        return (type(self).__name__, self.gaps, self.rescale)

    def traced_sampler(self):
        import jax
        import jax.numpy as jnp
        trace = np.asarray(self.gaps, dtype=np.float64)
        n = trace.size
        trace_mu = float(self.mu)
        rescale = self.rescale

        def fn(key, size, mean, params):
            tr = jnp.asarray(trace, dtype=jnp.float64)
            start = jax.random.randint(key, size[:-1] + (1,), 0, n)
            idx = (start + jnp.arange(size[-1], dtype=start.dtype)) % n
            # mean arrives pre-resolved (resolve_mean), so with
            # rescale=False it already equals the trace mean and the
            # static 1.0 below is exact, not an approximation.
            scale = mean / trace_mu if rescale else 1.0
            return jnp.broadcast_to(tr[idx] * scale, size)
        return ("trace", self.gaps, self.rescale), (), fn

    def iter_gaps(self, rng, mean=None):
        """Cyclic replay from one uniformly random starting offset — the
        scalar counterpart of the per-trajectory ``sample`` rows, keeping
        the trace's ordering/autocorrelation (i.i.d. draws would not)."""
        trace = np.asarray(self.gaps, dtype=np.float64)
        scale = float(self.resolve_mean(mean) / self.mu)
        i = int(rng.integers(trace.size))
        while True:
            yield float(trace[i]) * scale
            i = (i + 1) % trace.size


# ---------------------------------------------------------------------------
# Registry / coercion
# ---------------------------------------------------------------------------

PROCESSES = {
    "exponential": Exponential,
    "weibull": Weibull,
    "lognormal": LogNormal,
    "trace": TraceReplay,
}


def get_process(name: str, **kwargs) -> FailureProcess:
    """Build a process by name (``weibull``, ``lognormal``, ...)."""
    try:
        cls = PROCESSES[name]
    except KeyError:
        raise KeyError(f"unknown failure process {name!r}; "
                       f"one of {sorted(PROCESSES)}") from None
    return cls(**kwargs)


def as_process(p) -> FailureProcess:
    """Coerce None (-> Exponential), a name, or a process instance."""
    if p is None:
        return Exponential()
    if isinstance(p, str):
        return get_process(p)
    if isinstance(p, FailureProcess):
        return p
    raise TypeError(f"not a failure process: {p!r}")


# ---------------------------------------------------------------------------
# Level-loss flags (two-level checkpointing)
# ---------------------------------------------------------------------------

#: ``fold_in`` tag that derives the flag stream from a lane's gap key.
LEVEL_LOSS_STREAM = 1


def level_loss_flags(key, size: tuple, q):
    """Bernoulli(``q``) level-loss flags on device, one per failure.

    The lane whose gaps come from threefry ``key`` draws its flags from a
    second stream of the same key: flag ``j`` is
    ``uniform(fold_in(key, LEVEL_LOSS_STREAM), size, float64)[j] < q``
    (failure ``j`` also loses the buddy copy).  ``q`` may be a traced
    scalar; ``q = 0`` gives no hard failure and ``q = 1`` only hard ones.
    A reference replays the stream from the key alone.
    """
    import jax
    import jax.numpy as jnp
    u = jax.random.uniform(jax.random.fold_in(key, LEVEL_LOSS_STREAM), size,
                           dtype=jnp.float64)
    return u < q


# ---------------------------------------------------------------------------
# Scalar helpers (math.gamma / erfc vectorized over small parameter arrays)
# ---------------------------------------------------------------------------

_vgamma = np.vectorize(math.gamma, otypes=[np.float64])
_verfc = np.vectorize(math.erfc, otypes=[np.float64])


def _gamma1p(x):
    """Gamma(1 + x), elementwise (scipy-free)."""
    out = _vgamma(1.0 + np.asarray(x, dtype=np.float64))
    return out if out.ndim else float(out)


def _erfc(x):
    out = _verfc(np.asarray(x, dtype=np.float64))
    return out if out.ndim else float(out)
