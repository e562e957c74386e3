"""A lane's failure schedule is a prefix of its key's stream.

The sampled sweeps draw each capacity bucket's schedules at that
bucket's capacity, and rely on the draw of ``n`` values from a lane's
folded key being the first ``n`` of any longer draw from it: that is
what keeps a fixed seed's results independent of how the grid is cut
into buckets, chunks, trial blocks and devices.  Counter-based threefry
(``jax_threefry_partitionable``) gives it; these tests hold every traced
sampler and the level-loss flag stream to it, bit for bit.
"""
import numpy as np
import pytest

import jax
from jax import enable_x64

from repro.core import (EXASCALE_POWER_RHO55, Exponential, LogNormal,
                        TraceReplay, Weibull, fig12_checkpoint)
from repro.core.failures import level_loss_flags
from repro.sim import ParamGrid, engine

PROCESSES = [
    Exponential(),
    Weibull(shape=0.7),
    LogNormal(sigma=1.0),
    TraceReplay(gaps=[40.0, 500.0, 120.0, 90.0, 800.0, 33.0]),
]

LONG = 4096
LENGTHS = (1, 128, 1000, LONG)


def test_threefry_is_partitionable():
    assert jax.config.jax_threefry_partitionable is True, (
        "the sampled sweeps draw each bucket at its own capacity and need "
        "a lane's stream prefix to be independent of the draw's length: "
        "jax_threefry_partitionable must be on")


def _lanes(proc, seed=2**40 + 17):
    """The engine's sampler, per-point arguments and lane keys for two
    grid points and two trials."""
    base = ParamGrid.from_params(fig12_checkpoint(300.0),
                                 EXASCALE_POWER_RHO55)
    flat = ParamGrid(**{f: (np.array([60.0, 400.0]) if f == "mu"
                            else np.broadcast_to(v, (2,)))
                        for f, v in base.fields().items()})
    _, params, fn, mean, _, key = engine._sampler_inputs(
        proc.ravel(), flat, seed)
    with enable_x64():
        lanes = [(jax.random.fold_in(jax.random.fold_in(key, i), t),
                  mean[i], tuple(p[i] for p in params))
                 for i in range(2) for t in (0, 5)]
    return fn, lanes


def _draw(f, n):
    with enable_x64():
        return np.asarray(jax.jit(f, static_argnums=0)(n))


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("proc", PROCESSES, ids=lambda p: p.name)
def test_gap_draw_is_prefix_of_longer_draw(proc, n):
    fn, lanes = _lanes(proc)
    for key, mean, pp in lanes:
        short = _draw(lambda k: fn(key, (k,), mean, pp), n)
        full = _draw(lambda k: fn(key, (k,), mean, pp), LONG)
        assert short.shape == (n,) and short.dtype == np.float64
        np.testing.assert_array_equal(short, full[:n])


@pytest.mark.parametrize("n", LENGTHS)
def test_flag_draw_is_prefix_of_longer_draw(n):
    _, lanes = _lanes(Exponential())
    for q in (0.05, 0.3, 0.9):
        for key, _, _ in lanes:
            short = _draw(lambda k: level_loss_flags(key, (k,), q), n)
            full = _draw(lambda k: level_loss_flags(key, (k,), q), LONG)
            assert short.shape == (n,)
            np.testing.assert_array_equal(short, full[:n])
