"""A whole run of each cell at a tiny size on the CPU, past the harness's
look for a chip: sound, it comes out correct; with the timed path broken
underneath, once for each fault the cell can have, it does not."""
import dataclasses

import numpy as np
import pytest

from bench import run as harness
from bench_cells import tiny_cell

SWEEP = "fig2-exascale-weibull07.sweep"
SOLVE = "fig5-robustness-exascale55.crn-solve"


def _run(name: str) -> dict:
    return harness.run(tiny_cell(name), seed=2**40 + 77, seconds=0.0,
                       trace=False, require_chip=False)


@pytest.fixture
def fresh_runners():
    """Compiled runners built for this test only: a planted fault must not
    leave its runner behind for the next test."""
    from repro.sim import dispatch

    dispatch._RUNNERS.clear()
    yield
    dispatch._RUNNERS.clear()


def _state_unchanged(monkeypatch):
    """The engine's step leaves its state as it was: no step runs."""
    from repro.sim import engine

    kernel = engine._KERNELS["event"]
    monkeypatch.setitem(engine._KERNELS, "event",
                        lambda *a: kernel(*a[:-1], 0))


def _wrap(monkeypatch, module, name, change):
    inner = getattr(module, name)

    def broken(*args, **kwargs):
        return change(inner(*args, **kwargs))
    monkeypatch.setattr(module, name, broken)


def _half_batch(tb):
    """The second half of the trials left out: the first half stands in
    for it, so every mean is taken over the rest."""
    n = tb.wall_time.shape[-1] // 2
    return dataclasses.replace(tb, **{
        f.name: np.concatenate([getattr(tb, f.name)[..., :n]] * 2, axis=-1)
        for f in dataclasses.fields(tb)})


def _half_batch_mean(tb):
    """Half of the trials left out: the means are over the rest."""
    n = tb.wall_time.shape[-1] // 2
    return dataclasses.replace(tb, **{
        f.name: getattr(tb, f.name)[..., :n] for f in dataclasses.fields(tb)})


def _altered(tb):
    """One answer altered where it is produced: the energy of every
    trajectory of one grid point, by one part in a million."""
    energy = tb.energy.copy()
    energy[..., 0, 0, :] *= 1.0 + 1e-6
    return dataclasses.replace(tb, energy=energy)


def _altered_everywhere(tb):
    return dataclasses.replace(tb, energy=tb.energy * (1.0 + 1e-6))


@pytest.mark.parametrize("name", [SWEEP, SOLVE])
def test_sound_run_is_correct(name, fresh_runners):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"


def _plant(fault, name, monkeypatch):
    import repro.sim
    from repro.sim import engine

    if fault == "state_unchanged":
        _state_unchanged(monkeypatch)
    elif name == SWEEP:
        change = {"half_batch": _half_batch, "altered": _altered}[fault]
        _wrap(monkeypatch, repro.sim, "simulate_trajectories", change)
    else:
        change = {"half_batch": _half_batch_mean,
                  "altered": _altered_everywhere}[fault]
        _wrap(monkeypatch, engine, "simulate_candidates", change)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered"])
@pytest.mark.parametrize("name", [SWEEP, SOLVE])
def test_fault_is_not_correct(name, fault, monkeypatch, fresh_runners):
    _plant(fault, name, monkeypatch)
    res = _run(name)
    assert res["correct"] is False, (fault, res["checks"])
