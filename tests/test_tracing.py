"""The program's trace instrumentation (docs/simulation.md "Tracing").

Host spans (``jax.profiler.TraceAnnotation``, names ``repro.*``) mark the
solver, engine and dispatch layers and nest as documented; device scopes
(``jax.named_scope`` ``mc.sample`` / ``mc.scan``) mark the sampler and the
trajectory kernel in every Monte-Carlo program; and neither changes a
result.
"""
import functools
import pathlib

import numpy as np
import pytest

import jax
from jax import enable_x64

from repro import sim
from repro.core import Weibull
from repro.core.failures import as_process
from repro.sim import engine

GRID = sim.mu_rho_grid([60.0, 300.0], [2.0, 8.0], alpha=1.0)
WEIBULL = Weibull(shape=0.7)


@functools.lru_cache(maxsize=1)
def _periods():
    return np.asarray(sim.evaluate_grid(GRID).T_energy)


def _sweep():
    b = sim.simulate_trajectories(_periods(), GRID, 600.0, n_trials=8,
                                  seed=11, process=WEIBULL)
    return {f: getattr(b, f) for f in ("wall_time", "energy", "n_failures",
                                       "n_checkpoints", "truncated")}


def _solve():
    r = sim.sweep_weibull_shapes([0.7], [300.0], n_trials=16, seed=5,
                                 n_candidates=5, rounds=1)
    return {f: getattr(r, f) for f in ("T_mc_time", "T_mc_energy",
                                       "eval_periods", "wall_mc",
                                       "energy_mc", "time_penalty_exp")}


ML_GRID = sim.buddy_ratio_grid([0.1], [0.1, 0.5], mu_min=120.0)


def _sweep_ml():
    b = sim.simulate_trajectories_ml(np.array([[16.0, 18.0]]),
                                     np.array([[8, 4]]), ML_GRID, 600.0,
                                     n_trials=8, seed=11, process=WEIBULL)
    return {f: getattr(b, f) for f in ("wall_time", "energy", "n_failures",
                                       "n_hard_failures", "n_ckpt2",
                                       "truncated")}


ENTRIES = {"sweep": _sweep, "solve": _solve, "sweep_ml": _sweep_ml}


def _program_spans(trace_dir) -> list:
    """(name, start, end) of the ``repro.*`` host events, by start."""
    from jax.profiler import ProfileData

    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


@pytest.fixture(scope="module", params=sorted(ENTRIES))
def traced(request, tmp_path_factory):
    """(entry, plain result, result under the profiler, program spans);
    the plain call runs first, so both calls run compiled programs."""
    name = request.param
    plain = ENTRIES[name]()
    trace_dir = tmp_path_factory.mktemp(f"trace_{name}")
    with jax.profiler.trace(str(trace_dir)):
        profiled = ENTRIES[name]()
    return name, plain, profiled, _program_spans(trace_dir)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_results_unchanged_under_profiler(traced):
    _, plain, profiled, _ = traced
    for k, v in plain.items():
        np.testing.assert_array_equal(profiled[k], v, err_msg=k)


def test_spans_nest(traced):
    """``repro.dispatch.*`` inside ``repro.mc.*`` (or the closed forms)
    inside ``repro.robust.solve``; each chunk's launch before its fetch."""
    name, _, _, spans = traced
    by = lambda n: [s for s in spans if s[0] == n]
    launch, fetch = by("repro.dispatch.launch"), by("repro.dispatch.fetch")
    assert launch and len(launch) == len(fetch)
    for a, b in zip(launch, fetch):
        assert a[2] <= b[1]
    if name.startswith("sweep"):
        entry = {"sweep": "repro.mc.trajectories",
                 "sweep_ml": "repro.mc.trajectories_ml"}[name]
        (outer,) = by(entry)
        assert all(_inside(s, outer) for s in launch + fetch)
        assert {s[0] for s in spans} == {entry, "repro.dispatch.launch",
                                         "repro.dispatch.fetch"}
        return
    (solve,) = by("repro.robust.solve")
    (closed,) = by("repro.robust.closed_forms")
    (schedule,) = by("repro.robust.schedule")
    cands = by("repro.mc.candidates")
    # rounds=1: one scoring round (shared candidate set) and the final
    # scoring of the refined candidates for each objective, then the six
    # reported periods.
    assert len(cands) == 4
    assert all(_inside(s, solve) for s in spans if s != solve)
    assert closed[2] <= schedule[1] and schedule[2] <= cands[0][1]
    for s in launch + fetch:
        assert sum(_inside(s, o) for o in cands + [closed]) == 1


BUILDS = ("sampled", "cand_sampled", "explicit", "cand_explicit")


def _lowered(build: str, kind: str) -> str:
    """StableHLO of one Monte-Carlo program at a tiny shape, with the
    locations that carry each op's scope."""
    flat = GRID.ravel()
    B, cap, n_steps = flat.size, 8, 9
    T = np.full(B, 50.0)
    Tb = np.full(B, 600.0)
    grid_args = (flat.C, flat.R, flat.D, flat.omega, Tb)
    if build.endswith("sampled"):
        _, params, fn, mean, idx, key = engine._sampler_inputs(
            as_process(WEIBULL).ravel(), flat, 3)
        tail = (mean, idx, np.arange(4, dtype=np.uint32), key) + params
        if build == "sampled":
            f = engine._sampled_build(fn, cap, n_steps, kind)
            args = (T,) + grid_args + tail
        else:
            f = engine._cand_sampled_build(fn, cap, n_steps, kind)
            args = (np.stack([T, 2 * T]),) + grid_args + tail
    else:
        gaps = np.full((B, 4, cap), 100.0)
        if build == "explicit":
            f = engine._grid_fn(n_steps, kind)
            args = (T,) + grid_args + (gaps,)
        else:
            f = engine._cand_fn(n_steps, kind)
            args = (np.stack([T, 2 * T]),) + grid_args + (gaps,)
    with enable_x64():
        return jax.jit(f).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("kind", ("event", "step", "pallas"))
@pytest.mark.parametrize("build", BUILDS)
def test_scopes_in_lowered_programs(build, kind):
    text = _lowered(build, kind)
    assert engine.SCAN_SCOPE in text
    assert (engine.SAMPLE_SCOPE in text) == build.endswith("sampled")


@pytest.mark.parametrize("build", ("sampled", "explicit"))
def test_scopes_in_lowered_two_level_programs(build):
    """The two-level programs carry the same scopes: the gap and flag
    sampler under ``mc.sample``, the two-level scan under ``mc.scan``."""
    flat = ML_GRID.ravel()
    B, cap = flat.size, 8
    per_point = (np.full(B, 16.0), np.full(B, 4, np.int32)) + tuple(
        getattr(flat, f) for f in engine._ML_PARAMS)
    Tb = np.full(B, 600.0)
    if build == "sampled":
        _, params, fn, mean, idx, key = engine._sampler_inputs(
            as_process(WEIBULL).ravel(), flat, 3)
        f = engine._sampled_build_ml(fn, cap, cap + 1)
        args = per_point + (Tb, flat.q, mean, idx,
                            np.arange(4, dtype=np.uint32), key) + params
    else:
        f = engine._grid_fn_ml(cap + 1)
        args = per_point + (Tb, np.full((B, 4, cap), 100.0),
                            np.zeros((B, 4, cap), bool))
    with enable_x64():
        text = jax.jit(f).lower(*args).as_text(debug_info=True)
    assert engine.SCAN_SCOPE in text
    assert (engine.SAMPLE_SCOPE in text) == (build == "sampled")
