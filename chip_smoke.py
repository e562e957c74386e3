"""Smoke run of the system's main path on a TPU, checked by the repo's own
oracles.

    python chip_smoke.py             # one chip: sweep, advisor, trainer
    python chip_smoke.py --chips 4   # four chips: the sharded sweep only

Phases (each a plain function; ``tests/test_chip_smoke.py`` runs them on
the CPU at tiny sizes):

* sweep — the paper's Figure-2 grid (9 MTBFs x 10 rho values) under
  Weibull(k = 0.7) failures, 1024 trials per point, through
  ``simulate_trajectories`` with the default engine and with the Pallas
  engine under ``compensated_f32``.  Gates: the compensated-f32 closed
  forms against the f64 oracle (objective at the f32 argmin within
  ``objective_tol``), both engines against the scalar
  ``core/simulator.py`` oracle on a subset, the two engines' means
  against each other, and ``tpu_custom_call`` in the compiled kernel.
* advisor — ``repro.launch.serve advisor --smoke``: batched == unbatched
  bit for bit, then an open-loop run through ``ThreadedAdvisor``.
* trainer — ``repro.ft.run.execute`` on xlstm-125m at its published
  widths with AdamW, under injected failures: saves, a failure and a
  restore through ``ckpt/manager.py`` -> ``ckpt/store.py``.
* sharded (``--chips 4``) — the sweep on four chips under the default
  ``DispatchConfig()``, bit-equal to ``DispatchConfig(shard=False)``.

Every line before the last is one phase's record (compile seconds, peak
device bytes, parity figures).  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

#: Figure 2 of the paper: MTBFs (minutes) x 10 rho values in [1, 10].
FIG2_RHOS = tuple(float(r) for r in (1, 2, 3, 4, 5, 6, 7, 8, 9, 10))

#: job length of the sweep (minutes): a 72-hour run.  At the AlgoE
#: periods its largest capacity bucket (MTBF 30 min) is 4096 failures.
SWEEP_T_BASE = 4320.0

#: where the trainer phase keeps its checkpoints (removed afterwards).
CKPT_DIR = ROOT / ".smoke_ckpt"


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """Progress on stderr, stamped with seconds since start."""
    print(f"[{time.perf_counter() - _T0:8.1f}s] {msg}", file=sys.stderr,
          flush=True)


def require_tpu(count: int) -> list:
    """The TPU devices, or exit non-zero before anything runs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX finds no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: {count} chips asked, "
                         f"{len(devs)} found")
    return devs


class CompileClock:
    """Seconds JAX spends in backend compiles, from its own monitoring
    (a persistent-cache hit is not a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0

        def listen(name, duration, **_):
            if name == self.EVENT:
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(listen)

    def since(self, t0: float) -> float:
        return round(self.seconds - t0, 3)


def peak_device_bytes():
    """``peak_bytes_in_use`` of device 0 so far (None where the backend
    does not report it)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _rel(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b) / np.abs(b)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL {what}")


# ---------------------------------------------------------------------------
# Sweep
# ---------------------------------------------------------------------------

def _fig2_sweep(mus, rhos):
    """(mus, grid, T): the Figure-2 grid (``benchmarks/fig2_mu_rho.py``
    MTBFs when ``mus`` is None) and its f64 AlgoE periods, at which the
    Monte-Carlo runs."""
    import numpy as np

    from repro.sim import evaluate_grid, mu_rho_grid

    if mus is None:
        from benchmarks.fig2_mu_rho import MUS
        mus = MUS
    mus = tuple(float(m) for m in mus)
    grid = mu_rho_grid(mus, rhos)
    T = np.asarray(evaluate_grid(grid, precision="f64").T_energy)
    return mus, grid, T


def _point_params(mus, rhos):
    """Scalar (CheckpointParams, PowerParams) of every raveled point of
    ``mu_rho_grid(mus, rhos)``."""
    from repro.core import PowerParams
    from repro.sim import get_scenario

    return [(get_scenario("fig12", mu_min=m).ckpt,
             PowerParams.from_rho(rho=r)) for m in mus for r in rhos]


def closed_form_gate(mus, rhos) -> dict:
    """Compensated-f32 and f64 closed forms against the host scalar
    oracle: the objective at each served argmin, re-evaluated in f64 on
    the host, within ``objective_tol`` of the oracle's optimum."""
    import numpy as np

    from repro.core import model, optimal
    from repro.sim import COMPENSATED_F32, F64, evaluate_grid, mu_rho_grid

    grid = mu_rho_grid(mus, rhos)
    pts = _point_params(mus, rhos)
    out = {}
    for pol in (COMPENSATED_F32, F64):
        r = evaluate_grid(grid, precision=pol)
        _check(bool(np.asarray(r.valid).all()), f"{pol.name}: invalid points")
        T_t = np.asarray(r.T_time).ravel()
        T_e = np.asarray(r.T_energy).ravel()
        rel_t, rel_e = [], []
        for (ck, pw), tt, te in zip(pts, T_t, T_e):
            ref_t = model.time_final(optimal.t_opt_time(ck), ck)
            ref_e = model.energy_final(optimal.t_opt_energy(ck, pw), ck, pw)
            rel_t.append(abs(model.time_final(float(tt), ck) - ref_t) / ref_t)
            rel_e.append(abs(model.energy_final(float(te), ck, pw) - ref_e)
                         / ref_e)
        tol = COMPENSATED_F32.objective_tol
        worst = max(max(rel_t), max(rel_e))
        _check(worst <= tol, f"{pol.name} objective {worst:.3g} > {tol:g}")
        out[pol.name] = {"objective_rel_time_max": max(rel_t),
                         "objective_rel_energy_max": max(rel_e)}
    return out


def oracle_gate(mus, rhos, T, T_base: float, n_points: int, n_trials: int,
                seed: int = 1) -> dict:
    """Both engines on a host-drawn Weibull schedule for a subset of the
    grid, trajectory for trajectory against ``simulate_once``."""
    import numpy as np

    from repro.core import Weibull, simulate_once
    from repro.sim import (COMPENSATED_F32, ParamGrid, ScheduledRNG,
                           mu_rho_grid, simulate_trajectories)
    from repro.sim.engine import default_fail_capacity, presample_gaps

    flat = mu_rho_grid(mus, rhos).ravel()
    pts = _point_params(mus, rhos)
    pick = np.unique(np.linspace(0, flat.size - 1, n_points).astype(int))
    sub = ParamGrid(**{f: v[pick] for f, v in flat.fields().items()})
    T_sub = np.asarray(T, np.float64).ravel()[pick]
    proc = Weibull(shape=0.7)
    cap = default_fail_capacity(T_sub, sub, T_base, process=proc)
    gaps = presample_gaps(sub, n_trials, cap, seed=seed, process=proc)
    runs = {"event": simulate_trajectories(T_sub, sub, T_base, gaps=gaps,
                                           engine_kind="event"),
            "pallas": simulate_trajectories(T_sub, sub, T_base, gaps=gaps,
                                            engine_kind="pallas",
                                            precision=COMPENSATED_F32)}
    # The event engine is the f64 oracle's twin; the Pallas engine holds
    # the compensated-f32 engine gate of tests/test_pallas_engine.py.
    tols = {"event": 1e-9, "pallas": 1e-5}
    out = {"points": len(pick), "trials": n_trials, "capacity": cap}
    worst = {k: 0.0 for k in runs}
    for i, p in enumerate(pick):
        ck, pw = pts[p]
        for t in range(n_trials):
            ref = simulate_once(float(T_sub[i]), ck, pw, T_base,
                                ScheduledRNG(gaps[i, t]))
            for kind, tb in runs.items():
                _check(int(tb.n_failures[i, t]) == ref.n_failures,
                       f"{kind} failure count vs oracle at point {p}")
                for f in ("wall_time", "energy", "work_executed", "io_time"):
                    worst[kind] = max(worst[kind], float(_rel(
                        getattr(tb, f)[i, t], getattr(ref, f))))
    for kind, tol in tols.items():
        _check(worst[kind] <= tol,
               f"{kind} vs scalar oracle {worst[kind]:.3g} > {tol:g}")
        out[f"{kind}_rel_max"] = worst[kind]
    return out


def kernel_has_custom_call(n_trials: int, capacity: int) -> bool:
    """Whether the compiled compensated-f32 event kernel, at a real tile
    and ``capacity``, contains ``tpu_custom_call`` (False when the backend
    interprets it)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.event_sweep import event_sweep

    fn = jax.jit(functools.partial(event_sweep, n_steps=capacity + 1,
                                   dtype="float32", compensated=True))
    # Under x64, as the engine calls it.
    with jax.enable_x64(True):
        col = jax.ShapeDtypeStruct((8,), jnp.float64)
        gaps = jax.ShapeDtypeStruct((8, n_trials, capacity), jnp.float64)
        text = fn.lower(*[col] * 6, gaps).compile().as_text()
    return "tpu_custom_call" in text


def sweep_phase(mus=None, rhos=FIG2_RHOS, n_trials: int = 1024,
                T_base: float = SWEEP_T_BASE, oracle_points: int = 6,
                oracle_trials: int = 32, seed: int = 0) -> dict:
    """The Figure-2 Monte-Carlo sweep on both engines, with its gates."""
    import numpy as np

    from repro.core import Weibull
    from repro.sim import (COMPENSATED_F32, DispatchConfig, backend_info,
                           simulate_trajectories)
    from repro.sim import dispatch as dsp
    from repro.sim.engine import fail_capacity_points, resolve_engine_kind

    mus, grid, T = _fig2_sweep(mus, rhos)
    out = {"points": grid.size, "trials": n_trials, "T_base": T_base,
           "device_kind": backend_info().device_kind}
    out["closed_form"] = closed_form_gate(mus, rhos)
    log("sweep: closed-form gate passed")

    proc = Weibull(shape=0.7)
    caps = fail_capacity_points(T.ravel(), grid.ravel(), T_base,
                                process=proc)
    buckets, counts = np.unique(caps, return_counts=True)
    out["capacity_buckets"] = {int(b): int(c)
                               for b, c in zip(buckets, counts)}
    cap_sample = int(caps.max())
    # The schedule the top bucket's sampler draws (f64) for one dispatch
    # chunk; every other bucket draws at its own, smaller capacity.
    per_point = 8 * n_trials * (cap_sample + 32)
    chunk = max(stop - start for start, stop, _ in dsp.chunk_plan(
        grid.size, dsp.effective_devices(DispatchConfig()), per_point))
    out["gap_schedule_bytes_per_dispatch"] = chunk * n_trials * cap_sample * 8

    runs = {}
    kinds = ((resolve_engine_kind(None), None),
             ("pallas", COMPENSATED_F32))
    for kind, pol in kinds:
        t0 = time.perf_counter()
        tb = simulate_trajectories(T, grid, T_base, n_trials=n_trials,
                                   seed=seed, process=proc,
                                   engine_kind=kind, precision=pol)
        secs = time.perf_counter() - t0
        _check(not tb.truncated.any() and not tb.gaps_exhausted.any(),
               f"{kind}: truncated or exhausted trajectories")
        for f in ("wall_time", "energy"):
            _check(bool(np.isfinite(getattr(tb, f)).all()),
                   f"{kind}: non-finite {f}")
        runs[kind] = tb
        log(f"sweep: {kind} engine done in {secs:.1f}s")
        out[f"{kind}_seconds_first_call"] = round(secs, 3)
        out[f"{kind}_mean_failures"] = float(tb.n_failures.mean())
    ev, pl = runs[kinds[0][0]], runs["pallas"]
    se = ev.energy.std(axis=-1, ddof=1) / math.sqrt(n_trials)
    gap = np.abs(pl.energy.mean(axis=-1) - ev.energy.mean(axis=-1))
    out["engines_energy_mean_rel_max"] = float(
        _rel(pl.energy.mean(axis=-1), ev.energy.mean(axis=-1)).max())
    out["engines_same_failure_count_frac"] = float(
        (pl.n_failures == ev.n_failures).mean())
    _check(bool((gap <= 3.0 * se).all()),
           "pallas and default engine means differ by more than 3 SE")

    out["oracle"] = oracle_gate(mus, rhos, T, T_base, oracle_points,
                                oracle_trials)
    log("sweep: scalar-oracle gate passed")
    out["tpu_custom_call"] = kernel_has_custom_call(min(n_trials, 128),
                                                    cap_sample)
    return out


# ---------------------------------------------------------------------------
# Advisor
# ---------------------------------------------------------------------------

def advisor_phase() -> dict:
    """``repro.launch.serve advisor --smoke`` (exits non-zero on failure)."""
    from repro.launch.serve import advisor_main

    rep = advisor_main(["--smoke"])
    return {"requests": rep.n, "rps": rep.rps, "p50_ms": rep.p50_ms,
            "p99_ms": rep.p99_ms, "hit_rate": rep.hit_rate}


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------

def trainer_phase(total_steps: int = 24, batch: int = 8, seq: int = 2048,
                  reduce: bool = False, ckpt_dir: Path = CKPT_DIR) -> dict:
    """xlstm-125m under injected failures; every save and every restore
    goes through the on-disk store (no buddy level)."""
    from repro.ft.run import RunSpec, execute
    from repro.ft.tracker import MemoryTracker

    spec = RunSpec(arch="xlstm-125m", reduce=reduce, batch=batch, seq=seq,
                   total_steps=total_steps, strategy="algo_t", step_s=1.0,
                   mu_s=12.0, C_s=2.0, R_s=2.0, D_s=1.0, use_buddy=False,
                   compress=True, ckpt_dir=str(ckpt_dir), seed=0)
    tracker = MemoryTracker()
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    t0 = time.perf_counter()
    try:
        rep = execute(spec, tracker=tracker)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    secs = time.perf_counter() - t0
    losses = rep["losses"]
    saves = tracker.of_kind("checkpoint")
    restores = [r for r in tracker.of_kind("failure")
                if r["source"] == "store"]
    _check(rep["final_step"] == total_steps, "trainer did not finish")
    _check(all(math.isfinite(x) for x in losses), "non-finite loss")
    # Random tokens: the loss stays near ln(vocab); a restore that
    # corrupts the optimizer state sends it far above its start.
    _check(max(losses) <= 1.5 * losses[0], "loss diverged")
    _check(rep["n_failures"] >= 1 and rep["n_rollbacks"] >= 1,
           "no failure injected")
    _check(len(saves) >= 1 and len(restores) >= 1,
           "no save and restore through the store")
    return {"arch": spec.arch, "batch": batch, "seq": seq,
            "final_step": rep["final_step"], "steps_run": len(losses),
            "failures": rep["n_failures"], "rollbacks": rep["n_rollbacks"],
            "saves": len(saves), "store_restores": len(restores),
            "loss_first": losses[0], "loss_last": losses[-1],
            "seconds": round(secs, 3)}


# ---------------------------------------------------------------------------
# Sharded sweep (four chips)
# ---------------------------------------------------------------------------

def sharded_phase(mus=None, rhos=FIG2_RHOS, n_trials: int = 1024,
                  T_base: float = SWEEP_T_BASE, seed: int = 0) -> dict:
    """Default DispatchConfig (every device) == shard=False (one device),
    bit for bit, for the default engine and the Pallas engine."""
    import numpy as np

    from repro.core import Weibull
    from repro.sim import (COMPENSATED_F32, DispatchConfig,
                           simulate_trajectories)
    from repro.sim.dispatch import effective_devices
    from repro.sim.engine import resolve_engine_kind

    _, grid, T = _fig2_sweep(mus, rhos)
    out = {"points": grid.size, "trials": n_trials,
           "devices": effective_devices(DispatchConfig())}
    kw = dict(n_trials=n_trials, seed=seed, process=Weibull(shape=0.7))
    for kind, pol in ((resolve_engine_kind(None), None),
                      ("pallas", COMPENSATED_F32)):
        res = {}
        for name, cfg in (("sharded", DispatchConfig()),
                          ("single", DispatchConfig(shard=False))):
            t0 = time.perf_counter()
            res[name] = simulate_trajectories(T, grid, T_base,
                                              engine_kind=kind,
                                              precision=pol, dispatch=cfg,
                                              **kw)
            out[f"{kind}_{name}_seconds_first_call"] = round(
                time.perf_counter() - t0, 3)
        equal = all(np.array_equal(getattr(res["sharded"], f),
                                   getattr(res["single"], f))
                    for f in ("wall_time", "energy", "work_executed",
                              "io_time", "down_time", "n_failures",
                              "n_checkpoints", "truncated",
                              "gaps_exhausted"))
        out[f"{kind}_bit_equal"] = equal
        _check(equal, f"{kind}: sharded != single device")
    return out


# ---------------------------------------------------------------------------

def _emit(name: str, record: dict) -> None:
    print(f"{name}: {json.dumps(record, default=float)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded sweep across four chips")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    import repro.sim  # noqa: F401  (compile cache on before any compile)

    clock = CompileClock()
    phases = ((("sharded", sharded_phase),) if args.chips == 4 else
              (("sweep", sweep_phase), ("advisor", advisor_phase),
               ("trainer", trainer_phase)))
    for name, phase in phases:
        log(f"{name}: start")
        c0, t0 = clock.seconds, time.perf_counter()
        record = phase()
        record["compile_seconds"] = clock.since(c0)
        record["phase_seconds"] = round(time.perf_counter() - t0, 3)
        record["peak_device_bytes"] = peak_device_bytes()
        _emit(name, record)
        if name == "sweep":
            _check(record["tpu_custom_call"],
                   "compiled Pallas kernel has no tpu_custom_call")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
