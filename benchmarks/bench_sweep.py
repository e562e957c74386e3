"""Scalar vs batched sweep/engine timings -> BENCH_sweep.json (+ CI gate).

Times the seed per-point loop (``tradeoff.sweep_mu_rho(engine="scalar")``)
against the batched ``repro.sim`` grid evaluation on (a) the seed benchmark
grid and (b) a dense production-resolution grid; the Monte-Carlo engine
entries: the event kernel vs the scalar oracle on the canonical Weibull
workload (``weibull_event_engine``), the Pallas event kernel vs the scan
event engine on the same workload with full bit-parity asserted
(``pallas_event_engine``, gated at its no-regression cap; the raw ratio
and backend ride along ungated), and the warm MC-surrogate solve
step-vs-event (``mc_solver_warm``); the dispatch-layer entries: the
multi-device sharded dense sweep (``sharded_dense_grid``, measured on
virtual CPU devices in a subprocess), the memory-bounded 10^6-point
chunked sweep (``chunked_dense_1m``, asserts chunked == unchunked
bit-for-bit), and the persistent-compile-cache cold start
(``cold_start_cached``, two fresh interpreters against one cache dir);
the async-flush model entry (``async_overlap_collapse``, gated on a
DETERMINISTIC quantity: the collapse of the time overhead above
failure-free execution as the deep-flush overlap ``omega2`` -> 1, pure
model arithmetic so it pins the per-level omega model itself);
and the serving entries from ``bench_advisor``: the micro-batched
512-request advisor burst vs the naive per-request loop (``advisor_rps``,
gated, with open-loop p50/p99 riding along) and the batch-window x
cache-hit-rate open-loop sweep (``advisor_load_regimes``, ungated:
absolute latency is machine-dependent).
``weibull_step_engine_reference`` keeps the RETAINED step kernel's
Weibull-vs-exponential ratio as an ungated-by-design reference — it reads
~0.3x by construction (the cv^2-scaled step budget the event kernel was
built to avoid) and must not trip the gate.  Every run also renders the
warm/cold timings as ``benchmarks/results/bench_sweep_table.md`` (uploaded
as a CI artifact).

The canonical artifact is ``BENCH_sweep.json`` at the repo root — the
committed baseline the CI regression gate compares against.  There is
deliberately no second copy under ``benchmarks/results/``.

Modes:
  python -m benchmarks.bench_sweep           # measure + rewrite the baseline
  python -m benchmarks.bench_sweep --check   # measure, compare the warm
                                             # scalar-vs-batched speedup
                                             # against the committed baseline,
                                             # exit non-zero on a >2x drop
                                             # (machine-normalized; baseline
                                             # file left untouched)

Note: regenerate the committed baseline ONLY with a standalone bench_sweep
run.  ``benchmarks.run`` invokes this module with ``--no-write`` — its jit
cache is pre-warmed by the other figure benches, which would record a
meaninglessly small ``batched_cold_s`` into the baseline.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from ._util import emit

SEED_MUS = [30, 60, 90, 120, 180, 240, 300, 420, 600]
ROOT = Path(__file__).resolve().parents[1]
#: the one canonical timing artifact (committed baseline for --check).
CANONICAL = ROOT / "BENCH_sweep.json"
#: >2x warm-timing slowdown vs the committed baseline fails the CI job.
REGRESSION_FACTOR = 2.0


def _best_of(fn, repeat):
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _time_pair(mus, rhos, scalar_repeat, batched_repeat):
    from repro.core.tradeoff import sweep_mu_rho
    from repro.sim import sweep_mu_rho_grid

    scalar_s = _best_of(lambda: sweep_mu_rho(mus, rhos, engine="scalar"),
                        scalar_repeat)
    t0 = time.perf_counter()
    res = sweep_mu_rho_grid(mus, rhos)
    cold_s = time.perf_counter() - t0
    batched_s = _best_of(lambda: sweep_mu_rho_grid(mus, rhos), batched_repeat)

    # Cross-check the two paths agree before trusting the timing.
    ref = sweep_mu_rho(mus, rhos, engine="scalar")
    err = max(abs(res.energy_ratio[i][j] - ref[i][j].energy_ratio)
              for i in range(len(mus)) for j in range(len(rhos)))
    assert err < 1e-9, f"scalar/batched sweep disagree: {err}"

    return {"n_points": len(mus) * len(rhos),
            "scalar_s": scalar_s,
            "batched_cold_s": cold_s,
            "batched_warm_s": batched_s,
            "speedup_warm": scalar_s / batched_s}


def _weibull_workload(n_points=12, n_trials=128, shape=0.7):
    """The canonical non-exponential engine workload: a mixed-mu exascale
    grid (the regime where cv-scaled step budgets used to blow up)."""
    import numpy as np

    from repro.core import fig12_checkpoint, EXASCALE_POWER_RHO55
    from repro.core.failures import Weibull
    from repro.sim import ParamGrid

    mus = np.linspace(120.0, 600.0, n_points)
    base = ParamGrid.from_params(fig12_checkpoint(300.0),
                                 EXASCALE_POWER_RHO55)
    grid = ParamGrid(**{f: (mus if f == "mu"
                            else np.broadcast_to(v, (n_points,)))
                        for f, v in base.fields().items()})
    return grid, Weibull(shape=shape), 60.0, 1500.0, n_trials


def _time_weibull_step_engine_reference(n_points=12, n_trials=128,
                                        shape=0.7, repeat=5):
    """The RETAINED step kernel's Weibull-vs-exponential ratio (reference).

    Runs ``sim.simulate_trajectories`` with ``engine_kind="step"`` on the
    canonical workload twice — exponential and Weibull auto-sampled
    schedules — and reports the within-run ratio.  It reads ~0.3x BY
    CONSTRUCTION: the step kernel's scan budget scales with the gap cv^2,
    which is exactly the cost the event kernel (the default) erased; the
    entry exists to keep that reference measurable, not to gate it.
    Hence ``"ungated": True`` — ``check_regression`` skips it by design
    (a glance at 0.3x used to read as a live regression of the hot path,
    which it is not; the gated hot-path entries are
    ``weibull_event_engine`` and ``mc_solver_warm``).
    """
    from repro.sim.engine import simulate_trajectories

    grid, proc, T, T_base, n_trials = _weibull_workload(n_points, n_trials,
                                                        shape)

    def run_exp():
        return simulate_trajectories(T, grid, T_base, n_trials=n_trials,
                                     seed=0, engine_kind="step")

    def run_weibull():
        return simulate_trajectories(T, grid, T_base, n_trials=n_trials,
                                     seed=0, process=proc,
                                     engine_kind="step")

    t0 = time.perf_counter()
    run_weibull()
    weibull_cold_s = time.perf_counter() - t0
    run_exp()                              # warm the exponential program too
    weibull_warm_s = _best_of(run_weibull, repeat)
    exp_warm_s = _best_of(run_exp, repeat)
    return {"n_points": n_points, "n_trials": n_trials,
            "weibull_shape": shape,
            "ungated": True,               # reference entry, by design
            "exp_warm_s": exp_warm_s,
            "batched_cold_s": weibull_cold_s,
            "batched_warm_s": weibull_warm_s,
            "speedup_warm": exp_warm_s / weibull_warm_s}


def _time_weibull_event_engine(n_points=12, n_trials=128, shape=0.7,
                               repeat=5):
    """Event engine vs the SCALAR oracle on the Weibull workload.

    This is the PR-4 before/after story: on exactly the 12-point/128-trial
    workload where the step kernel measured 0.32x against the scalar
    per-trajectory loop, the event kernel must win outright
    (``speedup_warm`` = scalar / event-warm; the acceptance floor is 5x).
    """
    from repro.core.simulator import simulate
    from repro.sim.engine import simulate_trajectories

    grid, proc, T, T_base, n_trials = _weibull_workload(n_points, n_trials,
                                                        shape)

    def run_scalar():
        for i in range(grid.size):
            simulate(T, grid.ckpt_at(i), grid.power_at(i), T_base,
                     n_trials=n_trials, seed=0, process=proc)

    def run_event():
        return simulate_trajectories(T, grid, T_base, n_trials=n_trials,
                                     seed=0, process=proc)

    # The step reference entry compiled only step-kernel programs, so the
    # first event call here is an honest cold measurement.
    t0 = time.perf_counter()
    run_event()
    event_cold_s = time.perf_counter() - t0
    event_warm_s = _best_of(run_event, repeat)
    scalar_s = _best_of(run_scalar, 1)     # the python loop needs no warmup
    return {"n_points": grid.size, "n_trials": n_trials,
            "weibull_shape": shape,
            "scalar_s": scalar_s,
            "batched_cold_s": event_cold_s,
            "batched_warm_s": event_warm_s,
            "speedup_warm": scalar_s / event_warm_s}


#: cap on the pallas entry's GATED ratio (same portability argument as
#: ``_SHARDED_GATE_CAP``): the gate asserts "the pallas engine does not
#: regress below the event scan", not this machine's exact margin.
_PALLAS_GATE_CAP = 1.5


def _time_pallas_event_engine(n_points=12, n_trials=128, shape=0.7,
                              repeat=5):
    """Pallas event kernel vs the lax.scan event engine, same workload.

    Both run the identical auto-sampled Weibull schedules (CRN), so the
    run asserts full bit parity before trusting the timing.  On CPU the
    kernel executes via ``pallas_call(..., interpret=True)`` — traced to
    plain XLA ops — and still wins: its all-done early exit skips the
    power-of-two padding tail the scan kernel burns through.  That
    no-regression claim (>= 1.0x, capped at ``_PALLAS_GATE_CAP``) is the
    gated ``speedup_warm``; the RAW ratio rides along ungated as
    ``pallas_speedup`` with the backend/device it was measured on (on an
    accelerator backend the kernel lowers natively and the raw ratio is
    the interesting number).
    """
    import jax

    from repro.sim.engine import simulate_trajectories

    grid, proc, T, T_base, n_trials = _weibull_workload(n_points, n_trials,
                                                        shape)
    run = lambda kind: simulate_trajectories(
        T, grid, T_base, n_trials=n_trials, seed=0, process=proc,
        engine_kind=kind)

    r_event = run("event")                 # warm (or reuse) the scan program
    t0 = time.perf_counter()
    r_pallas = run("pallas")
    pallas_cold_s = time.perf_counter() - t0
    import numpy as np
    for f in ("wall_time", "energy", "n_failures", "n_checkpoints"):
        assert np.array_equal(np.asarray(getattr(r_event, f)),
                              np.asarray(getattr(r_pallas, f))), \
            f"pallas engine diverged from the event scan on {f}"
    event_warm_s = _best_of(lambda: run("event"), repeat)
    pallas_warm_s = _best_of(lambda: run("pallas"), repeat)
    ratio = event_warm_s / pallas_warm_s
    dev = jax.devices()[0]
    return {"n_points": grid.size, "n_trials": n_trials,
            "weibull_shape": shape,
            "backend": jax.default_backend(),
            "device_kind": dev.device_kind,
            "interpret": jax.default_backend() != "tpu",
            "event_warm_s": event_warm_s,
            "batched_cold_s": pallas_cold_s,
            "batched_warm_s": pallas_warm_s,
            "pallas_speedup": ratio,
            "speedup_warm": min(ratio, _PALLAS_GATE_CAP)}


def _time_mc_solver(repeat=3):
    """Warm MC-surrogate solve: event kernel vs the retained step kernel.

    Both solves share the same CRN schedules and converge to the same
    period; the within-run step/event ratio is machine-normalized and
    regresses exactly when the event hot path (candidate-vmap + per-call
    dispatch) loses ground to the step machine.
    """
    from repro.core import fig12_checkpoint, EXASCALE_POWER_RHO55
    from repro.core.failures import Weibull
    from repro.core.optimal import MCSurrogate

    ck = fig12_checkpoint(300.0)
    proc = Weibull(shape=0.7)

    def solve(kind):
        return MCSurrogate(ck, EXASCALE_POWER_RHO55, proc, T_base=1500.0,
                           n_trials=96, seed=0,
                           engine_kind=kind).argmin("time")

    t0 = time.perf_counter()
    t_event = solve("event")
    event_cold_s = time.perf_counter() - t0
    t_step = solve("step")                 # warms the step programs
    # The two kernels share schedules but not arithmetic; a ~1e-13 tie in
    # a golden-section branch can wiggle the argmin, so gate at the MC
    # solvers' own agreement tolerance rather than exact equality.
    assert abs(t_event - t_step) <= 5e-3 * t_step, (t_event, t_step)
    event_warm_s = _best_of(lambda: solve("event"), repeat)
    step_warm_s = _best_of(lambda: solve("step"), repeat)
    return {"n_trials": 96, "weibull_shape": 0.7,
            "step_warm_s": step_warm_s,
            "batched_cold_s": event_cold_s,
            "batched_warm_s": event_warm_s,
            "speedup_warm": step_warm_s / event_warm_s}


#: cap on the sharded entry's GATED ratio: makes the committed baseline
#: machine-portable (see _time_sharded_dense) — raising it requires a
#: baseline machine whose capped value every CI runner can reach half of.
_SHARDED_GATE_CAP = 2.0


def _cpu_child_env(**extra) -> dict:
    """Environment of a bench subprocess: pinned to the CPU backend, so a
    child never asks for the accelerator its parent process holds."""
    return dict(os.environ, JAX_PLATFORMS="cpu", **extra)


#: virtual devices for the sharded bench subprocess: one per core, capped
#: at the acceptance target's 8 (oversubscribing cores with more virtual
#: devices than hardware threads just measures scheduler noise).
def _bench_device_count() -> int:
    return max(1, min(8, os.cpu_count() or 1))


_SHARDED_SCRIPT = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%(ndev)d "
                           + os.environ.get("XLA_FLAGS", ""))
sys.path.insert(0, r"%(src)s")
import numpy as np
import jax
from repro.sim import DispatchConfig, ParamGrid, simulate_trajectories
from repro.core import fig12_checkpoint, EXASCALE_POWER_RHO55
from repro.core.failures import Weibull

B, trials = 512, 128
base = ParamGrid.from_params(fig12_checkpoint(300.0), EXASCALE_POWER_RHO55)
mus = np.linspace(120.0, 600.0, B)
grid = ParamGrid(**{f: (mus if f == "mu" else np.broadcast_to(v, (B,)))
                    for f, v in base.fields().items()})
kw = dict(T_base=1500.0, n_trials=trials, seed=0, process=Weibull(shape=0.7))
single = DispatchConfig(shard=False)
sharded = DispatchConfig()

def best(fn, repeat=5):
    b = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter(); fn(); b = min(b, time.perf_counter() - t0)
    return b

r1 = simulate_trajectories(60.0, grid, dispatch=single, **kw)   # compile
r2 = simulate_trajectories(60.0, grid, dispatch=sharded, **kw)
eq = bool(np.array_equal(r1.wall_time, r2.wall_time)
          and np.array_equal(r1.energy, r2.energy))
single_s = best(lambda: simulate_trajectories(60.0, grid, dispatch=single,
                                              **kw))
sharded_s = best(lambda: simulate_trajectories(60.0, grid, dispatch=sharded,
                                               **kw))
print(json.dumps({"n_devices": jax.device_count(), "bit_equal": eq,
                  "n_points": B, "n_trials": trials,
                  "single_warm_s": single_s, "sharded_warm_s": sharded_s}))
"""


def _time_sharded_dense():
    """Sharded vs single-device dense MC-engine grid sweep on virtual CPU
    devices.

    Runs in a subprocess (the device count must be fixed before jax
    initializes) with one virtual device per core (<= 8) — the scan-heavy
    engine sweep is where device sharding is the real parallelism lever
    (the elementwise model sweep is already saturated by XLA:CPU's
    intra-op threading on a CPU host).  The subprocess asserts sharded ==
    single-device bit parity on the full result.  Note: virtual devices
    SHARE the host's cores (and its intra-op thread pool), so the
    measured speedup tracks physical cores, not the virtual device
    count; dedicated-accelerator hosts see the near-linear version of
    the same dispatch.

    The raw single/sharded ratio scales with the host's PHYSICAL cores
    (and per-unit efficiency falls as units rise), so gating either
    quantity raw against a committed baseline from a different machine
    class can fail CI for core-count reasons alone.  The gated
    ``speedup_warm`` is therefore the raw ratio CAPPED at
    ``_SHARDED_GATE_CAP`` (2.0): any healthy multi-core host clears the
    cap's half-way mark (failing requires sharding to be actively slower
    than single-device — a genuine dispatch-overhead regression), while
    a many-core machine regenerating the baseline can never raise the
    bar above the cap.  The uncapped ratio is recorded as
    ``sharded_speedup`` alongside n_devices/n_cores.
    """
    ndev = _bench_device_count()
    script = _SHARDED_SCRIPT % {"ndev": ndev, "src": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=1200,
                         env=_cpu_child_env())
    if out.returncode != 0:
        raise RuntimeError(f"sharded bench subprocess failed:\n"
                           f"{out.stderr[-3000:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["bit_equal"], "sharded sweep diverged from single-device"
    ratio = r["single_warm_s"] / r["sharded_warm_s"]
    return {"n_points": r["n_points"], "n_trials": r["n_trials"],
            "n_devices": r["n_devices"], "n_cores": os.cpu_count(),
            "single_warm_s": r["single_warm_s"],
            "batched_warm_s": r["sharded_warm_s"],
            "sharded_speedup": ratio,
            "speedup_warm": min(ratio, _SHARDED_GATE_CAP)}


def _time_chunked_dense_1m(repeat=2):
    """10^6-point dense sweep, streamed under the 2 GiB memory budget.

    The chunked run (default budget -> two 512k-point chunks at the
    4 KiB/point model estimate) must be bit-identical to the unchunked
    single-dispatch run; the gated ratio unchunked/chunked (~1x) watches
    for chunking overhead creeping in.
    """
    import numpy as np

    from repro.sim import DispatchConfig, evaluate_grid, mu_rho_grid

    grid = mu_rho_grid(list(np.linspace(30.0, 600.0, 1000)),
                       list(np.linspace(1.0, 10.0, 1000)))
    unchunked = DispatchConfig(shard=False, memory_budget_bytes=1 << 40)
    chunked = DispatchConfig(shard=False)    # default 2 GiB budget

    t0 = time.perf_counter()
    ref = evaluate_grid(grid, dispatch=chunked)
    cold_s = time.perf_counter() - t0
    out = evaluate_grid(grid, dispatch=unchunked)
    for f in ("T_time", "T_energy", "time_ratio", "energy_ratio"):
        a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(out, f))
        assert np.array_equal(a, b, equal_nan=True), \
            f"chunked 1M sweep diverged from unchunked on {f}"
    chunked_s = _best_of(lambda: evaluate_grid(grid, dispatch=chunked),
                         repeat)
    unchunked_s = _best_of(lambda: evaluate_grid(grid, dispatch=unchunked),
                           repeat)
    return {"n_points": 1_000_000,
            "memory_budget_bytes": DispatchConfig().budget(),
            "unchunked_warm_s": unchunked_s,
            "batched_cold_s": cold_s,
            "batched_warm_s": chunked_s,
            "speedup_warm": unchunked_s / chunked_s}


_COLD_START_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, r"%(src)s")
import numpy as np
from repro.sim import mu_rho_grid, evaluate_grid, ParamGrid, \
    simulate_trajectories
from repro.core import fig12_checkpoint, EXASCALE_POWER_RHO55
from repro.core.failures import Weibull

t0 = time.perf_counter()
evaluate_grid(mu_rho_grid([30, 60, 90, 120, 180, 240, 300, 420, 600],
                          list(np.linspace(1.0, 10.0, 10))))
base = ParamGrid.from_params(fig12_checkpoint(300.0), EXASCALE_POWER_RHO55)
mus = np.linspace(120.0, 600.0, 12)
grid = ParamGrid(**{f: (mus if f == "mu" else np.broadcast_to(v, (12,)))
                    for f, v in base.fields().items()})
simulate_trajectories(60.0, grid, 1500.0, n_trials=128, seed=0,
                      process=Weibull(shape=0.7))
print("COLD_S", time.perf_counter() - t0)
"""


def _time_async_overlap_collapse(repeat=5):
    """Async-flush payoff on the model itself: as the deep-flush overlap
    ``omega2`` -> 1 the PFS write leaves the critical path — the time
    overhead above failure-free execution collapses and the jointly
    time-optimal deep cadence m* drops to 1 (flush every period) — while
    the energy-optimal point barely moves: the I/O energy is paid for
    the full write whether or not it overlaps, so overlap *widens* the
    time-vs-energy tension instead of dissolving it.

    The gated ``speedup_warm`` is the DETERMINISTIC overhead collapse
    ``overhead(omega2=0) / overhead(omega2=1)`` — pure model arithmetic,
    identical on every machine, so this entry pins the per-level omega
    model rather than a timing; the warm solve time rides along for the
    table."""
    from repro.core import model, optimal
    from repro.core.params import (MultilevelCheckpointParams,
                                   MultilevelPowerParams)

    pw = MultilevelPowerParams(P_static=10.0, P_cal=10.0, P_io1=20.0,
                               P_io2=100.0)
    grid = [0.0, 0.5, 0.9, 1.0]

    def solve():
        rows = []
        for w2 in grid:
            ck = MultilevelCheckpointParams(C1=1.0, R1=1.0, C2=10.0,
                                            R2=10.0, D1=0.5, D2=1.0,
                                            mu=300.0, q=0.1, omega=0.0,
                                            omega2=w2)
            T_t, m_t = optimal.t_opt_time_multilevel(ck)
            T_e, m_e = optimal.t_opt_energy_multilevel(ck, pw)
            overhead = float(model.ml_time_final(T_t, m_t, ck)) - 1.0
            e_pen = (float(model.ml_energy_final(T_t, m_t, ck, pw))
                     / float(model.ml_energy_final(T_e, m_e, ck, pw)) - 1.0)
            rows.append((T_t, m_t, T_e, m_e, overhead, e_pen))
        return rows

    warm_s = _best_of(solve, repeat)
    rows = solve()
    overheads = [r[4] for r in rows]
    if not all(b < a for a, b in zip(overheads, overheads[1:])):
        raise AssertionError(
            f"time overhead must fall monotonically as omega2 -> 1, got "
            f"{overheads} (per-level omega model broken?)")
    return {
        "omega2_grid": grid,
        "T_opt_time": [round(r[0], 6) for r in rows],
        "m_opt_time": [r[1] for r in rows],
        "T_opt_energy": [round(r[2], 6) for r in rows],
        "m_opt_energy": [r[3] for r in rows],
        "time_overhead": [round(r[4], 9) for r in rows],
        "energy_penalty_at_time_opt": [round(r[5], 9) for r in rows],
        "batched_warm_s": warm_s,
        "speedup_warm": overheads[0] / overheads[-1],
    }


def _time_cold_start_cached():
    """Persistent-compile-cache cold start: two fresh interpreters, one
    cache directory.

    The first run compiles everything and populates the cache; the second
    pays tracing/lowering but loads the serialized executables.  The
    gated ratio uncached/cached is the once-per-machine-vs-once-per-
    process compile story (``repro.sim.cache``); it is measured entirely
    inside the subprocesses (jax import time excluded).
    """
    def one(cache_dir):
        script = _COLD_START_SCRIPT % {"src": str(ROOT / "src")}
        # Cache every program, however fast it compiles on the CPU.
        env = _cpu_child_env(JAX_COMPILATION_CACHE_DIR=cache_dir,
                             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True, timeout=1200,
                             env=env)
        if out.returncode != 0:
            raise RuntimeError(f"cold-start subprocess failed:\n"
                               f"{out.stderr[-3000:]}")
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("COLD_S")][-1]
        return float(line.split()[1])

    with tempfile.TemporaryDirectory(prefix="repro-compile-cache-") as d:
        uncached_s = one(d)      # populates the cache
        cached_s = one(d)        # second process: cache hits
    return {"cold_uncached_s": uncached_s,
            "batched_cold_s": cached_s,
            "batched_warm_s": cached_s,
            "speedup_warm": uncached_s / cached_s}


def run(write: bool = True):
    import numpy as np

    seed_grid = _time_pair(SEED_MUS, list(np.linspace(1.0, 10.0, 10)),
                           scalar_repeat=5, batched_repeat=10)
    dense_grid = _time_pair(list(np.linspace(30.0, 600.0, 96)),
                            list(np.linspace(1.0, 10.0, 100)),
                            scalar_repeat=1, batched_repeat=3)
    weibull_step_ref = _time_weibull_step_engine_reference()
    weibull_event_engine = _time_weibull_event_engine()
    pallas_event_engine = _time_pallas_event_engine()
    mc_solver_warm = _time_mc_solver()
    chunked_dense_1m = _time_chunked_dense_1m()
    sharded_dense_grid = _time_sharded_dense()
    cold_start_cached = _time_cold_start_cached()
    async_overlap_collapse = _time_async_overlap_collapse()
    from .bench_advisor import time_advisor_regimes, time_advisor_rps
    advisor_rps = time_advisor_rps()
    advisor_load_regimes = time_advisor_regimes()
    payload = {
        "benchmark": "fig2_mu_rho_sweep",
        "unit": "seconds",
        "fig2_seed_grid": seed_grid,
        "dense_grid": dense_grid,
        "weibull_step_engine_reference": weibull_step_ref,
        "weibull_event_engine": weibull_event_engine,
        "pallas_event_engine": pallas_event_engine,
        "mc_solver_warm": mc_solver_warm,
        "sharded_dense_grid": sharded_dense_grid,
        "chunked_dense_1m": chunked_dense_1m,
        "cold_start_cached": cold_start_cached,
        "async_overlap_collapse": async_overlap_collapse,
        "advisor_rps": advisor_rps,
        "advisor_load_regimes": advisor_load_regimes,
    }
    if write:
        # Carry forward baseline keys owned by other tools (e.g. the
        # recompile_budget entry written by `python -m repro.sanitize
        # --write`) — regenerating the timing baseline must not drop
        # them.
        try:
            with open(CANONICAL) as f:
                prev = json.load(f)
        except (OSError, json.JSONDecodeError):
            prev = {}
        for k, v in prev.items():
            payload.setdefault(k, v)
        with open(CANONICAL, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
    return payload


def write_timing_table(payload: dict, path=None) -> str:
    """Render the payload as a warm/cold timing table
    (``benchmarks/results/bench_sweep_table.md``, uploaded as a CI
    artifact next to the raw JSON)."""
    from ._util import RESULTS
    if path is None:
        path = RESULTS / "bench_sweep_table.md"
    lines = ["# bench_sweep timings",
             "",
             "| grid | cold (s) | warm (s) | reference (s) | speedup_warm |",
             "|---|---|---|---|---|"]
    for grid, entry in payload.items():
        if not (isinstance(entry, dict) and "speedup_warm" in entry):
            continue
        ref = next((entry[k] for k in ("scalar_s", "exp_warm_s",
                                       "step_warm_s", "event_warm_s",
                                       "single_warm_s",
                                       "unchunked_warm_s",
                                       "cold_uncached_s", "naive_s")
                    if k in entry),
                   float("nan"))
        cold = entry.get("batched_cold_s")
        tag = " (ungated ref)" if entry.get("ungated") else ""
        lines.append(
            f"| {grid}{tag} | {'—' if cold is None else format(cold, '.4g')} "
            f"| {entry['batched_warm_s']:.4g} | {ref:.4g} "
            f"| {entry['speedup_warm']:.2f}x |")
    text = "\n".join(lines) + "\n"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)
    return str(path)


def check_regression(baseline: dict, payload: dict,
                     factor: float = REGRESSION_FACTOR) -> list:
    """Warm-timing regressions of ``payload`` vs ``baseline`` (> factor x).

    The compared quantity is ``speedup_warm`` — the batched path's warm
    speedup over the scalar path *measured in the same run* — so the gate
    is machine-normalized: a CI runner that is uniformly slower than the
    machine that committed the baseline shifts both numerators and
    denominators and passes, while a real batched-path regression drops
    the speedup and fails.  Pure comparison (no timing) so the CI gate
    logic is unit-testable.

    Entries carrying ``"ungated": true`` are reference measurements
    excluded from the gate BY DESIGN (in both directions) — e.g.
    ``weibull_step_engine_reference``, which reads ~0.3x by construction
    because it measures the retained step kernel the event kernel
    replaced.
    """
    def gated(entry) -> bool:
        return (isinstance(entry, dict) and "speedup_warm" in entry
                and not entry.get("ungated"))

    regressions = []
    # The gate set must match in BOTH directions.  A grid the committed
    # baseline gates must be present in the payload — a renamed/dropped
    # bench disables its gate and must fail loudly, not pass silently.
    for grid in sorted(baseline):
        if not gated(baseline[grid]):
            continue
        if not gated(payload.get(grid)):
            regressions.append(
                f"{grid}: present in the committed baseline but missing "
                f"from this run's payload — bench renamed/dropped without "
                f"regenerating BENCH_sweep.json?")
            continue
        base = baseline[grid]["speedup_warm"]
        now = payload[grid]["speedup_warm"]
        if now * factor < base:
            regressions.append(
                f"{grid}: speedup_warm {now:.1f}x is {base / now:.1f}x "
                f"below the baseline {base:.1f}x (limit {factor:g}x)")
    # ...and a gated grid the payload produces must be baselined — an
    # unbaselined bench is an ungated bench, which silently exempts every
    # future regression of that path.
    for grid in sorted(payload):
        if gated(payload[grid]) and not gated(baseline.get(grid)):
            regressions.append(
                f"{grid}: gated entry missing from the committed baseline "
                f"— regenerate BENCH_sweep.json (standalone bench_sweep "
                f"run) to baseline the new bench")
    return regressions


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="compare against the committed baseline instead of "
                         "rewriting it; exit non-zero on regression")
    ap.add_argument("--no-write", action="store_true",
                    help="measure and report only; leave the committed "
                         "baseline untouched (used by benchmarks.run)")
    args = ap.parse_args(argv)

    wrote = not (args.check or args.no_write)
    payload = run(write=wrote)
    table = write_timing_table(payload)
    s, d, ev, mc = (payload["fig2_seed_grid"], payload["dense_grid"],
                    payload["weibull_event_engine"],
                    payload["mc_solver_warm"])
    sh, ch, cc = (payload["sharded_dense_grid"],
                  payload["chunked_dense_1m"],
                  payload["cold_start_cached"])
    ad = payload["advisor_rps"]
    emit("bench_sweep", s["batched_warm_s"] * 1e6,
         f"fig2 {s['n_points']}pts speedup={s['speedup_warm']:.1f}x; "
         f"dense {d['n_points']}pts speedup={d['speedup_warm']:.1f}x; "
         f"event vs scalar={ev['speedup_warm']:.1f}x; "
         f"pallas vs event="
         f"{payload['pallas_event_engine']['speedup_warm']:.2f}x; "
         f"mc solver step/event={mc['speedup_warm']:.1f}x; "
         f"sharded x{sh['n_devices']}dev={sh['speedup_warm']:.2f}x; "
         f"chunked 1M={ch['speedup_warm']:.2f}x; "
         f"cold-start cached={cc['speedup_warm']:.2f}x; "
         f"advisor {ad['rps']:.0f} rps={ad['speedup_warm']:.0f}x "
         + (f"-> BENCH_sweep.json + {table}" if wrote
            else f"-> {table} (baseline untouched)"))

    if args.check:
        baseline = json.loads(CANONICAL.read_text())
        regressions = check_regression(baseline, payload)
        if regressions:
            raise SystemExit("benchmark regression gate FAILED:\n  "
                             + "\n  ".join(regressions))
        print(f"bench_sweep --check OK: warm speedups within "
              f"{REGRESSION_FACTOR:g}x of the committed baseline")


if __name__ == "__main__":
    main()
