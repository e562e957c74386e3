"""Compile/leak sanitizer tier: mechanical backstops for the
pow2-bucketing / LRU discipline the dispatch stack enforces by hand.

Two gates, both driven from the canonical workloads below (small fig2,
multilevel, and advisor sweeps — the same code paths the committed
benchmarks exercise):

* **Recompilation budget** — every workload is run from a cold jit
  cache under ``jax.log_compiles`` and the number of compiled programs
  is counted (the WARNING-level ``Compiling <name> ...`` records jax
  emits while the flag is on).  The count must stay within the budget
  committed in ``BENCH_sweep.json`` under the ``recompile_budget`` key.
  A shape-unbucketed code path (the seed-era per-point pattern) shows
  up as one program per grid point and blows the budget immediately.

* **Leak check** — the same workloads run under ``jax.checking_leaks``,
  which raises if a traced value escapes its trace (the failure mode
  that turns pure solver code into silent nondeterminism).

Budgets carry slack of ``max(4, 25%)`` over the measured count so
small compile-behavior drift does not trip the gate, while a per-point
compile explosion (O(grid size) programs) still does.

Regenerate the committed budgets after a deliberate compile-behavior
change (new kernel, different bucketing) the same way the bench
baseline is regenerated::

    PYTHONPATH=src python -m repro.sanitize --write

and commit the resulting ``BENCH_sweep.json``.  ``python -m
repro.sanitize`` alone measures and checks against the committed
budgets (exit 1 on breach) — the pytest tier
(``tests/test_sanitizers.py``, marker ``sanitizer``) asserts the same
thing per-workload, plus leak-cleanliness.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import logging
import math
import sys
from pathlib import Path
from typing import Callable, Dict, Iterator, Optional

BENCH_PATH = Path(__file__).resolve().parents[2] / "BENCH_sweep.json"
BUDGET_KEY = "recompile_budget"

#: the logger that emits jax's ``Compiling <name> ...`` records (one per
#: lowered program, persistent-cache hit or not).
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla",)


class CompileCounter(logging.Handler):
    """Counts jax compile events while ``jax.log_compiles`` is on."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.count = 0
        self.names = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.count += 1
            self.names.append(msg.split(" ", 2)[1])


@contextlib.contextmanager
def count_compiles() -> Iterator[CompileCounter]:
    """Context manager counting compiled programs inside the block."""
    import jax

    counter = CompileCounter()
    loggers = [logging.getLogger(n) for n in _COMPILE_LOGGERS]
    # the counter is the only consumer: stop the log_compiles record
    # flood from propagating to the root handlers while we count.
    prev = [lg.propagate for lg in loggers]
    for lg in loggers:
        lg.addHandler(counter)
        lg.propagate = False
    try:
        with jax.log_compiles(True):
            yield counter
    finally:
        for lg, p in zip(loggers, prev):
            lg.removeHandler(counter)
            lg.propagate = p


# ---------------------------------------------------------------------------
# canonical workloads
# ---------------------------------------------------------------------------


def _run_fig2_small() -> None:
    from repro.sim import sweep_mu_rho_grid

    sweep_mu_rho_grid([120.0, 300.0, 600.0], [1.0, 2.5, 5.0])


def _run_multilevel_small() -> None:
    from repro.sim import buddy_ratio_grid, evaluate_multilevel_grid

    grid = buddy_ratio_grid([0.1, 0.5], [0.05, 0.2], mu_min=300.0)
    evaluate_multilevel_grid(grid, m_values=(1, 2, 3, 4))


def _run_advisor_batch() -> None:
    from repro.serve.loadgen import synthetic_requests
    from repro.serve.service import AdvisorService

    svc = AdvisorService(cache_name=None)
    svc.advise_many(synthetic_requests(12, seed=0, repeat_frac=0.25))


#: name -> zero-arg canonical workload.  These are the sweeps the
#: committed benchmarks gate; keeping the sanitizer on the same paths
#: means a bucketing regression fails both tiers for the same reason.
CANONICAL_WORKLOADS: Dict[str, Callable[[], None]] = {
    "fig2_small": _run_fig2_small,
    "multilevel_small": _run_multilevel_small,
    "advisor_batch": _run_advisor_batch,
}


def measure_workload(fn: Callable[[], None], clear: bool = True) -> int:
    """Compiled-program count for one cold run of ``fn``.

    ``clear=True`` resets the jit caches first, so the count is the
    workload's full compile footprint regardless of what ran earlier in
    the process (the committed budgets assume this).
    """
    import jax

    if clear:
        jax.clear_caches()
    with count_compiles() as counter:
        fn()
    return counter.count


def run_leak_checked(fn: Callable[[], None]) -> None:
    """Run a workload under ``jax.checking_leaks`` (raises on leaks)."""
    import jax

    with jax.checking_leaks():
        fn()


# ---------------------------------------------------------------------------
# budgets
# ---------------------------------------------------------------------------


class RecompileBudgetError(AssertionError):
    """A workload compiled more programs than its committed budget."""


def _slack(measured: int) -> int:
    return max(4, math.ceil(0.25 * measured))


def load_budgets(path: Path = BENCH_PATH) -> Optional[Dict]:
    try:
        with open(path) as f:
            return json.load(f).get(BUDGET_KEY)
    except (OSError, json.JSONDecodeError):
        return None


def recompile_gate(name: str, measured: int,
                   budgets: Optional[Dict] = None,
                   path: Path = BENCH_PATH) -> None:
    """Raise :class:`RecompileBudgetError` if ``measured`` breaches the
    committed budget for workload ``name`` (no-op when no budget is
    committed — the pytest tier skips in that case instead)."""
    if budgets is None:
        budgets = load_budgets(path)
    entry = (budgets or {}).get(name)
    if entry is None:
        return
    if measured > entry["budget"]:
        raise RecompileBudgetError(
            f"{name}: compiled {measured} programs, budget is "
            f"{entry['budget']} (measured {entry['measured']} at commit "
            "time). A new shape reached the jit cache per grid point or "
            "per request — check pow2 bucketing / static-argument "
            "hygiene, or regenerate via `python -m repro.sanitize "
            "--write` if the change is deliberate.")


def measure_all(clear: bool = True) -> Dict[str, int]:
    return {name: measure_workload(fn, clear=clear)
            for name, fn in CANONICAL_WORKLOADS.items()}


def write_budgets(measured: Dict[str, int],
                  path: Path = BENCH_PATH) -> Dict:
    """Fold measured counts into ``BENCH_sweep.json`` (other keys kept)."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        payload = {}
    payload[BUDGET_KEY] = {
        "unit": "compiled programs per cold canonical workload",
        **{name: {"measured": n, "budget": n + _slack(n)}
           for name, n in measured.items()},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload[BUDGET_KEY]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.sanitize",
        description="Measure canonical-workload compile counts and "
                    "check (or --write) the committed recompile budget.")
    ap.add_argument("--write", action="store_true",
                    help="regenerate the recompile_budget entry in "
                         "BENCH_sweep.json from this run")
    ap.add_argument("--path", type=Path, default=BENCH_PATH)
    args = ap.parse_args(argv)

    measured = measure_all()
    for name, n in measured.items():
        print(f"{name}: {n} compiled programs")
    if args.write:
        entry = write_budgets(measured, path=args.path)
        print(f"wrote {BUDGET_KEY} to {args.path}: "
              f"{json.dumps(entry, indent=2)}")
        return 0
    budgets = load_budgets(args.path)
    if budgets is None:
        print(f"no {BUDGET_KEY} committed in {args.path}; run with "
              "--write to create it", file=sys.stderr)
        return 1
    failed = False
    for name, n in measured.items():
        try:
            recompile_gate(name, n, budgets)
        except RecompileBudgetError as e:
            print(f"FAIL {e}", file=sys.stderr)
            failed = True
    print("recompile budget:", "BREACHED" if failed else "ok")
    return 1 if failed else 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
