"""Benchmark harness: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
(configuration, traffic mix, chips, metrics), the configuration in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, which names its generator, the traffic
kind ``bench/traffic/<kind>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

A run: find the TPU (exit 2 with no result without one, or with fewer
chips than the cell asks for); set up, which runs one unit of the cell's
traffic at the timed shapes; run units back to back for ``--seconds``
(with ``--trace 1`` under the profiler); read peak device memory; replay
a sample of the window's answers through the plain reference; print.
Earlier stdout lines record compiles, the window and the Monte-Carlo
standard error of each unit; the last stderr lines and the result's
``checks`` give each compared number beside its limit; the last stdout
line is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache: a fixed directory in the checkout
#: that only the benchmark writes.
CACHE_DIR = ROOT / ".bench_cache"
#: where a traced run writes its profile (emptied after reading).
TRACE_DIR = ROOT / ".bench_trace"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str):
        bench = load_json(ROOT / "BENCHMARK.json")
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
        w = by_name[name]
        self.name, self.chips = name, int(w["chips"])
        self.config = load_json(BENCH / "configs" / f"{w['config']}.json")
        self.mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        self.kind = BENCH / "traffic" / f"{self.mix['kind']}.py"

        def applies(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in bench["end_to_end"] if applies(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"] if applies(m)
                          and m["moves"] in reported]


class CompileClock:
    """Backend compiles, from JAX's own monitoring events (a persistent
    cache hit is not a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0

        def listen(name, duration, **_):
            if name == self.EVENT:
                self.count += 1
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


class Seeds:
    """The unit seeds of a run, drawn in order from ``--seed``."""

    def __init__(self, seed: int):
        import numpy as np

        self._rng = np.random.default_rng([seed & (2**64 - 1), 0xB3AC])

    def next(self) -> int:
        return int(self._rng.integers(0, 2**62))


def find_chips(chips: int) -> list:
    """The TPU devices the cell uses, or SystemExit(2) before any work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devs[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX finds "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def emit(tag: str, record: dict) -> None:
    print(f"{tag}: {json.dumps(record)}", flush=True)


def run_window(work, seeds: Seeds, seconds: float, annotate) -> tuple:
    """Units back to back, at least one, until ``seconds`` have passed;
    returns the (seed, output) pairs, the failed count and the window's
    seconds from its start to the end of the last unit."""
    outs, failed = [], 0
    t0 = t_end = time.perf_counter()
    while not outs and not failed or t_end - t0 < seconds:
        s = seeds.next()
        try:
            with annotate("bench.unit"):
                out = work.unit(s)
        except (RuntimeError, ValueError, FloatingPointError) as e:
            failed += 1
            print(f"bench: unit with seed {s} failed: {e!r}", file=sys.stderr)
            out = None
        t_end = time.perf_counter()
        if out is not None:
            outs.append((s, out))
    return outs, failed, t_end - t0


@contextlib.contextmanager
def _no_span(name: str):
    yield


def compared(work, mix: dict, outs: list, seed: int) -> dict:
    """Each compared number with its limit: the program's sampled answers
    against the reference's."""
    import numpy as np

    if not outs:
        return {}
    sample = work.sample(outs, seed)
    got = work.extract(outs, sample)
    want = work.reference(sample, np.float64)
    values = work.compare(got, want)
    return {k: {"value": float(v), "limit": float(mix["limits"][k])}
            for k, v in values.items()}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True) -> dict:
    """One run of ``cell``; returns the result record."""
    import jax

    if require_chip:
        devs = find_chips(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
    clock = CompileClock()
    kind = load_module(cell.kind)
    work = kind.Workload(cell.config, cell.mix, cell.chips)
    seeds = Seeds(seed)
    _, setup_failed, _ = run_window(work, seeds, 0.0, _no_span)
    setup_s = time.perf_counter() - T_START
    emit("setup", {"setup_s": setup_s, "compiles": clock.count,
                   "compile_s": clock.seconds,
                   "cache_dir": jax.config.jax_compilation_cache_dir})

    trace_dir = None
    annotate = jax.profiler.TraceAnnotation
    if trace:
        if hasattr(work, "record_engine_calls"):
            work.record_engine_calls()
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    c0, s0 = clock.count, clock.seconds
    try:
        outs, failed, window_s = run_window(work, seeds, seconds, annotate)
    finally:
        if trace:
            jax.profiler.stop_trace()
    failed += setup_failed
    emit("window", {"units": len(outs), "failed": failed,
                    "seconds": window_s,
                    "compiles_in_window": clock.count - c0,
                    "compile_s_in_window": clock.seconds - s0})
    for s, out in outs:
        emit("unit", {"seed": s, **work.describe(out)})
    peak = memory_peak(devs)
    device = {"platform": devs[0].platform,
              "kind": str(getattr(devs[0], "device_kind", devs[0].platform)),
              "count": len(devs), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    if trace:
        from bench import trace_reduce

        reading = trace_reduce.read_profile(trace_dir, n_devices=len(devs))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = trace_reduce.Context(
            reading=reading, units=len(outs), chips=cell.chips,
            bytes_per_unit=_bytes_per_unit(work, outs),
            peaks=trace_reduce.peaks_for(device["kind"]))
        emit("trace", reading.summary())
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        breakdown = reading.breakdown()
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        per_unit = window_s / len(outs) if outs else math.inf
        values = {"setup_s": setup_s, cell.mix["metric"]: per_unit}
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    checks = compared(work, cell.mix, outs, seed)
    correct = (bool(outs) and failed == 0 and set(checks) ==
               set(cell.mix["limits"]) and all(
                   math.isfinite(c["value"]) and c["value"] <= c["limit"]
                   for c in checks.values()))
    emit("checks", checks)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result = {"correct": correct, "attempted": len(outs) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _bytes_per_unit(work, outs: list) -> float | None:
    """Bytes moved per unit of the traced window, or None."""
    counts = [work.bytes_moved(out) for _, out in outs]
    if not counts or any(c is None for c in counts):
        return None
    return sum(counts) / len(counts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    # Set, not defaulted: a directory inherited from the environment could
    # be shared with another checkout, and the two sides of a comparison
    # must share no compiled program.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu's logs would go to a fixed directory under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    # Every program goes to the cache, so only a cell's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
