"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12

Sets the cell up as ``bench/run.py`` does, then for each seed (from
``FIRST_SEED``, ``SEED_STEP`` apart) runs one unit of the cell's traffic and compares a sample of its answers, drawn
from that seed, with the plain reference (the program's reading), and
puts the reference computed in float32 in the program's place on the
same sample (the control's reading).  Prints one JSON line per seed and,
last, for each compared number the largest program reading and the
smallest control reading.  The benchmark's own runs never run the
control.

``--record-trace PATH`` also traces one unit and writes the events of its
first ``RECORD_MS`` milliseconds of device work to PATH, cut to that
stretch, for the trace reduction's test.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: the first seed read, and the distance between the seeds read.
FIRST_SEED, SEED_STEP = 2**40 + 1, 7919
#: milliseconds of device work that ``--record-trace`` keeps.
RECORD_MS = 20.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--record-trace", type=Path)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as harness

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np

    cell = harness.Cell(args.workload)
    harness.find_chips(cell.chips)
    kind = harness.load_module(cell.kind)
    work = kind.Workload(cell.config, cell.mix, cell.chips)
    lower: dict = {}
    upper: dict = {}
    for i in range(args.seeds):
        seed = FIRST_SEED + SEED_STEP * i
        out = work.unit(harness.Seeds(seed).next())
        outs = [(harness.Seeds(seed).next(), out)]
        sample = work.sample(outs, seed)
        want = work.reference(sample, np.float64)
        prog = work.compare(work.extract(outs, sample), want)
        ctrl = work.compare(work.reference(sample, np.float32), want)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), float(v))
        for k, v in ctrl.items():
            upper[k] = min(upper.get(k, float("inf")), float(v))
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          **work.describe(out)}), flush=True)
    if args.record_trace:
        record_trace(work, args.record_trace)
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "lower": lower, "upper": upper,
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


def record_trace(work, path: Path) -> None:
    """Trace one unit; keep the events of its first ``RECORD_MS`` ms,
    clipped to that stretch and with operations named short, under a
    ``bench.unit`` span of the stretch."""
    import shutil

    import jax

    from bench import trace_reduce as tr

    tmp = ROOT / ".bench_trace" / "record"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation(tr.UNIT_SPAN):
        work.unit(1)
    jax.profiler.stop_trace()
    events = tr.load_events(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    a = min(e.start_ns for e in events if e.name == tr.UNIT_SPAN)
    b = a + int(RECORD_MS * 1e6)
    keep = [tr.Event(e.plane, e.line, tr.op_name(e.name)
                     if e.line == tr.OPS_LINE else e.name, max(e.start_ns, a),
                     min(e.start_ns + e.dur_ns, b) - max(e.start_ns, a))
            for e in events
            if e.name != tr.UNIT_SPAN and e.start_ns < b
            and e.start_ns + e.dur_ns > a]
    keep.append(tr.Event(tr.HOST_PLANE, tr.HOST_LINE, tr.UNIT_SPAN, a, b - a))
    path.parent.mkdir(parents=True, exist_ok=True)
    tr.save_events(keep, path)
    print(json.dumps({"recorded": str(path), "events": len(keep)}),
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
