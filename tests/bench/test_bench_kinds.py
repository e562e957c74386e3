"""Each traffic kind at a tiny size on the CPU: one unit of the program,
its answers against the plain reference (every number inside its limit),
and the control, the reference in float32 put in the program's place,
which has to fail a limit."""
import numpy as np
import pytest

from bench import run as harness
from bench_cells import TINY, tiny_cell


@pytest.fixture(scope="module", params=sorted(TINY))
def unit_run(request):
    cell = tiny_cell(request.param)
    kind = harness.load_module(cell.kind)
    work = kind.Workload(cell.config, cell.mix, 1)
    outs = [(s, work.unit(s)) for s in (2**40 + 3, 2**33 - 5)]
    return cell, work, outs


def _fails(values: dict, limits: dict) -> list:
    return [k for k, v in values.items() if not v <= limits[k]]


@pytest.mark.parametrize("seed", [1, 2**41 + 9])
def test_program_matches_reference(unit_run, seed):
    cell, work, outs = unit_run
    sample = work.sample(outs, seed)
    got = work.compare(work.extract(outs, sample),
                       work.reference(sample, np.float64))
    assert set(got) == set(cell.mix["limits"])
    assert _fails(got, cell.mix["limits"]) == []


@pytest.mark.parametrize("seed", [3, 2**35 + 1, 17])
def test_control_fails(unit_run, seed):
    cell, work, outs = unit_run
    sample = work.sample(outs, seed)
    want = work.reference(sample, np.float64)
    control = work.compare(work.reference(sample, np.float32), want)
    assert _fails(control, cell.mix["limits"])


def test_sample_covers_every_point_and_the_longest(unit_run):
    cell, work, outs = unit_run
    sample = work.sample(outs, 5)
    if "trials" in sample:          # the sweep: every grid point, at least
        n_points = work.grid.size   # one trial, and the most failures
        assert set(sample["points"]) == set(range(n_points))
        out = outs[sample["unit"]][1]
        nf = out.n_failures.reshape(n_points, -1)
        i, t = np.unravel_index(int(np.argmax(nf)), nf.shape)
        assert np.any((sample["points"] == i) & (sample["trials"] == t))
    else:                           # the solve: the heaviest-tailed point
        assert len(sample["points"]) == cell.mix["check"]["points"]
        assert 0 in sample["points"]   # k = 0.5 at the shortest MTBF


def test_describe_reports_standard_errors(unit_run):
    _, work, outs = unit_run
    d = work.describe(outs[0][1])
    assert 0.0 < d["wall_time_se_rel_max"] < 1.0
    assert 0.0 < d["energy_se_rel_max"] < 1.0


def test_solve_bytes_silent_without_engine_calls(monkeypatch):
    """A traced solve whose engine calls bypass the counted entry point
    gives no byte count, so the solve's roofline drops out of the result
    instead of reading 0."""
    from repro.sim import engine

    from bench import trace_reduce as tr

    name = "fig5-robustness-exascale55.crn-solve"
    cell = tiny_cell(name)
    work = harness.load_module(cell.kind).Workload(cell.config, cell.mix, 1)
    original = engine.simulate_candidates
    monkeypatch.setattr(engine, "simulate_candidates", original)
    work.record_engine_calls()
    counted = work.unit(2**40 + 3)
    assert counted["bytes_moved"] > 0
    monkeypatch.setattr(engine, "simulate_candidates", original)
    silent = work.unit(2**40 + 3)
    assert work.bytes_moved(silent) is None
    assert harness._bytes_per_unit(work, [(1, counted), (2, silent)]) is None

    reader = harness.load_module(harness.BENCH / "metrics"
                                 / "mc_roofline.solve.py")
    dev = "/device:TPU:0"
    reading = tr.Reading([
        tr.Event(tr.HOST_PLANE, tr.HOST_LINE, tr.UNIT_SPAN, 0, 10_000),
        tr.Event(dev, tr.MODULES_LINE, "jit_run_cands(1)", 1_000, 8_000),
        tr.Event(dev, tr.OPS_LINE, "%fusion.1", 1_000, 8_000)], n_devices=1)

    def ctx(n_bytes):
        return tr.Context(reading=reading, units=1, chips=1,
                          bytes_per_unit=n_bytes,
                          peaks=tr.peaks_for("TPU v5 lite"))
    assert reader.read(ctx(None)) is None
    # 819 bytes in 8 us at 819 GB/s: 1 ns of 8000 ns.
    assert reader.read(ctx(819.0)) == pytest.approx(100.0 / 8000)
