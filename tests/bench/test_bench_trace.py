"""The trace reduction of ``bench/trace_reduce.py``: on a hand-built trace
whose figures are worked out below, and on a slice of a trace recorded on
a TPU v5e chip, against a timeline of its own."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr

HERE = Path(__file__).resolve().parent
US = 1000  # ns


def _ev(plane, line, name, start_us, end_us):
    return tr.Event(plane, line, name, start_us * US, (end_us - start_us) * US)


def _device(i):
    d = f"/device:TPU:{i}"
    return [
        _ev(d, tr.MODULES_LINE, "jit_build(11)", 100, 400),
        _ev(d, tr.MODULES_LINE, "jit_run(22)", 600, 900),
        _ev(d, tr.OPS_LINE, "%while.1 = (f32[8]) while(...)", 100, 350),
        _ev(d, tr.OPS_LINE, "%fusion.2 = f32[8] fusion(...)", 120, 200),
        _ev(d, tr.OPS_LINE, "%copy.3 = f32[8] copy(...)", 360, 400),
        _ev(d, tr.OPS_LINE, "%fusion.4 = f32[8] fusion(...)", 600, 700),
        _ev(d, tr.OPS_LINE, "%fusion.5 = f32[8] fusion(...)", 705, 900),
        # outside the window: not counted
        _ev(d, tr.MODULES_LINE, "jit_build(11)", 2000, 2100),
    ]


HOST = [
    _ev(tr.HOST_PLANE, tr.HOST_LINE, tr.UNIT_SPAN, 50, 1000),
    _ev(tr.HOST_PLANE, tr.HOST_LINE, "$engine.py:1 simulate", 50, 980),
    _ev(tr.HOST_PLANE, tr.HOST_LINE, "$numpy asarray", 400, 600),
    _ev(tr.HOST_PLANE, "main/1", "AllocateRawBuffer", 390, 610),
]


@pytest.fixture
def one_chip():
    return tr.Reading(HOST + _device(0), n_devices=1)


def test_window_busy_idle(one_chip):
    r = one_chip
    assert r.window_s == pytest.approx(950e-6)
    # union: [100,350] + [360,400] + [600,700] + [705,900] us
    assert r.busy_s == pytest.approx((250 + 40 + 100 + 195) * 1e-6)
    assert r.idle_pct() == pytest.approx(100 * (1 - 585 / 950))


def test_programs_and_launches(one_chip):
    r = one_chip
    assert r.launches() == 2
    assert r.program_seconds(["jit_build"]) == pytest.approx(300e-6)
    assert r.program_seconds(["jit_build", "jit_run"]) == pytest.approx(
        600e-6)
    assert r.program_seconds(["jit_other"]) is None


def test_breakdown(one_chip):
    b = one_chip.breakdown()
    ops = dict(b["device_ops"])
    # The loop's body op lies inside the loop: only outermost ops count.
    assert ops == pytest.approx({"jit_build/%while.1": 250e-6,
                                 "jit_build/%copy.3": 40e-6,
                                 "jit_run/%fusion.4": 100e-6,
                                 "jit_run/%fusion.5": 195e-6})
    gaps = dict(b["idle_gaps"])
    # [50,100], [350,360] and [900,1000] under the engine call; [400,600]
    # in numpy; the 5 us between two fusions is between operations.
    assert gaps == pytest.approx({"$engine.py:1 simulate": 160e-6,
                                  "$numpy asarray": 200e-6,
                                  tr.SHORT_GAP_NAME: 5e-6})
    assert sum(gaps.values()) == pytest.approx(950e-6 - 585e-6)


def test_chips_are_averaged():
    r = tr.Reading(HOST + _device(0) + _device(1)[:2], n_devices=2)
    # chip 1 has modules only: its busy time is their union (600 us)
    assert r.busy_s == pytest.approx((585 + 600) / 2 * 1e-6)
    assert r.launches() == 2
    with pytest.raises(ValueError):
        tr.Reading(HOST + _device(0), n_devices=2)


def test_no_unit_span_is_an_error():
    with pytest.raises(ValueError):
        tr.Reading(_device(0), n_devices=1)


def test_peaks_table():
    assert tr.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        tr.peaks_for("TPU v9 imaginary")


def test_save_and_read(tmp_path):
    events = HOST + _device(0)
    tr.save_events(events, tmp_path / "t.json")
    assert tr.read_events(tmp_path / "t.json") == events


RECORDED = sorted(HERE.glob("trace_*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    """A slice of a real v5e trace: busy time and idle share against a
    nanosecond timeline; launches and program time against the module
    events themselves."""
    events = tr.read_events(path)
    r = tr.Reading(events, n_devices=1)
    t0, t1 = r.t0, r.t1
    ops = [e for e in events if e.line == tr.OPS_LINE]
    assert ops
    line = np.zeros(t1 - t0, bool)
    for e in ops:
        line[max(e.start_ns, t0) - t0:min(e.start_ns + e.dur_ns, t1) - t0] = 1
    assert r.busy_s == pytest.approx(line.sum() / 1e9, rel=1e-12)
    assert r.idle_pct() == pytest.approx(100 * (1 - line.mean()), abs=1e-9)
    mods = [e for e in events if e.line == tr.MODULES_LINE]
    assert r.launches() == len(mods)
    names = {tr.program_name(e.name) for e in mods}
    assert r.program_seconds(names) == pytest.approx(
        sum(e.dur_ns for e in mods) / 1e9)
    b = r.breakdown()
    assert sum(v for _, v in b["idle_gaps"]) <= r.window_s - r.busy_s + 1e-12
