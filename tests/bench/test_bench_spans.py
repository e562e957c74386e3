"""Device idle time put down to the program's spans (``bench/spans.py``)
and the metrics that read it: on a hand-built trace whose figures are
worked out below, on the slices of v5e traces recorded before the
program had spans, and on slices recorded after."""
from pathlib import Path

import pytest

from bench import run as harness
from bench import spans
from bench import trace_reduce as tr

HERE = Path(__file__).resolve().parent
US = 1000  # ns
#: the span metrics and the span each reads.
READERS = {"fetch_idle_s.sweep": "repro.dispatch.fetch",
           "fetch_idle_s.solve": "repro.dispatch.fetch",
           "schedule_idle_s.solve": "repro.robust.schedule"}


def _ev(plane, line, name, start_us, end_us):
    return tr.Event(plane, line, name, start_us * US, (end_us - start_us) * US)


def _host(name, start_us, end_us):
    return _ev(tr.HOST_PLANE, tr.HOST_LINE, name, start_us, end_us)


def _device(i, busy):
    d = f"/device:TPU:{i}"
    return ([_ev(d, tr.MODULES_LINE, "jit_run(7)", 100, 1900)]
            + [_ev(d, tr.OPS_LINE, f"%fusion.{j}", s, e)
               for j, (s, e) in enumerate(busy)])


#: busy stretches of chip 0; its gaps in the window [50, 2000] us are
#: [50,100] [350,360] [400,600] [700,705] [900,1100] [1500,1600]
#: [1900,2000].
BUSY = [(100, 350), (360, 400), (600, 700), (705, 900), (1100, 1500),
        (1600, 1900)]

HOST = [
    _host(tr.UNIT_SPAN, 50, 1000), _host(tr.UNIT_SPAN, 1000, 2000),
    _host("$sweep.py:1 solve", 50, 1990),           # not a program span
    _host("repro.robust.solve", 50, 1945),
    _host("repro.robust.schedule", 50, 100),
    _host("repro.mc.candidates", 120, 950),
    _host("repro.dispatch.launch", 120, 130),
    _host("repro.dispatch.fetch", 130, 620),
    _host("repro.dispatch.launch", 620, 640),
    _host("repro.dispatch.fetch", 640, 940),
    _host("repro.mc.candidates", 1100, 1940),
    _host("repro.dispatch.launch", 1100, 1110),
    _host("repro.dispatch.fetch", 1110, 1560),
    # on another thread: not on the line of the unit spans
    _ev(tr.HOST_PLANE, "main/1", "repro.dispatch.fetch", 880, 1200),
]


@pytest.fixture
def one_chip():
    return tr.Reading(HOST + _device(0, BUSY), n_devices=1)


def _context(reading, units=2):
    return tr.Context(reading=reading, units=units, chips=1,
                      bytes_per_unit=None, peaks={})


def test_idle_by_innermost_span(one_chip):
    got = spans.idle_by_span(one_chip)
    # [50,100] under the schedule draw; [350,360] (not shorter than
    # SHORT_GAP_NS) and [400,600] under a fetch inside a candidate call;
    # [900,1100] cut at the ends of a fetch (940) and of its call (950),
    # the rest under the solve alone; [1500,1600] cut at the end of a
    # fetch (1560); [1900,2000] cut at the end of the call (1940) and of
    # the solve (1945), then under no span; [700,705] lies between
    # operations.
    assert got == pytest.approx({"repro.robust.schedule": 50e-6,
                                 "repro.dispatch.fetch": 310e-6,
                                 "repro.mc.candidates": 90e-6,
                                 "repro.robust.solve": 155e-6,
                                 spans.NO_SPAN: 55e-6})
    idle = one_chip.window_s - one_chip.busy_s
    assert sum(got.values()) == pytest.approx(idle - 5e-6)


def test_chips_are_averaged():
    r = tr.Reading(HOST + _device(0, BUSY) + _device(1, [(50, 2000)]),
                   n_devices=2)
    got = spans.idle_by_span(r)
    assert got["repro.dispatch.fetch"] == pytest.approx(155e-6)
    assert got[spans.NO_SPAN] == pytest.approx(27.5e-6)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers(one_chip, name):
    reader = harness.load_module(HERE.parents[1] / "bench/metrics"
                                 / f"{name}.py")
    assert reader.SPAN == READERS[name]
    want = {"repro.dispatch.fetch": 155e-6, "repro.robust.schedule": 25e-6}
    assert reader.read(_context(one_chip)) == pytest.approx(want[reader.SPAN])


def test_span_without_idle_reads_zero_and_absent_span_none(one_chip):
    ctx = _context(one_chip)
    assert spans.idle_per_unit(ctx, "repro.dispatch.launch") == 0.0
    assert spans.idle_per_unit(ctx, "repro.mc.trajectories") is None
    assert spans.idle_per_unit(_context(one_chip, units=0),
                               "repro.dispatch.fetch") is None


#: every span the program has (docs/simulation.md "Tracing").
PROGRAM_SPANS = {"repro.robust.solve", "repro.robust.closed_forms",
                 "repro.robust.schedule", "repro.mc.trajectories",
                 "repro.mc.candidates", "repro.dispatch.launch",
                 "repro.dispatch.fetch"}
BEFORE = sorted(p for p in HERE.glob("trace_*.json")
                if not p.stem.endswith("_spans"))
AFTER = sorted(HERE.glob("trace_*_spans.json"))


@pytest.mark.parametrize("path", BEFORE, ids=lambda p: p.stem)
def test_program_without_spans_reads_none(path):
    """A program without spans (these slices predate them) gives no
    reading, never 0."""
    r = tr.Reading(tr.read_events(path), n_devices=1)
    assert spans.idle_by_span(r) is None
    for name in READERS:
        reader = harness.load_module(HERE.parents[1] / "bench/metrics"
                                     / f"{name}.py")
        assert reader.read(_context(r, units=1)) is None


@pytest.mark.parametrize("path", AFTER, ids=lambda p: p.stem)
def test_recorded_chip_trace_spans(path):
    """A slice of a v5e trace of the program with its spans, from the end
    of one program to the start of the next: the one long gap between
    them is cut into the copy-back, the host's work and the next launch,
    and the pieces account for every gap not shorter than SHORT_GAP_NS."""
    r = tr.Reading(tr.read_events(path), n_devices=1)
    got = spans.idle_by_span(r)
    assert got is not None
    assert set(got) <= PROGRAM_SPANS | {spans.NO_SPAN}
    assert {"repro.dispatch.fetch", "repro.dispatch.launch"} <= set(got)
    long_gaps = sum(e - s for s, e in r.chips[0].gaps
                    if e - s >= tr.SHORT_GAP_NS) / 1e9
    assert sum(got.values()) == pytest.approx(long_gaps, rel=1e-12)
