"""Reduction of a JAX profiler trace to device metrics.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  A
TPU trace holds, per chip, a plane ``/device:TPU:<i>`` whose line
``XLA Modules`` has one event per program execution (``jit_<fn>(<id>)``)
and whose line ``XLA Ops`` has one event per operation executed, loop
bodies included; the host plane ``/host:CPU`` has a line per thread: the
Python thread's (named after the executable) holds the benchmark's
``bench.unit`` spans and the Python tracer's function events.  Device and
host events share one clock.

The window is the span from the start of the first ``bench.unit`` to the
end of the last.  Within it, per chip:

* busy: the union of the intervals of its ``XLA Ops`` events (of its
  ``XLA Modules`` events where it has no ops line);
* programs: executions and device seconds per program name;
* idle gaps: the stretches of the window in which it runs nothing, each
  named by the innermost event of the thread of the ``bench.unit`` spans
  that covers the gap's middle.

Chip figures are averaged over the chips used.  ``bench/metrics/*.py``
read a :class:`Reading` through a :class:`Context`.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import re
from pathlib import Path

from bench import work

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the Python thread's line where a trace is cut by hand (tests).
HOST_LINE = "python3"
UNIT_SPAN = "bench.unit"
#: idle stretches shorter than this lie between operations of a program;
#: they are summed under one name rather than attributed to the host.
SHORT_GAP_NS = 10_000
SHORT_GAP_NAME = "between operations"

#: one trace event: plane name, line name, event name, start, duration (ns).
Event = collections.namedtuple("Event", "plane line name start_ns dur_ns")

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; a kind missing from
    ``bench/peaks.json`` is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: add it with its source")
    return table[device_kind]


def load_events(trace_dir) -> list[Event]:
    """The events a :class:`Reading` needs, from the newest ``.xplane.pb``
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 int(e.start_ns), int(e.duration_ns)))
    return out


def save_events(events: list[Event], path) -> None:
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def read_events(path) -> list[Event]:
    with open(path) as f:
        return [Event(*e) for e in json.load(f)]


def program_name(module_event_name: str) -> str:
    """``jit_build(812706542843602520)`` -> ``jit_build``."""
    return module_event_name.split("(", 1)[0]


def op_name(op_event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return op_event_name.split(" = ", 1)[0]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


@dataclasses.dataclass
class Chip:
    busy: list            # disjoint busy intervals in the window
    programs: dict        # program name -> [executions, device seconds]
    top_ops: dict         # "program/op" -> seconds of outermost ops
    gaps: list            # idle (start, end) in the window


class Reading:
    """Device figures of one traced window over ``n_devices`` chips."""

    def __init__(self, events: list[Event], n_devices: int):
        spans = [e for e in events if e.plane == HOST_PLANE
                 and e.name == UNIT_SPAN]
        if not spans:
            raise ValueError(f"no {UNIT_SPAN!r} span in the trace")
        host_lines = {e.line for e in spans}
        self.t0 = min(e.start_ns for e in spans)
        self.t1 = max(e.start_ns + e.dur_ns for e in spans)
        self.units = len(spans)
        by_plane = collections.defaultdict(list)
        for e in events:
            if DEVICE_PLANE.match(e.plane):
                by_plane[e.plane].append(e)
        planes = sorted(by_plane, key=lambda p: int(
            DEVICE_PLANE.match(p).group(1)))[:n_devices]
        if len(planes) < n_devices:
            raise ValueError(f"{len(planes)} device planes in the trace, "
                             f"{n_devices} chips used")
        self.host = sorted((e for e in events if e.plane == HOST_PLANE
                            and e.line in host_lines),
                           key=lambda e: e.start_ns)
        self._host_starts = [e.start_ns for e in self.host]
        self.chips = [self._chip(by_plane[p]) for p in planes]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(sum(e - s for s, e in c.busy)
                   for c in self.chips) / len(self.chips) / 1e9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_seconds(self, names) -> float | None:
        """Device seconds of the named programs, averaged over the chips;
        None where none of them ran."""
        found = [c.programs[n][1] for c in self.chips for n in names
                 if n in c.programs]
        return sum(found) / len(self.chips) if found else None

    def launches(self) -> float:
        """Program executions, averaged over the chips."""
        return sum(n for c in self.chips
                   for n, _ in c.programs.values()) / len(self.chips)

    def _chip(self, events: list[Event]) -> Chip:
        t0, t1 = self.t0, self.t1
        inside = [e for e in events
                  if e.start_ns < t1 and e.start_ns + e.dur_ns > t0]
        modules = sorted((e for e in inside if e.line == MODULES_LINE),
                         key=lambda e: e.start_ns)
        ops = sorted((e for e in inside if e.line == OPS_LINE),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        programs: dict = {}
        for m in modules:
            rec = programs.setdefault(program_name(m.name), [0, 0.0])
            rec[0] += 1
            rec[1] += m.dur_ns / 1e9
        source = ops if ops else modules
        busy = union(clip([(e.start_ns, e.start_ns + e.dur_ns)
                           for e in source], t0, t1))

        # Outermost ops (a loop's body ops lie inside the loop's event),
        # each under the program whose execution contains it.
        starts = [m.start_ns for m in modules]
        top_ops: dict = collections.defaultdict(float)
        end = None
        for e in ops:
            if end is not None and e.start_ns < end:
                continue
            end = e.start_ns + e.dur_ns
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = program_name(modules[i].name) if i >= 0 else "?"
            top_ops[f"{prog}/{op_name(e.name)}"] += e.dur_ns / 1e9

        gaps, cursor = [], t0
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < t1:
            gaps.append((cursor, t1))
        return Chip(busy=busy, programs=programs, top_ops=dict(top_ops),
                    gaps=gaps)

    def host_activity(self, t: int) -> str:
        """Name of the innermost Python-thread event covering time ``t``."""
        best = None
        for e in self.host[:bisect.bisect_right(self._host_starts, t)]:
            if e.start_ns + e.dur_ns >= t and (best is None
                                               or e.dur_ns < best.dur_ns):
                best = e
        return best.name if best is not None else "no host event"

    def idle_by_activity(self) -> dict:
        """Idle seconds by what the host was doing, averaged over chips."""
        out: dict = collections.defaultdict(float)
        for c in self.chips:
            for s, e in c.gaps:
                name = (SHORT_GAP_NAME if e - s < SHORT_GAP_NS
                        else self.host_activity((s + e) // 2))
                out[name] += (e - s) / 1e9 / len(self.chips)
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        ops: dict = collections.defaultdict(float)
        for c in self.chips:
            for k, v in c.top_ops.items():
                ops[k] += v / len(self.chips)
        rank = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(ops),
                "idle_gaps": rank(self.idle_by_activity())}

    def summary(self) -> dict:
        progs: dict = collections.defaultdict(lambda: [0.0, 0.0])
        for c in self.chips:
            for k, (n, s) in c.programs.items():
                progs[k][0] += n / len(self.chips)
                progs[k][1] += s / len(self.chips)
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "units": self.units, "programs": dict(progs)}


def read_profile(trace_dir, n_devices: int) -> Reading:
    return Reading(load_events(trace_dir), n_devices)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader is given."""

    reading: Reading
    units: int
    chips: int
    bytes_per_unit: float | None
    peaks: dict

    def seconds_per_unit(self, programs) -> float | None:
        """Device seconds of the named programs per unit, averaged over the
        chips; None where none of them ran."""
        s = self.reading.program_seconds(programs)
        return s / self.units if s and self.units else None

    def launches_per_unit(self) -> float | None:
        return self.reading.launches() / self.units if self.units else None

    def roofline_pct(self, programs) -> float | None:
        """Least time to move a unit's bytes at the chips' HBM bandwidth,
        as a share of the named programs' device seconds per unit, per
        cent; None without bytes or device time."""
        s = self.seconds_per_unit(programs)
        if s is None or self.bytes_per_unit is None:
            return None
        return work.roofline_pct(self.bytes_per_unit, s,
                                 self.peaks["hbm_bytes_per_s"], self.chips)
