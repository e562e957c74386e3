"""Device idle time put down to the program's own spans.

The program marks its layers with ``jax.profiler.TraceAnnotation`` spans
whose names start with ``repro.`` (docs/simulation.md "Tracing").  They
lie on the Python thread's line of the host plane, beside the benchmark's
``bench.unit`` spans and on the devices' clock, so a traced window's
:class:`~bench.trace_reduce.Reading` holds them among its host events.

Each idle gap of a chip in the window (``Chip.gaps``) is cut where a
program span starts or ends, and each piece is put down to the innermost
program span that covers it, or to :data:`NO_SPAN` where none does: one
gap often runs from a result's copy-back through the host's work to the
next launch.  Gaps shorter than ``trace_reduce.SHORT_GAP_NS`` lie between
the operations of a program and are left out, as in the reading's
breakdown.  A window with no program span at all, as from a program
without them, reads None.
"""
from __future__ import annotations

import bisect
import collections

from bench import trace_reduce as tr

#: the prefix of every program span's name.
PREFIX = "repro."
#: where idle time outside every program span goes.
NO_SPAN = "(no span)"


def program_spans(reading: tr.Reading) -> list:
    """The program spans among the reading's host events that overlap
    its window, by start."""
    return [e for e in reading.host if e.name.startswith(PREFIX)
            and e.start_ns < reading.t1
            and e.start_ns + e.dur_ns > reading.t0]


def innermost(spans: list, starts: list, t: int) -> str:
    """Name of the shortest of ``spans`` (sorted by start, ``starts``
    their starts) that covers time ``t``, or :data:`NO_SPAN`."""
    best = None
    for e in spans[:bisect.bisect_right(starts, t)]:
        if e.start_ns + e.dur_ns >= t and (best is None
                                           or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else NO_SPAN


def idle_by_span(reading: tr.Reading) -> dict | None:
    """Idle seconds of the window by innermost program span, averaged over
    the chips; None where no program span lies in the window."""
    spans = program_spans(reading)
    if not spans:
        return None
    starts = [e.start_ns for e in spans]
    edges = sorted({t for e in spans for t in (e.start_ns,
                                                e.start_ns + e.dur_ns)})
    out: dict = collections.defaultdict(float)
    for c in reading.chips:
        for s, e in c.gaps:
            if e - s < tr.SHORT_GAP_NS:
                continue
            cuts = edges[bisect.bisect_right(edges, s):
                         bisect.bisect_left(edges, e)]
            for a, b in zip([s] + cuts, cuts + [e]):
                name = innermost(spans, starts, (a + b) // 2)
                out[name] += (b - a) / 1e9 / len(reading.chips)
    return dict(out)


def idle_per_unit(ctx: tr.Context, name: str) -> float | None:
    """Idle seconds per unit under the program span ``name``; None where
    that span does not occur in the window."""
    if not ctx.units or not any(e.name == name
                                for e in program_spans(ctx.reading)):
        return None
    return idle_by_span(ctx.reading).get(name, 0.0) / ctx.units
