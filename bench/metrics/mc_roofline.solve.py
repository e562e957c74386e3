"""Share of the Monte-Carlo programs' roofline per solve, per cent: the
least time to move the unit's bytes (bench/work.py: a gap per failure plus
one, and the output fields, per trajectory) at the chips' HBM bandwidth,
over the device seconds of the programs mc_device_s.solve counts (moves
solve_s)."""

#: program names of the Monte-Carlo work in the trace.
PROGRAMS = ("jit_run_cands",)


def read(ctx):
    return ctx.roofline_pct(PROGRAMS)
