"""Share of the Monte-Carlo programs' roofline per sweep, per cent: the
least time to move the unit's bytes (bench/work.py: a gap per failure plus
one, and the output fields, per trajectory) at the chips' HBM bandwidth,
over the device seconds of the programs mc_device_s.sweep counts (moves
sweep_s)."""

#: program names of the Monte-Carlo work in the trace.
PROGRAMS = ("jit_build",)


def read(ctx):
    return ctx.roofline_pct(PROGRAMS)
