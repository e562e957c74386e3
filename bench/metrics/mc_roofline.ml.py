"""Share of the two-level Monte-Carlo programs' roofline per sweep, per
cent: the least time to move the unit's bytes (bench/traffic/mc_sweep_ml.py:
a gap and a flag per failure plus one, and the output fields, per
trajectory) at the chips' HBM bandwidth, over the device seconds of the
programs mc_device_s.ml counts (moves sweep_s)."""

#: program names of the two-level Monte-Carlo work in the trace.
PROGRAMS = ("jit_build_ml",)


def read(ctx):
    return ctx.roofline_pct(PROGRAMS)
