"""Device seconds per solve of the Monte-Carlo programs: the
candidate-axis engine calls of ``sim/engine`` (``jit_run_cands``);
averaged over the chips (moves solve_s)."""

#: program names of the Monte-Carlo work in the trace.
PROGRAMS = ("jit_run_cands",)


def read(ctx):
    return ctx.seconds_per_unit(PROGRAMS)
