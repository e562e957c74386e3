"""Device seconds per sweep of the Monte-Carlo programs: the sampled
bucket dispatches of ``sim/engine`` (sampler and event scan in one
program, ``jit_build``); averaged over the chips (moves sweep_s)."""

#: program names of the Monte-Carlo work in the trace.
PROGRAMS = ("jit_build",)


def read(ctx):
    return ctx.seconds_per_unit(PROGRAMS)
