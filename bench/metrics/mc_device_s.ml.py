"""Device seconds per sweep of the two-level Monte-Carlo programs: the
sampled bucket dispatches of ``sim/engine.simulate_trajectories_ml``
(gap and flag sampler and the two-level event scan in one program,
``jit_build_ml``); averaged over the chips; None where the program has no
such program (moves sweep_s)."""

#: program names of the two-level Monte-Carlo work in the trace.
PROGRAMS = ("jit_build_ml",)


def read(ctx):
    return ctx.seconds_per_unit(PROGRAMS)
