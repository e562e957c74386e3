"""Program executions on a chip per sweep, from the trace's XLA Modules
line (moves sweep_s)."""


def read(ctx):
    return ctx.launches_per_unit()
