"""Program executions on a chip per solve, from the trace's XLA Modules
line (moves solve_s)."""


def read(ctx):
    return ctx.launches_per_unit()
