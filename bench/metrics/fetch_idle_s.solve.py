"""Device idle seconds per solve under the program's result copy-back:
the ``repro.dispatch.fetch`` spans of ``sim/dispatch.run`` (each engine
call's and the closed forms' transfer to the host and reassembly),
averaged over the chips; None where the program has no such span (moves
solve_s)."""
from bench import spans

SPAN = "repro.dispatch.fetch"


def read(ctx):
    return spans.idle_per_unit(ctx, SPAN)
