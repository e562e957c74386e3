"""Device idle seconds per solve under the solver's schedule draw: the
``repro.robust.schedule`` span of ``sim/sweep.evaluate_robustness_grid``
(the host-sampled common-random-number schedule and its transfer to the
device), averaged over the chips; None where the program has no such
span (moves solve_s)."""
from bench import spans

SPAN = "repro.robust.schedule"


def read(ctx):
    return spans.idle_per_unit(ctx, SPAN)
