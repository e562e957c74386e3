"""Device idle seconds per sweep under the program's result copy-back:
the ``repro.dispatch.fetch`` spans of ``sim/dispatch.run`` (each chunk's
transfer to the host and its reassembly), averaged over the chips; None
where the program has no such span (moves sweep_s)."""
from bench import spans

SPAN = "repro.dispatch.fetch"


def read(ctx):
    return spans.idle_per_unit(ctx, SPAN)
