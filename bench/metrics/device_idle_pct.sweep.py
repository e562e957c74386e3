"""Share of the traced window in which no operation ran on the chips, per
cent (device layer; moves sweep_s)."""


def read(ctx):
    return ctx.reading.idle_pct()
