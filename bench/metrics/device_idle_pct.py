"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 100 * (1 - busy / window)."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
