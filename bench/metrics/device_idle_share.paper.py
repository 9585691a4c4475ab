"""Share of the traced window in which no operation ran on the device
(layer: device), paper cells: 100 * (1 - busy / window)."""


def read(ctx):
    return ctx.trace.idle_share()
