"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    lo, hi = ctx.window()
    return 1.0 - ctx.busy() / (hi - lo)
