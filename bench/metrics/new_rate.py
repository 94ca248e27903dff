"""Triplets of all one-shot assemblies of new structures completed in
the window, in millions, over the window's seconds (host clock; the
window ends on a request boundary)."""


def read(ctx):
    return len(ctx.latencies) * ctx.triplets / ctx.window_s / 1e6
