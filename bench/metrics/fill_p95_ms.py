"""95th percentile of all warm refill latencies in the window, from
submission to the result being ready on the device (host clock)."""

import statistics


def read(ctx):
    if len(ctx.latencies) < 20:
        return None
    return statistics.quantiles(ctx.latencies, n=20)[-1] * 1e3
