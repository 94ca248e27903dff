"""Host time per request of the served refill, in ms: each
``bench.request`` span minus the part of it in which the device ran any
operation (front end: validation, key hashing, LRU lookup, host to
device copies, dispatch)."""


def read(ctx):
    reqs = ctx.requests()
    if not reqs:
        return None
    host = sum(r.dur - ctx.busy(r.start, r.end) for r in reqs)
    return host / len(reqs) / 1e6
