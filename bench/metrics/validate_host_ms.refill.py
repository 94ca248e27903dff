"""Host time per request of the index validation and casts in the served
refill, in ms: integral, in range, then int32 (``coo_from_matlab``
before its device copies)."""

from bench import spantime

SPANS = ("sparse.validate",)


def read(ctx):
    return spantime.self_ms(ctx, SPANS)
