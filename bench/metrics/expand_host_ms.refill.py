"""Host time per request of the index expansion in the served refill,
in ms: the request's indices and values made float64 vectors
(``expand_indices``)."""

from bench import spantime

SPANS = ("sparse.expand",)


def read(ctx):
    return spantime.self_ms(ctx, SPANS)
