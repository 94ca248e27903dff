"""Host time per request of the host to device copies of the request's
rows, columns and values in the served refill, with the zero-offset and
float32 casts made between them, in ms."""

from bench import spantime

SPANS = ("sparse.upload",)


def read(ctx):
    return spantime.self_ms(ctx, SPANS)
