"""Host time per request of the structure key and the plan LRU lookup in
the served refill, in ms: the indices read back from the device and
made bytes for the key, then the lookup (a planning nested in it is
left out)."""

from bench import spantime

SPANS = ("sparse.plan_key", "sparse.plan_cache")


def read(ctx):
    return spantime.self_ms(ctx, SPANS)
