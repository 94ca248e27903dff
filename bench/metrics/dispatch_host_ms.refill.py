"""Host time per request of the executable-tier lookup and the dispatch
of the fill executable in the served refill, in ms (a compile nested
in the lookup is left out)."""

from bench import spantime

SPANS = ("sparse.exec_cache", "sparse.fill")


def read(ctx):
    return spantime.self_ms(ctx, SPANS)
