"""Share of the bandwidth roof that the served fill reaches, in %: the
fill's computed bytes (``kernel_bytes.fill_bytes``, a lower bound) for
every request, over the fill's device time, over the chip's HBM
bandwidth."""

from bench import kernel_bytes

MODULES = ("jit_scatter",)


def read(ctx):
    reqs = ctx.requests()
    t = ctx.op_time(modules=MODULES)
    if not reqs or t == 0:
        return None
    L = int(ctx.cfg["L"])
    moved = len(reqs) * kernel_bytes.fill_bytes(L, L)
    return 100.0 * moved / (t / 1e9) / ctx.peak["hbm_bytes_s"]
