"""Device time per request of the symbolic phase (the jitted ``plan``
program: radix digit passes and Parts 3-4), in ms."""

MODULES = ("jit_plan",)


def read(ctx):
    reqs = ctx.requests()
    t = ctx.op_time(modules=MODULES)
    if not reqs or t == 0:
        return None
    return t / len(reqs) / 1e6
