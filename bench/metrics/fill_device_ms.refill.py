"""Device time per request of the served fill executable, in ms."""

#: the XLA module that ``PlanService`` compiles from
#: ``SparsePattern.scatter``, as the trace names it
MODULES = ("jit_scatter",)


def read(ctx):
    reqs = ctx.requests()
    t = ctx.op_time(modules=MODULES)
    if not reqs or t == 0:
        return None
    return t / len(reqs) / 1e6
