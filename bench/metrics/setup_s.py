"""Set-up seconds: generating the inputs, planning, compiling and
warming every program the window uses (host clock)."""


def read(ctx):
    return ctx.setup_s
