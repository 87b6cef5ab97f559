"""solve_s: window time over the solves completed in it, host clock."""


def read(ctx):
    if ctx.unit != "solve" or not ctx.units:
        return None
    return ctx.window_s / ctx.units
