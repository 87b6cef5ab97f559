"""step_s: window time over the hot steps (update, assemble, recompute,
solve) completed in it, host clock."""


def read(ctx):
    if ctx.unit != "step" or not ctx.units:
        return None
    return ctx.window_s / ctx.units
