"""cg_iters: outer CG iterations per solve in the window, as the solver
reports them, averaged."""


def read(ctx):
    its = ctx.counters.get("cg_iters")
    return sum(its) / len(its) if its else None
