"""gamg_setup_s: host clock around ``GAMGSolver`` construction (GAMG
setup and the first hierarchy recompute), blocked until the hierarchy
is on the device."""


def read(ctx):
    d = ctx.spans.durations("gamg_setup")
    return sum(d) if d else None
