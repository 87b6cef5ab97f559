"""vcycle_s: device seconds per solve of the solve program's ops under
the ``pcg/precond`` stage scope: the V-cycle with its precision casts.
With ``outer_cg_s`` it partitions the solve program's device time."""
import scopes


def read(ctx):
    t = scopes.for_run(ctx)
    split = None if t is None else scopes.solve_split(t)
    return None if split is None else split[1] / ctx.units
