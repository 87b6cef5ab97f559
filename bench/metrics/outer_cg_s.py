"""outer_cg_s: device seconds per solve of the solve program's ops that
are not under the ``pcg/precond`` stage scope: the outer Krylov iteration
(the fine-operator SpMV under ``pcg/apply_a``, dots, updates, the f64
emulation's splits), read from the trace's op metadata
(``scopes.solve_split``)."""
import scopes


def read(ctx):
    t = scopes.for_run(ctx)
    split = None if t is None else scopes.solve_split(t)
    return None if split is None else split[0] / ctx.units
