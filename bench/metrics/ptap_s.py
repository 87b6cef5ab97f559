"""ptap_s: device seconds per hot step of the ops under the
``recompute/level*/ptap`` stage scopes: the Galerkin products of every
level, read from the trace's op metadata."""
import scopes


def read(ctx):
    t = scopes.for_run(ctx)
    sec = None if t is None else scopes.recompute_seconds(
        t, "recompute/level*/ptap")
    return None if sec is None else sec / ctx.units
