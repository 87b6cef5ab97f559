"""device_assembly_s: device seconds per hot step of the ops under the
``recompute/assemble`` stage scope: the element blocks and their scatter
into the fine operator's values."""
import scopes


def read(ctx):
    t = scopes.for_run(ctx)
    sec = None if t is None else scopes.recompute_seconds(
        t, "recompute/assemble")
    return None if sec is None else sec / ctx.units
