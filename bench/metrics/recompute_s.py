"""recompute_s: host clock around ``update_coefficients`` (device
assembly, PtAP chain, coarse Cholesky) in the window, blocked until the
hierarchy is on the device, averaged over steps."""


def read(ctx):
    d = ctx.window_spans("recompute")
    return sum(d) / len(d) if d else None
