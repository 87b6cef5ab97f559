"""fused_smoother_roofline: the fused smoother kernel's share (%) of its
bandwidth roofline over the traced window.

Calls are matched to levels by the output dims ``(bs, k, rows)`` in
their HLO text, where rows is the level's block rows padded to at least
one 128-lane tile; bytes per call are ``roofline.smoother_step_bytes``."""
from roofline import kernel_share, smoother_step_bytes

KERNEL = "_smoother_step_ell"


def read(ctx):
    if ctx.ops is None:
        return None

    def bytes_of(dims):
        bs, k, rows = dims
        for lv in ctx.levels:
            if lv.bs == bs and max(lv.nbr, 128) == rows:
                return smoother_step_bytes(lv, ctx.itemsize, k)
        return None

    return kernel_share(ctx.ops, ctx.dims, KERNEL, bytes_of,
                        ctx.peaks["hbm_bytes_per_s"])
