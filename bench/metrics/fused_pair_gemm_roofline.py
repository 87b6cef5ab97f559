"""fused_pair_gemm_roofline: the fused Galerkin pair-GEMM kernel's share
(%) of its bandwidth roofline over the traced window.

Calls are matched to the recompute's products by their output dims
``(br, bc, slots)``; bytes per call are ``roofline.product_bytes``: both
operands' stored blocks read and the product's blocks written once."""
from roofline import kernel_share, product_bytes

KERNEL = "fused_pair_gemm_lanes"


def read(ctx):
    if ctx.ops is None:
        return None

    def bytes_of(dims):
        br, bc, slots = dims
        for lv in ctx.levels:
            for p in lv.products:
                if p.c_block == (br, bc) and p.slots == slots:
                    return product_bytes(p, ctx.itemsize)
        return None

    return kernel_share(ctx.ops, ctx.dims, KERNEL, bytes_of,
                        ctx.peaks["hbm_bytes_per_s"])
