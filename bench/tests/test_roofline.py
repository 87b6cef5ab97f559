"""Byte counts of the roofline functions against a small setup."""
import numpy as np
import pytest

import roofline
from roofline import Level, Product

def test_byte_functions_count_blocks_indices_and_vectors():
    lv = Level(nbr=10, bs=3, nnzb=40)
    assert roofline.smoother_step_bytes(lv, 4) == (
        40 * (9 * 4 + 4) + 10 * 9 * 4 + 5 * 10 * 3 * 4)
    assert roofline.spmm_bytes(lv, 4, 8) == 40 * 40 + 2 * 10 * 3 * 4 * 8
    p = Product(40, (3, 3), 12, (3, 6), 20, (3, 6), 128)
    assert roofline.product_bytes(p, 4) == (
        40 * 40 + 12 * (18 * 4 + 4) + 20 * (18 * 4 + 4))


def test_kernel_share():
    dims = {"p/k.1": (3, 1, 10), "p/k.2": (3, 1, 20), "p/other": (1,)}
    ops = {"p/k.1": (2.0, 4), "p/k.2": (1.0, 1), "p/other": (5.0, 9)}
    nbytes = {10: 100.0, 20: 300.0}
    share = roofline.kernel_share(ops, dims, "k.",
                                  lambda d: nbytes[d[2]], 100.0)
    assert share == pytest.approx(100 * (4 * 100 + 300) / 100.0 / 3.0)
    # a call that cannot be matched to its work gives no share at all
    assert roofline.kernel_share(ops, dims, "k.",
                                 lambda d: None, 1.0) is None
    assert roofline.kernel_share({}, dims, "k.", lambda d: 1, 1.0) is None


def test_levels_of_small_setup_match_nnzb_and_block_sizes():
    """The harness's Level numbers are the setup's own: stored blocks and
    block sizes of every operator and product of the chain."""
    from problem import levels_of
    from repro.core import gamg
    from repro.fem.assemble import assemble_elasticity
    prob = assemble_elasticity(6, path="host")
    sd = gamg.setup(prob.A, prob.B, coarse_size=12, coarsener="greedy",
                    precision="f32")
    levels = levels_of(sd)
    assert [lv.nnzb for lv in levels] == sd.stats["level_nnzb"]
    assert [lv.nbr * lv.bs for lv in levels] == sd.stats["level_rows"]
    assert [lv.bs for lv in levels] == sd.stats["level_bs"]
    for lv, ls, nxt in zip(levels, sd.levels, levels[1:]):
        ap, ac = lv.products
        assert ap.x_nnzb == lv.nnzb and ap.y_nnzb == ls.P.nnzb
        assert ap.c_block == (lv.bs, nxt.bs) == ap.y_block
        assert ac.x_block == (nxt.bs, lv.bs) and ac.c_block == (nxt.bs,) * 2
        assert ac.c_nnzb == nxt.nnzb and ac.y_nnzb == ap.c_nnzb
        # the A P product's stored blocks, counted from the operands
        Ad = np.zeros((lv.nbr, lv.nbr), bool)
        rows = np.repeat(np.arange(lv.nbr), np.diff(ls.A0.indptr))
        Ad[rows, ls.A0.indices] = True
        Pd = np.zeros((lv.nbr, nxt.nbr), bool)
        prow = np.repeat(np.arange(lv.nbr), np.diff(ls.P.indptr))
        Pd[prow, ls.P.indices] = True
        assert ap.c_nnzb == int(((Ad.astype(int) @ Pd.astype(int)) > 0).sum())
        assert roofline.smoother_step_bytes(lv, 4) == (
            lv.nnzb * (lv.bs ** 2 * 4 + 4) + lv.nbr * lv.bs ** 2 * 4
            + 5 * lv.nbr * lv.bs * 4)
