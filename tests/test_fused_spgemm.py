"""Fused tiled SpGEMM path vs the einsum+segment_sum oracle.

Property coverage per the deliverables: rectangular block mixes
(3x3 @ 3x6, 6x3 @ 3x3, 6x6 @ 6x6), empty block rows, padded tile edges
(tile_slots sweeps), and the structural guarantee the fusion exists for —
no ``(npairs, br, bc)`` pair-product intermediate anywhere in the jaxpr.
All Pallas execution is interpret-mode (CPU CI).
"""
import ml_dtypes
import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on)
import jax
import jax.numpy as jnp

from repro.core.block_csr import BlockCSR
from repro.core.spgemm import spgemm_symbolic, spgemm_numeric_data
from repro.kernels.fused_pair_gemm.fused_pair_gemm import fused_pair_gemm
from repro.kernels.fused_pair_gemm.ref import fused_pair_gemm_ref

from helpers import jaxpr_outputs, random_bcsr

RNG = np.random.default_rng(11)


def _tol(dtype):
    if dtype == np.float64:
        return dict(rtol=1e-12, atol=1e-12)
    if dtype == ml_dtypes.bfloat16:
        return dict(rtol=5e-2, atol=5e-2)
    return dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Kernel-level: fused contract+reduce vs pure-jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,accum",
                         [(np.float64, None), (np.float32, None),
                          (ml_dtypes.bfloat16, np.float32)],
                         ids=["f64", "f32", "bf16"])
@pytest.mark.parametrize("nslots,kmax,br,bk,bc",
                         [(1, 1, 3, 3, 3), (7, 3, 3, 3, 6), (33, 5, 6, 3, 6),
                          (64, 2, 6, 6, 6), (9, 4, 1, 1, 1), (20, 7, 2, 4, 5)])
def test_fused_pair_gemm_sweep(nslots, kmax, br, bk, bc, dtype, accum):
    lhs = jnp.asarray(
        RNG.standard_normal((nslots, kmax, br, bk)).astype(dtype))
    rhs = jnp.asarray(
        RNG.standard_normal((nslots, kmax, bk, bc)).astype(dtype))
    got = fused_pair_gemm(lhs, rhs, interpret=True, accum_dtype=accum)
    want = fused_pair_gemm_ref(lhs, rhs, accum_dtype=accum)
    assert got.dtype == lhs.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("tile_slots", [1, 3, 8, 64])
def test_fused_pair_gemm_tile_edge_invariance(tile_slots):
    """Padded tile edges: nslots not divisible by the grid tile."""
    lhs = jnp.asarray(RNG.standard_normal((13, 4, 3, 3)))
    rhs = jnp.asarray(RNG.standard_normal((13, 4, 3, 6)))
    got = fused_pair_gemm(lhs, rhs, tile_slots=tile_slots, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(fused_pair_gemm_ref(lhs, rhs)),
                               rtol=1e-12)


def test_fused_pair_gemm_zero_width():
    got = fused_pair_gemm(jnp.zeros((5, 0, 3, 3)), jnp.zeros((5, 0, 3, 6)),
                          interpret=True)
    assert got.shape == (5, 3, 6) and not np.asarray(got).any()


# ---------------------------------------------------------------------------
# Plan-level: tiled layout is an exact re-packing of the pair list
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n1,n2,br,bk,bc",
                         [(10, 8, 3, 3, 6), (8, 10, 6, 3, 3), (6, 6, 6, 6, 6),
                          (12, 5, 1, 2, 4)])
def test_tiled_layout_matches_pair_list(n1, n2, br, bk, bc):
    A = random_bcsr(RNG, n1, n2, br, bk, density=0.35)
    B = random_bcsr(RNG, n2, n1 + 1, bk, bc, density=0.35)
    plan = spgemm_symbolic(A, B)
    assert plan.bk == bk
    assert plan.tile_pair_a.shape == (plan.tile_rows, plan.pair_kmax)
    nonempty = int((np.bincount(plan.out_idx, minlength=plan.nnzb) > 0).sum())
    assert plan.tile_rows >= nonempty
    assert int(plan.tile_mask.sum()) == plan.npairs
    assert (np.diff(plan.tile_seg) >= 0).all(), "rows must stay sorted"
    # every (slot, pair) of the flat list appears in one of its slot's rows
    slot_pairs = {}
    for r in range(plan.tile_rows):
        s = int(plan.tile_seg[r])
        for a, b in zip(plan.tile_pair_a[r][plan.tile_mask[r]],
                        plan.tile_pair_b[r][plan.tile_mask[r]]):
            slot_pairs.setdefault(s, set()).add((a, b))
    for p in range(plan.npairs):
        assert (plan.pair_a[p], plan.pair_b[p]) in \
            slot_pairs[int(plan.out_idx[p])]
    assert plan.plan_tiled_bytes > 0
    assert 0 < plan.tile_fill <= 1.0


@pytest.mark.parametrize("n1,n2,br,bk,bc",
                         [(10, 8, 3, 3, 6), (8, 10, 6, 3, 3),
                          (6, 6, 6, 6, 6)])
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_fused_numeric_matches_oracle(n1, n2, br, bk, bc, dtype):
    A = random_bcsr(RNG, n1, n2, br, bk, density=0.3, dtype=dtype)
    B = random_bcsr(RNG, n2, n1, bk, bc, density=0.3, dtype=dtype)
    plan = spgemm_symbolic(A, B)
    ref = spgemm_numeric_data(plan, A.data, B.data, path="reference")
    fused = spgemm_numeric_data(plan, A.data, B.data, path="fused",
                                interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               **_tol(dtype))


def test_fused_numeric_empty_block_rows():
    """Rows of A with zero stored blocks -> empty C rows, zero pairs."""
    indptr = np.array([0, 2, 2, 3, 3], dtype=np.int64)   # rows 1, 3 empty
    indices = np.array([0, 2, 1], dtype=np.int32)
    data = RNG.standard_normal((3, 3, 3))
    A = BlockCSR.from_arrays(indptr, indices, data, 3)
    B = random_bcsr(RNG, 3, 4, 3, 6, density=0.5)
    plan = spgemm_symbolic(A, B)
    ref = spgemm_numeric_data(plan, A.data, B.data, path="reference")
    fused = spgemm_numeric_data(plan, A.data, B.data, path="fused",
                                interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-12, atol=1e-12)


def test_fused_no_pair_product_intermediate():
    """The point of the fusion: the jaxpr must not contain the
    materialized pair-product array — shape (npairs, br, bc), or its
    lane-dense forms (br, bc, npairs) / (br*bc, npairs)."""
    rng = np.random.default_rng(123)
    A = random_bcsr(rng, 16, 12, 3, 3, density=0.5)
    B = random_bcsr(rng, 12, 14, 3, 6, density=0.5)
    plan = spgemm_symbolic(A, B)
    # preconditions that keep the shape check meaningful: multi-pair tiles
    # and strictly fewer tile rows than pairs
    assert plan.pair_kmax > 1 and plan.tile_rows < plan.npairs
    assert plan.npairs != plan.nnzb
    bad = {(plan.npairs, plan.br, plan.bc),
           (plan.br, plan.bc, plan.npairs),
           (plan.br * plan.bc, plan.npairs)}

    def walk(jaxpr):
        return [s for _, s in jaxpr_outputs(jaxpr)]

    fused_fn = lambda a, b: spgemm_numeric_data(  # noqa: E731
        plan, a, b, path="fused", interpret=True)
    jaxpr = jax.make_jaxpr(fused_fn)(A.data, B.data)
    fused_shapes = walk(jaxpr.jaxpr)
    assert not bad & set(fused_shapes), \
        f"fused path materialized a pair-product array {bad}"

    ref_fn = lambda a, b: spgemm_numeric_data(  # noqa: E731
        plan, a, b, path="reference")
    ref_shapes = walk(jax.make_jaxpr(ref_fn)(A.data, B.data).jaxpr)
    assert bad & set(ref_shapes), "oracle check is not sensitive"


def test_fused_ptap_on_elasticity_hierarchy():
    """Acceptance: fused A_c.data == oracle A_c.data on every level of the
    elasticity hierarchy (all block-size mixes of the Galerkin chain)."""
    from repro.core import gamg
    from repro.core.ptap import ptap_numeric_data
    from repro.fem.assemble import assemble_elasticity

    prob = assemble_elasticity(4)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=10)
    assert setupd.levels, "need at least one Galerkin level"
    a_data = prob.A.data * 1.25      # a "Newton step": new values
    for ls in setupd.levels:
        ref = ptap_numeric_data(ls.ptap_cache, a_data, ls.P.data,
                                path="reference")
        fused = ptap_numeric_data(ls.ptap_cache, a_data, ls.P.data,
                                  path="fused", interpret=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   rtol=1e-11, atol=1e-11)
        a_data = ref
