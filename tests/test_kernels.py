"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Each kernel is swept over shapes and dtypes per the deliverables spec —
f64 and f32 with native accumulation, bf16 with the explicit fp32
accumulator (the ``accum_dtype`` rule every kernel family shares; see
``src/repro/kernels/README.md``).  The blocked SpMV/SpGEMM paths are
additionally validated end-to-end against the core references.
"""
import ml_dtypes
import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on)
import jax
import jax.numpy as jnp

from repro.core.spmv import apply_ell, spmm, spmm_ell, spmv, spmv_ell
from repro.core.spgemm import spgemm_symbolic, spgemm_numeric
from repro.kernels.block_spmv.block_spmv import block_spmv_ell
from repro.kernels.block_spmv.ref import block_spmv_ell_ref
from repro.kernels.block_spmm.block_spmm import block_spmm_ell
from repro.kernels.block_spmm.ops import block_spmm
from repro.kernels.block_spmm.ref import block_spmm_ell_ref
from repro.kernels.block_pair_gemm.block_pair_gemm import block_pair_gemm
from repro.kernels.block_pair_gemm.ref import block_pair_gemm_ref
from repro.kernels.block_seg_sum.ops import block_seg_sum
from repro.kernels.block_seg_sum.ref import block_seg_sum_ref
from repro.kernels.pbjacobi.pbjacobi import pbjacobi_update
from repro.kernels.pbjacobi.ref import pbjacobi_update_ref

from helpers import random_bcsr

RNG = np.random.default_rng(7)

# dtype rows of the kernel sweeps: (value dtype, accum_dtype knob).  bf16
# uses the explicit fp32 accumulator — the supported low-precision mode.
DTYPES = [(np.float64, None), (np.float32, None),
          (ml_dtypes.bfloat16, np.float32)]
DTYPE_IDS = ["f64", "f32", "bf16"]


def _tol(dtype):
    if dtype == np.float64:
        return dict(rtol=1e-12, atol=1e-12)
    if dtype == ml_dtypes.bfloat16:
        # kernel and oracle share the fp32-accumulate/round-to-bf16 rule;
        # the slack covers reduction-order ulps at bf16 resolution
        return dict(rtol=5e-2, atol=5e-2)
    return dict(rtol=2e-5, atol=2e-5)


def _cast(a, dtype):
    """Numpy fp arrays -> jnp at the sweep dtype (bf16 via ml_dtypes)."""
    return jnp.asarray(np.asarray(a).astype(dtype))


@pytest.mark.parametrize("dtype,accum", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("nbr,kmax,br,bc",
                         [(5, 3, 3, 3), (16, 7, 3, 6), (33, 2, 6, 6),
                          (8, 4, 1, 1), (64, 9, 6, 3), (3, 1, 2, 5)])
def test_block_spmv_kernel_sweep(nbr, kmax, br, bc, dtype, accum):
    nbc = nbr + 3
    indices = jnp.asarray(RNG.integers(0, nbc, (nbr, kmax)), jnp.int32)
    data = _cast(RNG.standard_normal((nbr, kmax, br, bc)), dtype)
    x = _cast(RNG.standard_normal((nbc, bc)), dtype)
    got = block_spmv_ell(indices, data, x, interpret=True,
                         accum_dtype=accum)
    want = block_spmv_ell_ref(indices, data, x, accum_dtype=accum)
    assert got.dtype == data.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("tile_rows", [1, 4, 8, 32])
def test_block_spmv_kernel_tile_invariance(tile_rows):
    indices = jnp.asarray(RNG.integers(0, 10, (13, 5)), jnp.int32)
    data = jnp.asarray(RNG.standard_normal((13, 5, 3, 3)))
    x = jnp.asarray(RNG.standard_normal((10, 3)))
    got = block_spmv_ell(indices, data, x, tile_rows=tile_rows,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(block_spmv_ell_ref(
                                   indices, data, x)), rtol=1e-12)


def test_block_spmv_end_to_end_matches_core():
    A = random_bcsr(RNG, 20, 20, 3, 3, density=0.2)
    x = jnp.asarray(RNG.standard_normal(60))
    got = spmv(A, x, use_kernel=True, interpret=True)
    want = spmv_ell(A.to_ell(), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


# (nbr, nbc, br, bc, density) of small ELLPlan-built operators shaped like
# the V-cycle's single-vector SpMVs: a fine-level operator (3x3 square), a
# fine prolongator (3x6 rectangular) and a dense coarse operator (6x6,
# kmax in the hundreds)
VCYCLE_ELLS = {"A0-3x3": (40, 40, 3, 3, 0.2),
               "P0-3x6": (40, 9, 3, 6, 0.3),
               "A2-6x6-wide": (24, 300, 6, 6, 0.9)}


def _vcycle_ell(name, dtype):
    nbr, nbc, br, bc, density = VCYCLE_ELLS[name]
    A = random_bcsr(np.random.default_rng(11), nbr, nbc, br, bc, density,
                    dtype=dtype)
    ell = A.ell_plan().build(A.data)
    return ell, jnp.asarray(np.random.default_rng(12).standard_normal(
        nbc * bc).astype(dtype))


@pytest.mark.parametrize("name", sorted(VCYCLE_ELLS))
def test_spmv_kernel_matches_reference_at_vcycle_shapes(name):
    """The compiled-path kernel against the XLA reference, on ELLs built
    with their window plan as the hierarchy builds them."""
    ell, x = _vcycle_ell(name, np.float32)
    if name == "A2-6x6-wide":
        assert ell.kmax >= 200
    got = spmv(ell, x, use_kernel=True, interpret=True)
    want = spmv_ell(ell, x)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(np.float32))


@pytest.mark.parametrize("backend_name,dtype,kernel",
                         [("tpu", np.float32, True),
                          ("tpu", np.float64, False),
                          ("cpu", np.float32, False)],
                         ids=["tpu-f32", "tpu-f64", "cpu-f32"])
@pytest.mark.parametrize("name", sorted(VCYCLE_ELLS))
def test_apply_ell_single_vector_dispatch(monkeypatch, name, backend_name,
                                          dtype, kernel):
    """A single vector dispatches by the panels' rule: the Pallas kernel
    on TPU for an f32 payload, else exactly the ``spmv_ell`` trace."""
    monkeypatch.setenv("REPRO_BACKEND", backend_name)
    monkeypatch.setenv("REPRO_TUNE", "off")
    ell, x = _vcycle_ell(name, dtype)
    jaxpr = str(jax.make_jaxpr(lambda v: apply_ell(ell, v))(x))
    assert ("pallas_call" in jaxpr) == kernel
    if not kernel:
        assert jaxpr == str(jax.make_jaxpr(lambda v: spmv_ell(ell, v))(x))


@pytest.mark.parametrize("dtype,accum", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("nbr,kmax,br,bc,k",
                         [(5, 3, 3, 3, 1), (16, 7, 3, 6, 4),
                          (33, 2, 6, 6, 8), (8, 4, 1, 1, 3),
                          (64, 9, 6, 3, 16), (3, 1, 2, 5, 2)])
def test_block_spmm_kernel_sweep(nbr, kmax, br, bc, k, dtype, accum):
    nbc = nbr + 3
    indices = jnp.asarray(RNG.integers(0, nbc, (nbr, kmax)), jnp.int32)
    data = _cast(RNG.standard_normal((nbr, kmax, br, bc)), dtype)
    x = _cast(RNG.standard_normal((nbc, bc, k)), dtype)
    got = block_spmm_ell(indices, data, x, interpret=True,
                         accum_dtype=accum)
    want = block_spmm_ell_ref(indices, data, x, accum_dtype=accum)
    assert got.dtype == data.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("tile_rows,pad_k_to", [(1, 1), (4, 4), (8, 8),
                                                (32, 2)])
def test_block_spmm_wrapper_tile_and_pad_invariance(tile_rows, pad_k_to):
    A = random_bcsr(RNG, 13, 10, 3, 3, density=0.3)
    ell = A.to_ell()
    X = jnp.asarray(RNG.standard_normal((A.shape[1], 5)))
    got = block_spmm(ell, X, interpret=True, tile_rows=tile_rows,
                     pad_k_to=pad_k_to)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(spmm_ell(ell, X)), rtol=1e-12)


def test_block_spmm_end_to_end_matches_core():
    A = random_bcsr(RNG, 20, 20, 3, 3, density=0.2)
    X = jnp.asarray(RNG.standard_normal((60, 4)))
    got = spmm(A, X, path="kernel", interpret=True)
    want = spmm_ell(A.to_ell(), X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


@pytest.mark.parametrize("dtype,accum", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("npairs,br,bk,bc",
                         [(1, 3, 3, 3), (7, 3, 3, 6), (130, 6, 3, 6),
                          (256, 6, 6, 6), (9, 1, 1, 1), (50, 2, 4, 5)])
def test_block_pair_gemm_sweep(npairs, br, bk, bc, dtype, accum):
    lhs = _cast(RNG.standard_normal((npairs, br, bk)), dtype)
    rhs = _cast(RNG.standard_normal((npairs, bk, bc)), dtype)
    got = block_pair_gemm(lhs, rhs, interpret=True, accum_dtype=accum)
    want = block_pair_gemm_ref(lhs, rhs, accum_dtype=accum)
    assert got.dtype == lhs.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("dtype,accum", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("n,nseg,br,bc",
                         [(12, 5, 3, 3), (100, 1, 3, 6), (64, 64, 6, 6),
                          (300, 37, 1, 1), (5, 9, 2, 2)])
def test_block_seg_sum_sweep(n, nseg, br, bc, dtype, accum):
    # sorted segment ids, some segments possibly empty
    ids = np.sort(RNG.integers(0, nseg, n)).astype(np.int32)
    vals = _cast(RNG.standard_normal((n, br, bc)), dtype)
    got = block_seg_sum(vals, jnp.asarray(ids), nseg, interpret=True,
                        accum_dtype=accum)
    want = block_seg_sum_ref(vals, jnp.asarray(ids), nseg,
                             accum_dtype=accum)
    assert got.dtype == vals.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("tile_n", [1, 16, 256])
def test_block_seg_sum_carry_across_tiles(tile_n):
    """The cross-tile carry is the subtle part — sweep tile boundaries."""
    n, nseg = 40, 7
    ids = np.sort(RNG.integers(0, nseg, n)).astype(np.int32)
    vals = jnp.asarray(RNG.standard_normal((n, 3, 3)))
    got = block_seg_sum(vals, jnp.asarray(ids), nseg, tile_n=tile_n,
                        interpret=True)
    want = block_seg_sum_ref(vals, jnp.asarray(ids), nseg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-12)


def test_spgemm_with_kernels_matches_ref():
    A = random_bcsr(RNG, 10, 8, 3, 3)
    B = random_bcsr(RNG, 8, 6, 3, 6)
    plan = spgemm_symbolic(A, B)
    C_k = spgemm_numeric(plan, A, B, use_kernel=True, interpret=True)
    C_r = spgemm_numeric(plan, A, B)
    np.testing.assert_allclose(np.asarray(C_k.data), np.asarray(C_r.data),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("dtype,accum", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("nbr,bs", [(4, 3), (100, 6), (17, 3), (1, 1)])
def test_pbjacobi_sweep(nbr, bs, dtype, accum):
    dinv = _cast(RNG.standard_normal((nbr, bs, bs)), dtype)
    r = _cast(RNG.standard_normal((nbr, bs)), dtype)
    x = _cast(RNG.standard_normal((nbr, bs)), dtype)
    got = pbjacobi_update(dinv, r, x, 0.7, interpret=True,
                          accum_dtype=accum)
    want = pbjacobi_update_ref(dinv, r, x, 0.7, accum_dtype=accum)
    assert got.dtype == dinv.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


def test_compiled_f64_kernel_call_names_the_policy(monkeypatch):
    """No compiled Pallas kernel takes an f64 payload (Mosaic has no 64-bit
    floats): a front door asked for one refuses with the policy to use."""
    from repro.kernels.block_spmv.ops import block_spmv
    A = random_bcsr(RNG, 12, 12, 3, 3, density=0.3)
    x = jnp.asarray(RNG.standard_normal(A.shape[1]))
    with pytest.raises(ValueError, match="'f32' policy"):
        block_spmv(A.to_ell(), x, interpret=False)
    monkeypatch.setenv("REPRO_BACKEND", "tpu")      # compiled by default
    with pytest.raises(ValueError, match="'f32' policy"):
        spmv(A, x, use_kernel=True)


def test_tpu_default_dispatch_keeps_f64_on_xla(monkeypatch):
    """On a TPU the default path is the kernel for f32/bf16 payloads and
    the XLA reference for f64 (the ``f64`` policy runs end to end on XLA)."""
    from repro.kernels import backend
    for knob in ("REPRO_SPGEMM_PATH", "REPRO_SPMM_PATH", "REPRO_SMOOTH_PATH"):
        monkeypatch.delenv(knob, raising=False)
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    f64, f32 = np.dtype(np.float64), np.dtype(np.float32)
    assert backend.resolve_use_kernel(None, f32)
    assert not backend.resolve_use_kernel(None, f64)
    assert backend.resolve_smooth_path(None, f32) == "fused"
    assert backend.resolve_smooth_path(None, f64) == "reference"
    assert backend.resolve_spmm_path(None, f32) == "kernel"
    assert backend.resolve_spmm_path(None, f64) == "reference"
    assert backend.resolve_spgemm_path(None, f32) == "fused"
    assert backend.resolve_spgemm_path(None, f64) == "reference"
