"""Blocked SpMV / SpMM — the V-cycle's dominant kernel (paper Sec. 4.2).

The blocked SpMV moves one 4-byte column index per ``br x bc`` block instead
of ``br*bc`` indexed scalars; for bs=3/fp64 that is 76 B per block vs 108 B
scalar — the paper's 1.42x traffic ceiling.  ``benchmarks/table5_traffic.py``
re-derives that accounting from these containers.

Two execution paths:

* ``spmv_ref`` — pure-jnp oracle over the ELL layout (always available).
* ``spmv`` — dispatches to the Pallas TPU kernel (``repro.kernels.block_spmv``)
  where it compiles natively (TPU, f32/bf16 payload) or when
  ``use_kernel=True`` (validated in interpret mode on CPU), else the ref.
  ``apply_ell`` sends every single-vector operator apply of the solve path
  through it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_csr import BlockCSR, BlockELL, EllTransposePlan
from repro.core.lanes import gather_lanes, to_lanes
from repro.obs import trace as obs_trace

Array = jax.Array


def ell_contract(data: Array, x_blocks: Array, indices) -> Array:
    """``y[r,a(,m)] = sum_{k,b} data[r,k,a,b] * x_blocks[indices[r,k],b(,m)]``
    for an ELL payload ``(nbr, kmax, br, bc)`` and a block vector
    ``(nbc, bc)`` or panel ``(nbc, bc, m)``.

    ``x`` is gathered lane-dense, ``(bc, m, kmax, nbr)``, by one flat
    element gather (``repro.core.lanes``): a row-major ``(nbr, kmax, bc)``
    gather pads its small minor dims to the TPU's (8, 128) tile and takes
    the TPU compiler seconds per instance.  Summed in the Pallas kernels'
    order — per slot sequentially in ``b``, then over the slots — so the
    XLA and kernel paths of a smoother agree to the bit in f64.
    """
    xb = x_blocks if x_blocks.ndim == 3 else x_blocks[..., None]
    nbc, bc, m = xb.shape
    idx_t = jnp.asarray(indices).T                # (kmax, nbr)
    xg = gather_lanes(xb.reshape(nbc, bc * m).T, idx_t).reshape(
        (bc, m) + idx_t.shape)
    d = jnp.transpose(data, (2, 3, 1, 0))[:, :, None]  # (br, bc, 1, kmax, r)
    out = []
    for a in range(d.shape[0]):
        t = d[a, 0] * xg[0]
        for b in range(1, bc):
            t = t + d[a, b] * xg[b]
        out.append(jnp.sum(t, axis=1))            # (m, nbr)
    y = jnp.transpose(jnp.stack(out), (2, 0, 1))  # (nbr, br, m)
    return y if x_blocks.ndim == 3 else y[..., 0]


def block_matvec(mats: Array, v: Array) -> Array:
    """``y[n,a(,m)] = sum_c mats[n,a,c] * v[n,c(,m)]``, sequential in ``c``
    — the kernels' order, like ``ell_contract``."""
    if v.ndim == 2:
        terms = (mats[..., c] * v[:, c, None] for c in range(mats.shape[2]))
    else:
        terms = (mats[..., c, None] * v[:, None, c]
                 for c in range(mats.shape[2]))
    acc = next(terms)
    for t in terms:
        acc = acc + t
    return acc


@jax.jit
def spmv_ell(ell: BlockELL, x: Array) -> Array:
    """y = A @ x on the padded ELL layout.  x: (nbc*bc,) -> y: (nbr*br,).

    The XLA reference of a single-vector apply.  Solve-path callers go
    through ``apply_ell``, where single vectors and panels dispatch by the
    same rule: this runs where the compiled kernel does not (CPU and GPU,
    and f64 payloads on TPU).  Padded slots point at column 0 with
    exactly-zero data blocks, so they contribute nothing."""
    with obs_trace.scope("spmv_ell"):
        xb = x.reshape(ell.nbc, ell.bc)
        return ell_contract(ell.data, xb, ell.indices).reshape(
            ell.nbr * ell.br)


@jax.jit
def spmm_ell(ell: BlockELL, X: Array) -> Array:
    """Y = A @ X for multiple right-hand sides. X: (nbc*bc, m).

    ``m == 1`` delegates to ``spmv_ell`` so the single-column panel is
    *bitwise* the single-RHS result (same reduction graph) — the multi-RHS
    layer's k=1 exactness contract rests on this.
    """
    with obs_trace.scope("spmm_ell"):
        m = X.shape[1]
        if m == 1:
            return spmv_ell(ell, X[:, 0])[:, None]
        xb = X.reshape(ell.nbc, ell.bc, m)
        return ell_contract(ell.data, xb, ell.indices).reshape(
            ell.nbr * ell.br, m)


@jax.jit
def apply_ell_t(ell: BlockELL, pt: EllTransposePlan, x: Array) -> Array:
    """y = A^T @ x straight off A's ELL blocks (transpose-free restriction).

    ``pt`` (``repro.core.block_csr.transpose_apply_plan``) addresses A's own
    flattened ``(nbr*kmax, br, bc)`` payload, so the restriction reuses the
    prolongator's value stream byte-for-byte — the stored ``r_ell``
    duplicate is gone from the hierarchy.  Padded plan slots point at slot
    0 (a real block) and are zeroed by the mask.  The blocks are contracted
    transposed, which is exactly the stored-``r_ell`` apply's operand, in
    the same order, so the two restrictions are bitwise equal on the
    reference path only: on TPU ``apply_ell`` sends a stored f32 ``r_ell``
    to the Pallas kernels, which this always-XLA contraction does not
    follow.  Panel-polymorphic like ``apply_ell``: ``x`` is ``(nbr*br,)``
    or ``(nbr*br, k)``.
    """
    with obs_trace.scope("apply_ell_t"):
        nbr, kmax, br, bc = ell.data.shape
        flat = to_lanes(ell.data.reshape(nbr * kmax, br, bc))
        mask_t = jnp.asarray(pt.mask).T
        blocks = jnp.where(
            mask_t, gather_lanes(flat, jnp.asarray(pt.gather).T), 0)
        # (nbc, tkmax, bc, br): the stored restriction's own payload,
        # materialized (not fused into the contraction) so the multiply-add
        # chain is the stored apply's, bit for bit
        r_data = jax.lax.optimization_barrier(jnp.transpose(
            blocks.reshape((br, bc) + mask_t.shape), (3, 2, 1, 0)))
        xb = x.reshape((nbr, br) + x.shape[1:])
        y = ell_contract(r_data, xb, pt.rows)
        return y.reshape((ell.nbc * bc,) + x.shape[1:])


def apply_ell(ell: BlockELL, x: Array) -> Array:
    """Shape-polymorphic ELL apply: (n,) -> ``spmv``, (n, k) -> ``spmm``.

    The V-cycle and both Krylov paths route every operator application
    through this, so the whole solve hierarchy accepts column panels
    without duplicating the recursion.  Single vectors and panels dispatch
    by the same rule, the backend and the payload dtype
    (``repro.kernels.backend.kernels_by_default``): the Pallas
    ``block_spmv`` / ``block_spmm`` kernels, with the ELL's own window
    plan, inside the jitted solves on TPU for f32/bf16 payloads;
    ``spmv_ell`` / ``spmm_ell`` everywhere else (so CPU and the ``f64``
    policy trace exactly the XLA reference).  Resolution happens at
    *trace* time; ``REPRO_SPMM_PATH`` forces the panel branch only.
    """
    return spmv(ell, x) if x.ndim == 1 else spmm(ell, x)


def spmv_bcsr_ref(A: BlockCSR, x: Array) -> Array:
    """Reference SpMV straight off BCSR (gather + segment-sum).

    Used as the oracle for property tests; the production path is the ELL
    kernel (regular layout — the TPU adaptation of the paper's BSR kernel).
    """
    rows = np.repeat(np.arange(A.nbr), np.diff(A.indptr))
    xb = x.reshape(A.nbc, A.bc)
    contrib = jnp.einsum("nab,nb->na", A.data, xb[A.indices])
    y = jax.ops.segment_sum(contrib, jnp.asarray(rows), num_segments=A.nbr,
                            indices_are_sorted=True)
    return y.reshape(A.nbr * A.br)


def spmv(A, x: Array, *, use_kernel: bool | None = None,
         interpret: bool | None = None, tile_rows: int | None = None,
         accum_dtype=None) -> Array:
    """Front door: accepts BlockCSR (converts) or BlockELL.

    ``use_kernel=None`` / ``interpret=None`` resolve per backend: the Pallas
    kernel compiled natively on TPU for an f32/bf16 payload, the jnp
    reference elsewhere (see ``repro.kernels.backend``).  ``tile_rows=None`` resolves through the
    autotuner (``repro.kernels.autotune``, governed by ``REPRO_TUNE``) with
    the static default as fallback.  ``accum_dtype`` threads the kernel
    accumulator rule (None = native; the jnp reference path accumulates
    natively and low-precision callers should use the kernel path).
    """
    from repro.kernels import backend as _backend
    ell = A.to_ell() if isinstance(A, BlockCSR) else A
    if _backend.resolve_use_kernel(use_kernel, ell.data.dtype):
        from repro.kernels.block_spmv import ops as _k
        return _k.block_spmv(ell, x, interpret=interpret,
                             tile_rows=tile_rows, accum_dtype=accum_dtype)
    return spmv_ell(ell, x)


def spmm(A, X: Array, *, path: str | None = None,
         interpret: bool | None = None, tile_rows: int | None = None,
         accum_dtype=None) -> Array:
    """Multi-RHS front door: Y = A @ X, X: (n, k), A BlockCSR or BlockELL.

    ``path=None`` resolves per backend (``repro.kernels.backend
    .resolve_spmm_path``): the Pallas panel kernel where it compiles
    natively (TPU), the jnp reference elsewhere; ``REPRO_SPMM_PATH``
    forces it globally.  ``tile_rows=None`` resolves through the autotuner
    (``REPRO_TUNE``); ``accum_dtype`` threads the kernel accumulator
    (None = native).
    """
    from repro.kernels import backend as _backend
    ell = A.to_ell() if isinstance(A, BlockCSR) else A
    if _backend.resolve_spmm_path(path, ell.data.dtype) == "kernel":
        from repro.kernels.block_spmm import ops as _k
        return _k.block_spmm(ell, X, interpret=interpret,
                             tile_rows=tile_rows, accum_dtype=accum_dtype)
    return spmm_ell(ell, X)


# ---------------------------------------------------------------------------
# Scalar-CSR baseline SpMV (the format the paper compares against).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("nrows",))
def spmv_csr_ref(indices: Array, data: Array, row_of_nnz: Array, nrows: int,
                 x: Array) -> Array:
    """Scalar CSR SpMV via gather + sorted segment-sum (AIJ baseline)."""
    contrib = data * x[indices]
    return jax.ops.segment_sum(contrib, row_of_nnz, num_segments=nrows,
                               indices_are_sorted=True)


def residual(A, x: Array, b: Array, **kw) -> Array:
    return b - spmv(A, x, **kw)


@partial(jax.jit, static_argnames=("transpose_blocks",))
def block_diag_apply(diag_inv: Array, x: Array,
                     transpose_blocks: bool = False) -> Array:
    """y_i = D_i^{-1} x_i given pre-inverted (nbr, bs, bs) diagonal blocks.

    This is the point-block Jacobi application (paper's pbjacobi smoother).
    """
    nbr, bs = diag_inv.shape[0], diag_inv.shape[1]
    xb = x.reshape(nbr, bs)
    eq = "nba,nb->na" if transpose_blocks else "nab,nb->na"
    return jnp.einsum(eq, diag_inv, xb).reshape(-1)
