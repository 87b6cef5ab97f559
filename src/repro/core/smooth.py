"""Prolongator smoothing P = (I - omega D^{-1} A) P~ (paper Sec. 2.2).

All blocked, no scalar conversion:

* ``D^{-1}`` is the batched inverse of the diagonal blocks (pbjacobi data —
  shared with the smoother);
* ``D^{-1} A`` is a block-row scaling of A's payloads (no structure change);
* the product with P~ uses the cached two-phase SpGEMM;
* the final subtraction is the *native block AXPY* over the union sparsity —
  the operation whose scalar fallback is the one residual conversion in the
  paper's cold path (Sec. 4.9), implemented natively here.

``omega = (4/3) / lambda_max(D^{-1}A)`` with lambda_max from a short device
power iteration (deterministic start vector).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jit_args
from repro.core.block_csr import BlockCSR, structure_bcsr
from repro.core.lanes import block_matmul_lanes, from_lanes, gather_lanes, \
    to_lanes
from repro.core.spgemm import (
    BlockAXPYPlan,
    block_axpy_numeric_data,
    block_axpy_symbolic,
    spgemm_numeric_data,
    spgemm_symbolic,
    SpGEMMPlan,
)
from repro.core.spmv import ell_contract

Array = jax.Array


@jax.jit
def invert_diag_blocks(diag: Array) -> Array:
    """Batched small-block inverse; the pbjacobi setup kernel.

    Gauss-Jordan elimination without pivoting, all blocks at once with the
    block count on the lanes, one pivot per loop step.  The blocks are
    diagonal blocks of an SPD operator (SPD themselves), so no pivoting is
    needed.  XLA on the TPU has no f64 LU, and its batched QR of tiny
    blocks takes over ten seconds to compile per shape; this form compiles
    in about one and serves every policy on every backend.
    """
    bs = diag.shape[-1]
    t = jnp.transpose(diag, (1, 2, 0))                       # (bs, bs, n)
    eye = jnp.broadcast_to(jnp.eye(bs, dtype=diag.dtype)[..., None],
                           t.shape)
    aug = jnp.concatenate([t, eye], axis=1)                  # (bs, 2bs, n)

    def pivot(k, aug):
        row = aug[k] / aug[k, k]
        aug = aug - aug[:, k][:, None] * row[None]
        return aug.at[k].set(row)

    aug = jax.lax.fori_loop(0, bs, pivot, aug)
    return jnp.transpose(aug[:, bs:], (2, 0, 1))


@jax.jit
def _row_scale(dinv: Array, rows, data: Array) -> Array:
    """``dinv[rows[i]] @ data[i]`` for every block, lane-dense."""
    bs, bc = dinv.shape[1], data.shape[2]
    d = gather_lanes(to_lanes(dinv), rows).reshape(bs, bs, -1)
    a = to_lanes(data).reshape(bs, bc, -1)
    return from_lanes(block_matmul_lanes(d, a).reshape(bs * bc, -1),
                      (bs, bc))


def scale_rows_data(A: BlockCSR, dinv: Array) -> Array:
    """Payloads of D^{-1} A: left-multiply each block by its row's D^{-1}."""
    return _row_scale(dinv, np.repeat(np.arange(A.nbr), np.diff(A.indptr)),
                      A.data)


@partial(jax.jit, static_argnames=("nbr", "bs", "iters"))
def lambda_max_dinv_a(ell_indices: Array, dinva_ell_data: Array,
                      ell_mask: Array, nbr: int, bs: int,
                      iters: int = 10) -> Array:
    """lambda_max(D^{-1}A) by power iteration on the ELL layout (device)."""

    def spmv(xb):
        return ell_contract(dinva_ell_data, xb, ell_indices)

    x0 = jnp.ones((nbr, bs), dinva_ell_data.dtype)
    x0 = x0 / jnp.linalg.norm(x0)

    def body(_, x):
        y = spmv(x)
        # finfo tiny, not a literal: 1e-300 underflows to 0 below f64
        return y / jnp.maximum(jnp.linalg.norm(y), jnp.finfo(y.dtype).tiny)

    x = jax.lax.fori_loop(0, iters, body, x0)
    y = spmv(x)
    return jnp.linalg.norm(y)  # Rayleigh-ish estimate, GAMG style


def smoothed_prolongator(A: BlockCSR, P_tent: BlockCSR,
                         omega_scale: float = 4.0 / 3.0,
                         lam_max: Optional[Array] = None
                         ) -> Tuple[BlockCSR, Array, Array, dict]:
    """One damped-Jacobi smoothing step of the tentative prolongator.

    Returns (P, omega, lam_max, plans) where plans carries the cached
    symbolic pieces so hot hierarchy recomputes can redo the numeric
    smoothing without symbolic work.  The symbolic plans are built first
    on the host; the numeric chain is then one device program
    (``_smooth_numeric``) with the plans as its arguments.
    """
    # D^{-1} A has A's structure (and state token), so A plans the product
    ap_plan = spgemm_symbolic(A, P_tent)
    AP = structure_bcsr(ap_plan.indptr, ap_plan.indices, ap_plan.nbc,
                        ap_plan.br, ap_plan.bc, A.data.dtype)
    axpy_plan = block_axpy_symbolic(AP, P_tent)
    ell_plan = A.ell_plan() if lam_max is None else None
    p_data, omega, lam_max = jit_args.call(
        _smooth_numeric, (ap_plan, axpy_plan, ell_plan), A, P_tent,
        omega_scale, lam_max)
    P = BlockCSR.from_arrays(axpy_plan.indptr, axpy_plan.indices, p_data,
                             axpy_plan.nbc)
    plans = dict(ap_plan=ap_plan, axpy_plan=axpy_plan)
    return P, omega, lam_max, plans


def _smooth_numeric(plans, A: BlockCSR, P_tent: BlockCSR, omega_scale,
                    lam_max):
    """``P = P~ - omega D^{-1} A P~`` and its omega / lambda_max."""
    ap_plan, axpy_plan, ell_plan = plans
    dinv = invert_diag_blocks(A.diagonal_blocks())
    dinva_data = scale_rows_data(A, dinv)
    if lam_max is None:
        lam_max = lambda_max_dinv_a(jnp.asarray(ell_plan.indices),
                                    ell_plan.ell_data(dinva_data),
                                    jnp.asarray(ell_plan.mask), A.nbr, A.br)
    omega = omega_scale / lam_max
    ap_data = spgemm_numeric_data(ap_plan, dinva_data, P_tent.data)
    p_data = block_axpy_numeric_data(axpy_plan, -omega, ap_data,
                                     P_tent.data)
    return p_data, omega, lam_max


def resmooth_prolongator_data(ap_plan: SpGEMMPlan, axpy_plan: BlockAXPYPlan,
                              a_data: Array, dinv: Array, omega: Array,
                              p_tent_data: Array,
                              row_of_nnz: Array) -> Array:
    """Hot numeric re-smoothing with cached plans (new A values, same P~)."""
    ap = spgemm_numeric_data(ap_plan, _row_scale(dinv, row_of_nnz, a_data),
                             p_tent_data)
    return block_axpy_numeric_data(axpy_plan, -omega, ap, p_tent_data)
