"""Galerkin triple product A_c = P^T A P with device-resident, state-gated
reuse — paper Sec. 3.5.

Production AMG reuses the hierarchy (P fixed) while A changes every
Newton/time step.  The paper caches everything on the prolongator side —
R = P^T, the off-process rows P_oth, the stacked operand and the symbolic
products — and gates the cache on P's object state, so the *hot* numeric
PtAP is a local blocked triple product plus an off-process reduction with no
host round trip.

Functional rendering: ``ptap_symbolic(A, P)`` builds a ``PtAPCache`` (host
symbolic work, done once); ``ptap_numeric(cache, a_data, p_data)`` is a pure
jitted function — the hot PtAP.  ``ptap()`` front door checks the state gate
exactly like PetscObjectState: if the caller passes a cache built for this
(P structure, A structure), zero symbolic work happens.

The distributed version (slab halo of the off-process operands over the rank
mesh, with the off-process prolongator rows P_oth cached device-side) lives
in ``repro.dist.pamg``; this module is the single-device core it shares.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_csr import BlockCSR, structure_bcsr, \
    transpose_structure
from repro.core.spgemm import (
    SpGEMMPlan,
    spgemm_numeric_data,
    spgemm_symbolic,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PtAPCache:
    """Prolongator-side cached data, valid while (P, A) structures hold."""

    r_indptr: np.ndarray        # R = P^T structure
    r_indices: np.ndarray
    r_perm: np.ndarray          # numeric transpose permutation
    ap_plan: SpGEMMPlan         # A @ P
    ac_plan: SpGEMMPlan         # R @ (A @ P)
    p_state: int                # state gate: P's token at build time
    a_struct_state: int         # A's *structure* token (values may change)
    n_coarse: int               # coarse block dim
    bs_c: int                   # coarse block size

    @property
    def plan_bytes(self) -> int:
        return (self.r_indptr.nbytes + self.r_indices.nbytes
                + self.r_perm.nbytes + self.ap_plan.plan_bytes
                + self.ac_plan.plan_bytes)


def ptap_symbolic(A: BlockCSR, P: BlockCSR) -> PtAPCache:
    """Cold symbolic phase: transpose plan + both SpGEMM plans.

    Everything here is structure-only; it never touches A.data/P.data, so the
    same cache serves every numeric recompute with new values.
    """
    assert A.nbc == P.nbr and A.bc == P.br, "A (f x f) must feed P (f x c)"
    r_indptr, r_indices, r_perm = transpose_structure(P.indptr, P.indices,
                                                      P.nbc)
    # R is (n_coarse x n_fine) with (bs_c x bs_f) blocks
    R_struct = structure_bcsr(r_indptr, r_indices, P.nbr, P.bc, P.br,
                              P.data.dtype, state_token=P.state_token)
    ap_plan = spgemm_symbolic(A, P)
    AP_struct = structure_bcsr(ap_plan.indptr, ap_plan.indices, ap_plan.nbc,
                               ap_plan.br, ap_plan.bc, A.data.dtype)
    ac_plan = spgemm_symbolic(R_struct, AP_struct)
    return PtAPCache(r_indptr=r_indptr, r_indices=r_indices, r_perm=r_perm,
                     ap_plan=ap_plan, ac_plan=ac_plan,
                     p_state=P.state_token, a_struct_state=A.state_token,
                     n_coarse=P.nbc, bs_c=P.bc)


def ptap_numeric_data(cache: PtAPCache, a_data: Array, p_data: Array,
                      **kw) -> Array:
    """Hot PtAP: pure device function (local blocked triple product).

    Both Galerkin products (A @ P and R @ (A P)) share the SpGEMM numeric
    machinery; ``path=`` / ``interpret=`` flow through, so the backend
    default dispatches the fused tiled kernel on accelerators.
    """
    r_data = p_data[jnp.asarray(cache.r_perm)].transpose(0, 2, 1)
    ap_data = spgemm_numeric_data(cache.ap_plan, a_data, p_data, **kw)
    return spgemm_numeric_data(cache.ac_plan, r_data, ap_data, **kw)


def ptap_numeric(cache: PtAPCache, A: BlockCSR, P: BlockCSR, **kw
                 ) -> BlockCSR:
    data = ptap_numeric_data(cache, A.data, P.data, **kw)
    return BlockCSR.from_arrays(cache.ac_plan.indptr, cache.ac_plan.indices,
                                data, cache.n_coarse)


def ptap(A: BlockCSR, P: BlockCSR, cache: Optional[PtAPCache] = None,
         **kw) -> Tuple[BlockCSR, PtAPCache]:
    """Front door with the state gate.

    Matches PETSc semantics: MAT_REUSE_MATRIX with an up-to-date
    PetscObjectState reuses the cached prolongator-side data; anything else
    rebuilds symbolically (the "ungated" path measured in paper Table 3).
    """
    gate_ok = (cache is not None
               and cache.p_state == P.state_token
               and cache.a_struct_state == A.state_token)
    if not gate_ok:
        cache = ptap_symbolic(A, P)
    return ptap_numeric(cache, A, P, **kw), cache


def galerkin_flops(cache: PtAPCache, bs_f: int) -> int:
    """Useful flop count of the numeric phase (for the traffic model)."""
    # each AP pair: (br x bk)(bk x bc) => 2*br*bk*bc
    ap = cache.ap_plan
    ac = cache.ac_plan
    return (2 * ap.npairs * ap.br * bs_f * ap.bc
            + 2 * ac.npairs * ac.br * bs_f * ac.bc)
