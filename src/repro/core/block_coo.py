"""Blocked COO assembly — ``MatCOOUseBlockIndices`` (paper Secs. 3.4, 5).

PETSc's device-assembly path is coordinate format: declare the (i, j)
coordinates of every contribution once (``MatSetPreallocationCOO``), build a
cached communication-and-scatter plan, then every numeric assembly is a
single device scatter-sum (``MatSetValuesCOO``).  The paper generalizes the
coordinates to address dense ``bs_r x bs_c`` blocks, shrinking every plan
array by the block area.

Functional JAX rendering:

* ``BlockCOOPlan`` = the symbolic phase.  Built once on the host from the
  block coordinates; owns the output ``BlockCSR`` structure, the stable sort
  order and the duplicate-summation segment map.
* ``set_values_coo(plan, values)`` = the numeric phase.  A single jitted
  gather + sorted ``segment_sum`` over block payloads (or the Pallas
  ``block_seg_sum`` kernel), entirely device-resident.

Negative coordinates are ignored (the PETSc convention used by boundary
conditions); their payloads are dropped by the plan, not branched on at
runtime.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import jit_args
from repro.core.block_csr import BlockCSR, coo_to_csr_structure
from repro.core.lanes import from_lanes, gather_lanes, segment_sum_lanes, \
    to_lanes

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BlockCOOPlan:
    """Cached symbolic assembly plan (the PETSc COO preallocation plan)."""

    indptr: np.ndarray        # output structure
    indices: np.ndarray
    nbr: int
    nbc: int
    br: int
    bc: int
    nnzb: int                 # deduped output blocks
    keep: np.ndarray          # indices of non-ignored input coordinates
    out_idx_sorted: np.ndarray  # per *sorted* kept coordinate: output slot
    order: np.ndarray         # stable sort of kept coordinates by (row, col)
    n_input: int              # declared coordinates (before drop/dedup)

    @property
    def plan_bytes(self) -> int:
        """Bytes of plan index data — the quantity the paper's blocked COO
        shrinks by the block area (Sec. 5)."""
        return (self.indptr.nbytes + self.indices.nbytes + self.keep.nbytes
                + self.out_idx_sorted.nbytes + self.order.nbytes)


def preallocate_coo(rows, cols, nbr: int, nbc: int, br: int, bc: int
                    ) -> BlockCOOPlan:
    """Symbolic phase: sort/unique block coordinates, build the scatter map.

    ``rows``/``cols`` are *block* coordinates of every contribution,
    duplicates allowed, negatives ignored.
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    # ValueError, not assert: validation must survive ``python -O``
    if rows.shape != cols.shape:
        raise ValueError(f"rows/cols shape mismatch: {rows.shape} != "
                         f"{cols.shape}")
    keep = np.flatnonzero((rows >= 0) & (cols >= 0))
    kr, kc = rows[keep], cols[keep]
    if len(kr) and (kr.max() >= nbr or kc.max() >= nbc):
        raise ValueError(
            f"block coordinate out of range: max (row, col) = "
            f"({int(kr.max())}, {int(kc.max())}) for a {nbr} x {nbc} "
            f"block grid")
    indptr, indices, order, out_idx, nnzb = coo_to_csr_structure(
        kr, kc, nbr, sum_duplicates=True)
    # re-express out_idx in sorted order so the numeric segment_sum sees
    # monotone segment ids (indices_are_sorted=True fast path).
    out_idx_sorted = out_idx[order]
    return BlockCOOPlan(indptr=indptr, indices=indices, nbr=nbr, nbc=nbc,
                        br=br, bc=bc, nnzb=nnzb, keep=keep,
                        out_idx_sorted=out_idx_sorted.astype(np.int32),
                        order=order.astype(np.int64),
                        n_input=len(rows))


def set_values_coo(plan: BlockCOOPlan, values: Array, *,
                   use_kernel: bool | None = None,
                   interpret: bool | None = None) -> BlockCSR:
    """Numeric phase: one device scatter-sum of dense block payloads.

    ``values``: (n_input, br, bc) dense blocks, one per declared coordinate,
    in declaration order — exactly PETSc's MatSetValuesCOO value stream.
    The XLA ``segment_sum`` runs by default on every backend: the Pallas
    streaming segment-sum (``use_kernel=True``) needs an in-kernel cumsum,
    which Mosaic does not lower, so it runs in interpret mode only.
    """
    expected = (plan.n_input, plan.br, plan.bc)
    if values.shape != expected:
        raise ValueError(f"value stream shape {values.shape} != {expected} "
                         f"(one ({plan.br}, {plan.bc}) block per declared "
                         f"coordinate, in declaration order)")
    if use_kernel:
        from repro.kernels.block_seg_sum import ops as _k
        vals = values[jnp.asarray(plan.keep[plan.order])]
        data = _k.block_seg_sum(vals, jnp.asarray(plan.out_idx_sorted),
                                plan.nnzb, interpret=interpret)
    else:
        data = _program(plan)(values)
    return BlockCSR.from_arrays(plan.indptr, plan.indices, data, plan.nbc)


def _program(plan: BlockCOOPlan) -> jit_args.Program:
    """``set_values_coo_data`` compiled once per plan, the plan's arrays
    passed as arguments."""
    prog = plan.__dict__.get("_set_values")
    if prog is None:
        prog = plan.__dict__["_set_values"] = jit_args.Program(
            set_values_coo_data, plan)
    return prog


def set_values_coo_data(plan: BlockCOOPlan, values: Array) -> Array:
    """Numeric phase returning only the data array (for jitted pipelines):
    the kept blocks in sorted order, summed into their output slots, with
    the block count on the lanes (``repro.core.lanes``)."""
    t = gather_lanes(to_lanes(values), plan.keep[plan.order])
    out = segment_sum_lanes(t, plan.out_idx_sorted, plan.nnzb)
    return from_lanes(out, (plan.br, plan.bc))


def scalar_coo_plan_bytes(plan: BlockCOOPlan) -> int:
    """Index bytes the equivalent *scalar* COO plan would need.

    Every block coordinate expands to br*bc scalar coordinates, each carrying
    its own sort/scatter entries — the factor-of-block-area growth the paper
    removes (Sec. 5).  Used by benchmarks/table5_traffic.py.
    """
    area = plan.br * plan.bc
    n_in = len(plan.keep) * area
    nnz = plan.nnzb * area
    # indptr + indices + keep + out_idx + order at scalar granularity
    return (8 * (plan.nbr * plan.br + 1) + 4 * nnz + 8 * n_in + 4 * n_in
            + 8 * n_in)
