"""Two-phase rectangular-block SpGEMM (C = A @ B).

The Galerkin product is the paper's second hot kernel.  Mixed block sizes
(A: br_a x k, B: k x bc_b) are exactly what the vendor square-BSR formats
cannot express (paper Sec. 2.4) and what this module is templated on.

Phases, mirroring cuSPARSE/PETSc symbolic+numeric:

symbolic (host, cached)
    Expand the multiply into a flat *pair list*: pair p contributes
    ``A.data[pair_a[p]] @ B.data[pair_b[p]]`` to output block
    ``out_idx[p]``.  Pairs are sorted by output slot, so the numeric scatter
    is a sorted segment reduction.  The pair list is the JAX analogue of the
    spgemm symbolic buffer whose bs^2-inflated scalar version OOMs the GPU in
    paper Sec. 4.5 — ``plan_bytes``/``scalar_plan_bytes`` quantify that.

numeric (device, jitted)
    Three paths, selected by ``path=`` (``None`` -> backend default, see
    ``repro.kernels.backend``):

    "fused"      the hot path.  The symbolic phase additionally re-packs the
                 sorted pair list into a *tiled* fixed-width layout (one row
                 of ``pair_kmax`` zero-padded pair slots per output block,
                 ELL-of-pairs), and ``repro.kernels.fused_pair_gemm`` runs
                 gather -> rectangular block GEMM -> segment reduce as one
                 ``pallas_call`` that accumulates each output block in VMEM.
                 The ``(npairs, br, bc)`` pair-product array never touches
                 HBM.
    "pairs"      the unfused kernel chain: gather -> batched block GEMM
                 (``repro.kernels.block_pair_gemm``) -> streaming segment
                 sum (``repro.kernels.block_seg_sum``); materializes the
                 pair products.
    "reference"  einsum + sorted ``segment_sum`` — the always-available
                 oracle the fused path is validated against.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_csr import BlockCSR
from repro.core.lanes import block_matmul_lanes, from_lanes, gather_lanes, \
    segment_sum_lanes, to_lanes

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SpGEMMPlan:
    """Cached symbolic phase of C = A @ B (structure-only function)."""

    indptr: np.ndarray       # C structure
    indices: np.ndarray
    nbr: int                 # C block rows
    nbc: int                 # C block cols
    br: int                  # C block shape
    bc: int
    bk: int                  # inner (contracted) block dim: A.bc == B.br
    nnzb: int
    pair_a: np.ndarray       # (npairs,) indices into A.data
    pair_b: np.ndarray       # (npairs,) indices into B.data
    out_idx: np.ndarray      # (npairs,) sorted output slot per pair
    a_state: int             # state tokens of the operands the plan matches
    b_state: int
    # Tiled (ELL-of-pairs) layout for the fused one-pass numeric kernel:
    # each tile row holds up to ``pair_kmax`` zero-padded pair slots of ONE
    # output block, so each kernel grid step owns a contiguous run of rows
    # and reduces them entirely in VMEM.  ``pair_kmax`` is chosen from the
    # pair histogram to minimize modeled traffic; output blocks with more
    # pairs span several consecutive rows (``tile_seg`` maps row -> output
    # slot) and their partials are combined by an O(nnzb)-sized sorted
    # segment-sum — never an O(npairs) one.  When no slot overflows
    # (``tile_identity``) the kernel's output IS C.data: a true single pass.
    tile_pair_a: np.ndarray  # (tile_rows, pair_kmax) int32 into A.data
    tile_pair_b: np.ndarray  # (tile_rows, pair_kmax) int32 into B.data
    tile_mask: np.ndarray    # (tile_rows, pair_kmax) bool, False on padding
    tile_seg: np.ndarray     # (tile_rows,) int32 sorted output slot per row
    tile_identity: bool      # tile_seg == arange(nnzb): no combine needed

    @property
    def npairs(self) -> int:
        return int(self.pair_a.shape[0])

    @property
    def pair_kmax(self) -> int:
        """Tile width: pair slots per tile row (histogram-chosen)."""
        return int(self.tile_pair_a.shape[1])

    @property
    def tile_rows(self) -> int:
        return int(self.tile_pair_a.shape[0])

    @property
    def tile_fill(self) -> float:
        """Occupancy of the tiled layout (1.0 = no padding waste)."""
        cells = self.tile_pair_a.size
        return self.npairs / cells if cells else 1.0

    @property
    def plan_bytes(self) -> int:
        return (self.indptr.nbytes + self.indices.nbytes + self.pair_a.nbytes
                + self.pair_b.nbytes + self.out_idx.nbytes)

    @property
    def plan_tiled_bytes(self) -> int:
        """Index bytes of the tiled layout (the fused path's whole plan)."""
        return (self.indptr.nbytes + self.indices.nbytes
                + self.tile_pair_a.nbytes + self.tile_pair_b.nbytes
                + self.tile_mask.nbytes + self.tile_seg.nbytes)

    def numeric_intermediate_bytes(self, path: str = "fused",
                                   itemsize: int = 8) -> int:
        """Peak HBM bytes of numeric-phase intermediates.

        The unfused paths materialize the gathered operands *and* the
        ``(npairs, br, bc)`` pair-product array; the fused path streams the
        gathered tiled operands and reduces in VMEM — at worst it adds the
        O(nnzb)-sized row partials when the histogram forced row splits.
        """
        br, bk, bc = self.br, self.bk, self.bc
        if path == "fused":
            operands = self.tile_pair_a.size * (br * bk + bk * bc) * itemsize
            partials = (0 if self.tile_identity
                        else self.tile_rows * br * bc * itemsize)
            return operands + partials
        lhs_rhs = self.npairs * (br * bk + bk * bc) * itemsize
        prod = self.npairs * br * bc * itemsize
        return lhs_rhs + prod

    def scalar_plan_bytes(self, bk: int) -> int:
        """Pair-list bytes if the same product ran in scalar CSR.

        Each block pair (br x bk)·(bk x bc) expands to br*bc output scalars
        times bk scalar multiply pairs — the bs^2/bs^3 growth behind the
        cuSPARSE symbolic-buffer OOM of paper Sec. 4.5.
        """
        scalar_pairs = self.npairs * self.br * self.bc * bk
        scalar_nnz = self.nnzb * self.br * self.bc
        return (8 * (self.nbr * self.br + 1) + 4 * scalar_nnz
                + (4 + 4 + 4) * scalar_pairs)


def spgemm_symbolic(A: BlockCSR, B: BlockCSR) -> SpGEMMPlan:
    """Host symbolic phase: C structure + flat pair lists."""
    assert A.nbc == B.nbr, (A.nbc, B.nbr)
    assert A.bc == B.br, ("inner block size mismatch", A.bc, B.br)
    nbr, nbc = A.nbr, B.nbc
    a_counts = np.diff(A.indptr)
    a_rows = np.repeat(np.arange(nbr, dtype=np.int64), a_counts)
    j = A.indices.astype(np.int64)                    # mid index per A nnz
    b_counts = np.diff(B.indptr)
    per_a = b_counts[j]                               # B-row length per A nnz
    total = int(per_a.sum())
    pair_a = np.repeat(np.arange(A.nnzb, dtype=np.int64), per_a)
    starts = np.repeat(B.indptr[j], per_a)
    csum = np.zeros(A.nnzb + 1, dtype=np.int64)
    np.cumsum(per_a, out=csum[1:])
    within = np.arange(total, dtype=np.int64) - np.repeat(csum[:-1], per_a)
    pair_b = starts + within
    pair_row = np.repeat(a_rows, per_a)
    pair_col = B.indices[pair_b].astype(np.int64)
    # unique (row, col) -> C structure; sort pairs by output slot
    key = pair_row * nbc + pair_col
    order = np.argsort(key, kind="stable")
    skey = key[order]
    uniq, inv = np.unique(skey, return_inverse=True)
    u_rows = uniq // nbc
    u_cols = (uniq % nbc).astype(np.int32)
    indptr = np.zeros(nbr + 1, dtype=np.int64)
    np.add.at(indptr, u_rows + 1, 1)
    indptr = np.cumsum(indptr)
    pair_a_s = pair_a[order]
    pair_b_s = pair_b[order]
    out_idx = inv.astype(np.int32)
    tile_a, tile_b, tile_mask, tile_seg, ident = _tile_pairs(
        pair_a_s, pair_b_s, out_idx, len(uniq), A.br, A.bc, B.bc)
    return SpGEMMPlan(indptr=indptr, indices=u_cols, nbr=nbr, nbc=nbc,
                      br=A.br, bc=B.bc, bk=A.bc, nnzb=len(uniq),
                      pair_a=pair_a_s, pair_b=pair_b_s, out_idx=out_idx,
                      a_state=A.state_token, b_state=B.state_token,
                      tile_pair_a=tile_a, tile_pair_b=tile_b,
                      tile_mask=tile_mask, tile_seg=tile_seg,
                      tile_identity=ident)


def _choose_tile_width(counts: np.ndarray, br: int, bk: int, bc: int) -> int:
    """Pick the tile width from the pair histogram by modeled traffic.

    Width k costs ``k * sum(ceil(c/k))`` operand cells (each moving one
    (br, bk) + one (bk, bc) block) plus, whenever any slot splits, a write +
    read of one (br, bc) partial per tile row.  Minimizing this trades ELL
    padding against the partial combine; skewed histograms (the R@AP stage)
    get a small k with row splits, tight ones get kmax and a true single
    pass.
    """
    kmax = int(counts.max())
    if kmax <= 1:
        return max(kmax, 1)
    hist = np.bincount(np.minimum(counts, kmax))
    vals = np.arange(len(hist), dtype=np.int64)
    nnzb = int((counts > 0).sum())
    operand = br * bk + bk * bc
    partial = 2 * br * bc
    if kmax <= 512:
        cands = np.arange(1, kmax + 1)
    else:  # pathological width: probe the histogram quantiles only
        qs = np.percentile(counts[counts > 0],
                           [25, 50, 75, 90, 95, 99]).astype(np.int64)
        cands = np.unique(np.clip(np.concatenate([qs, [kmax]]), 1, kmax))
    best_k, best_cost = kmax, None
    for k in cands:
        nrows = int((hist * -(-vals // k)).sum())
        cost = k * nrows * operand + (partial * nrows
                                      if nrows > nnzb else 0)
        if best_cost is None or cost < best_cost:
            best_cost, best_k = cost, int(k)
    return best_k


def _tile_pairs(pair_a: np.ndarray, pair_b: np.ndarray, out_idx: np.ndarray,
                nnzb: int, br: int, bk: int, bc: int):
    """Re-pack the sorted pair list into the fixed-width tiled layout.

    Rows of ``pair_kmax`` zero-padded pair slots; an output block with more
    pairs than the width gets consecutive rows (``tile_seg`` maps row ->
    slot).  Padded cells gather block 0 and are masked out (the numeric
    phase zeroes the gathered lhs, so padding contributes exactly 0.0).
    """
    npairs = len(out_idx)
    if not npairs or not nnzb:
        return (np.zeros((nnzb, 0), np.int32), np.zeros((nnzb, 0), np.int32),
                np.zeros((nnzb, 0), bool),
                np.arange(nnzb, dtype=np.int32), True)
    counts = np.bincount(out_idx, minlength=nnzb).astype(np.int64)
    width = _choose_tile_width(counts, br, bk, bc)
    rows_per_slot = -(-counts // width)          # ceil; 0 for empty slots
    nrows = int(rows_per_slot.sum())
    row_start = np.zeros(nnzb + 1, dtype=np.int64)
    np.cumsum(rows_per_slot, out=row_start[1:])
    seg = np.repeat(np.arange(nnzb, dtype=np.int32), rows_per_slot)
    starts = np.zeros(nnzb + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    within = np.arange(npairs, dtype=np.int64) - starts[out_idx]
    r_idx = row_start[out_idx] + within // width
    c_idx = within % width
    tile_a = np.zeros((nrows, width), dtype=np.int32)
    tile_b = np.zeros((nrows, width), dtype=np.int32)
    mask = np.zeros((nrows, width), dtype=bool)
    tile_a[r_idx, c_idx] = pair_a
    tile_b[r_idx, c_idx] = pair_b
    mask[r_idx, c_idx] = True
    ident = nrows == nnzb and bool(np.array_equal(
        seg, np.arange(nnzb, dtype=np.int32)))
    return tile_a, tile_b, mask, seg, ident


def spgemm_numeric_data(plan: SpGEMMPlan, a_data: Array, b_data: Array, *,
                        path: str | None = None,
                        use_kernel: bool | None = None,
                        interpret: bool | None = None,
                        tile_slots: int | None = None,
                        accum_dtype=None) -> Array:
    """Device numeric phase -> C.data.  Pure function of the plan + values.

    ``path`` selects the execution strategy ("fused" | "pairs" |
    "reference"); ``None`` resolves the backend default — fused on TPU,
    reference on CPU *and* GPU (Pallas does not lower these block shapes
    via Triton yet; see ``repro.kernels.backend``).  The
    legacy knob maps ``use_kernel=True`` to ``path="pairs"`` and an
    explicit ``use_kernel=False`` to ``path="reference"``.
    ``accum_dtype`` is the contraction/reduction accumulator on every path
    (None = native in ``a_data.dtype``; output always at ``a_data.dtype``).
    """
    from repro.kernels import backend as _backend
    if path is None and use_kernel is not None:
        path = "pairs" if use_kernel else "reference"
    path = _backend.resolve_spgemm_path(path, a_data.dtype)
    if path == "fused":
        return _fused_numeric(plan, a_data, b_data, interpret=interpret,
                              tile_slots=tile_slots,
                              accum_dtype=accum_dtype)
    seg = jnp.asarray(plan.out_idx)
    if path == "pairs":
        lhs = a_data[jnp.asarray(plan.pair_a)]           # (npairs, br, bk)
        rhs = b_data[jnp.asarray(plan.pair_b)]           # (npairs, bk, bc)
        # cast the operands up *before* the kernel chain so the pair
        # products stay at the accumulator between block_pair_gemm and
        # block_seg_sum (rounding each product back to the payload dtype
        # in between would violate the round-once accumulator rule)
        acc = (jnp.dtype(accum_dtype) if accum_dtype is not None
               else a_data.dtype)
        from repro.kernels.block_pair_gemm import ops as _kg
        prod = _kg.block_pair_gemm(lhs.astype(acc), rhs.astype(acc),
                                   interpret=interpret)
        from repro.kernels.block_seg_sum import ops as _ks
        out = _ks.block_seg_sum(prod, seg, plan.nnzb, interpret=interpret)
        return out.astype(a_data.dtype)
    # reference: lane-dense pair products (pairs on the minor axis), one
    # flat sorted segment sum
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else a_data.dtype
    br, bk, bc = a_data.shape[1], a_data.shape[2], b_data.shape[2]
    lhs = gather_lanes(to_lanes(a_data.astype(acc)), plan.pair_a)
    rhs = gather_lanes(to_lanes(b_data.astype(acc)), plan.pair_b)
    prod = block_matmul_lanes(lhs.reshape(br, bk, -1),
                              rhs.reshape(bk, bc, -1))
    out = segment_sum_lanes(prod.reshape(br * bc, -1), seg, plan.nnzb)
    return from_lanes(out, (br, bc)).astype(a_data.dtype)


def _fused_numeric(plan: SpGEMMPlan, a_data: Array, b_data: Array, *,
                   interpret: bool | None, tile_slots: int | None = None,
                   accum_dtype=None) -> Array:
    """One-pass numeric phase over the tiled plan layout.

    Gathers the A/B blocks into the fixed-width ELL-of-pairs operand stream
    (padded lhs slots zeroed, so padding contributes exactly 0.0) and hands
    it to the fused Pallas kernel, which contracts and reduces each output
    block in VMEM.  No array of shape ``(npairs, br, bc)`` is ever built.
    """
    from repro.kernels.fused_pair_gemm import ops as _kf
    br, bk, bc = a_data.shape[1], a_data.shape[2], b_data.shape[2]
    ta = jnp.asarray(plan.tile_pair_a).T     # (kmax, tile_rows)
    mask = jnp.asarray(plan.tile_mask).T
    # operands gathered lane-dense: (br, bk, kmax, tile_rows)
    lhs = jnp.where(mask, gather_lanes(to_lanes(a_data), ta), 0)
    rhs = gather_lanes(to_lanes(b_data), jnp.asarray(plan.tile_pair_b).T)
    out = _kf.fused_pair_gemm_lanes(
        lhs.reshape((br, bk) + ta.shape), rhs.reshape((bk, bc) + ta.shape),
        interpret=interpret, tile_slots=tile_slots, accum_dtype=accum_dtype)
    out = out.reshape(br * bc, -1)
    if plan.tile_identity:
        return from_lanes(out, (br, bc))
    # histogram-forced row splits: combine the O(nnzb)-sized row partials
    # (never the O(npairs) pair products), at the accumulator dtype
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else out.dtype
    out = segment_sum_lanes(out.astype(acc), plan.tile_seg, plan.nnzb)
    return from_lanes(out, (br, bc)).astype(a_data.dtype)


def spgemm_numeric(plan: SpGEMMPlan, A: BlockCSR, B: BlockCSR, **kw
                   ) -> BlockCSR:
    data = spgemm_numeric_data(plan, A.data, B.data, **kw)
    return BlockCSR.from_arrays(plan.indptr, plan.indices, data, plan.nbc)


def spgemm(A: BlockCSR, B: BlockCSR, **kw) -> BlockCSR:
    """One-shot product (symbolic + numeric).  Hot paths cache the plan."""
    return spgemm_numeric(spgemm_symbolic(A, B), A, B, **kw)


# ---------------------------------------------------------------------------
# Native block AXPY (paper Sec. 4.9 future work — implemented here).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockAXPYPlan:
    """Union-sparsity plan for C = alpha*X + Y with different patterns.

    PETSc's MatAXPY falls back to a scalar conversion when the operands do
    not share a sparsity pattern — the one residual conversion in the
    paper's cold path.  This plan makes it native: a one-time symbolic union
    plus numeric scatter of both operands.
    """
    indptr: np.ndarray
    indices: np.ndarray
    nbr: int
    nbc: int
    x_slot: np.ndarray     # output slot of every X block
    y_slot: np.ndarray     # output slot of every Y block
    nnzb: int
    x_state: int
    y_state: int


def block_axpy_symbolic(X: BlockCSR, Y: BlockCSR) -> BlockAXPYPlan:
    assert X.nbr == Y.nbr and X.nbc == Y.nbc
    assert X.block_shape == Y.block_shape
    nbr, nbc = X.nbr, X.nbc
    xr = np.repeat(np.arange(nbr, dtype=np.int64), np.diff(X.indptr))
    yr = np.repeat(np.arange(nbr, dtype=np.int64), np.diff(Y.indptr))
    keys = np.concatenate([xr * nbc + X.indices, yr * nbc + Y.indices])
    uniq, inv = np.unique(keys, return_inverse=True)
    indptr = np.zeros(nbr + 1, dtype=np.int64)
    np.add.at(indptr, (uniq // nbc) + 1, 1)
    return BlockAXPYPlan(indptr=np.cumsum(indptr),
                         indices=(uniq % nbc).astype(np.int32),
                         nbr=nbr, nbc=nbc,
                         x_slot=inv[:X.nnzb].astype(np.int64),
                         y_slot=inv[X.nnzb:].astype(np.int64),
                         nnzb=len(uniq),
                         x_state=X.state_token, y_state=Y.state_token)


def block_axpy_numeric_data(plan: BlockAXPYPlan, alpha, x_data: Array,
                            y_data: Array) -> Array:
    br, bc = x_data.shape[1], x_data.shape[2]
    # flat element scatters with the block count on the lanes
    n = plan.nnzb
    rows = jnp.arange(br * bc, dtype=jnp.int32)[:, None] * n
    out = jnp.zeros((br * bc * n,), x_data.dtype)
    out = out.at[(rows + jnp.asarray(plan.x_slot, jnp.int32)).reshape(-1)
                 ].add(to_lanes(alpha * x_data).reshape(-1))
    out = out.at[(rows + jnp.asarray(plan.y_slot, jnp.int32)).reshape(-1)
                 ].add(to_lanes(y_data).reshape(-1))
    return from_lanes(out.reshape(br * bc, n), (br, bc))


def block_axpy(alpha, X: BlockCSR, Y: BlockCSR) -> BlockCSR:
    """C = alpha*X + Y, natively blocked, no scalar conversion."""
    plan = block_axpy_symbolic(X, Y)
    data = block_axpy_numeric_data(plan, alpha, X.data, Y.data)
    return BlockCSR.from_arrays(plan.indptr, plan.indices, data, plan.nbc)
