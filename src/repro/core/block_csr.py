"""Rectangular-block sparse containers — the JAX analogue of MATBAIJKOKKOS.

The paper's contribution 1 is a portable blocked sparse matrix type whose
kernels are templated on *independent* row and column block sizes
(``bs_r x bs_c``).  Here the container is a host-symbolic / device-numeric
split:

* ``indptr`` / ``indices`` (the *structure*) are host ``numpy`` arrays.  All
  symbolic phases (SpGEMM plans, transpose plans, COO plans, strength graphs)
  consume them on the host, exactly as PETSc's symbolic phases do.
* ``data`` (the *values*) is a ``jax`` array of dense ``(nnzb, br, bc)``
  blocks, resident on the device.  All numeric phases are jitted functions of
  ``data`` (+ small device index arrays derived once from the structure).

This split is the functional rendering of PETSc's ``PetscObjectState`` gate
(paper Sec. 3.5): a *plan* is valid exactly as long as the structure it was
derived from; numeric recomputes reuse plans without any symbolic work.

Two layouts are provided:

``BlockCSR``
    the general container (BAIJ analogue), used by every symbolic phase.

``BlockELL``
    a padded fixed-width layout (``indices: (nbr, kmax)``) used by the SpMV
    kernels.  TPUs want regular grids: the ELL padding removes the
    data-dependent row loop, and rows are padded with index 0 + an explicit
    validity mask so padded lanes contribute exactly zero.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lanes import gather_lanes, to_lanes

Array = jax.Array

_STATE_COUNTER = [0]


def _next_state_token() -> int:
    """Monotone counter mirroring PetscObjectState (paper Sec. 3.5)."""
    _STATE_COUNTER[0] += 1
    return _STATE_COUNTER[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockCSR:
    """Rectangular-block CSR: ``nbr x nbc`` grid of ``br x bc`` dense blocks.

    Scalar shape is ``(nbr*br, nbc*bc)``.  ``br == bc == 1`` degenerates to
    scalar CSR (used by the scalar-AIJ baseline, see ``scalar_csr.py``).
    """

    indptr: np.ndarray      # (nbr+1,) int64/int32, host
    indices: np.ndarray     # (nnzb,)  int32, host
    data: Array             # (nnzb, br, bc), device
    nbc: int                # number of block columns
    state_token: int = 0    # bumped whenever structure is (re)created

    # ---- basic properties -------------------------------------------------
    @property
    def nbr(self) -> int:
        return len(self.indptr) - 1

    @property
    def nnzb(self) -> int:
        return int(self.indices.shape[0])

    @property
    def br(self) -> int:
        return int(self.data.shape[1])

    @property
    def bc(self) -> int:
        return int(self.data.shape[2])

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.nbr * self.br, self.nbc * self.bc)

    @property
    def block_shape(self) -> Tuple[int, int]:
        return (self.br, self.bc)

    # ---- constructors -----------------------------------------------------
    @staticmethod
    def from_arrays(indptr, indices, data, nbc) -> "BlockCSR":
        indptr = np.asarray(indptr, dtype=np.int64)
        indices = np.asarray(indices, dtype=np.int32)
        data = jnp.asarray(data)
        assert data.ndim == 3, "data must be (nnzb, br, bc)"
        assert data.shape[0] == indices.shape[0]
        return BlockCSR(indptr, indices, data, int(nbc),
                        state_token=_next_state_token())

    def with_data(self, data: Array) -> "BlockCSR":
        """Same structure, new values (numeric update — keeps state token)."""
        assert data.shape == self.data.shape, (data.shape, self.data.shape)
        return BlockCSR(self.indptr, self.indices, data, self.nbc,
                        self.state_token)

    # ---- conversions ------------------------------------------------------
    def to_dense(self) -> Array:
        """Densify (tests / coarse solve only — never on the hot path)."""
        br, bc = self.br, self.bc
        out = jnp.zeros((self.nbr, self.nbc, br, bc), self.data.dtype)
        rows = np.repeat(np.arange(self.nbr), np.diff(self.indptr))
        out = out.at[rows, self.indices].add(self.data)
        return out.transpose(0, 2, 1, 3).reshape(self.shape)

    def ell_plan(self, pad_to: int | None = None) -> "ELLPlan":
        """Host symbolic phase of the BCSR->BlockELL conversion.

        The plan (padded indices + gather map + validity mask) depends only
        on the structure; hot numeric recomputes rebuild ELL values with
        ``ell_data(plan, new_data)`` — no host round trip (paper Sec. 3.5).
        """
        counts = np.diff(self.indptr)
        kmax = int(counts.max()) if len(counts) else 0
        if pad_to is not None:
            kmax = max(kmax, pad_to)
        nbr = self.nbr
        idx = np.zeros((nbr, kmax), dtype=np.int32)
        sel = np.full((nbr, kmax), -1, dtype=np.int64)  # gather map into data
        for_r = np.repeat(np.arange(nbr), counts)
        within = np.arange(self.nnzb) - np.repeat(self.indptr[:-1], counts)
        idx[for_r, within] = self.indices
        sel[for_r, within] = np.arange(self.nnzb)
        mask = sel >= 0
        gather = np.where(mask, sel, 0)
        from repro.kernels.tiling import col_windows
        return ELLPlan(indices=idx, gather=gather, mask=mask, nbc=self.nbc,
                       state_token=self.state_token,
                       windows=col_windows(idx))

    def to_ell(self, pad_to: int | None = None) -> "BlockELL":
        """Convert to padded ELL layout for the SpMV kernels."""
        plan = self.ell_plan(pad_to)
        return plan.build(self.data)

    def block_norms(self) -> Array:
        """Frobenius norm of every block — strength-of-connection input.

        Paper Sec. 3.2: operator inspection runs over the bs x bs blocks of
        the block storage directly (no scalar expansion).
        """
        return _block_norms(self.data)

    def diagonal_blocks(self) -> Array:
        """(nbr, br, bc) array of diagonal blocks (zero where absent)."""
        assert self.br == self.bc, "diagonal blocks need square blocks"
        rows = np.repeat(np.arange(self.nbr), np.diff(self.indptr))
        is_diag = rows == self.indices
        out = jnp.zeros((self.nbr, self.br, self.bc), self.data.dtype)
        out = out.at[rows[is_diag]].set(self.data[np.flatnonzero(is_diag)])
        return out

    # ---- pytree protocol ----------------------------------------------
    # ``data`` is the only traced leaf; the structure is static aux data so a
    # jitted numeric phase retraces iff the structure object changes — the
    # functional analogue of the paper's state gate.
    def tree_flatten(self):
        aux = (_HashableArray(self.indptr), _HashableArray(self.indices),
               self.nbc, self.state_token)
        return (self.data,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        indptr, indices, nbc, tok = aux
        return cls(indptr.a, indices.a, children[0], nbc, tok)


@dataclasses.dataclass(frozen=True)
class ELLPlan:
    """Cached structure of a BCSR->ELL conversion (host symbolic)."""

    indices: np.ndarray   # (nbr, kmax) int32, padded -> block col 0
    gather: np.ndarray    # (nbr, kmax) int64 into BCSR data
    mask: np.ndarray      # (nbr, kmax) bool
    nbc: int
    state_token: int
    windows: np.ndarray = None  # x-window plan of the ELL kernels

    def ell_data(self, data: Array) -> Array:
        """Numeric phase: scatter BCSR values into the ELL layout (device).

        Gathered lane-dense (``repro.core.lanes``), then viewed as
        ``(nbr, kmax, br, bc)`` — the order the TPU stores that shape in."""
        t = gather_lanes(to_lanes(data), self.gather.T) * jnp.asarray(
            self.mask.T, data.dtype)
        return jnp.transpose(t.reshape(data.shape[1:] + self.gather.T.shape),
                             (3, 2, 0, 1))

    def build(self, data: Array) -> "BlockELL":
        return BlockELL(indices=jnp.asarray(self.indices),
                        data=self.ell_data(data),
                        mask=jnp.asarray(self.mask),
                        nbc=self.nbc,
                        state_token=self.state_token,
                        windows=jnp.asarray(self.windows))


class _HashableArray:
    """Identity-hashed numpy array wrapper for use in pytree aux data."""

    __slots__ = ("a", "_key")

    def __init__(self, a: np.ndarray):
        self.a = a
        self._key = (a.shape, a.dtype.str, a.tobytes())

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _HashableArray) and self._key == other._key


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockELL:
    """Padded fixed-width blocked layout (TPU-regular SpMV operand)."""

    indices: Array   # (nbr, kmax) int32, padded entries point at column 0
    data: Array      # (nbr, kmax, br, bc); padded blocks are exactly zero
    mask: Array      # (nbr, kmax) bool
    nbc: int
    state_token: int = 0
    # (ceil(nbr/128), n_win) int32: the 128-column tiles of x each 128-row
    # tile reads (``repro.kernels.tiling.col_windows``) — the plan of the
    # kernels' in-kernel x gather; None = derived from concrete indices
    windows: Array = None

    @property
    def nbr(self) -> int:
        return int(self.indices.shape[0])

    @property
    def kmax(self) -> int:
        return int(self.indices.shape[1])

    @property
    def br(self) -> int:
        return int(self.data.shape[2])

    @property
    def bc(self) -> int:
        return int(self.data.shape[3])

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.nbr * self.br, self.nbc * self.bc)

    def astype(self, dtype) -> "BlockELL":
        """Same structure, values cast to ``dtype`` (precision policies).

        Returns ``self`` when the dtype already matches, so full-precision
        policies stay bitwise on the original arrays.
        """
        if self.data.dtype == jnp.dtype(dtype):
            return self
        return BlockELL(indices=self.indices,
                        data=self.data.astype(dtype), mask=self.mask,
                        nbc=self.nbc, state_token=self.state_token,
                        windows=self.windows)

    def tree_flatten(self):
        return (self.indices, self.data, self.mask, self.windows), (
            self.nbc, self.state_token)

    @classmethod
    def tree_unflatten(cls, aux, children):
        nbc, tok = aux
        return cls(children[0], children[1], children[2], nbc, tok,
                   children[3])


# ---------------------------------------------------------------------------
# Structure helpers (host, numpy)
# ---------------------------------------------------------------------------

def coo_to_csr_structure(rows: np.ndarray, cols: np.ndarray, nbr: int,
                         sum_duplicates: bool = True):
    """Sort/unique (row, col) COO coordinates into CSR structure.

    Returns ``(indptr, indices, order, out_idx, nnzb)`` where ``order``
    stably sorts the input coordinates and ``out_idx[i]`` is the output slot
    of input coordinate ``i`` (after dedup).  This is the symbolic half of
    blocked COO assembly (paper Sec. 3.4 / 5).
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    ncols = int(cols.max()) + 1 if len(cols) else 0
    key = rows * max(ncols, 1) + cols
    order = np.argsort(key, kind="stable")
    skey = key[order]
    if sum_duplicates:
        uniq, inv_sorted = np.unique(skey, return_inverse=True)
    else:
        uniq, inv_sorted = skey, np.arange(len(skey))
    nnzb = len(uniq)
    out_idx = np.empty(len(key), dtype=np.int64)
    out_idx[order] = inv_sorted
    u_rows = uniq // max(ncols, 1)
    u_cols = uniq % max(ncols, 1)
    indptr = np.zeros(nbr + 1, dtype=np.int64)
    np.add.at(indptr, u_rows + 1, 1)
    indptr = np.cumsum(indptr)
    return indptr, u_cols.astype(np.int32), order, out_idx, nnzb


def transpose_structure(indptr: np.ndarray, indices: np.ndarray, nbc: int):
    """Symbolic CSR transpose: returns (t_indptr, t_indices, perm).

    ``perm[k]`` is the position in the input data of output nnz ``k``; the
    numeric transpose is ``data[perm].transpose(0, 2, 1)`` — this permutation
    is exactly the cached ``R = P^T`` of the paper's PtAP cache.
    """
    nbr = len(indptr) - 1
    rows = np.repeat(np.arange(nbr), np.diff(indptr))
    cols = np.asarray(indices, dtype=np.int64)
    key = cols * nbr + rows
    perm = np.argsort(key, kind="stable")
    t_rows = cols[perm]
    t_cols = rows[perm]
    t_indptr = np.zeros(nbc + 1, dtype=np.int64)
    np.add.at(t_indptr, t_rows + 1, 1)
    t_indptr = np.cumsum(t_indptr)
    return t_indptr, t_cols.astype(np.int32), perm


def transpose_bcsr(A: BlockCSR) -> BlockCSR:
    """Full (symbolic + numeric) blocked transpose."""
    t_indptr, t_indices, perm = transpose_structure(A.indptr, A.indices,
                                                    A.nbc)
    t_data = A.data[perm].transpose(0, 2, 1)
    return BlockCSR.from_arrays(t_indptr, t_indices, t_data, A.nbr)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EllTransposePlan:
    """Build-time plan for applying ``A^T`` straight off A's ELL blocks.

    The transpose-free restriction (``repro.core.spmv.apply_ell_t``): each
    output block row ``c`` lists the ELL *slots* of A holding a block in
    column ``c``, so the apply gathers from ``A``'s own ``(nbr, kmax, br,
    bc)`` payload, transposing block-local on register — no duplicated
    ``r_ell`` values or indices ever stored.  Slot order per output row
    matches ``transpose_structure``'s (fine rows ascending), so the
    summation order equals the stored-``r_ell`` apply's.

    Like ``BlockELL``, the index arrays are traced pytree leaves (constants
    inside jitted solves); ``nbr`` — A's block-row count, needed to fold
    the input vector into blocks — is static aux data.
    """

    rows: Array     # (nbc, tkmax) int32 — A's block row per slot, pad -> 0
    gather: Array   # (nbc, tkmax) int32 — flattened (nbr*kmax) ELL slots
    mask: Array     # (nbc, tkmax) bool — False on padded slots
    nbr: int        # block rows of the underlying A

    @property
    def nbc(self) -> int:
        return int(self.rows.shape[0])

    @property
    def tkmax(self) -> int:
        return int(self.rows.shape[1])

    def tree_flatten(self):
        return (self.rows, self.gather, self.mask), (self.nbr,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0])


def transpose_apply_plan(A: BlockCSR, kmax: int) -> EllTransposePlan:
    """Host symbolic phase of the transpose-free ``A^T`` apply.

    ``kmax`` is the slot width of A's ELL form (``A.to_ell().kmax``); the
    ELL slot of BCSR nonzero ``j`` is ``row(j) * kmax + within-row(j)``,
    which is what ``gather`` indexes after flattening A's ELL payload.
    """
    counts = np.diff(A.indptr)
    for_r = np.repeat(np.arange(A.nbr), counts)
    within = np.arange(A.nnzb) - np.repeat(A.indptr[:-1], counts)
    slot = for_r * kmax + within
    t_indptr, t_rows, perm = transpose_structure(A.indptr, A.indices, A.nbc)
    t_counts = np.diff(t_indptr)
    tkmax = max(int(t_counts.max()) if len(t_counts) else 0, 1)
    rows = np.zeros((A.nbc, tkmax), dtype=np.int32)
    gather = np.zeros((A.nbc, tkmax), dtype=np.int32)
    mask = np.zeros((A.nbc, tkmax), dtype=bool)
    out_r = np.repeat(np.arange(A.nbc), t_counts)
    out_w = np.arange(A.nnzb) - np.repeat(t_indptr[:-1], t_counts)
    rows[out_r, out_w] = t_rows
    gather[out_r, out_w] = slot[perm]
    mask[out_r, out_w] = True
    return EllTransposePlan(rows=jnp.asarray(rows),
                            gather=jnp.asarray(gather),
                            mask=jnp.asarray(mask), nbr=A.nbr)


@jax.jit
def _block_norms(data: Array) -> Array:
    return jnp.sqrt(jnp.sum(data * data, axis=(1, 2)))


def structure_bcsr(indptr, indices, nbc: int, br: int, bc: int, dtype,
                   state_token: int = 0) -> BlockCSR:
    """A ``BlockCSR`` standing for a structure only (symbolic phases): its
    data is a zero-stride host array of the right shape, so nothing is
    allocated or computed on the device."""
    data = np.broadcast_to(np.zeros((), dtype), (len(indices), br, bc))
    return BlockCSR(np.asarray(indptr, np.int64),
                    np.asarray(indices, np.int32), data, int(nbc),
                    state_token=state_token)


@partial(jax.jit, static_argnames=("nbr", "br", "bc"))
def _zeros_blocks(nbr: int, br: int, bc: int, dtype) -> Array:
    return jnp.zeros((nbr, br, bc), dtype)


def identity_bcsr(nbr: int, bs: int, dtype=jnp.float64) -> BlockCSR:
    indptr = np.arange(nbr + 1, dtype=np.int64)
    indices = np.arange(nbr, dtype=np.int32)
    eye = jnp.broadcast_to(jnp.eye(bs, dtype=dtype), (nbr, bs, bs))
    return BlockCSR.from_arrays(indptr, indices, eye, nbr)
