"""Shared test helpers: random blocked matrices + dense oracles."""
from __future__ import annotations

import numpy as np

import repro.core  # noqa: F401  (enables x64)
from repro.core.block_csr import BlockCSR


def random_bcsr(rng: np.random.Generator, nbr: int, nbc: int, br: int,
                bc: int, density: float = 0.3, ensure_diag: bool = False,
                dtype=np.float64) -> BlockCSR:
    """Random rectangular-block CSR with at least one block per row."""
    mask = rng.random((nbr, nbc)) < density
    for i in range(nbr):
        if not mask[i].any():
            mask[i, rng.integers(nbc)] = True
        if ensure_diag and nbr == nbc:
            mask[i, i] = True
    rows, cols = np.nonzero(mask)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    indptr = np.zeros(nbr + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr)
    data = rng.standard_normal((len(rows), br, bc)).astype(dtype)
    return BlockCSR.from_arrays(indptr, cols.astype(np.int32), data, nbc)


def spd_bcsr(rng: np.random.Generator, nbr: int, bs: int,
             density: float = 0.25) -> BlockCSR:
    """Random symmetric positive definite blocked matrix (for solvers)."""
    A = random_bcsr(rng, nbr, nbr, bs, bs, density, ensure_diag=True)
    dense = np.asarray(A.to_dense())
    sym = 0.5 * (dense + dense.T)
    n = dense.shape[0]
    spd = sym + n * np.eye(n)  # diagonally dominant => SPD
    # rebuild blocked structure from the symmetrized dense (union pattern)
    blocks = spd.reshape(nbr, bs, nbr, bs).transpose(0, 2, 1, 3)
    bmask = (np.abs(blocks).max(axis=(2, 3)) > 0)
    rows, cols = np.nonzero(bmask)
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    indptr = np.zeros(nbr + 1, dtype=np.int64)
    np.add.at(indptr, rows + 1, 1)
    return BlockCSR.from_arrays(np.cumsum(indptr), cols.astype(np.int32),
                                blocks[rows, cols], nbr)


def jaxpr_outputs(jaxpr) -> list:
    """``(primitive name, output shape)`` of every equation of ``jaxpr``
    and, recursively, of every sub-jaxpr in its equations' params (scan
    and loop bodies, pallas kernel bodies)."""
    from jax.extend import core as jcore
    acc = []

    def walk(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                shape = getattr(getattr(v, "aval", None), "shape", None)
                if shape is not None:
                    acc.append((eqn.primitive.name, tuple(shape)))
            for val in eqn.params.values():
                if isinstance(val, jcore.ClosedJaxpr):
                    walk(val.jaxpr)
                elif isinstance(val, jcore.Jaxpr):
                    walk(val)

    walk(jaxpr)
    return acc
