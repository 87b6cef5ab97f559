"""Tentative prolongator from the near-null space (paper Sec. 2.2).

Each aggregate contributes ``nns`` coarse degrees of freedom (six rigid-body
modes for 3D elasticity), so the tentative prolongator P~ has rectangular
``bs_f x nns`` blocks — the shape square-BSR vendor formats cannot store and
the reason this framework exists.

Construction: stack the near-null-space rows of every aggregate, batched
(reduced) QR on device, Q gives the prolongator blocks and R the coarse
near-null space.  Aggregates are padded to the maximum size with zero rows;
because R is invertible (the aggregator guarantees >= nns rows per
aggregate), padded rows of Q are exactly zero and are simply not stored.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import Aggregation
from repro.core.block_csr import BlockCSR

Array = jnp.ndarray


def tentative_prolongator(aggr: Aggregation, B: Array, bs_f: int
                          ) -> Tuple[BlockCSR, Array]:
    """Build P~ (block rows = fine nodes, block cols = aggregates) and B_c.

    B: (n_nodes * bs_f, nns) fine near-null space.
    Returns (P~ as BlockCSR with (bs_f x nns) blocks, B_c (n_agg*nns, nns)).
    """
    n_nodes = len(aggr.node_to_agg)
    nns = B.shape[1]
    assert B.shape[0] == n_nodes * bs_f, (B.shape, n_nodes, bs_f)
    sizes = aggr.sizes()
    max_sz = int(sizes.max())
    assert (sizes * bs_f >= nns).all(), (
        "aggregate too small for full-rank tentative prolongator; "
        "the aggregator's min_size repair should prevent this")
    # order nodes by aggregate; position of each node within its aggregate
    order = np.argsort(aggr.node_to_agg, kind="stable")
    agg_sorted = aggr.node_to_agg[order]
    starts = np.zeros(aggr.n_agg + 1, dtype=np.int64)
    np.add.at(starts, agg_sorted + 1, 1)
    starts = np.cumsum(starts)
    pos_in_agg = np.arange(n_nodes) - starts[agg_sorted]

    inv = np.empty(n_nodes, dtype=np.int64)
    inv[order] = np.arange(n_nodes)
    p_data, B_c = _tentative_numeric(B, order, agg_sorted, pos_in_agg, inv,
                                   n_agg=aggr.n_agg, max_sz=max_sz,
                                   bs_f=bs_f)
    indptr = np.arange(n_nodes + 1, dtype=np.int64)
    indices = aggr.node_to_agg.astype(np.int32)
    P = BlockCSR.from_arrays(indptr, indices, p_data, aggr.n_agg)
    return P, B_c


@partial(jax.jit, static_argnames=("n_agg", "max_sz", "bs_f"))
def _tentative_numeric(B, order, agg_sorted, pos_in_agg, inv, *,
                       n_agg: int, max_sz: int, bs_f: int):
    """Device part of ``tentative_prolongator``, one program: per-node
    ``(bs_f, nns)`` blocks of P~ in node order, and the coarse near-null
    space (each aggregate's R, stacked)."""
    nns = B.shape[1]
    # padded per-aggregate near-null blocks: (n_agg, max_sz, bs_f, nns)
    Bn = B.reshape(-1, bs_f, nns)
    padded = jnp.zeros((n_agg, max_sz, bs_f, nns), B.dtype)
    padded = padded.at[agg_sorted, pos_in_agg].set(Bn[order])
    stacked = padded.reshape(n_agg, max_sz * bs_f, nns)

    Q, R = jnp.linalg.qr(stacked)            # (n_agg, max_sz*bs_f, nns)
    # sign-fix for determinism: positive R diagonal
    sgn = jnp.sign(jnp.diagonal(R, axis1=1, axis2=2))
    sgn = jnp.where(sgn == 0, 1.0, sgn)
    Q = Q * sgn[:, None, :]
    R = R * sgn[:, :, None]

    # extract each node's (bs_f x nns) slice of its aggregate's Q, then
    # back to node order (one block per node row, column = aggregate)
    Qb = Q.reshape(n_agg, max_sz, bs_f, nns)
    return Qb[agg_sorted, pos_in_agg][inv], R.reshape(n_agg * nns, nns)
