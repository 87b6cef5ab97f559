"""Pallas TPU kernel: blocked ELL SpMV (the V-cycle hot spot).

TPU adaptation of the paper's BSR SpMV (Sec. 4.2).  A GPU BSR kernel assigns
a warp per block row and coalesces the per-block index gather; the TPU
analogue is *regular tiling*: the padded BlockELL layout gives every block
row exactly ``kmax`` slots, so the kernel is dense over lane tiles of rows
with no data-dependent control flow, which is what the TPU pipeline wants.

Index-traffic amortization (the paper's core argument) survives: one int32
per block addresses the whole ``br*bc`` payload; the ELL padding adds only
zero blocks.

The SpMV is the single-column case of the panel kernel
(``repro.kernels.block_spmm``): same lane-dense layout (rows on the 128
lanes, ``(br, bc)`` block dims leading), same windowed in-kernel gather
of ``x``, same accumulator rule (``accum_dtype``; None =
native, bf16 payloads should pass ``jnp.float32``).
"""
from __future__ import annotations

import jax

from repro.kernels.block_spmm.block_spmm import block_spmm_ell


def block_spmv_ell(indices: jax.Array, data: jax.Array, x_blocks: jax.Array,
                   *, interpret: bool, tile_rows: int | None = None,
                   accum_dtype=None, windows=None) -> jax.Array:
    """y = A @ x with A in padded BlockELL form.

    indices:  (nbr, kmax) int32, padded slots point at block-col 0
    data:     (nbr, kmax, br, bc), padded slots are zero blocks
    x_blocks: (nbc, bc)
    windows:  column-window plan of ``indices`` (see ``block_spmm_ell``)
    returns   (nbr, br) at ``data.dtype``
    """
    return block_spmm_ell(indices, data, x_blocks[..., None],
                          interpret=interpret, tile_rows=tile_rows,
                          accum_dtype=accum_dtype, windows=windows)[..., 0]
