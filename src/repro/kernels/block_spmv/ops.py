"""Jit'd wrapper dispatching the blocked SpMV kernel on a BlockELL."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.block_csr import BlockELL
from repro.kernels import backend
from repro.kernels.block_spmv.block_spmv import block_spmv_ell
from repro.obs import trace as obs_trace


def block_spmv(ell: BlockELL, x: jax.Array, *, interpret: bool | None = None,
               tile_rows: int | None = None, accum_dtype=None) -> jax.Array:
    """y = A @ x, flat vectors in/out (matches repro.core.spmv.spmv_ell).

    ``interpret=None`` compiles on TPU and interprets elsewhere
    (``backend.kernel_interpret``, which refuses a compiled f64 call).
    ``tile_rows=None`` resolves through the autotuner
    (``repro.kernels.autotune``, governed by ``REPRO_TUNE``; without a
    cached winner the kernel sizes its lane tile from the VMEM budget).
    """
    with obs_trace.scope("kernels/block_spmv"):
        interpret = backend.kernel_interpret(interpret, ell.data.dtype,
                                             "block_spmv")
        if tile_rows is None:
            from repro.kernels import autotune
            tile_rows = autotune.resolve_param(
                "block_spmv",
                dict(br=ell.br, bc=ell.bc, kmax=ell.kmax,
                     dtype=jnp.dtype(ell.data.dtype).name),
                "tile_rows", None, None)
        xb = x.reshape(ell.nbc, ell.bc)
        y = block_spmv_ell(ell.indices, ell.data, xb, tile_rows=tile_rows,
                           interpret=interpret, accum_dtype=accum_dtype,
                           windows=ell.windows)
        return y.reshape(ell.nbr * ell.br)
