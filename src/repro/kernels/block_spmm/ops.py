"""Jit'd wrapper dispatching the blocked SpMM kernel on a BlockELL.

Pads the panel width to a ``pad_k_to`` multiple before the ``pallas_call``
(lane alignment — see the kernel docstring) and slices the padding back
off, so callers see exactly the ``(n, k)`` contract of
``repro.core.spmv.spmm_ell``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.block_csr import BlockELL
from repro.kernels import backend
from repro.kernels.block_spmm.block_spmm import block_spmm_ell
from repro.obs import trace as obs_trace


def block_spmm(ell: BlockELL, X: jax.Array, *, interpret: bool | None = None,
               tile_rows: int | None = None, pad_k_to: int | None = None,
               accum_dtype=None) -> jax.Array:
    """Y = A @ X, flat (n, k) panels in/out (matches core ``spmm_ell``).

    ``interpret=None`` compiles on TPU and interprets elsewhere
    (``backend.kernel_interpret``, which refuses a compiled f64 call).
    ``tile_rows=None`` / ``pad_k_to=None`` resolve through the autotuner
    (``repro.kernels.autotune``, governed by ``REPRO_TUNE``; without a
    cached winner the lane tile comes from the VMEM budget and the panel
    pads to 8 columns).
    """
    with obs_trace.scope("kernels/block_spmm"):
        interpret = backend.kernel_interpret(interpret, ell.data.dtype,
                                             "block_spmm")
        k = X.shape[1]
        if tile_rows is None or pad_k_to is None:
            from repro.kernels import autotune
            sig = dict(br=ell.br, bc=ell.bc, kmax=ell.kmax, k=k,
                       dtype=jnp.dtype(ell.data.dtype).name)
            if tile_rows is None:
                tile_rows = autotune.resolve_param(
                    "block_spmm", sig, "tile_rows", None, None)
            if pad_k_to is None:
                pad_k_to = autotune.resolve_param(
                    "block_spmm", sig, "pad_k_to", None, 8)
        kp = -(-k // pad_k_to) * pad_k_to if pad_k_to > 1 else k
        xb = X.reshape(ell.nbc, ell.bc, k)
        if kp != k:
            xb = jnp.pad(xb, ((0, 0), (0, 0), (0, kp - k)))
        y = block_spmm_ell(ell.indices, ell.data, xb, tile_rows=tile_rows,
                           interpret=interpret, accum_dtype=accum_dtype,
                           windows=ell.windows)
        return y.reshape(ell.nbr * ell.br, kp)[:, :k]
