"""Jit'd wrapper for the fused smoother step on flat vectors.

``repro.core.vcycle.apply_smoother`` dispatches here when the smoother
path resolves to "fused" (``REPRO_SMOOTH_PATH``); the dist solver's
replicated tail rides the same dispatch, so single-device and distributed
smoothing share one source of truth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.block_csr import BlockELL
from repro.kernels import backend
from repro.kernels.fused_smoother.fused_smoother import smoother_step_ell
from repro.obs import trace as obs_trace


def smoother_step(a_ell: BlockELL, dinv: jax.Array, b: jax.Array,
                  x: jax.Array, d: jax.Array, c1, c2, *,
                  interpret: bool | None = None,
                  tile_rows: int | None = None,
                  accum_dtype=None):
    """One fused step: d' = c1*d + c2*D^{-1}(b - A x), x' = x + d'.

    b/x/d are flat ``(n,)`` vectors or ``(n, k)`` panels; returns
    ``(x', d')`` in the same shape.  ``c1``/``c2`` may be python scalars
    or traced values.  ``interpret=None`` compiles on TPU and interprets
    elsewhere (``backend.kernel_interpret``, which refuses a compiled f64
    call).  ``tile_rows=None`` resolves through the autotuner
    (``repro.kernels.autotune``, governed by ``REPRO_TUNE``; without a
    cached winner the lane tile comes from the VMEM budget).
    """
    with obs_trace.scope("kernels/fused_smoother"):
        interpret = backend.kernel_interpret(interpret, a_ell.data.dtype,
                                             "fused_smoother")
        nbr, kmax, bs, _ = a_ell.data.shape
        if tile_rows is None:
            from repro.kernels import autotune
            tile_rows = autotune.resolve_param(
                "fused_smoother",
                dict(br=bs, bc=bs, kmax=kmax,
                     dtype=jnp.dtype(a_ell.data.dtype).name),
                "tile_rows", None, None)
        shape = (nbr, bs) + b.shape[1:]
        dt = a_ell.data.dtype
        coef = jnp.stack([jnp.asarray(c1, dt), jnp.asarray(c2, dt)])
        x_new, d_new = smoother_step_ell(
            a_ell.indices, a_ell.data, dinv, b.reshape(shape),
            x.reshape(shape), d.reshape(shape), coef,
            tile_rows=tile_rows, interpret=interpret,
            accum_dtype=accum_dtype, windows=a_ell.windows)
        return x_new.reshape(b.shape), d_new.reshape(b.shape)
