"""Jit'd wrapper for the pbjacobi smoother update on flat vectors."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.pbjacobi.pbjacobi import pbjacobi_update
from repro.obs import trace as obs_trace


def pbjacobi_apply(dinv: jax.Array, r: jax.Array, x: jax.Array, omega,
                   *, interpret: bool | None = None,
                   tile_rows: int | None = None,
                   accum_dtype=None) -> jax.Array:
    """Flat-vector front door: x, r are (nbr*bs,).

    ``interpret=None`` compiles on TPU and interprets elsewhere
    (``backend.kernel_interpret``, which refuses a compiled f64 call).
    ``tile_rows=None`` resolves through the autotuner
    (``repro.kernels.autotune``, governed by ``REPRO_TUNE``; without a
    cached winner the lane tile comes from the VMEM budget).
    """
    with obs_trace.scope("kernels/pbjacobi"):
        interpret = backend.kernel_interpret(interpret, dinv.dtype,
                                             "pbjacobi")
        nbr, bs, _ = dinv.shape
        if tile_rows is None:
            from repro.kernels import autotune
            tile_rows = autotune.resolve_param(
                "pbjacobi",
                dict(bs=bs, dtype=jnp.dtype(dinv.dtype).name),
                "tile_rows", None, None)
        out = pbjacobi_update(dinv, r.reshape(nbr, bs), x.reshape(nbr, bs),
                              omega, tile_rows=tile_rows,
                              interpret=interpret, accum_dtype=accum_dtype)
        return out.reshape(-1)
