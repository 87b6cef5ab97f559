"""Jit'd wrapper for the fused tiled pair-GEMM + segment-reduce kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_pair_gemm.fused_pair_gemm import (
    default_tile_slots,
    fused_pair_gemm_lanes as _fused_pair_gemm_lanes,
)
from repro.kernels import backend
from repro.obs import trace as obs_trace

__all__ = ["fused_pair_gemm", "fused_pair_gemm_lanes", "default_tile_slots"]


def fused_pair_gemm_lanes(lhs: jax.Array, rhs: jax.Array, *,
                          tile_slots: int | None = None,
                          interpret: bool | None = None,
                          accum_dtype=None) -> jax.Array:
    """Front door on lane-dense operands ``(br, bk, kmax, nslots)`` and
    ``(bk, bc, kmax, nslots)`` -> ``(br, bc, nslots)``, inside the
    ``kernels/fused_pair_gemm`` stage scope.

    ``interpret=None`` compiles on TPU and interprets elsewhere
    (``backend.kernel_interpret``, which refuses a compiled f64 call).
    ``tile_slots=None`` resolves through the autotuner
    (``repro.kernels.autotune``, governed by ``REPRO_TUNE``); no cached
    winner falls back to the kernel's VMEM-budget ``default_tile_slots``.
    """
    with obs_trace.scope("kernels/fused_pair_gemm"):
        interpret = backend.kernel_interpret(interpret, lhs.dtype,
                                             "fused_pair_gemm")
        if tile_slots is None:
            from repro.kernels import autotune
            br, bk, kmax, _ = lhs.shape
            tile_slots = autotune.resolve_param(
                "fused_pair_gemm",
                dict(br=br, bk=bk, bc=rhs.shape[1], kmax=kmax,
                     dtype=jnp.dtype(lhs.dtype).name),
                "tile_slots", None, None)
        return _fused_pair_gemm_lanes(lhs, rhs, tile_slots=tile_slots,
                                      interpret=interpret,
                                      accum_dtype=accum_dtype)


def fused_pair_gemm(lhs: jax.Array, rhs: jax.Array, *,
                    tile_slots: int | None = None,
                    interpret: bool | None = None,
                    accum_dtype=None) -> jax.Array:
    """Row-major blocks: ``(nslots, kmax, br, bk) @ (nslots, kmax, bk, bc)
    -> (nslots, br, bc)`` through ``fused_pair_gemm_lanes``."""
    out = fused_pair_gemm_lanes(jnp.transpose(lhs, (2, 3, 1, 0)),
                                jnp.transpose(rhs, (2, 3, 1, 0)),
                                tile_slots=tile_slots, interpret=interpret,
                                accum_dtype=accum_dtype)
    return jnp.transpose(out, (2, 0, 1))
