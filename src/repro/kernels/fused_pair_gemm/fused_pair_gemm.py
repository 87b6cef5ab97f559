"""Pallas TPU kernel: fused pair-GEMM + segment reduce over the tiled
(ELL-of-pairs) SpGEMM plan layout — the one-pass Galerkin numeric phase.

The unfused numeric SpGEMM runs as three device dispatches

    gather -> batched rectangular block GEMM -> sorted segment-sum

and materializes the full ``(npairs, br, bc)`` pair-product array in HBM
between the last two.  That intermediate is the JAX-level rendition of the
cuSPARSE symbolic/numeric buffer blowup the paper escapes (Sec. 4.5): it is
pure bandwidth with zero arithmetic intensity.

This kernel consumes the *tiled* plan layout instead (``SpGEMMPlan.tile_*``):
the sorted pair list is re-packed into one fixed-width row per output block
slot (width ``pair_kmax`` from the pair histogram, zero-padded), so

  * each grid step owns a contiguous run of ``TS`` output slots,
  * the ``(br, bk) @ (bk, bc)`` contractions of a slot's pairs are whole
    ``(kmax, TS)`` slab products, and
  * the per-slot reduction runs over the slot's pairs on-register — the
    pair-product array never exists in HBM.

Layout / tiling (lane-dense, see ``repro.kernels.tiling``)
  grid     = (ceil(nslots / TS),)
  lhs tile = (br, bk, kmax, TS)  VMEM   gathered A blocks (padded pairs = 0)
  rhs tile = (bk, bc, kmax, TS)  VMEM   gathered B blocks
  out tile = (br, bc, TS)        VMEM   fully reduced output blocks

Slots sit on the 128 lanes; with bs = 3..6 the kernel stays
bandwidth-bound and the win is the removed ``npairs * br * bc`` round trip
plus the index bytes (paper Sec. 4.7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _fused_kernel(acc_dt, lhs_ref, rhs_ref, o_ref):
    br, bk = lhs_ref.shape[:2]
    for i in range(br):
        for j in range(rhs_ref.shape[1]):
            acc = (lhs_ref[i, 0].astype(acc_dt)
                   * rhs_ref[0, j].astype(acc_dt))
            for c in range(1, bk):
                acc = acc + (lhs_ref[i, c].astype(acc_dt)
                             * rhs_ref[c, j].astype(acc_dt))
            o_ref[i, j:j + 1, :] = jnp.sum(
                acc, axis=0, keepdims=True).astype(o_ref.dtype)


def _lane_bytes(kmax: int, br: int, bk: int, bc: int, dtype) -> int:
    return (tiling.lane_bytes((br, bk, kmax), dtype)
            + tiling.lane_bytes((bk, bc, kmax), dtype)
            + tiling.lane_bytes((br, bc), dtype))


def default_tile_slots(nslots: int, kmax: int, br: int, bk: int, bc: int,
                       dtype=jnp.float32) -> int:
    """Slots per grid step from the padded VMEM bytes of one step."""
    return tiling.lane_tile(nslots, _lane_bytes(kmax, br, bk, bc, dtype))


@functools.partial(jax.jit,
                   static_argnames=("tile_slots", "interpret", "accum_dtype"))
def fused_pair_gemm_lanes(lhs: jax.Array, rhs: jax.Array, *,
                          interpret: bool, tile_slots: int | None = None,
                          accum_dtype=None) -> jax.Array:
    """Lane-dense operands: ``(br, bk, kmax, nslots)`` @ ``(bk, bc, kmax,
    nslots)`` -> ``(br, bc, nslots)``.

    Contracts each slot's ``kmax`` padded block pairs and reduces them into
    the slot's output block in one pass (padded pairs must be zero blocks on
    at least one side).  ``accum_dtype`` is the accumulator dtype (None =
    native in ``lhs.dtype``); the output rounds back to ``lhs.dtype``.
    ``tile_slots`` is rounded up to a multiple of 128 lanes; None sizes it
    from the VMEM budget (``default_tile_slots``).
    """
    br, bk, kmax, nslots = lhs.shape
    bk2, bc, kmax2, _ = rhs.shape
    assert kmax == kmax2 and bk == bk2, (lhs.shape, rhs.shape)
    dt = lhs.dtype
    acc_dt = jnp.dtype(accum_dtype) if accum_dtype is not None else dt
    if nslots == 0 or kmax == 0:
        return jnp.zeros((br, bc, nslots), dt)
    per_lane = _lane_bytes(kmax, br, bk, bc, dt)
    ts = tiling.lane_tile(nslots, per_lane, tile_slots)
    return pl.pallas_call(
        functools.partial(_fused_kernel, acc_dt),
        grid=(pl.cdiv(nslots, ts),),
        in_specs=[tiling.lane_spec((br, bk, kmax, ts)),
                  tiling.lane_spec((bk, bc, kmax, ts))],
        out_specs=tiling.lane_spec((br, bc, ts)),
        out_shape=jax.ShapeDtypeStruct((br, bc, nslots), dt),
        compiler_params=tiling.compiler_params(per_lane * ts),
        interpret=interpret,
    )(lhs, rhs)


def fused_pair_gemm(lhs: jax.Array, rhs: jax.Array, *, interpret: bool,
                    tile_slots: int | None = None,
                    accum_dtype=None) -> jax.Array:
    """Row-major blocks: (nslots, kmax, br, bk) @ (nslots, kmax, bk, bc) ->
    (nslots, br, bc); ``fused_pair_gemm_lanes`` on the transposed
    operands."""
    out = fused_pair_gemm_lanes(jnp.transpose(lhs, (2, 3, 1, 0)),
                                jnp.transpose(rhs, (2, 3, 1, 0)),
                                interpret=interpret, tile_slots=tile_slots,
                                accum_dtype=accum_dtype)
    return jnp.transpose(out, (2, 0, 1))
