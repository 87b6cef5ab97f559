"""Pallas TPU kernel: point-block Jacobi apply (the paper's smoother).

pbjacobi applies the inverse of each diagonal ``bs x bs`` block to the
residual block: ``y_i = D_i^{-1} r_i``.  The inverses are precomputed at
setup (cold); the hot kernel is a batched small matvec, fused with the
damped-Jacobi update ``x += omega * y`` so the smoother reads r and x once.

Layout / tiling (lane-dense, see ``repro.kernels.tiling``)
  grid      = (ceil(nbr / TL),)
  omega     = (1,)          SMEM
  dinv tile = (bs, bs, TL)  VMEM   rows on lanes
  r/x tiles = (bs, TL)      VMEM
  out tile  = (bs, TL)      VMEM
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _pbjacobi_kernel(acc_dt, omega_ref, dinv_ref, r_ref, x_ref, o_ref):
    bs = dinv_ref.shape[0]
    omega = omega_ref[0].astype(acc_dt)
    r = [r_ref[b:b + 1, :].astype(acc_dt) for b in range(bs)]
    for a in range(bs):
        y = dinv_ref[a, 0:1, :].astype(acc_dt) * r[0]
        for b in range(1, bs):
            y = y + dinv_ref[a, b:b + 1, :].astype(acc_dt) * r[b]
        o_ref[a:a + 1, :] = (x_ref[a:a + 1, :].astype(acc_dt)
                             + omega * y).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile_rows", "interpret", "accum_dtype"))
def pbjacobi_update(dinv: jax.Array, r: jax.Array, x: jax.Array,
                    omega: jax.Array, *, interpret: bool,
                    tile_rows: int | None = None,
                    accum_dtype=None) -> jax.Array:
    """x + omega * D^{-1} r over (nbr, bs) block vectors.

    ``accum_dtype`` is the on-register dtype of the block matvec and the
    damped update (None = native in ``dinv.dtype``); the result is rounded
    back to ``dinv.dtype``.  ``tile_rows`` is rows (lanes) per grid step,
    rounded up to a multiple of 128; None sizes it from the VMEM budget.
    """
    nbr, bs, _ = dinv.shape
    dt = dinv.dtype
    acc_dt = jnp.dtype(accum_dtype) if accum_dtype is not None else dt
    per_lane = (tiling.lane_bytes((bs, bs), dt)
                + 3 * tiling.lane_bytes((bs,), dt))
    tl = tiling.lane_tile(nbr, per_lane, tile_rows)
    omega = jnp.asarray(omega, acc_dt).reshape(1)
    out = pl.pallas_call(
        functools.partial(_pbjacobi_kernel, acc_dt),
        grid=(pl.cdiv(nbr, tl),),
        in_specs=[tiling.smem_spec(1),
                  tiling.lane_spec((bs, bs, tl)),
                  tiling.lane_spec((bs, tl)),
                  tiling.lane_spec((bs, tl))],
        out_specs=tiling.lane_spec((bs, tl)),
        out_shape=jax.ShapeDtypeStruct((bs, nbr), dt),
        compiler_params=tiling.compiler_params(per_lane * tl),
        interpret=interpret,
    )(omega, jnp.transpose(dinv, (1, 2, 0)), r.T, x.T)
    return out.T
