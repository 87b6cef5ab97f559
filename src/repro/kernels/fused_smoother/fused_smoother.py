"""Pallas TPU kernel: one fused Chebyshev/Jacobi smoother recurrence step.

The unfused smoother recurrences in ``repro.core.vcycle`` materialize two
HBM intermediates per step — the residual ``r = b - A x`` and the
preconditioned residual ``z = D^{-1} r`` — each written by one dispatch and
re-read by the next.  This kernel computes the whole step

    d' = c1 * d + c2 * D^{-1}(b - A x)
    x' = x + d'

in a single pass per lane tile: the A-row contraction, the dinv block
matvec, the direction recurrence and the iterate update all happen
on-register, so ``r`` and ``z`` never touch HBM.  Both smoothers are this
one step with different coefficients (Chebyshev: ``c1 = 0, c2 = 1/theta``
first, then ``c1 = rho' rho, c2 = 2 rho'/delta``; damped block-Jacobi:
``c1 = 0, c2 = omega`` every step) — see ``repro.core.vcycle``.

The residual is formed fresh from the *current* iterate each step (the
paper's ``x += f(D^{-1}(b - A x))`` form), which is mathematically
identical to the unfused incremental update ``r -= A d`` and differs only
in rounding.

Layout / tiling (lane-dense, mirrors ``block_spmm``)
  grid       = (ceil(nbr / 128), n_win)   row tiles x their x windows
  windows    = (ceil(nbr / 128), n_win)   SMEM (scalar prefetch)
  coef       = (2,)                 SMEM          [c1, c2] at accum dtype
  index tile = (kmax, 128)          VMEM          A's indices, rows on lanes
  data tile  = (bs, bs, kmax, 128)  VMEM          A's payload, rows on lanes
  x window   = (bs*k, 128)          VMEM          128 columns of x
  x-gather   = (bs*k, kmax, 128)    VMEM          scratch, assembled over j
  dinv tile  = (bs, bs, 128)        VMEM
  b/d/x      = (bs, k, 128)         VMEM          (k = 1 for a vector)
  out tiles  = x' and d' (bs, k, 128)

``x`` is gathered inside the kernel from the windows its row tile reads
(``repro.kernels.tiling.gather_window``), so neither the gathered operand
nor ``r``/``z`` exists outside VMEM.
``accum_dtype`` follows the family contract: operands cast up on-register,
contracted/updated at that dtype, results rounded back to the payload
dtype (None = native).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling


def _smoother_kernel(acc_dt, k, win_ref, coef_ref, idx_ref, a_ref, xw_ref,
                     dinv_ref, b_ref, d_ref, x_ref, ox_ref, od_ref,
                     xg_ref):
    """One (row tile, x window) step; once the tile's last window is
    gathered: residual, precondition, recurrence, update — fused."""
    tiling.gather_window(win_ref, idx_ref, xw_ref, xg_ref)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        bs = a_ref.shape[0]
        c1 = coef_ref[0].astype(acc_dt)
        c2 = coef_ref[1].astype(acc_dt)
        for m in range(k):
            r = [b_ref[a, m:m + 1, :].astype(acc_dt)
                 - tiling.slab_dot(acc_dt, a_ref, xg_ref, a, m, k)
                 for a in range(bs)]
            for a in range(bs):
                z = dinv_ref[a, 0:1, :].astype(acc_dt) * r[0]
                for c in range(1, bs):
                    z = z + dinv_ref[a, c:c + 1, :].astype(acc_dt) * r[c]
                d_new = c1 * d_ref[a, m:m + 1, :].astype(acc_dt) + c2 * z
                od_ref[a, m:m + 1, :] = d_new.astype(od_ref.dtype)
                ox_ref[a, m:m + 1, :] = (x_ref[a, m:m + 1, :].astype(acc_dt)
                                         + d_new).astype(ox_ref.dtype)


def smoother_step_ell(indices: jax.Array, data: jax.Array, dinv: jax.Array,
                      b_blocks: jax.Array, x_blocks: jax.Array,
                      d_blocks: jax.Array, coef: jax.Array, *,
                      interpret: bool, tile_rows: int | None = None,
                      accum_dtype=None, windows=None):
    """(x', d') for one fused recurrence step over block vectors.

    indices/data: A in padded BlockELL form (square: nbc == nbr)
    dinv:         (nbr, bs, bs) pre-inverted diagonal blocks
    b/x/d_blocks: (nbr, bs) or (nbr, bs, k) block vectors
    coef:         (2,) = [c1, c2]
    windows:      column-window plan of ``indices``
                  (``repro.kernels.tiling.col_windows``); computed here
                  when ``indices`` is concrete
    returns       (x', d') at ``data.dtype``
    ``tile_rows`` may only ask for the one 128-row tile.
    """
    tiling.gather_tile(tile_rows)
    return _smoother_step_ell(indices, data, dinv, b_blocks, x_blocks,
                              d_blocks, coef,
                              tiling.ell_windows(indices, windows),
                              interpret=interpret, accum_dtype=accum_dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "accum_dtype"))
def _smoother_step_ell(indices, data, dinv, b_blocks, x_blocks, d_blocks,
                       coef, windows, *, interpret: bool, accum_dtype=None):
    nbr, kmax, bs, _ = data.shape
    dt = data.dtype
    acc_dt = jnp.dtype(accum_dtype) if accum_dtype is not None else dt
    gdt = tiling.gather_dtype(dt)
    panel = b_blocks.ndim == 3
    b3, x3, d3 = (v if panel else v[..., None]
                  for v in (b_blocks, x_blocks, d_blocks))
    k = b3.shape[2]
    lanes = tiling.LANE
    step = (tiling.lane_bytes((kmax,), jnp.int32)
            + tiling.lane_bytes((bs, bs, kmax), dt)
            + tiling.lane_bytes((bs * k,), dt)
            + tiling.lane_bytes((bs * k, kmax), gdt)
            + tiling.lane_bytes((bs, bs), dt)
            + 5 * tiling.lane_bytes((bs, k), dt)) * lanes
    # blocks narrower than one lane tile are padded up to it
    rows = max(nbr, lanes)
    vspec = tiling.window_spec((bs, k, lanes))
    vec = lambda v: tiling.lane_pad(jnp.transpose(v, (1, 2, 0)),  # noqa: E731
                                    rows)
    x_new, d_new = pl.pallas_call(
        functools.partial(_smoother_kernel, acc_dt, k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(nbr, lanes), windows.shape[1]),
            in_specs=[pl.BlockSpec((2,), lambda i, j, w: (jnp.int32(0),),
                                   memory_space=pltpu.SMEM),
                      tiling.window_spec((kmax, lanes)),
                      tiling.window_spec((bs, bs, kmax, lanes)),
                      tiling.x_window_spec(bs * k),
                      tiling.window_spec((bs, bs, lanes)),
                      vspec, vspec, vspec],
            out_specs=(vspec, vspec),
            scratch_shapes=[pltpu.VMEM((bs * k, kmax, lanes), gdt)]),
        out_shape=(jax.ShapeDtypeStruct((bs, k, rows), dt),
                   jax.ShapeDtypeStruct((bs, k, rows), dt)),
        compiler_params=tiling.compiler_params(
            step, ("parallel", "arbitrary")),
        interpret=interpret,
    )(windows, coef.astype(acc_dt),
      tiling.lane_pad(jnp.asarray(indices, jnp.int32).T, rows),
      tiling.lane_pad(jnp.transpose(data, (2, 3, 1, 0)), rows),
      tiling.lane_pad(x3.reshape(nbr, bs * k).T, lanes),
      tiling.lane_pad(jnp.transpose(dinv, (1, 2, 0)), rows),
      vec(b3), vec(d3), vec(x3))
    x_new, d_new = (jnp.transpose(v[..., :nbr], (2, 0, 1))
                    for v in (x_new, d_new))
    if not panel:
        x_new, d_new = x_new[..., 0], d_new[..., 0]
    return x_new, d_new
