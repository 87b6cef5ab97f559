"""Pallas TPU kernel: blocked ELL SpMM over column panels (multi-RHS).

The paper's traffic argument (one 4-byte index amortized over a ``br x bc``
block payload) gets a second lever with multiple right-hand sides: the
*operator* stream — values AND indices — is amortized over ``k`` columns,
so arithmetic intensity rises with the panel width while the dominant HBM
traffic (the A values) stays constant.  ``benchmarks/table6_multirhs.py``
evaluates that model exactly.  ``block_spmv`` is this kernel at ``k = 1``.

Layout / tiling (lane-dense, see ``repro.kernels.tiling``)
  grid        = (ceil(nbr / 128), n_win)   row tiles x their x windows
  windows     = (ceil(nbr / 128), n_win)   SMEM (scalar prefetch)
  index tile  = (kmax, 128)                VMEM   A's indices, rows on lanes
  data tile   = (br, bc, kmax, 128)        VMEM   the payload, rows on lanes
  x window    = (bc*k, 128)                VMEM   128 columns of x
  x-gather    = (bc*k, kmax, 128)          VMEM   scratch, assembled over j
  out tile    = (br, k, 128)               VMEM

``x`` is gathered inside the kernel from the windows its row tile reads
(``tiling.gather_window``); once the last window is in, the tile is dense:
per output row ``(a, m)`` it multiplies ``bc`` whole ``(kmax, 128)``
slabs, adds them, and reduces over the ``kmax`` slots
(``tiling.slab_dot``).  Padded ELL slots carry exactly-zero data blocks
and contribute 0.

Dtype polymorphism: any floating payload dtype.  ``accum_dtype`` is the
on-register accumulator (cast up on load, round once on store; ``None`` =
native).  Compiled (non-interpret) calls take f32/bf16 payloads only:
Mosaic has no 64-bit floats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling


def _spmm_kernel(acc_dt, k, win_ref, idx_ref, d_ref, x_ref, o_ref, xg_ref):
    """One (row tile, x window) step: ``y[a, m] = sum_{s,b} A[a,b,s]
    x[idx[s], b, m]``, written once the tile's last window is gathered."""
    tiling.gather_window(win_ref, idx_ref, x_ref, xg_ref)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        for a in range(d_ref.shape[0]):
            for m in range(k):
                o_ref[a, m:m + 1, :] = tiling.slab_dot(
                    acc_dt, d_ref, xg_ref, a, m, k).astype(o_ref.dtype)


def block_spmm_ell(indices: jax.Array, data: jax.Array, x_panels: jax.Array,
                   *, interpret: bool, tile_rows: int | None = None,
                   accum_dtype=None, windows=None) -> jax.Array:
    """Y = A @ X with A in padded BlockELL form and X a column panel.

    indices:  (nbr, kmax) int32, padded slots point at block-col 0
    data:     (nbr, kmax, br, bc), padded slots are zero blocks
    x_panels: (nbc, bc, k)
    windows:  the column-window plan of ``indices``
              (``tiling.col_windows``; ``BlockELL.windows``); computed
              here when ``indices`` is concrete
    returns   (nbr, br, k) at ``data.dtype``; ``accum_dtype`` sets the
    contraction accumulator (None = native; bf16 inputs should accumulate
    in fp32).  ``tile_rows`` may only ask for the one 128-row tile
    (``tiling.gather_tile``).
    """
    tiling.gather_tile(tile_rows)
    return _block_spmm_ell(indices, data, x_panels,
                           tiling.ell_windows(indices, windows),
                           interpret=interpret, accum_dtype=accum_dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "accum_dtype"))
def _block_spmm_ell(indices, data, x_panels, windows, *, interpret: bool,
                    accum_dtype=None):
    nbr, kmax, br, bc = data.shape
    nbc, _, k = x_panels.shape
    dt = data.dtype
    acc_dt = jnp.dtype(accum_dtype) if accum_dtype is not None else dt
    gdt = tiling.gather_dtype(dt)
    lanes = tiling.LANE
    n_win = windows.shape[1]
    step = (tiling.lane_bytes((br, bc, kmax), dt)
            + tiling.lane_bytes((kmax,), jnp.int32)
            + tiling.lane_bytes((bc * k,), dt)
            + tiling.lane_bytes((bc * k, kmax), gdt)
            + tiling.lane_bytes((br, k), dt)) * lanes
    # blocks narrower than one lane tile are padded up to it
    rows = max(nbr, lanes)
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, acc_dt, k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(nbr, lanes), n_win),
            in_specs=[tiling.window_spec((kmax, lanes)),
                      tiling.window_spec((br, bc, kmax, lanes)),
                      tiling.x_window_spec(bc * k)],
            out_specs=tiling.window_spec((br, k, lanes)),
            scratch_shapes=[pltpu.VMEM((bc * k, kmax, lanes), gdt)]),
        out_shape=jax.ShapeDtypeStruct((br, k, rows), dt),
        compiler_params=tiling.compiler_params(
            step, ("parallel", "arbitrary")),
        interpret=interpret,
    )(windows,
      tiling.lane_pad(jnp.asarray(indices, jnp.int32).T, rows),
      tiling.lane_pad(jnp.transpose(data, (2, 3, 1, 0)), rows),
      tiling.lane_pad(x_panels.reshape(nbc, bc * k).T, lanes))
    return jnp.transpose(out[..., :nbr], (2, 0, 1))
