"""Pallas TPU kernel: blocked segment reduction (COO scatter / SpGEMM
accumulate), paper Secs. 3.4 / 5.

GPU COO assembly scatters with atomics; TPUs have none, and Pallas TPU
writes must be tile-regular.  The TPU-native rendering of "sum duplicates
into their output slot" for *sorted* segment ids is a streaming prefix sum:

  1. kernel: blocked inclusive cumsum over the pair stream, carrying the
     running prefix across grid steps in a VMEM scratch accumulator — TPU
     grids execute sequentially, so the carry is legal and race-free (and,
     unlike GPU atomics, bit-for-bit deterministic);
  2. wrapper: the per-segment sum is ``csum[end-1] - csum[start-1]`` with the
     (static, host-side) segment boundaries — a regular gather, no scatter.

Everything the scalar path would stream (bs^2 coordinates per block) shrinks
to one coordinate per block — the paper's block-area saving on plan + traffic.

Layout / tiling
  grid       = (ceil(n / TN),)           sequential, carries prefix
  in tile    = (TN, br, bc)  VMEM
  out tile   = (TN, br, bc)  VMEM        inclusive cumsum of the stream
  scratch    = (1, br, bc)   VMEM        running carry
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _cumsum_kernel(x_ref, o_ref, carry_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...].astype(o_ref.dtype)           # (TN, br, bc)
    csum = jnp.cumsum(x, axis=0) + carry_ref[...]
    o_ref[...] = csum
    carry_ref[...] = csum[-1:, :, :]


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "interpret", "accum_dtype"))
def block_stream_cumsum(x: jax.Array, *, interpret: bool,
                        tile_n: int = 256,
                        accum_dtype=None) -> jax.Array:
    """Inclusive cumsum over axis 0 of a (n, br, bc) block stream.

    The running prefix (output, VMEM carry) is held at ``accum_dtype``
    (None = native in ``x.dtype``): the difference-of-prefix trick in the
    wrapper cancels catastrophically below fp32, so low-precision streams
    must accumulate wider.  The *returned cumsum* stays at the accumulator
    dtype — the wrapper rounds only the final per-segment sums.
    """
    n, br, bc = x.shape
    acc_dt = jnp.dtype(accum_dtype) if accum_dtype is not None else x.dtype
    tn = min(tile_n, max(n, 1))
    pad = (-n) % tn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    grid = ((n + pad) // tn,)
    out = pl.pallas_call(
        _cumsum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tn, br, bc), lambda i: (i, jnp.int32(0), jnp.int32(0)))],
        out_specs=pl.BlockSpec((tn, br, bc), lambda i: (i, jnp.int32(0), jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((n + pad, br, bc), acc_dt),
        scratch_shapes=[pltpu.VMEM((1, br, bc), acc_dt)],
        interpret=interpret,
    )(x)
    return out[:n]
