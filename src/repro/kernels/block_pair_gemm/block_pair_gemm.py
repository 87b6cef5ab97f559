"""Pallas TPU kernel: batched rectangular block GEMM (SpGEMM numeric hot
spot, paper Secs. 3.4/4.4).

The numeric Galerkin phase is a stream of tiny rectangular products
``(br x bk) @ (bk x bc)`` — the <3,3,6> shapes of the paper's
``RunNumericAB_SeqBAIJKokkos<3,3,6>`` kernel (Table 5).  On the GPU these are
one-warp-per-pair; on TPU the right shape is *batched VPU work*: a tile of
``TP`` pairs is one ``(TP, br, bk) x (TP, bk, bc)`` contraction, unrolled
over the tiny ``bk`` dimension so it maps onto 8x128 vector registers with
the pair dimension on the lanes.

The arithmetic-intensity argument (paper Sec. 4.7) carries over: a pair
moves O(bs^2) bytes and performs O(bs^3) flops plus one amortized index; at
bs=3..6 and fp64 this stays far below the TPU ridge, so the kernel is
bandwidth-bound and the win is moving bs^2x fewer index bytes.

Layout / tiling
  grid     = (ceil(npairs / TP),)
  lhs tile = (TP, br, bk)  VMEM
  rhs tile = (TP, bk, bc)  VMEM
  out tile = (TP, br, bc)  VMEM
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pair_gemm_kernel(acc_dt, lhs_ref, rhs_ref, o_ref):
    lhs = lhs_ref[...].astype(acc_dt)        # (TP, br, bk)
    rhs = rhs_ref[...].astype(acc_dt)        # (TP, bk, bc)
    # unroll the tiny contraction dim: TP stays on lanes, no transposes
    acc = jnp.zeros(o_ref.shape, acc_dt)
    for k in range(lhs.shape[2]):
        acc = acc + lhs[:, :, k][:, :, None] * rhs[:, k, :][:, None, :]
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tile_pairs", "interpret", "accum_dtype"))
def block_pair_gemm(lhs: jax.Array, rhs: jax.Array, *,
                    interpret: bool, tile_pairs: int = 128,
                    accum_dtype=None) -> jax.Array:
    """(npairs, br, bk) @ (npairs, bk, bc) -> (npairs, br, bc).

    ``accum_dtype`` is the on-register contraction dtype (None = native in
    ``lhs.dtype``, bitwise legacy); the output rounds back to ``lhs.dtype``.
    """
    npairs, br, bk = lhs.shape
    _, bk2, bc = rhs.shape
    assert bk == bk2, (bk, bk2)
    acc_dt = jnp.dtype(accum_dtype) if accum_dtype is not None else lhs.dtype
    tp = min(tile_pairs, max(npairs, 1))
    pad = (-npairs) % tp
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0), (0, 0)))
        rhs = jnp.pad(rhs, ((0, pad), (0, 0), (0, 0)))
    grid = ((npairs + pad) // tp,)
    out = pl.pallas_call(
        functools.partial(_pair_gemm_kernel, acc_dt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tp, br, bk), lambda i: (i, jnp.int32(0), jnp.int32(0))),
            pl.BlockSpec((tp, bk, bc), lambda i: (i, jnp.int32(0), jnp.int32(0))),
        ],
        out_specs=pl.BlockSpec((tp, br, bc), lambda i: (i, jnp.int32(0), jnp.int32(0))),
        out_shape=jax.ShapeDtypeStruct((npairs + pad, br, bc), lhs.dtype),
        interpret=interpret,
    )(lhs, rhs)
    return out[:npairs]
