"""Jit'd wrapper: sorted blocked segment-sum via the streaming cumsum kernel.

Segment boundaries are derived from the sorted ids (device) or supplied from
host-static indptr; the difference-of-prefix gather is a regular read with no
scatter, which is the TPU-legal formulation of the COO duplicate-sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.block_seg_sum.block_seg_sum import block_stream_cumsum
from repro.obs import trace as obs_trace


def block_seg_sum(vals: jax.Array, seg_ids: jax.Array, num_segments: int,
                  *, interpret: bool | None = None, tile_n: int = 256,
                  accum_dtype=None) -> jax.Array:
    """Sum (n, br, bc) blocks into (num_segments, br, bc) by sorted ids.

    Empty segments produce zero blocks (start == end collapses the prefix
    difference to 0).  ``accum_dtype`` is the dtype of the streamed prefix
    sum and its boundary differences (None = native, bitwise legacy); the
    per-segment results round back to ``vals.dtype``.  ``interpret=None``
    compiles on TPU and interprets elsewhere (``backend.kernel_interpret``).
    """
    with obs_trace.scope("kernels/block_seg_sum"):
        interpret = backend.kernel_interpret(interpret, vals.dtype,
                                             "block_seg_sum")
        return _block_seg_sum(vals, seg_ids, num_segments,
                              interpret=interpret, tile_n=tile_n,
                              accum_dtype=accum_dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "interpret", "tile_n",
                                    "accum_dtype"))
def _block_seg_sum(vals, seg_ids, num_segments, *, interpret, tile_n,
                   accum_dtype):
    csum = block_stream_cumsum(vals, tile_n=tile_n, interpret=interpret,
                               accum_dtype=accum_dtype)
    # end[s] = one past last input of segment s; start[s] = end[s-1]
    ends = jnp.searchsorted(seg_ids, jnp.arange(num_segments),
                            side="right")
    starts = jnp.searchsorted(seg_ids, jnp.arange(num_segments),
                              side="left")
    zero = jnp.zeros((1,) + vals.shape[1:], csum.dtype)
    padded = jnp.concatenate([zero, csum], axis=0)   # prefix with 0
    return (padded[ends] - padded[starts]).astype(vals.dtype)
