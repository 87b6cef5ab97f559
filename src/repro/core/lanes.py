"""Lane-dense block gathers, scatters and segment sums.

On a TPU an array of small blocks ``(n, br, bc)`` that a gather writes or a
scatter reads is tiled with the block dims minor and padded to the
(sublane, 128-lane) tile: a 3x3 f32 block then occupies 4 KiB instead of
36 bytes, and a segment sum over a million such blocks wants gigabytes of
scratch.  These helpers keep the block *count* on the minor axis: a block
array travels as ``(br*bc, n)`` (``to_lanes``), gathers and segment sums
run on flat element indices, and nothing is padded beyond the lane tile.
The helpers are jitted, so the eager cold setup compiles each once per
shape rather than once per element operation.

On every backend the results are the same sums in the same order as the
row-major forms (``x[idx]``, ``jax.ops.segment_sum`` over block rows).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Array = jax.Array


def to_lanes(blocks: Array) -> Array:
    """``(n, *block) -> (prod(block), n)``."""
    return blocks.reshape(blocks.shape[0], -1).T


def from_lanes(t: Array, block_shape) -> Array:
    """``(prod(block), n) -> (n, *block)``."""
    return t.T.reshape((t.shape[1],) + tuple(block_shape))


@jax.jit
def gather_lanes(table: Array, idx) -> Array:
    """``out[r, *i] = table[r, idx[*i]]`` for a lane-dense ``(R, n)`` table,
    as one flat element gather (no block-shaped slices)."""
    idx = jnp.asarray(idx, jnp.int32)
    n = table.shape[1]
    base = jnp.arange(table.shape[0], dtype=jnp.int32) * n
    flat = base.reshape((-1,) + (1,) * idx.ndim) + idx[None]
    return table.reshape(-1)[flat]


@partial(jax.jit, static_argnames=("num_segments", "indices_are_sorted"))
def segment_sum_lanes(t: Array, seg, num_segments: int,
                      indices_are_sorted: bool = True) -> Array:
    """``(R, m)`` values summed into ``(R, num_segments)`` by ``seg (m,)``.

    One scalar segment sum over flat ids ``r * num_segments + seg``: sorted
    ``seg`` stays sorted, and each output is summed in input order.
    """
    seg = jnp.asarray(seg, jnp.int32)
    rows = t.shape[0]
    ids = (jnp.arange(rows, dtype=jnp.int32)[:, None] * num_segments
           + seg[None, :]).reshape(-1)
    out = jax.ops.segment_sum(t.reshape(-1), ids,
                              num_segments=rows * num_segments,
                              indices_are_sorted=indices_are_sorted)
    return out.reshape(rows, num_segments)


@jax.jit
def block_matmul_lanes(x: Array, y: Array) -> Array:
    """Blockwise ``x @ y`` on lane-dense blocks: ``x (a, b, ...)`` and
    ``y (b, c, ...)`` -> ``(a, c, ...)``, trailing dims broadcast.

    One broadcast product and one reduction over ``b``: a single fusion,
    where an unrolled sum of products per output entry made the emulated
    f64 program on the TPU take tens of seconds to compile."""
    n = max(x.ndim, y.ndim) - 2
    x = x.reshape(x.shape[:2] + (1,) * (n + 2 - x.ndim) + x.shape[2:])
    y = y.reshape(y.shape[:2] + (1,) * (n + 2 - y.ndim) + y.shape[2:])
    return jnp.sum(x[:, :, None] * y[None], axis=1)
