"""Lane-dense tiling shared by the blocked Pallas kernels.

Every kernel in this package streams its block payloads with the *row*
(or output-slot) axis on the 128-wide lane axis.  A BlockELL payload
``(nbr, kmax, br, bc)`` enters a kernel as ``(br, bc, kmax, nbr)``: the
tiny block dims become leading, untiled dims, and the ``(kmax, rows)``
pair fills whole (sublane, 128) vreg tiles.  XLA already stores such an
array in that order on the TPU (``f32[nbr,kmax,br,bc]{0,1,3,2:T(8,128)}``),
so the wrapper transposes cost no copy there.  Each grid step owns a
contiguous run of lanes; nothing in a kernel mixes lanes, so a ragged last
tile only computes lanes that are dropped on write-back.

Index maps return int32 explicitly: ``repro.core`` runs with x64 on, under
which a literal ``0`` in an index map is int64, which Mosaic refuses.

The ELL kernels gather ``x`` inside the kernel, from windows of it
(``gather_window``): Mosaic gathers only within one vreg, along its 128
lanes.  ``x`` enters lane-dense, ``(bc*k, nbc)``; the grid's second axis
walks, for each 128-row tile, the 128-column tiles of ``x`` that the
tile's ELL indices touch (``col_windows``, scalar-prefetched), and each
step picks the lanes whose column falls in the window it holds.  Nothing
the size of the gathered operand ever exists outside VMEM.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# bytes of one grid step's blocks (inputs and outputs, before double
# buffering) that the lane tile is sized for
VMEM_BLOCK_BUDGET = 6 * 2 ** 20
MAX_LANES = 2048
# scoped-VMEM limit: room for both buffers of every block plus the kernel's
# temporaries, at least the usual 32 MiB and at most most of a v5e core's
# 128 MiB (dense coarse levels need one 128-lane step above the budget)
VMEM_LIMIT_MIN = 32 * 2 ** 20
VMEM_LIMIT_MAX = 100 * 2 ** 20
VMEM_SLACK = 8 * 2 ** 20


def sublanes(dtype) -> int:
    """Second-minor tile height: 8 rows of 32-bit words, packed below."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def lane_bytes(shape, dtype) -> int:
    """VMEM bytes per lane of a block ``(*lead, rows, lanes)`` after the
    second-minor dim is padded to the sublane tile (``shape`` omits the
    lane dim)."""
    *lead, rows = shape
    tile = sublanes(dtype)
    return math.prod(lead) * (-(-rows // tile) * tile) \
        * jnp.dtype(dtype).itemsize


def lane_tile(n: int, per_lane: int, requested: int | None = None) -> int:
    """Lanes per grid step for an axis of length ``n``.

    ``per_lane`` is the padded bytes all blocks of one step hold per lane
    (``lane_bytes`` summed).  ``requested`` (a tile knob) is rounded up to
    a multiple of 128; ``None`` takes the largest multiple of 128 that
    keeps the step within ``VMEM_BLOCK_BUDGET``.  A tile that covers the
    axis becomes the whole axis (a full-extent block needs no alignment).
    """
    if requested is None:
        t = VMEM_BLOCK_BUDGET // max(per_lane, 1) // LANE * LANE
        t = max(LANE, min(MAX_LANES, t))
    else:
        t = -(-max(int(requested), 1) // LANE) * LANE
    return n if t >= n else t


def lane_spec(block_shape) -> pl.BlockSpec:
    """Block tiled along its last (lane) axis by the grid index."""
    lead = len(block_shape) - 1
    return pl.BlockSpec(tuple(block_shape),
                        lambda i: (jnp.int32(0),) * lead + (i,))


def smem_spec(n: int) -> pl.BlockSpec:
    """Whole ``(n,)`` operand of scalar coefficients in SMEM."""
    return pl.BlockSpec((n,), lambda i: (jnp.int32(0),),
                        memory_space=pltpu.SMEM)


def compiler_params(step_bytes: int, semantics=("parallel",)
                    ) -> pltpu.CompilerParams:
    """Mosaic parameters for a grid step whose blocks hold ``step_bytes``
    (``lane_bytes`` summed, times the lane tile).  The gathering kernels'
    ``(rows, windows)`` grid passes ``("parallel", "arbitrary")``: the
    windows of one row tile accumulate into its scratch in order."""
    limit = min(max(2 * step_bytes + VMEM_SLACK, VMEM_LIMIT_MIN),
                VMEM_LIMIT_MAX)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)



# --------------------------------------------------------------------------
# Windowed in-kernel gather of x (the ELL kernels)
# --------------------------------------------------------------------------

def col_windows(indices) -> np.ndarray:
    """Host plan of the windowed gather for ELL ``indices (nbr, kmax)``.

    Row ``t`` lists, ascending, the 128-column tiles of ``x`` that block
    rows ``[128 t, 128 t + 128)`` read (padded slots read column 0, as the
    XLA gather does), padded by repeating its last entry:
    ``(ceil(nbr / 128), n_win)`` int32.
    """
    idx = np.asarray(indices).astype(np.int64) // LANE
    nbr, kmax = idx.shape
    nt = -(-nbr // LANE)
    if nt == 0 or kmax == 0:
        return np.zeros((nt, 1), np.int32)
    idx = np.concatenate([idx, np.repeat(idx[-1:], nt * LANE - nbr, 0)])
    per = np.sort(idx.reshape(nt, LANE * kmax), axis=1)
    first = np.ones(per.shape, bool)
    first[:, 1:] = per[:, 1:] != per[:, :-1]
    counts = first.sum(axis=1)
    n_win = int(counts.max())
    out = np.zeros((nt, n_win), np.int64)
    pos = np.cumsum(first, axis=1) - 1
    rows = np.broadcast_to(np.arange(nt)[:, None], per.shape)
    out[rows[first], pos[first]] = per[first]
    last = out[np.arange(nt), counts - 1][:, None]
    out = np.where(np.arange(n_win)[None] < counts[:, None], out, last)
    return out.astype(np.int32)


def ell_windows(indices, windows=None):
    """``windows`` if given, else ``col_windows`` of concrete ``indices``
    (inside a traced program the ELL must carry its plan:
    ``BlockELL.windows``, built with the ELL by ``ELLPlan``)."""
    if windows is not None:
        return jnp.asarray(windows, jnp.int32)
    if isinstance(indices, jax.core.Tracer):
        raise ValueError(
            "the windowed x gather needs the ELL's column-window plan: "
            "build the ELL through ELLPlan (BlockELL.windows) or pass "
            "windows= (repro.kernels.tiling.col_windows of the indices)")
    return jnp.asarray(col_windows(np.asarray(indices)))


def gather_tile(requested: int | None) -> int:
    """Rows per grid step of the gathering kernels: one 128-lane tile
    (the window plan is per 128 rows); a ``tile_rows`` knob may only ask
    for that."""
    if requested is not None and -(-int(requested) // LANE) * LANE != LANE:
        raise ValueError(f"tile_rows={requested}: the ELL kernels gather x "
                         f"per {LANE}-row tile, so tile_rows must be at "
                         f"most {LANE}")
    return LANE


def lane_pad(a, n: int):
    """Pad the lane (last) axis of ``a`` up to ``n`` with zeros."""
    pad = n - a.shape[-1]
    if pad <= 0:
        return a
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])


def window_spec(block_shape) -> pl.BlockSpec:
    """A row-tile block (lane axis indexed by the row tile ``i``) on the
    ``(rows, windows)`` grid with the window plan scalar-prefetched."""
    lead = len(block_shape) - 1
    return pl.BlockSpec(tuple(block_shape),
                        lambda i, j, w: (jnp.int32(0),) * lead + (i,))


def x_window_spec(c: int) -> pl.BlockSpec:
    """The ``(c, 128)`` tile of lane-dense ``x`` that window ``j`` of row
    tile ``i`` names."""
    return pl.BlockSpec((c, LANE), lambda i, j, w: (jnp.int32(0), w[i, j]))


def gather_dtype(dtype):
    """Dtype of the gathered-x scratch: 32-bit (Mosaic gathers 32-bit
    lanes) or wider."""
    return jnp.promote_types(dtype, jnp.float32)


def _lane_gather(x, idx):
    """``out[r, l] = x[r, idx[r, l]]`` within one 128-lane tile."""
    dn = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return lax.gather(x, idx[..., None], dn, slice_sizes=(1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def gather_window(win_ref, idx_ref, x_ref, xg_ref) -> None:
    """Kernel side of the windowed gather, one grid step ``(i, j)``.

    ``idx_ref (kmax, 128)`` holds the row tile's ELL indices, ``x_ref
    (c, 128)`` the window of lane-dense ``x``, ``xg_ref (c, kmax, 128)``
    the gathered operand being assembled (zeroed at the first window):
    ``xg[:, s, l] = x[:, idx[s, l]]`` for every slot whose column lies in
    this window.  The gather moves values exactly.  Slots are walked by a
    loop, not unrolled: coarse levels have hundreds.
    """
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        xg_ref[...] = jnp.zeros_like(xg_ref)

    c = x_ref.shape[0]
    base = win_ref[i, j] * LANE
    xt = x_ref[...].astype(xg_ref.dtype)

    def slot(s, carry):
        local = idx_ref[pl.ds(s, 1), :] - base       # (1, 128)
        hit = (local >= 0) & (local < LANE)
        g = _lane_gather(xt, jnp.broadcast_to(
            lax.clamp(jnp.int32(0), local, jnp.int32(LANE - 1)), (c, LANE)))
        old = xg_ref[:, pl.ds(s, 1), :]
        xg_ref[:, pl.ds(s, 1), :] = jnp.where(hit, g[:, None], old)
        return carry

    lax.fori_loop(jnp.int32(0), jnp.int32(idx_ref.shape[0]), slot,
                  jnp.int32(0))


def slab_dot(dt, d, x, a: int, m: int, k: int) -> jax.Array:
    """``sum_b d[a, b] * x[b*k + m]`` over whole ``(kmax, rows)`` slabs,
    then summed over the slot axis -> ``(1, rows)``, all at dtype ``dt``.

    The contraction order of every blocked ELL product in this package:
    ``d`` is a payload laid out lane-dense ``(br, bc, kmax, rows)`` and
    ``x`` the gathered operand ``(bc*k, kmax, rows)`` (refs or arrays).
    """
    acc = d[a, 0].astype(dt) * x[m].astype(dt)
    for b in range(1, d.shape[1]):
        acc = acc + d[a, b].astype(dt) * x[b * k + m].astype(dt)
    return jnp.sum(acc, axis=0, keepdims=True)
