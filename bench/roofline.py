"""Bytes each kernel call needs, from the level's real shapes.

A kernel's roofline share is ``bytes / HBM bandwidth / device time``: all
three kernels are memory-bound at these block sizes (3x3, 3x6, 6x6 blocks
at 4 bytes hold far fewer operations per byte than the chip's ridge
point), so bandwidth is the bound and operations are not counted.

The bytes are those the algorithm needs, not those an implementation
happens to move: every stored block once (``nnzb`` x block bytes at the
hierarchy's value width), one int32 column index per block, every vector
read once and written once.  ELL padding, pair padding and the gather
strategy are not counted, so another implementation of the same call is
held to the same work.

``Level`` numbers come from the program's setup (its ``nnzb`` and block
sizes); the trace's kernel events are matched to levels by the output
dims in their HLO text (``devtrace.parse_op``).
"""
from __future__ import annotations

import dataclasses

INDEX_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Product:
    """One sparse product ``C = X Y`` of the Galerkin chain."""

    x_nnzb: int
    x_block: tuple      # (rows, cols) of an X block
    y_nnzb: int
    y_block: tuple
    c_nnzb: int
    c_block: tuple
    slots: int          # output slots of the kernel's tiled layout


@dataclasses.dataclass(frozen=True)
class Level:
    """What the algorithm touches on one level of the hierarchy."""

    nbr: int            # block rows
    bs: int             # block size
    nnzb: int           # stored blocks of the level operator
    products: tuple = ()  # (A P, R (A P)) of the recompute


def blocks_bytes(nnzb: int, block, itemsize: int) -> int:
    """Stored blocks with one int32 index each."""
    return nnzb * (block[0] * block[1] * itemsize + INDEX_BYTES)


def smoother_step_bytes(lv: Level, itemsize: int, k: int = 1) -> int:
    """One fused smoother step ``x, d <- f(A, D^-1, b, x, d)`` on ``k``
    columns: A and the inverted diagonal blocks read once; b, x, d read
    and x, d written once each."""
    vec = lv.nbr * lv.bs * itemsize * k
    return (blocks_bytes(lv.nnzb, (lv.bs, lv.bs), itemsize)
            + lv.nbr * lv.bs * lv.bs * itemsize + 5 * vec)


def spmm_bytes(lv: Level, itemsize: int, k: int) -> int:
    """One blocked operator apply ``Y = A X`` on a ``k``-column panel."""
    return (blocks_bytes(lv.nnzb, (lv.bs, lv.bs), itemsize)
            + 2 * lv.nbr * lv.bs * itemsize * k)


def product_bytes(p: Product, itemsize: int) -> int:
    """One sparse block product: both operands read, the result written."""
    return (blocks_bytes(p.x_nnzb, p.x_block, itemsize)
            + blocks_bytes(p.y_nnzb, p.y_block, itemsize)
            + blocks_bytes(p.c_nnzb, p.c_block, itemsize))


def kernel_share(ops: dict, dims: dict, prefix: str, bytes_of,
                 bandwidth: float):
    """Roofline share (%) of one kernel over a traced window.

    ``ops``: op key ``<program>/<instruction>`` -> (device seconds, runs)
    in the window; ``dims``: op key -> output dims.  A kernel's calls are
    the instructions whose names start with ``prefix``.
    ``bytes_of(dims)``: bytes of one call with those output dims, or None
    where the dims match no level.  Returns None where no call of the
    kernel ran, or where one cannot be matched to its level's work."""
    need = seconds = 0.0
    for name, (sec, count) in ops.items():
        if not name.rsplit("/", 1)[-1].startswith(prefix):
            continue
        if name not in dims:
            return None
        b = bytes_of(dims[name])
        if b is None:
            return None
        need += b * count
        seconds += sec
    if seconds <= 0:
        return None
    return 100.0 * need / bandwidth / seconds
