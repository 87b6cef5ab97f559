"""Jit'd wrapper for the batched block GEMM kernel."""
from repro.kernels import backend
from repro.kernels.block_pair_gemm.block_pair_gemm import (
    block_pair_gemm as _block_pair_gemm,
)
from repro.obs import trace as obs_trace

__all__ = ["block_pair_gemm"]


def block_pair_gemm(lhs, rhs, *, interpret: bool | None = None, **kwargs):
    """Front door inside the ``kernels/block_pair_gemm`` stage scope.

    ``interpret=None`` compiles on TPU and interprets elsewhere
    (``backend.kernel_interpret``, which refuses a compiled f64 call).
    """
    with obs_trace.scope("kernels/block_pair_gemm"):
        interpret = backend.kernel_interpret(interpret, lhs.dtype,
                                             "block_pair_gemm")
        return _block_pair_gemm(lhs, rhs, interpret=interpret, **kwargs)
