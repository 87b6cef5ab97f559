"""The paper's own configuration: 3D Q1/Q2 hex elasticity + GAMG.

Mirrors the experimental setup of Sec. 4.1: block size 3, GAMG with a
point-block-Jacobi-preconditioned smoother and a CG accelerator,
unpreconditioned residual norm, reused interpolation across solves, and the
weak-scaling ladder (one rank per accelerator, 98 304 unknowns per device).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class ElasticityConfig:
    m: int                       # grid nodes per edge (m^3 node grid)
    order: int = 1               # 1 = Q1 (paper main), 2 = Q2 (Sec. 4.6)
    E: float = 1.0               # Young's modulus
    nu: float = 0.3              # Poisson ratio
    theta: float = 0.08          # strength-of-connection threshold
    smoother: str = "chebyshev"  # pbjacobi-preconditioned (paper default)
    degree: int = 2
    coarse_size: int = 100
    coarsener: str = "greedy"    # "mis" = device Luby-MIS (paper Sec. 6)
    rtol: float = 1e-8           # unpreconditioned residual norm
    maxiter: int = 200
    reuse_interpolation: bool = True   # -pc_gamg_reuse_interpolation
    # assembly path: "device" (JAX element blocks + DeviceAssembler —
    # enables the jitted update_coefficients hot loop) or "host" (numpy
    # golden reference)
    assembly: str = "device"
    # distributed placement: agglomerate levels at or below this many equations
    # per rank (PETSc -pc_gamg_process_eq_limit; None = dist default,
    # 0 = keep every level slab-sharded)
    coarse_eq_limit: "int | None" = None

    def build(self):
        """Assemble the problem and the solver (cold setup)."""
        from repro.core.gamg import GAMGSolver
        from repro.fem.assemble import assemble_elasticity
        prob = assemble_elasticity(self.m, order=self.order, E=self.E,
                                   nu=self.nu, path=self.assembly)
        solver = GAMGSolver(prob.A, prob.B, theta=self.theta,
                            smoother=self.smoother, degree=self.degree,
                            coarse_size=self.coarse_size,
                            coarsener=self.coarsener, rtol=self.rtol,
                            maxiter=self.maxiter,
                            coarse_eq_limit=self.coarse_eq_limit)
        if prob.assembler is not None:
            # device path: enable the jitted coefficient hot loop
            solver.bind_assembler(prob.assembler)
        return prob, solver


# the paper's weak-scaling ladder: m^3 node grids on {1, 8, 27, 64} devices,
# 98 304 unknowns per device (Sec. 4.1)
PAPER_LADDER: Tuple[Tuple[int, int], ...] = (
    (32, 1), (64, 8), (96, 27), (128, 64))

# the capacity experiment of Sec. 4.5: 128^3 packed onto 8 devices
CAPACITY_CASE = (128, 8)

# CPU-scale ladder used by benchmarks/ (same shapes, reduced m)
CPU_LADDER: Tuple[int, ...] = (7, 10, 13)

CONFIG = ElasticityConfig(m=32)
