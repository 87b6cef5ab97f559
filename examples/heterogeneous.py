"""Heterogeneous quasi-static loop: device assembly + coefficient updates.

A two-material problem (stiff spherical inclusion in a soft matrix) whose
inclusion stiffness ramps over "load steps".  Each step runs the fused
device hot loop — per-element material fields in, hierarchy out, solve —
as one jitted program: no per-step host assembly, no value-stream upload,
no retraces (the paper's recurring-recompute scenario with the assembly
itself device-resident).

Run:  PYTHONPATH=src python examples/heterogeneous.py [m]
"""
import sys
import time

import numpy as np

import repro.core  # noqa: F401  (enables fp64)
from repro.core import gamg
from repro.fem.assemble import assemble_elasticity, inclusion_fields
from repro import compile_cache


def main(m: int = 7) -> None:
    print(f"assembling {m}^3 Q1 elasticity on device")
    prob = assemble_elasticity(m)                  # path="device" default
    ne = prob.mesh.n_elements
    print(f"  n = {prob.n} unknowns, {ne} elements, coefficient update "
          f"payload = {2 * ne * 8} bytes (vs "
          f"{np.asarray(prob.values).nbytes} value-stream bytes)")

    solver = gamg.GAMGSolver(prob.A, prob.B, coarse_size=40,
                             rtol=1e-8, maxiter=100)
    solver.bind_assembler(prob.assembler)
    print(f"cold setup: {solver.setup_data.n_levels} levels, "
          f"rows/level = {solver.setup_data.stats['level_rows']}")

    for step, contrast in enumerate((1.0, 10.0, 100.0, 1000.0)):
        E, nu = inclusion_fields(prob.mesh, E_inclusion=contrast)
        t0 = time.perf_counter()
        solver.update_coefficients(E, nu)   # assemble+recompute, one program
        t_up = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = solver.solve(prob.b)
        t_solve = time.perf_counter() - t0
        print(f"step {step}: E_inclusion {contrast:7.1f} | "
              f"update {t_up * 1e3:7.1f} ms | solve {t_solve * 1e3:7.1f} ms"
              f" | iters {int(res.iters):3d} | relres {float(res.relres):.2e}")
        assert bool(res.converged)
    assert solver._coeff_recompute._cache_size() == 1, "retraced!"
    print("converged; one traced update program served every step.")


if __name__ == "__main__":
    compile_cache.enable()
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 7)
