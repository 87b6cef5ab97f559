"""Quickstart: blocked AMG on 3D elasticity (the paper's workflow).

Assembles a Q1 hex elasticity operator through the blocked COO primitive,
builds the GAMG hierarchy once, then runs the production loop: the operator
changes every "Newton step", the hierarchy is reused, the hot PtAP
recompute and the hot KSPSolve stay on-device in blocks.

Run:  PYTHONPATH=src python examples/quickstart.py [m]
"""
import sys
import time

import jax.numpy as jnp

import repro.core  # noqa: F401  (enables fp64)
from repro.core import gamg
from repro.fem.assemble import assemble_elasticity
from repro import compile_cache


def main(m: int = 9) -> None:
    print(f"assembling {m}^3 Q1 elasticity via blocked COO ...")
    prob = assemble_elasticity(m)
    print(f"  n = {prob.n} unknowns, {prob.A.nnzb} 3x3 blocks, "
          f"COO plan {prob.coo_plan.plan_bytes/1e6:.2f} MB")

    t0 = time.perf_counter()
    solver = gamg.GAMGSolver(prob.A, prob.B, coarse_size=40,
                             rtol=1e-8, maxiter=100)
    print(f"cold setup: {time.perf_counter()-t0:.2f}s, "
          f"{solver.setup_data.n_levels} levels, "
          f"rows/level = {solver.setup_data.stats['level_rows']}, "
          f"bs/level = {solver.setup_data.stats['level_bs']}")

    # production loop: operator changes, hierarchy (aggregates + P) reused
    for step in range(3):
        scale = 1.0 + 0.1 * step           # stand-in for a Newton update
        a_new = prob.reassemble(scale)     # one MatSetValuesCOO scatter
        t0 = time.perf_counter()
        solver.update_operator(a_new.data)  # hot PtAP chain (state-gated)
        t_ptap = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = solver.solve(prob.b)
        t_solve = time.perf_counter() - t0
        print(f"step {step}: hot PtAP {t_ptap*1e3:7.1f} ms | "
              f"hot KSPSolve {t_solve*1e3:7.1f} ms | "
              f"iters {int(res.iters):3d} | relres {float(res.relres):.2e}")
    assert bool(res.converged)
    print("converged.")


if __name__ == "__main__":
    compile_cache.enable()
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 9)
