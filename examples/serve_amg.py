"""Serve AMG: the hierarchy-reusing multi-RHS solve server, end to end.

The production story the ROADMAP aims at: one cold GAMG setup serves a
*stream* of solve requests (load cases, client queries, Newton steps).
The server buckets arriving right-hand sides into static panel widths
(k in {1, 2, 4, 8} here), pads the remainder columns with zeros (frozen
from iteration 0 by the masked PCG), and runs batched panel solves on the
cached hierarchy — each request gets its own iteration count and residual
back, identical to a dedicated solve.

Run:  PYTHONPATH=src python examples/serve_amg.py [m]
"""
import sys
import time

import numpy as np

import repro.core  # noqa: F401  (enables fp64)
from repro.core import gamg
from repro.fem.assemble import assemble_elasticity
from repro.multirhs import AMGSolveServer
from repro import compile_cache


def main(m: int = 7) -> None:
    print(f"assembling {m}^3 Q1 elasticity ...")
    prob = assemble_elasticity(m)
    t0 = time.perf_counter()
    # REPRO_PRECISION=f32 hosts an fp32-resident hierarchy that still
    # serves fp64 requests (fp64 outer CG, preconditioner-boundary cast)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=40)
    server = AMGSolveServer(setupd, prob.A.data, buckets=(1, 2, 4, 8),
                            rtol=1e-8, maxiter=100)
    print(f"cold setup + hierarchy: {time.perf_counter() - t0:.2f}s, "
          f"n = {prob.n}, buckets = {server.buckets}, "
          f"precision: {setupd.precision.describe()}")

    rng = np.random.default_rng(0)
    # bursty request stream: arrival counts deliberately off-bucket
    for burst in (1, 3, 8, 5):
        for _ in range(burst):
            server.submit(rng.standard_normal(prob.n))
        t0 = time.perf_counter()
        reports = server.flush()
        dt = time.perf_counter() - t0
        ks = sorted({r.k_bucket for r in reports})
        its = [r.iters for r in reports]
        print(f"burst of {burst}: buckets {ks} | iters {min(its)}-{max(its)}"
              f" | {dt * 1e3:7.1f} ms total | {dt * 1e3 / burst:6.1f}"
              f" ms/rhs | all converged: {all(r.converged for r in reports)}")

    # operator update mid-stream (a Newton step): hierarchy structure and
    # the traced bucket solves are reused, only the values recompute
    a_new = prob.reassemble(1.2)
    t0 = time.perf_counter()
    server.update_operator(a_new.data)
    print(f"hot operator update: {(time.perf_counter() - t0) * 1e3:.1f} ms")
    reports = server.serve([np.asarray(prob.b) for _ in range(4)])
    assert all(r.converged for r in reports)
    print(f"post-update burst: iters {[r.iters for r in reports]}")
    print(f"stats: {server.stats}")


if __name__ == "__main__":
    compile_cache.enable()
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 7)
