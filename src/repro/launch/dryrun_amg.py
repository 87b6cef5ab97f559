"""Distributed-AMG dry-run rows (the paper's own solver on the production
devices).

Lowers + compiles the full distributed hot path — recompute (chained
state-gated PtAP with cached P_oth) followed by the AMG-preconditioned CG
solve — via shard_map over the production devices flattened to a 1-D rank
axis (PETSc-style row slabs), for both the single-pod (256 ranks) and
multi-pod (512 ranks) device sets.  Records the same memory / cost /
collective census as the LM cells into the shared results JSON.

The grid is sized so host plan construction stays in CPU budget; the paper's
full weak-scaling ladder is exercised numerically by ``benchmarks/``.
"""
from __future__ import annotations

import json
import time

import jax

from repro.launch.dryrun import (
    RESULTS_PATH,
    _load_results,
    _save_results,
    collective_census,
)


def run_amg_dryrun(force: bool = False, m: int = 21) -> int:
    import numpy as np
    import repro.core  # noqa: F401  (x64)
    from repro.core import gamg
    from repro.dist.solver import build_dist_gamg, make_dist_solver, \
        rank_mesh
    from repro.fem.assemble import assemble_elasticity

    results = _load_results()
    prob = assemble_elasticity(m)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=60)
    failures = 0
    for mesh_name, ndev in (("single", 256), ("multi", 512)):
        key = f"amg-elasticity-q1-m{m}|solve|{mesh_name}|base"
        if key in results and not force and \
                results[key].get("status") == "OK":
            print(f"[cached] {key}")
            continue
        print(f"[run]    {key} (ndev={ndev}) ...", flush=True)
        try:
            mesh = rank_mesh(jax.devices()[:ndev])
            t0 = time.time()
            dg = build_dist_gamg(setupd, ndev)
            args = dg.sharded_args(setupd)
            a0 = dg.scatter_fine_payloads(prob.A.data)
            b = dg.scatter_vector(prob.b)
            run = make_dist_solver(dg, setupd, mesh, rtol=1e-8,
                                   maxiter=100)
            lowered = run.lower(args, a0, b)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            rec = {
                "status": "OK", "kind": "amg_solve",
                "mesh": [ndev], "n_devices": ndev,
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "grid": f"{m}^3 Q1 elasticity "
                        f"({prob.A.shape[0]} unknowns, "
                        f"{len(setupd.levels) + 1} levels)",
                "halo_strategy": dg.levels[0].a_op.halo.strategy,
                "memory": {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "peak_bytes": int(getattr(ma, "peak_memory_in_bytes",
                                              0)),
                    "alias_bytes": int(ma.alias_size_in_bytes),
                },
                "cost": {
                    "flops_per_device": float(ca.get("flops", -1.0)),
                    "bytes_accessed_per_device":
                        float(ca.get("bytes accessed", -1.0)),
                },
                "collectives": collective_census(compiled.as_text()),
            }
            results[key] = rec
            _save_results(results)
            print(f"         OK compile={rec['compile_s']}s "
                  f"peak/dev={rec['memory']['peak_bytes']/2**20:.1f}MiB "
                  f"coll={rec['collectives']['total_bytes']/2**20:.2f}MiB",
                  flush=True)
        except Exception as e:
            import traceback
            results[key] = {"status": "FAIL", "error": repr(e),
                            "trace": traceback.format_exc()[-2000:]}
            _save_results(results)
            failures += 1
            print(f"         FAIL {e!r}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(run_amg_dryrun())
