#!/usr/bin/env python3
"""Chip smoke test: the blocked-AMG main path on a TPU, end to end.

One chip (the default): the paper's per-device weak-scaling point, Q1
hex elasticity on an m=32 node grid (98,304 unknowns before the clamped
face is eliminated), with ``ElasticityConfig``'s GAMG settings under the
``f32`` precision policy — an f32-resident hierarchy driven by the
compiled Pallas kernels, inside an f64 outer CG on XLA.  Phases:

  1. cold setup: device assembly, GAMG setup, first hierarchy;
  2. the compiled solve program, checked to hold ``tpu_custom_call``
     (the kernels are compiled, not interpreted), and a cold solve;
  3. three hot steps of ``update_coefficients(E, nu)`` -> solve over a
     stiffening inclusion;
  4. six load cases through ``AMGSolveServer`` on the same setup (the
     ``block_spmm`` panel path).

Every solve must converge to rtol=1e-8 with a healthy status, and its
true residual is recomputed on the host in f64 from the assembled blocks.

``--chips 4``: only the distributed path on the first four devices —
``build_dist_gamg`` -> ``make_dist_solver`` at m=32 — compared with the
single-device solve of the same problem under the same policy.

The script needs a TPU: with any other backend it exits non-zero before
printing a result.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
phase raises and exits non-zero.

Run:  python3 chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

M = 32
POLICY = "f32"
N_LOAD_CASES = 6
CONTRASTS = (10.0, 100.0, 1000.0)     # inclusion stiffness, hot steps 1-3
# the solver stops on its recursive residual; the true residual may drift
# from it by rounding, so the host check allows this factor over rtol
TRUE_RESIDUAL_SLACK = 1.1


def require_tpu(devices):
    """The devices, if JAX found TPUs; otherwise exit non-zero."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "no devices"
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {found}")
    return devices


def host_relres(indptr, indices, data, x, b) -> float:
    """``||b - A x|| / ||b||`` in f64 numpy from BSR blocks — a plain
    reference independent of the solver's kernels and layouts."""
    import numpy as np
    data = np.asarray(data, np.float64)
    nbr, bs = len(indptr) - 1, data.shape[1]
    xb = np.asarray(x, np.float64).reshape(-1, bs)
    rows = np.repeat(np.arange(nbr), np.diff(indptr))
    contrib = np.einsum("nab,nb->na", data, xb[np.asarray(indices)])
    y = np.zeros((nbr, bs))
    for a in range(bs):
        y[:, a] = np.bincount(rows, weights=contrib[:, a], minlength=nbr)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - y.reshape(-1)) / np.linalg.norm(b))


def check_solve(tag, *, iters, relres, converged, status, host, rtol):
    ok = (bool(converged) and int(status) == 0 and float(relres) <= rtol
          and host <= TRUE_RESIDUAL_SLACK * rtol)
    print(f"  {tag}: iters={int(iters)} relres={float(relres):.3e} "
          f"host_relres={host:.3e} status={int(status)}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: {tag} did not converge to {rtol}")


def describe_policy(setupd):
    from repro.kernels import backend
    dt = setupd.precision.hierarchy_dtype
    print(f"policy: {POLICY} ({setupd.precision.describe()})")
    print(f"kernel paths: spgemm={backend.resolve_spgemm_path(None, dt)} "
          f"smooth={backend.resolve_smooth_path(None, dt)} "
          f"spmm={backend.resolve_spmm_path(None, dt)} "
          f"interpret={backend.resolve_interpret(None)}")
    print(f"levels: rows={setupd.stats['level_rows']} "
          f"nnzb={setupd.stats['level_nnzb']} "
          f"bs={setupd.stats['level_bs']}", flush=True)


def run_one_chip() -> None:
    import jax
    import numpy as np

    from repro.configs.elasticity import ElasticityConfig
    from repro.core import gamg
    from repro.fem.assemble import assemble_elasticity, inclusion_fields
    from repro.multirhs.server import AMGSolveServer

    cfg = ElasticityConfig(m=M)
    t0 = time.perf_counter()
    prob = assemble_elasticity(cfg.m, order=cfg.order, E=cfg.E, nu=cfg.nu,
                               path=cfg.assembly)
    t_asm = time.perf_counter() - t0
    print(f"problem: m={cfg.m} Q{cfg.order} grid unknowns={3 * cfg.m ** 3} "
          f"free unknowns={prob.n} elements={prob.mesh.n_elements}")
    t0 = time.perf_counter()
    solver = gamg.GAMGSolver(
        prob.A, prob.B, theta=cfg.theta, smoother=cfg.smoother,
        degree=cfg.degree, coarse_size=cfg.coarse_size,
        coarsener=cfg.coarsener, rtol=cfg.rtol, maxiter=cfg.maxiter,
        precision=POLICY)
    solver.bind_assembler(prob.assembler)
    jax.block_until_ready(solver.hierarchy)
    t_setup = time.perf_counter() - t0
    print(f"cold setup: assembly {t_asm:.2f} s, setup+first recompute "
          f"{t_setup:.2f} s")
    describe_policy(solver.setup_data)

    indptr, indices = prob.A.indptr, prob.A.indices
    t0 = time.perf_counter()
    res = jax.block_until_ready(solver.solve(prob.b))
    t_solve = time.perf_counter() - t0
    # the solver's own compiled program (an in-memory cache hit after the
    # call above), so the check reads exactly what ran
    n_calls = solver._solve.lower(solver.hierarchy, prob.b).compile(
        ).as_text().count("tpu_custom_call")
    print(f"cold solve: {t_solve:.2f} s (first call, compile included); "
          f"solve program holds {n_calls} tpu_custom_call sites",
          flush=True)
    if n_calls == 0:
        raise SystemExit("chip_smoke: the solve program holds no compiled "
                         "Pallas kernel (tpu_custom_call)")
    check_solve("cold", iters=res.iters, relres=res.relres,
                converged=res.converged, status=res.health.status,
                host=host_relres(indptr, indices, prob.A.data, res.x,
                                 prob.b), rtol=cfg.rtol)

    a_data = prob.A.data
    for step, contrast in enumerate(CONTRASTS, start=1):
        E, nu = inclusion_fields(prob.mesh, E_inclusion=contrast)
        t0 = time.perf_counter()
        solver.update_coefficients(E, nu)
        jax.block_until_ready(solver.hierarchy)
        t_rc = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = jax.block_until_ready(solver.solve(prob.b))
        t_solve = time.perf_counter() - t0
        print(f"hot step {step}: E_inclusion={contrast:g} "
              f"update+recompute {t_rc * 1e3:.1f} ms "
              f"solve {t_solve * 1e3:.1f} ms")
        a_data = prob.assembler.coo_data(*prob.assembler.as_fields(E, nu))
        check_solve(f"step {step}", iters=res.iters, relres=res.relres,
                    converged=res.converged, status=res.health.status,
                    host=host_relres(indptr, indices, a_data, res.x,
                                     prob.b), rtol=cfg.rtol)

    rng = np.random.default_rng(0)
    loads = [np.asarray(prob.b) * (1.0 + 0.5 * i)
             + rng.standard_normal(prob.n) for i in range(N_LOAD_CASES)]
    server = AMGSolveServer(solver.setup_data, a_data, rtol=cfg.rtol,
                            maxiter=cfg.maxiter)
    t0 = time.perf_counter()
    reports = server.serve(loads)
    t_serve = time.perf_counter() - t0
    print(f"server: {len(reports)} load cases in {t_serve * 1e3:.1f} ms "
          f"(first burst, compile included), buckets="
          f"{sorted({r.k_bucket for r in reports})}")
    for r, b in zip(reports, loads):
        if r.status != "ok":
            raise SystemExit(f"chip_smoke: request {r.request_id} "
                             f"status {r.status}")
        check_solve(f"request {r.request_id}", iters=r.iters,
                    relres=r.relres, converged=r.converged, status=r.health,
                    host=host_relres(indptr, indices, a_data, r.x, b),
                    rtol=cfg.rtol)


def run_four_chips(devices) -> None:
    import jax
    import numpy as np

    from repro.configs.elasticity import ElasticityConfig
    from repro.core import gamg
    from repro.dist.solver import build_dist_gamg, make_dist_solver, \
        rank_mesh
    from repro.fem.assemble import assemble_elasticity

    if len(devices) < 4:
        raise SystemExit(f"chip_smoke: --chips 4 needs four devices, "
                         f"found {len(devices)}")
    cfg = ElasticityConfig(m=M)
    prob = assemble_elasticity(cfg.m, order=cfg.order, E=cfg.E, nu=cfg.nu,
                               path=cfg.assembly)
    t0 = time.perf_counter()
    setupd = gamg.setup(prob.A, prob.B, theta=cfg.theta,
                        smoother=cfg.smoother, degree=cfg.degree,
                        coarse_size=cfg.coarse_size,
                        coarsener=cfg.coarsener, precision=POLICY)
    print(f"problem: m={cfg.m} free unknowns={prob.n}; setup "
          f"{time.perf_counter() - t0:.2f} s")
    describe_policy(setupd)

    hier = gamg.make_recompute(setupd)(prob.A.data)
    ref = jax.block_until_ready(
        gamg.make_solve(setupd, rtol=cfg.rtol, maxiter=cfg.maxiter)(
            hier, prob.b))
    indptr, indices = prob.A.indptr, prob.A.indices
    check_solve("single device", iters=ref.iters, relres=ref.relres,
                converged=ref.converged, status=ref.health.status,
                host=host_relres(indptr, indices, prob.A.data, ref.x,
                                 prob.b), rtol=cfg.rtol)

    t0 = time.perf_counter()
    dg = build_dist_gamg(setupd, 4)
    run = make_dist_solver(dg, setupd, rank_mesh(devices[:4]),
                           rtol=cfg.rtol, maxiter=cfg.maxiter)
    args = dg.sharded_args(setupd)
    a0 = dg.scatter_fine_payloads(prob.A.data)
    b = dg.scatter_vector(prob.b)
    x, iters, relres, ok, status = jax.block_until_ready(run(args, a0, b))
    print(f"dist (4 chips, placement={dg.placement}): build+first solve "
          f"{time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    jax.block_until_ready(run(args, a0, b))
    print(f"dist solve: {(time.perf_counter() - t0) * 1e3:.1f} ms")
    x_d = dg.gather_vector(x)
    check_solve("dist", iters=iters[0], relres=relres[0],
                converged=ok[0], status=status[0],
                host=host_relres(indptr, indices, prob.A.data, x_d, prob.b),
                rtol=cfg.rtol)
    x_s = np.asarray(ref.x)
    diff = float(np.linalg.norm(x_d - x_s) / np.linalg.norm(x_s))
    print(f"dist vs single device: iters {int(iters[0])} vs "
          f"{int(ref.iters)}, solution rel diff {diff:.3e}")
    if diff > 1e-5:
        raise SystemExit(f"chip_smoke: dist solution differs from the "
                         f"single-device one by {diff:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path (default); 4: only the "
                         "distributed path on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = require_tpu(jax.devices())
    from repro import compile_cache
    cache = compile_cache.enable()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    if args.chips == 4:
        run_four_chips(devices)
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
