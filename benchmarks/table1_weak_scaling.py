"""Paper Table 1: hot KSPSolve / SpMV / PtAP, blocked vs scalar — plus the
distributed per-level comm model behind coarse-level agglomeration.

CPU-scale ladder (m^3 Q1 elasticity grids).  Measures the same three hot
events as the paper with both storage formats running the identical
algorithm (same hierarchy, same iteration counts — asserted), plus the
analytic traffic model that explains the ratios.

``comm_model`` evaluates the per-cycle message/latency/byte accounting of
the distributed V-cycle for both placements (fully sharded vs
agglomerated coarse levels) at the paper's weak-scaling rank counts —
the latency-bound coarse grids are exactly where the paper is fastest,
and the rows show the agglomeration crossover paying from ndev >= 8
(asserted).  ``overlap_model`` extends the ladder to 2-D process meshes
(8/27/64 devices) where interior rows exist, emits the
``hidden_latency`` overlap split, and pins the model against the traced
collective counts of the actual V-cycle (``repro.dist.measure``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import repro.core  # noqa: F401
from repro.core import gamg
from repro.core.scalar_path import recompute_scalar  # noqa: F401
from repro.core.scalar_csr import bcsr_matrix_bytes, csr_matrix_bytes, \
    expand_bcsr
from repro.core.krylov import pcg
from repro.core.spmv import spmv_ell
from repro.core.vcycle import vcycle
from repro.fem.assemble import assemble_elasticity

from benchmarks.common import dist_cycle_comm, emit, time_fn


def run(ladder=(7, 10, 13)) -> None:
    for m in ladder:
        prob = assemble_elasticity(m)
        # the paper's fp64 setting; the blocked/scalar iteration-parity
        # assert below is an fp64 contract, so pin against REPRO_PRECISION
        setupd = gamg.setup(prob.A, prob.B, coarse_size=30,
                            precision="f64")
        recompute_b = gamg.make_recompute(setupd)
        hier_b = recompute_b(prob.A.data)
        hier_s = recompute_scalar(setupd, prob.A.data)
        n = prob.A.shape[0]

        # --- hot SpMV (finest level operator) ---------------------------
        x = jnp.ones(n, prob.A.data.dtype)
        f_b = jax.jit(lambda h, v: spmv_ell(h.levels[0].a_ell, v))
        f_s = jax.jit(lambda h, v: spmv_ell(h.levels[0].a_ell, v))
        us_b = time_fn(f_b, hier_b, x)
        us_s = time_fn(f_s, hier_s, x)
        emit(f"t1.spmv.block.m{m}", us_b, f"n={n}")
        emit(f"t1.spmv.scalar.m{m}", us_s,
             f"block_speedup={us_s/us_b:.2f}x")

        # --- hot KSPSolve ------------------------------------------------
        def solve(h):
            return pcg(lambda v: spmv_ell(h.levels[0].a_ell, v),
                       lambda r: vcycle(h, r), prob.b, rtol=1e-8,
                       maxiter=100)

        sol_b = jax.jit(solve)
        rb = sol_b(hier_b)
        rs = sol_b(hier_s)
        assert int(rb.iters) == int(rs.iters), "iteration parity violated"
        us_b = time_fn(sol_b, hier_b)
        us_s = time_fn(sol_b, hier_s)
        emit(f"t1.ksp.block.m{m}", us_b, f"iters={int(rb.iters)}")
        emit(f"t1.ksp.scalar.m{m}", us_s,
             f"block_speedup={us_s/us_b:.2f}x")

        # --- hot PtAP (numeric chain, cached plans, both formats) ---------
        from repro.core.scalar_path import build_scalar_ptap_chain
        from repro.core.ptap import ptap_numeric_data

        def blocked_chain(a_data):
            outs = []
            for ls in setupd.levels:
                a_data = ptap_numeric_data(ls.ptap_cache, a_data,
                                           ls.P.data)
                outs.append(a_data)
            return outs

        blk_chain = jax.jit(blocked_chain)
        sc_chain = build_scalar_ptap_chain(setupd)
        us_b = time_fn(blk_chain, prob.A.data)
        us_s = time_fn(sc_chain, prob.A.data)
        emit(f"t1.ptap.block.m{m}", us_b, f"levels={len(setupd.levels)+1}")
        emit(f"t1.ptap.scalar.m{m}", us_s,
             f"block_speedup={us_s/us_b:.2f}x")

        # --- traffic model (the paper's Sec. 4.2 accounting) --------------
        A = prob.A
        S = expand_bcsr(A)
        bb, sb = bcsr_matrix_bytes(A), csr_matrix_bytes(S)
        emit(f"t1.matrix_bytes.block.m{m}", 0.0, f"bytes={bb}")
        emit(f"t1.matrix_bytes.scalar.m{m}", 0.0,
             f"bytes={sb};ceiling={sb/bb:.2f}x")
    comm_model()


def comm_model(m: int = 7, ndevs=(8, 27, 64)) -> None:
    """Distributed V-cycle comm rows: sharded vs agglomerated placement.

    Host-only (``build_dist_gamg`` is pure staging — no devices needed),
    so the paper's rank counts evaluate exactly on the CPU-scale grid.
    Emits per-level message counts / latency units / byte split and the
    crossover row, and asserts the agglomerated coarse tail is strictly
    cheaper in both messages and latency at every ndev >= 8.
    """
    from repro.dist.solver import build_dist_gamg

    prob = assemble_elasticity(m)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=30, precision="f64")
    assert len(setupd.levels) >= 2, "comm model needs a mid level"
    for ndev in ndevs:
        sh = dist_cycle_comm(build_dist_gamg(setupd, ndev,
                                             coarse_eq_limit=0))
        ag_dg = build_dist_gamg(setupd, ndev)   # default placement policy
        ag = dist_cycle_comm(ag_dg)
        switch = len(ag_dg.levels)
        for r_sh, r_ag in zip(sh, ag):
            li = r_sh["level"]
            emit(f"t1.comm.sharded.nd{ndev}.L{li}", 0.0,
                 f"msgs={r_sh['msgs']};lat={r_sh['latency']};"
                 f"hidden={r_sh['hidden_latency']:.3f};"
                 f"halo_bytes={r_sh['halo_bytes']};"
                 f"gather_bytes={r_sh['gather_bytes']}")
            emit(f"t1.comm.agg.nd{ndev}.L{li}", 0.0,
                 f"placement={r_ag['placement']};"
                 f"msgs={r_ag['msgs']};lat={r_ag['latency']};"
                 f"halo_bytes={r_ag['halo_bytes']};"
                 f"gather_bytes={r_ag['gather_bytes']}")
        # whole-cycle totals: the agglomerated boundary pays one
        # all-gather where the sharded placement pays the boundary R/P
        # halos *plus* every coarse level's halo and the coarse-solve
        # gather — the crossover the placement policy buys
        msgs_sh = sum(r["msgs"] for r in sh)
        msgs_ag = sum(r["msgs"] for r in ag)
        lat_sh = sum(r["latency"] for r in sh)
        lat_ag = sum(r["latency"] for r in ag)
        emit(f"t1.comm.crossover.nd{ndev}", 0.0,
             f"switch_level={switch};"
             f"coarse_eq_limit={ag_dg.coarse_eq_limit};"
             f"cycle_msgs={msgs_sh}->{msgs_ag};"
             f"cycle_lat={lat_sh}->{lat_ag}")
        if ndev >= 8:
            assert ag_dg.repl, \
                f"default placement agglomerated nothing at ndev={ndev}"
            assert msgs_ag < msgs_sh and lat_ag < lat_sh, \
                (f"agglomeration must beat sharding at ndev={ndev}: "
                 f"msgs {msgs_sh}->{msgs_ag} lat {lat_sh}->{lat_ag}")
            for r_sh, r_ag in zip(sh[switch:], ag[switch:]):
                assert r_ag["msgs"] == 0 < r_sh["msgs"], (r_sh, r_ag)
                assert r_ag["latency"] == 0 < r_sh["latency"], (r_sh, r_ag)
    overlap_model()


def overlap_model(m: int = 7, meshes=((2, 4), (2, 16), (2, 32))) -> None:
    """Overlap accounting on 2-D process meshes, up to 64 fake devices.

    1-D slabs of a 3-D stencil stop having interior rows once the slab is
    thinner than the stencil reach — exactly the regime of the paper's
    large rank counts — so the weak-scaling meshes here keep the row axis
    at two slabs (at the CPU-scale grid even three-way slabs leave the
    middle rank interior-free) and scale through the column axis
    (``pc``): interior rows exist, and ``dist_cycle_comm`` charges each
    exchange as ``max(alpha, t_interior)``.  Emits the ``hidden_latency`` /
    ``eff_latency`` split per sharded level (asserted nonzero at every
    ndev >= 8 mesh) and closes with a model-vs-measured message-count
    column at the 64-device point: ``repro.dist.measure`` (subprocess —
    it needs ``pr`` fake devices) counts the collective equations in the
    traced V-cycle, and the model must agree exactly.
    """
    import json
    import os
    import subprocess
    import sys

    from repro.dist.partition import ProcessMesh
    from repro.dist.solver import build_dist_gamg
    from repro.obs.model import dist_cycle_comm as comm_rows

    prob = assemble_elasticity(m)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=30, precision="f64")
    for shape in meshes:
        nd = shape[0] * shape[1]
        dg = build_dist_gamg(setupd, ProcessMesh(shape))
        for r in comm_rows(dg):
            emit(f"t1.overlap.nd{nd}.L{r['level']}", 0.0,
                 f"mesh={shape[0]}x{shape[1]};"
                 f"placement={r['placement']};lat={r['latency']};"
                 f"hidden={r['hidden_latency']:.3f};"
                 f"eff={r['eff_latency']:.3f}")
            if nd >= 8 and r["placement"] == "sharded" \
                    and r["halo_bytes"] > 0:
                assert r["hidden_latency"] > 0.0, \
                    (f"no overlap headroom on sharded level "
                     f"{r['level']} of mesh {shape}: {r}")
    pr, pc = meshes[-1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={pr}"
    env["JAX_PLATFORMS"] = "cpu"       # fake devices, never the chip
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.dist.measure",
         str(m), str(pr), str(pc)],
        capture_output=True, text=True, timeout=520, env=env)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    measured = rep["measured"]["cycle"]["msgs"]
    model = rep["model_msgs"]
    err = abs(model - measured) / max(measured, 1)
    emit(f"t1.overlap.measured.nd{pr * pc}", 0.0,
         f"model_msgs={model};measured_msgs={measured};err={err:.3f}")
    assert model == measured, \
        f"comm model drifted from the traced cycle: {model} != {measured}"


if __name__ == "__main__":
    run()       # run() ends with the comm_model + overlap rows
