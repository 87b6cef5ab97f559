"""Distributed == single-device parity selftest.

Run as a subprocess (``python -m repro.dist.selftest <m>``) with
``REPRO_SELFTEST_NDEV`` ranks faked on the host platform, so the
placeholder-device XLA flag never leaks into the parent process.

Checks, on an m^3 Q1 elasticity problem:

  * the distributed solve converges in the *same iteration count* as the
    single-device ``GAMGSolver`` and to an allclose solution;
  * a hot recompute (scaled operator values, same structure) through the
    *state-gated* path (reusing the staged ``DistGAMG``) matches the
    single-device hot recompute;
  * the *ungated* path (rebuilding the prolongator-side staging from
    scratch, the paper's Table 3 ablation) produces identical results to
    the gated one;
  * the level-0 halo really is the neighbor slab exchange
    (``halo=ppermute``) rather than an allgather fallback;
  * with ``REPRO_SELFTEST_MRHS=1``: a k-column panel through the *same*
    shard_map program (scattered ``(n, k)`` payload -> masked multi-RHS
    PCG) matches the single-device batched solve per column — same
    iteration counts, allclose solutions;
  * with ``REPRO_PRECISION`` set to a reduced policy (e.g. ``f32``): a
    distributed solve on the reduced-precision-resident hierarchy (fp64
    outer CG, boundary casts) still converges to rtol with at most a
    small iteration-count growth over the fp64 reference and an allclose
    solution.  The *parity* sections above always pin ``precision="f64"``
    — exact iteration parity is an fp64 contract, and the env override
    must not silently weaken it.
  * with ``REPRO_SELFTEST_AGG=1``: the **agglomerated placement** — a
    hierarchy with at least one mid level replicated (threshold forced
    high) solves in *exactly* the same iteration count as the
    sharded-only placement of the same setup and as the single-device
    solver, to an allclose solution; with ``REPRO_SELFTEST_MRHS=1`` the
    panel goes through the agglomerated program too (per-column parity).
    The sharded baselines in the sections above pin
    ``coarse_eq_limit=0`` so their coverage of the ppermute paths never
    silently shrinks as placement defaults evolve.
  * with ``REPRO_SELFTEST_COEFF=1``: the **coefficient hot loop** — per-slab
    element coefficient fields scattered through the assembly staging
    (``build_dist_assembly`` / ``DistAssembly.scatter_fields``) and
    assembled rank-locally inside the shard_map program
    (``make_dist_coeff_solver``) match (a) the value-stream path fed the
    globally assembled operator, exactly, and (b) the single-device jitted
    ``update_coefficients -> recompute -> solve`` loop on a heterogeneous
    (two-material inclusion) problem — same iteration count, allclose
    solution — with zero retraces across repeated updates
    (``_cache_size() == 1``, including an f32-typed caller).
  * with ``REPRO_SELFTEST_MARCH=1``: the **warm-started time march over
    the wire** — a 3-step softening-coefficient march through the
    ``warm_start=True`` dist coefficient program (each step's x-output
    slab fed straight back as the next step's x0 slab, no gather/scatter
    round trip) matches the single-device fused march primitive
    (``gamg.make_coeff_solve``) step for step — same iteration counts,
    allclose solutions — with one compiled program for the whole march
    and the warm final step no slower than a cold re-solve.
  * with ``REPRO_SELFTEST_OVERLAP=1``: the **overlap schedule parity** —
    the ``REPRO_OVERLAP=on`` split apply (interior rows while the
    exchange flies, boundary rows off the finished window) solves in
    exactly the same iteration count as the blocking schedule with a
    *bitwise*-identical solution (f64); an apply-level battery pins
    bitwise split-vs-blocking equality across halo strategies
    (``ppermute``/``allgather`` at 4+ ranks/``replicated``), vector and
    panel right-hand sides, f64 and f32 payloads; a jaxpr check pins
    ``REPRO_OVERLAP=off`` residue-free identical to the hand-rolled
    pre-refactor blocking apply; and a ``halo:nan`` fault is detected
    with the *same* status and iteration count under both schedules
    (detection latency unchanged by the overlap).
  * with ``REPRO_SELFTEST_FAULT=1``: the **fault battery over the wire** —
    a NaN planted into the halo-exchange windows (``repro.robust.inject``,
    site ``"halo"``) of a freshly traced program trips the collective
    health flags (not-ok, status != healthy) while the returned best
    iterate stays finite (no silent NaN escapes a rank); an Inf planted
    into the distributed CG's operator apply at a chosen step is flagged
    within one outer iteration; and a clean re-staging afterwards restores
    exact (bitwise) parity with the unfaulted solve.
  * always: the healthy-path status is ``HEALTHY`` on every section's
    solve, and scatter staging dtypes are the *policy's*, not the
    caller's — an f32-cast payload/rhs stages at the same dtype as the
    f64 one (same compiled program, no retrace, no dtype poisoning).

Prints ``OK`` on success (asserts otherwise).
"""
from __future__ import annotations

import os
import sys


def main(m: int) -> int:
    ndev = int(os.environ.get("REPRO_SELFTEST_NDEV", "4"))
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ndev} " + flags)
    # a fake-device tool by design: it never takes an accelerator, so it
    # cannot contend with the process that holds one
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    import repro.core  # noqa: F401  (x64 on)
    from repro.core import gamg
    from repro.dist.solver import build_dist_gamg, make_dist_solver, \
        rank_mesh
    from repro.fem.assemble import assemble_elasticity

    assert len(jax.devices()) == ndev, (jax.devices(), ndev)
    prob = assemble_elasticity(m)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=30, precision="f64")
    assert setupd.levels, \
        (f"m={m} gives only {prob.A.nbr} block rows (< coarse_size=30): "
         f"no AMG levels to distribute — use m >= 4")

    # single-device reference
    solver = gamg.GAMGSolver(prob.A, prob.B, coarse_size=30, rtol=1e-8,
                             maxiter=200, precision="f64")
    ref0 = solver.solve(prob.b)

    # distributed: cold staging + hot solve (placement pinned fully
    # sharded — the agglomerated placement is checked against this below)
    mesh = rank_mesh(jax.devices())
    dg = build_dist_gamg(setupd, ndev, coarse_eq_limit=0)
    args = dg.sharded_args(setupd)
    run = make_dist_solver(dg, setupd, mesh, rtol=1e-8, maxiter=200)
    a0 = dg.scatter_fine_payloads(prob.A.data)
    b = dg.scatter_vector(prob.b)

    # scatter staging is policy-dtyped, never caller-dtyped: an f32-cast
    # update stages identically to the f64 one (no retrace, no poisoning)
    a0_32 = dg.scatter_fine_payloads(np.asarray(prob.A.data, np.float32))
    b_32 = dg.scatter_vector(np.asarray(prob.b, np.float32))
    assert a0_32.dtype == a0.dtype == dg.payload_stage_dtype, \
        (a0_32.dtype, a0.dtype, dg.payload_stage_dtype)
    assert b_32.dtype == b.dtype == setupd.precision.krylov_dtype, \
        (b_32.dtype, b.dtype)
    x, iters, relres, ok, status = jax.block_until_ready(run(args, a0, b))
    x_g = dg.gather_vector(x)
    assert int(status[0]) == 0, f"healthy solve flagged: {status}"

    halo = dg.levels[0].a_op.halo
    widths = [lv.a_op.halo.width for lv in dg.levels]
    print(f"ndev={ndev} m={m} levels={len(dg.levels) + 1} "
          f"halo={halo.strategy} widths={widths} "
          f"s2_halo={[lv.stage2.halo.strategy for lv in dg.levels]}")

    assert bool(ok[0]), (iters, relres)
    assert int(iters[0]) == int(ref0.iters), \
        f"iteration parity: dist={int(iters[0])} single={int(ref0.iters)}"
    np.testing.assert_allclose(x_g, np.asarray(ref0.x), rtol=1e-6,
                               atol=1e-9)
    print(f"cold solve parity: iters={int(iters[0])} "
          f"relres={float(relres[0]):.3e}")

    # hot recompute: new values, same structure (the state-gated path)
    a_new = prob.A.data * 1.5
    solver.update_operator(a_new)
    ref1 = solver.solve(prob.b)
    x1, it1, rr1, ok1, _ = jax.block_until_ready(
        run(args, dg.scatter_fine_payloads(a_new), b))
    assert bool(ok1[0])
    assert int(it1[0]) == int(ref1.iters), (int(it1[0]), int(ref1.iters))
    np.testing.assert_allclose(dg.gather_vector(x1), np.asarray(ref1.x),
                               rtol=1e-6, atol=1e-9)
    print(f"gated recompute parity: iters={int(it1[0])}")

    # ungated: rebuild the prolongator-side staging from scratch; results
    # must be identical to the gated path (paper Table 3's ablation only
    # costs time, never accuracy)
    dg2 = build_dist_gamg(setupd, ndev, coarse_eq_limit=0)
    run2 = make_dist_solver(dg2, setupd, mesh, rtol=1e-8, maxiter=200)
    x2, it2, _, ok2, _ = jax.block_until_ready(
        run2(dg2.sharded_args(setupd), dg2.scatter_fine_payloads(a_new), b))
    assert bool(ok2[0]) and int(it2[0]) == int(it1[0])
    np.testing.assert_allclose(dg.gather_vector(x2),
                               dg.gather_vector(x1), rtol=0, atol=0)
    print("ungated rebuild parity: identical")

    if os.environ.get("REPRO_SELFTEST_MRHS") == "1":
        # multi-RHS panel through the SAME jitted shard_map program (only
        # the b payload grows a trailing axis) vs the single-device
        # batched masked PCG: per-column iteration parity + allclose.
        rng = np.random.default_rng(0)
        B3 = np.stack([np.asarray(prob.b),
                       0.5 * np.asarray(prob.b) + rng.standard_normal(prob.n),
                       rng.standard_normal(prob.n)], axis=1)
        ref_mr = solver.solve_many(jax.numpy.asarray(B3))
        xm, itm, rrm, okm, stm = jax.block_until_ready(
            run(args, dg.scatter_fine_payloads(a_new),
                dg.scatter_vector(B3)))
        assert (np.asarray(stm[0]) == 0).all(), stm
        assert bool(np.asarray(okm[0]).all()), (itm, rrm)
        assert np.array_equal(np.asarray(itm[0]), np.asarray(ref_mr.iters)), \
            f"mrhs iters: dist={np.asarray(itm[0])} " \
            f"single={np.asarray(ref_mr.iters)}"
        np.testing.assert_allclose(dg.gather_vector(xm),
                                   np.asarray(ref_mr.x), rtol=1e-6,
                                   atol=1e-9)
        print(f"mrhs (k={B3.shape[1]}) parity: "
              f"iters={np.asarray(itm[0]).tolist()}")

    if os.environ.get("REPRO_SELFTEST_AGG") == "1":
        # agglomerated placement: force the threshold high so every level
        # above the finest is replicated, then demand *exact* iteration
        # parity with the sharded-only placement of the same setup (an
        # fp64 contract, like the sections above).  When the main setup
        # has no mid level to replicate, coarsen deeper.
        if len(setupd.levels) >= 2:
            setup_a, a_vals, b_a = setupd, a_new, b
            dg_sh, run_sh = dg, run
            sh_x, sh_iters = x1, int(it1[0])
        else:
            setup_a = gamg.setup(prob.A, prob.B, coarse_size=12,
                                 precision="f64")
            assert len(setup_a.levels) >= 2, setup_a.stats["level_rows"]
            a_vals = prob.A.data
            dg_sh = build_dist_gamg(setup_a, ndev, coarse_eq_limit=0)
            run_sh = make_dist_solver(dg_sh, setup_a, mesh, rtol=1e-8,
                                      maxiter=200)
            b_a = dg_sh.scatter_vector(prob.b)
            xs, its, _, oks, _ = jax.block_until_ready(
                run_sh(dg_sh.sharded_args(setup_a),
                       dg_sh.scatter_fine_payloads(a_vals), b_a))
            assert bool(oks[0])
            sh_x, sh_iters = xs, int(its[0])
        dg_ag = build_dist_gamg(setup_a, ndev, coarse_eq_limit=1 << 30)
        assert dg_ag.repl and len(dg_ag.levels) == 1, dg_ag.placement
        assert not dg_sh.repl, dg_sh.placement
        run_ag = make_dist_solver(dg_ag, setup_a, mesh, rtol=1e-8,
                                  maxiter=200)
        args_ag = dg_ag.sharded_args(setup_a)
        a0_ag = dg_ag.scatter_fine_payloads(a_vals)
        xa, ita, rra, oka, _ = jax.block_until_ready(run_ag(args_ag, a0_ag,
                                                            b_a))
        assert bool(oka[0]), (ita, rra)
        assert int(ita[0]) == sh_iters, \
            f"agg parity: agglomerated={int(ita[0])} sharded={sh_iters}"
        np.testing.assert_allclose(dg_ag.gather_vector(xa),
                                   dg_sh.gather_vector(sh_x),
                                   rtol=1e-6, atol=1e-9)
        print(f"agglomerated parity: iters={int(ita[0])} "
              f"placement={dg_ag.placement}")
        if os.environ.get("REPRO_SELFTEST_MRHS") == "1":
            # the panel through the agglomerated program: per-column
            # parity with the sharded placement
            rng_a = np.random.default_rng(0)
            Ba = np.stack(
                [np.asarray(prob.b),
                 0.5 * np.asarray(prob.b) + rng_a.standard_normal(prob.n),
                 rng_a.standard_normal(prob.n)], axis=1)
            xm_s, itm_s, _, okm_s, _ = jax.block_until_ready(
                run_sh(dg_sh.sharded_args(setup_a),
                       dg_sh.scatter_fine_payloads(a_vals),
                       dg_sh.scatter_vector(Ba)))
            xm_a, itm_a, _, okm_a, _ = jax.block_until_ready(
                run_ag(args_ag, a0_ag, dg_ag.scatter_vector(Ba)))
            assert bool(np.asarray(okm_s[0]).all())
            assert bool(np.asarray(okm_a[0]).all())
            assert np.array_equal(np.asarray(itm_a[0]),
                                  np.asarray(itm_s[0])), (itm_a, itm_s)
            np.testing.assert_allclose(dg_ag.gather_vector(xm_a),
                                       dg_sh.gather_vector(xm_s),
                                       rtol=1e-6, atol=1e-9)
            print(f"agglomerated mrhs (k={Ba.shape[1]}) parity: "
                  f"iters={np.asarray(itm_a[0]).tolist()}")

    if os.environ.get("REPRO_SELFTEST_COEFF") == "1":
        # device-resident coefficient hot loop through the dist staging:
        # heterogeneous fields -> rank-local assembly -> recompute -> solve
        from repro.dist.solver import build_dist_assembly, \
            make_dist_coeff_solver
        from repro.fem.assemble import inclusion_fields
        assert prob.assembler is not None      # device assembly default
        da = build_dist_assembly(dg, prob.assembler)
        run_c = make_dist_coeff_solver(dg, da, mesh, rtol=1e-8, maxiter=200)
        aargs = da.sharded_args()
        E_h, nu_h = inclusion_fields(prob.mesh)
        solver.bind_assembler(prob.assembler)
        solver.update_coefficients(E_h, nu_h)
        ref_c = solver.solve(prob.b)
        xc, itc, rrc, okc, stc = jax.block_until_ready(
            run_c(args, aargs, *da.scatter_fields(E_h, nu_h), b))
        assert int(stc[0]) == 0, stc
        assert bool(okc[0]), (itc, rrc)
        assert int(itc[0]) == int(ref_c.iters), \
            f"coeff parity: dist={int(itc[0])} single={int(ref_c.iters)}"
        np.testing.assert_allclose(dg.gather_vector(xc),
                                   np.asarray(ref_c.x), rtol=1e-6,
                                   atol=1e-9)
        # rank-local assembly == globally assembled value stream, exactly
        A_h = prob.coefficient_operator(E_h, nu_h)
        xv, itv, _, okv, _ = jax.block_until_ready(
            run(args, dg.scatter_fine_payloads(A_h.data), b))
        assert bool(okv[0]) and int(itv[0]) == int(itc[0])
        np.testing.assert_allclose(dg.gather_vector(xv),
                                   dg.gather_vector(xc), rtol=1e-12,
                                   atol=1e-12)
        # zero retraces across repeated updates — even f32-typed callers
        # (fields stage at the policy dtype, mirroring the payload scatter)
        run_c(args, aargs,
              *da.scatter_fields(np.asarray(E_h, np.float32) * 1.5, nu_h), b)
        assert run_c._cache_size() == 1, run_c._cache_size()
        print(f"coefficient hot-loop parity: iters={int(itc[0])} "
              f"(assembled rank-locally, no retrace)")

    if os.environ.get("REPRO_SELFTEST_MARCH") == "1":
        # warm-started coefficient time march over the wire: the same
        # softening trajectory stepped by (a) the single-device fused
        # march primitive (gamg.make_coeff_solve) and (b) the
        # warm_start=True dist coefficient program, whose x output slab
        # feeds straight back in as the next step's x0 slab — no
        # gather/scatter round trip, the slab-sharded twin of the
        # repro.sim march step.  Per-step iteration parity + allclose.
        from repro.dist.solver import build_dist_assembly, \
            make_dist_coeff_solver
        from repro.robust.health import HEALTHY
        from repro.sim.scenarios import SofteningScenario
        assert prob.assembler is not None
        da_m = build_dist_assembly(dg, prob.assembler)
        run_cm = make_dist_coeff_solver(dg, da_m, mesh, rtol=1e-8,
                                        maxiter=200, warm_start=True)
        aargs_m = da_m.sharded_args()
        coeff_solve = gamg.make_coeff_solve(setupd, prob.assembler,
                                            rtol=1e-8, maxiter=200)
        scen = SofteningScenario.build(prob, rate=0.3)
        state = scen.init_state()
        x_ref = jax.numpy.zeros_like(prob.b)
        # commit the cold x0 slab to the program's output sharding so the
        # warm feedback (x output slab -> next x0 slab) never retraces
        from jax.sharding import NamedSharding, PartitionSpec
        x_slab = jax.device_put(
            np.asarray(dg.scatter_vector(np.zeros(prob.n))),
            NamedSharding(mesh, PartitionSpec("rank")))
        march_iters = []
        for s in range(3):
            E_s, nu_s, state = scen.step_fields(
                state, x_ref, jax.numpy.asarray(s, jax.numpy.int32))
            res_s = jax.block_until_ready(
                coeff_solve(E_s, nu_s, prob.b, x_ref))
            xm2, itm2, rrm2, okm2, stm2 = jax.block_until_ready(
                run_cm(args, aargs_m, *da_m.scatter_fields(E_s, nu_s),
                       b, x_slab))
            assert int(np.asarray(stm2)[0]) == HEALTHY, stm2
            assert bool(okm2[0]), (itm2, rrm2)
            assert int(itm2[0]) == int(res_s.iters), \
                f"march step {s}: dist={int(itm2[0])} " \
                f"single={int(res_s.iters)}"
            np.testing.assert_allclose(dg.gather_vector(xm2),
                                       np.asarray(res_s.x), rtol=1e-6,
                                       atol=1e-9)
            march_iters.append(int(itm2[0]))
            x_ref, x_slab = res_s.x, xm2
        # warm start earns its keep: the last step re-solved cold needs
        # at least as many iterations as the warm dist step took
        res_cold = coeff_solve(E_s, nu_s, prob.b,
                               jax.numpy.zeros_like(prob.b))
        assert march_iters[-1] <= int(res_cold.iters), \
            (march_iters, int(res_cold.iters))
        # one compiled program serves the whole warm march
        assert run_cm._cache_size() == 1, run_cm._cache_size()
        print(f"dist warm march parity (3 steps): iters={march_iters} "
              f"(cold last step: {int(res_cold.iters)})")

    if os.environ.get("REPRO_SELFTEST_FAULT") == "1":
        # fault battery over the wire.  The schedule must be live while
        # the program under test is TRACED (injection is trace-time), so
        # each case stages and jits a fresh program inside the context.
        from repro.robust import inject
        from repro.robust.health import HEALTHY, STATUS_NAMES

        # (a) NaN into the halo-exchange windows: every ppermute/allgather
        # window in the program (CG spmv halos, recompute stage-2 windows,
        # power-iteration halos) is poisoned; the collective flags must
        # trip on every rank and the returned best iterate stays finite.
        with inject.active(inject.parse_schedule("halo:nan")):
            dg_f = build_dist_gamg(setupd, ndev, coarse_eq_limit=0)
            run_f = make_dist_solver(dg_f, setupd, mesh, rtol=1e-8,
                                     maxiter=200)
            xf, itf, rrf, okf, stf = jax.block_until_ready(
                run_f(dg_f.sharded_args(setupd),
                      dg_f.scatter_fine_payloads(prob.A.data), b))
        st_f = int(np.asarray(stf)[0])
        assert not bool(okf[0]), "halo fault must prevent convergence"
        assert st_f != HEALTHY, STATUS_NAMES.get(st_f, st_f)
        assert np.isfinite(dg_f.gather_vector(xf)).all(), \
            "a silent NaN escaped the flagged halo-faulted solve"
        print(f"halo fault detected: status={STATUS_NAMES[st_f]} "
              f"iters={int(itf[0])}")

        # (b) Inf into the distributed CG's operator apply at step 2:
        # flagged within one outer iteration of the injection.
        with inject.active(inject.parse_schedule("spmv:inf@2")):
            dg_f2 = build_dist_gamg(setupd, ndev, coarse_eq_limit=0)
            run_f2 = make_dist_solver(dg_f2, setupd, mesh, rtol=1e-8,
                                      maxiter=200)
            xf2, itf2, _, okf2, stf2 = jax.block_until_ready(
                run_f2(dg_f2.sharded_args(setupd),
                       dg_f2.scatter_fine_payloads(prob.A.data), b))
        st_f2 = int(np.asarray(stf2)[0])
        assert not bool(okf2[0]) and st_f2 != HEALTHY
        assert int(itf2[0]) <= 3, \
            f"step-2 spmv fault flagged late: iters={int(itf2[0])}"
        assert np.isfinite(dg_f2.gather_vector(xf2)).all()
        print(f"spmv@2 fault detected: status={STATUS_NAMES[st_f2]} "
              f"iters={int(itf2[0])}")

        # (c) recovery: a clean re-staging (no schedule installed) must
        # restore exact parity with the unfaulted cold solve.
        assert inject.current() is None
        dg_r = build_dist_gamg(setupd, ndev, coarse_eq_limit=0)
        run_r = make_dist_solver(dg_r, setupd, mesh, rtol=1e-8, maxiter=200)
        xr, itr, _, okr, str_ = jax.block_until_ready(
            run_r(dg_r.sharded_args(setupd),
                  dg_r.scatter_fine_payloads(prob.A.data), b))
        assert bool(okr[0]) and int(np.asarray(str_)[0]) == HEALTHY
        assert int(itr[0]) == int(iters[0]), (int(itr[0]), int(iters[0]))
        np.testing.assert_allclose(dg_r.gather_vector(xr), x_g,
                                   rtol=0, atol=0)
        print("post-fault re-staging parity: identical")

    if os.environ.get("REPRO_SELFTEST_OVERLAP") == "1":
        from jax.sharding import PartitionSpec

        from repro.core.block_csr import BlockCSR
        from repro.dist import pamg
        from repro.dist import solver as dist_solver
        from repro.dist.partition import partition_rows
        from repro.robust import inject
        from repro.robust.health import HEALTHY
        P_ = PartitionSpec

        def solve_with(mode, schedule=None):
            """Fresh staging + trace under one REPRO_OVERLAP rendering."""
            os.environ["REPRO_OVERLAP"] = mode
            try:
                ctx = (inject.active(inject.parse_schedule(schedule))
                       if schedule else None)
                try:
                    if ctx is not None:
                        ctx.__enter__()
                    dg_m = build_dist_gamg(setupd, ndev, coarse_eq_limit=0)
                    run_m = make_dist_solver(dg_m, setupd, mesh,
                                             rtol=1e-8, maxiter=200)
                    out = jax.block_until_ready(
                        run_m(dg_m.sharded_args(setupd),
                              dg_m.scatter_fine_payloads(prob.A.data), b))
                finally:
                    if ctx is not None:
                        ctx.__exit__(None, None, None)
                return dg_m, out
            finally:
                os.environ.pop("REPRO_OVERLAP", None)

        # (a) full-solve parity: same iteration count, bitwise solution
        dg_on, (x_on, it_on, _, ok_on, st_on) = solve_with("on")
        dg_off, (x_off, it_off, _, ok_off, st_off) = solve_with("off")
        assert bool(ok_on[0]) and bool(ok_off[0]), (it_on, it_off)
        assert int(st_on[0]) == int(st_off[0]) == HEALTHY
        assert int(it_on[0]) == int(it_off[0]), \
            f"overlap parity: on={int(it_on[0])} off={int(it_off[0])}"
        np.testing.assert_array_equal(np.asarray(x_on), np.asarray(x_off))
        # the fine level genuinely has both partitions to overlap
        op0 = dg_on.levels[0].a_op
        print(f"overlap solve parity: iters={int(it_on[0])} bitwise "
              f"(int_rows min={int(op0.int_counts.min())} "
              f"bnd_rows max={int(op0.bnd_counts.max())})")

        # (b) apply-level bitwise battery: strategies x rhs shapes x dtypes
        def banded_op(offs, dtype, wrap):
            nbr, bs = 4 * ndev, 2
            cols = [sorted({i} | {((i + o) % nbr if wrap
                                   else min(max(i + o, 0), nbr - 1))
                                  for o in offs})
                    for i in range(nbr)]
            indptr = np.cumsum([0] + [len(c) for c in cols])
            indices = np.concatenate(cols).astype(np.int64)
            rng_b = np.random.default_rng(7)
            data = rng_b.standard_normal(
                (len(indices), bs, bs)).astype(dtype)
            A = BlockCSR.from_arrays(indptr, indices,
                                     jax.numpy.asarray(data), nbr)
            part = partition_rows(nbr, ndev)
            return A, part, data

        def scatter_slabs(part, pad, xg):
            out = np.zeros((ndev, pad) + xg.shape[1:], xg.dtype)
            for r in range(ndev):
                sl = part.slab(r)
                out[r, :sl.stop - sl.start] = xg[sl]
            return out

        def assert_bitwise(op, x_slabs):
            stack = tuple(jax.numpy.asarray(s) for s in (
                op.indices, op.indices_local, op.int_mask, op.data))

            def rank(idx, loc, msk, dat, x):
                idx, loc, msk, dat, x = jax.tree.map(
                    lambda t: t[0], (idx, loc, msk, dat, x))
                y0 = pamg.dist_ell_apply(idx, dat,
                                         pamg.halo_window(x, op.halo))
                pend = pamg.start_halo_exchange(x, op.halo)
                yi = pamg.dist_ell_apply_interior(loc, dat, x)
                win = pamg.finish_halo_exchange(pend)
                yb = pamg.dist_ell_apply_boundary(idx, dat, win)
                y1 = pamg.combine_split(msk, yi, yb)
                return y0[None], y1[None]

            f = jax.shard_map(rank, mesh=mesh, in_specs=(P_("rank"),) * 5,
                              out_specs=P_("rank"), check_vma=False)
            y0, y1 = jax.jit(f)(*stack, jax.numpy.asarray(x_slabs))
            np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))

        cases = [("ppermute", (-1, 1), False)]
        if ndev >= 4:
            cases.append(("allgather", (4 * ndev // 2,), True))
        rng_x = np.random.default_rng(11)
        for name, offs, wrap in cases:
            for dtype in (np.float64, np.float32):
                A_c, part_c, data_c = banded_op(offs, dtype, wrap)
                op_c = pamg.build_dist_ell(A_c, part_c, part_c,
                                           const_data=data_c)
                assert op_c.halo.strategy == name, \
                    (name, op_c.halo.strategy)
                assert op_c.bnd_counts.max() > 0    # split is non-trivial
                for trail in ((), (3,)):            # vector + panel
                    xg = rng_x.standard_normal(
                        (A_c.nbr, 2) + trail).astype(dtype)
                    assert_bitwise(op_c, scatter_slabs(
                        part_c, op_c.halo.cpad, xg))
        # replicated halo: the split degenerates to all-interior and must
        # still be bitwise (every rank holds the global input)
        A_r, part_r, data_r = banded_op((-1, 1), np.float64, False)
        op_r = pamg.build_dist_ell(A_r, part_r, part_r, const_data=data_r,
                                   replicated_cols=True)
        assert op_r.halo.strategy == "replicated"
        xg_r = rng_x.standard_normal((A_r.nbr, 2))
        assert_bitwise(op_r, np.broadcast_to(
            xg_r, (ndev,) + xg_r.shape).copy())
        print(f"overlap apply battery bitwise: "
              f"strategies={[c[0] for c in cases] + ['replicated']} "
              f"x (vector, panel) x (f64, f32)")

        # (c) jaxpr residue: the off-rendering router IS the hand-rolled
        # blocking apply — identical jaxpr, not merely identical values
        A_j, part_j, data_j = banded_op((-1, 1), np.float64, False)
        op_j = pamg.build_dist_ell(A_j, part_j, part_j, const_data=data_j)
        # args pre-sliced *outside* the traced fns (as the solver's
        # sharded-args staging does), so the comparison covers exactly the
        # apply: the unused split-plan entries must leave zero residue
        a_j = {"a_idx": jax.numpy.asarray(op_j.indices[0]),
               "a_loc": jax.numpy.asarray(op_j.indices_local[0]),
               "a_msk": jax.numpy.asarray(op_j.int_mask[0])}
        dat_j = jax.numpy.asarray(op_j.data[0])
        xs_j = scatter_slabs(part_j, op_j.halo.cpad,
                             rng_x.standard_normal((A_j.nbr, 2)))

        def routed(x):
            return dist_solver._rank_spmv(
                op_j, a_j, "a_", dat_j, x[0], False)[None]

        def handrolled(x):
            return pamg.dist_ell_apply(
                a_j["a_idx"], dat_j,
                pamg.halo_window(x[0], op_j.halo))[None]

        jaxprs = [str(jax.make_jaxpr(jax.shard_map(
            f, mesh=mesh, in_specs=P_("rank"), out_specs=P_("rank"),
            check_vma=False))(jax.numpy.asarray(xs_j)))
            for f in (routed, handrolled)]
        assert jaxprs[0] == jaxprs[1], \
            "REPRO_OVERLAP=off left residue vs the blocking apply"
        print("overlap off-path jaxpr: residue-free identical")

        # (d) fault-detection latency is schedule-independent: a halo NaN
        # trips the same status in the same iteration under either
        # rendering (the "halo" site fires on the assembled window in
        # finish_halo_exchange, shared by both)
        _, (xf_on, itf_on, _, okf_on, stf_on) = solve_with(
            "on", schedule="halo:nan")
        _, (xf_off, itf_off, _, okf_off, stf_off) = solve_with(
            "off", schedule="halo:nan")
        assert not bool(okf_on[0]) and not bool(okf_off[0])
        assert int(np.asarray(stf_on)[0]) == int(np.asarray(stf_off)[0]) \
            != HEALTHY, (stf_on, stf_off)
        assert int(itf_on[0]) == int(itf_off[0]), \
            f"halo-fault detection latency changed under overlap: " \
            f"on={int(itf_on[0])} off={int(itf_off[0])}"
        print(f"overlap fault-detection parity: status="
              f"{int(np.asarray(stf_on)[0])} iters={int(itf_on[0])}")

    prec = os.environ.get("REPRO_PRECISION")
    if prec and prec not in ("f64", "fp64", "float64", "double"):
        # reduced-precision-resident distributed hierarchy: fp64 outer CG,
        # boundary casts.  Convergence + bounded iteration growth + close
        # solution vs the fp64 reference (exact parity is an fp64 claim).
        setup_p = gamg.setup(prob.A, prob.B, coarse_size=30, precision=prec)
        dg_p = build_dist_gamg(setup_p, ndev)
        run_p = make_dist_solver(dg_p, setup_p, mesh, rtol=1e-8, maxiter=200)
        xp, itp, rrp, okp, _ = jax.block_until_ready(
            run_p(dg_p.sharded_args(setup_p),
                  dg_p.scatter_fine_payloads(prob.A.data), b))
        assert bool(okp[0]), (itp, rrp)
        bound = int(np.ceil(1.3 * int(ref0.iters))) + 1
        assert int(itp[0]) <= bound, \
            f"{prec} dist iters {int(itp[0])} > {bound} (f64: {ref0.iters})"
        np.testing.assert_allclose(dg_p.gather_vector(xp),
                                   np.asarray(ref0.x), rtol=1e-5, atol=1e-7)
        h_dt = setup_p.precision.hierarchy_dtype
        # level 0's prolongator moves to the switch boundary when the
        # default placement agglomerates the first mid level
        p_stage = (dg_p.levels[0].p_op if dg_p.levels[0].p_op is not None
                   else dg_p.switch.p_b)
        assert p_stage.data.dtype == h_dt
        print(f"reduced precision ({prec}): iters={int(itp[0])} "
              f"(f64 ref {int(ref0.iters)}) relres={float(rrp[0]):.3e}")

    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 5))
