"""GAMG — smoothed-aggregation AMG with the paper's hot/cold split.

``setup``      cold symbolic phase (paper Sec. 3.1): strength graph,
               aggregation, tentative + smoothed prolongators, every SpGEMM/
               transpose/ELL plan, all computed *on the block format* — the
               coarsening path never touches scalar AIJ (the paper's first
               invariant; ``tests/test_no_scalar_expansion.py`` enforces it).

``recompute``  hot numeric phase: given new fine-operator values (same
               structure — a Newton/time step), rebuild every level operator
               through the cached, state-gated PtAP plans, plus the smoother
               data (pbjacobi inverses, Chebyshev bounds).  One jitted
               device graph, no host symbolic work — the paper's hot PtAP.

``solve``      hot KSPSolve: AMG-preconditioned CG, fully device-resident.

Reuse model = PETSc ``-pc_gamg_reuse_interpolation true``: aggregates and
prolongator *values* are fixed across recomputes; only operators and
smoother data refresh.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (
    Aggregation,
    aggregation_from_device,
    graph_to_ell,
    greedy_aggregate,
    mis_aggregate_device,
)
from repro.core.block_csr import (
    BlockCSR,
    ELLPlan,
    transpose_apply_plan,
    transpose_bcsr,
)
from repro.core import jit_args
from repro.core.lanes import block_matmul_lanes
from repro.core.ptap import PtAPCache, ptap_numeric_data, ptap_symbolic
from repro.core.smooth import (
    invert_diag_blocks,
    lambda_max_dinv_a,
    smoothed_prolongator,
)
from repro.core.precision import PrecisionPolicy
from repro.core.strength import strength_graph
from repro.core.tentative import tentative_prolongator
from repro.core.vcycle import Hierarchy, LevelState, fine_operator, vcycle
from repro.core.spmv import spmv_ell
from repro.core.krylov import CGResult, pcg
from repro.obs import trace as obs_trace
from repro.robust import inject

Array = jax.Array


@dataclasses.dataclass
class LevelSetup:
    """Cold, host-side symbolic data for one level (structure + plans).

    Under the transpose-free default (``setup(restriction=
    "transpose_free")``) ``R``/``r_ell`` are ``None`` and ``pt`` carries
    the build-time ``P^T``-apply plan instead: the hot path restricts
    straight off ``p_ell``'s blocks and the hierarchy never stores the
    transposed duplicate.  Cold consumers that genuinely need the stored
    form (the scalar baseline's expansion, the dist sharded staging) go
    through ``restriction_bcsr``.
    """

    A0: BlockCSR            # level operator at setup time
    P: BlockCSR             # smoothed prolongator (values fixed on reuse)
    R: "BlockCSR | None"    # stored transpose (restriction="stored" only)
    ptap_cache: PtAPCache
    a_ell_plan: ELLPlan
    p_ell: "object"         # BlockELL (fixed values)
    r_ell: "object"         # BlockELL or None (transpose-free default)
    aggr: Aggregation
    omega: Array
    n_fine: int
    n_coarse: int
    pt: "object" = None     # EllTransposePlan (transpose-free default)


def restriction_bcsr(ls: LevelSetup) -> BlockCSR:
    """The stored-form restriction of a level, computing the transpose on
    demand when the setup is transpose-free (cold consumers only — the hot
    path restricts via ``vcycle.apply_restriction`` without it)."""
    return ls.R if ls.R is not None else transpose_bcsr(ls.P)


@dataclasses.dataclass
class GAMGSetup:
    levels: List[LevelSetup]
    coarse_struct: BlockCSR   # coarsest-level operator structure
    bs_fine: int
    nns_dim: int
    smoother: str
    degree: int
    theta: float
    coarsener: str
    stats: dict
    precision: PrecisionPolicy = dataclasses.field(
        default_factory=PrecisionPolicy.double)
    # distributed placement hint (PETSc ``-pc_gamg_process_eq_limit``):
    # levels whose equations-per-rank are at or below this leave the slab-sharded
    # path and run agglomerated (``repro.dist.solver.build_dist_gamg``).
    # ``None`` defers to the dist layer's default.
    coarse_eq_limit: "int | None" = None

    @property
    def n_levels(self) -> int:
        return len(self.levels) + 1


def setup(A: BlockCSR, B: Array, *, theta: float = 0.08,
          max_levels: int = 10, coarse_size: int = 100,
          smoother: str = "chebyshev", degree: int = 2,
          coarsener: str = "mis", precision=None,
          restriction: str = "transpose_free",
          coarse_eq_limit: "int | None" = None) -> GAMGSetup:
    """Cold GAMG setup on the block format (no scalar expansion anywhere).

    ``coarsener`` selects the aggregation path: ``"mis"`` (default) keeps
    even the cold graph phase on device via the jitted Luby-MIS coarsener
    (paper Sec. 6's future work); ``"greedy"`` is the classical host-side
    Vanek covering, kept as the fallback and the quality baseline
    (``tests/test_amg_convergence.py`` checks the two stay comparable).

    ``precision`` is a ``PrecisionPolicy`` / stock-policy name; ``None``
    resolves ``REPRO_PRECISION`` via ``repro.kernels.backend`` (default
    full fp64).  The *setup* math (strength, aggregation, prolongator
    smoothing) always runs at the operator dtype; the policy governs what
    ``recompute`` builds and what the solves run at.

    ``restriction`` selects how ``P^T`` is applied in the V-cycle:
    ``"transpose_free"`` (default) stores no restriction at all — a
    build-time ``EllTransposePlan`` lets the hot path restrict straight
    off ``p_ell``'s blocks, roughly halving prolongator-side hierarchy
    memory and shedding the setup transpose; ``"stored"`` keeps the legacy
    explicit ``R = transpose_bcsr(P)`` / ``r_ell`` (bitwise the
    pre-transpose-free behaviour).

    ``coarse_eq_limit`` is the distributed placement hint (equations per
    rank at or below which a level is agglomerated, PETSc's
    ``-pc_gamg_process_eq_limit``); the single-device path ignores it and
    ``repro.dist.solver.build_dist_gamg`` consumes it.
    """
    from repro.kernels.backend import resolve_precision
    precision = resolve_precision(precision)
    assert A.br == A.bc, "system operator must have square blocks"
    if restriction not in ("transpose_free", "stored"):
        raise ValueError(
            f"invalid restriction mode {restriction!r}: expected "
            f"'transpose_free' or 'stored'")
    levels: List[LevelSetup] = []
    Acur, Bcur = A, jnp.asarray(B)
    nns = int(Bcur.shape[1])
    stats = {"level_rows": [A.nbr * A.br], "level_nnzb": [A.nnzb],
             "level_bs": [A.br], "conversions_to_scalar": 0}
    if coarsener not in ("mis", "greedy"):
        raise ValueError(f"invalid coarsener {coarsener!r}: "
                         f"expected 'mis' or 'greedy'")

    def phase(what):
        # host span of one set-up phase of the level being built; it waits
        # for the outputs it is handed, so its time is its own
        return obs_trace.host_span(f"setup/level{len(levels)}/{what}")

    while Acur.nbr > coarse_size and len(levels) < max_levels - 1:
        bs = Acur.br
        with phase("strength") as done:
            graph = done(strength_graph(Acur, theta))
        with phase("aggregate") as done:
            if coarsener == "mis":
                idx, mask = graph_to_ell(graph)
                aggr = aggregation_from_device(
                    mis_aggregate_device(idx, mask))
                aggr = _repair_small_aggregates(aggr, graph,
                                                min_size=-(-nns // bs))
            else:
                aggr = greedy_aggregate(graph, min_size=-(-nns // bs))
        if aggr.n_agg >= Acur.nbr:        # no coarsening possible
            break
        with phase("tentative") as done:
            Ptent, Bc = done(tentative_prolongator(aggr, Bcur, bs))
        with phase("prolongator") as done:
            P, omega, lam, _plans = done(smoothed_prolongator(Acur, Ptent))
        with phase("ptap_symbolic") as done:
            cache = done(ptap_symbolic(Acur, P))
        # each numeric phase is one program with its plan as arguments
        # (a TPU compiles every eager operation on its own)
        with phase("ptap_numeric") as done:
            a_next_data = done(jit_args.call(ptap_numeric_data, cache,
                                             Acur.data, P.data))
        Anext = BlockCSR.from_arrays(cache.ac_plan.indptr,
                                     cache.ac_plan.indices, a_next_data,
                                     cache.n_coarse)
        with phase("ell") as done:
            p_ell = done(jit_args.call(ELLPlan.build, P.ell_plan(),
                                       P.data))
            if restriction == "stored":
                R = transpose_bcsr(P)
                r_ell, pt = done(R.to_ell()), None
            else:
                R, r_ell = None, None
                pt = done(transpose_apply_plan(P, p_ell.kmax))
        levels.append(LevelSetup(
            A0=Acur, P=P, R=R, ptap_cache=cache,
            a_ell_plan=Acur.ell_plan(), p_ell=p_ell, r_ell=r_ell,
            aggr=aggr, omega=omega, n_fine=Acur.nbr, n_coarse=aggr.n_agg,
            pt=pt))
        stats["level_rows"].append(Anext.nbr * Anext.br)
        stats["level_nnzb"].append(Anext.nnzb)
        stats["level_bs"].append(Anext.br)
        Acur, Bcur = Anext, Bc
    return GAMGSetup(levels=levels, coarse_struct=Acur, bs_fine=A.br,
                     nns_dim=nns, smoother=smoother, degree=degree,
                     theta=theta, coarsener=coarsener, stats=stats,
                     precision=precision, coarse_eq_limit=coarse_eq_limit)


def _repair_small_aggregates(aggr: Aggregation, graph, min_size: int
                             ) -> Aggregation:
    """Merge undersized MIS aggregates into neighbors (host, cold)."""
    agg = aggr.node_to_agg.copy()
    sizes = np.bincount(agg, minlength=aggr.n_agg)
    indptr, indices = graph.indptr, graph.indices
    for i in range(len(agg)):
        a = agg[i]
        if sizes[a] >= min_size:
            continue
        nbrs = indices[indptr[i]:indptr[i + 1]]
        cand = nbrs[agg[nbrs] != a] if len(nbrs) else nbrs
        if len(cand):
            t = agg[cand[0]]
            sizes[t] += sizes[a]
            sizes[a] = 0
            agg[agg == a] = t
    uniq, agg = np.unique(agg, return_inverse=True)
    return Aggregation(node_to_agg=agg.astype(np.int64), n_agg=len(uniq))


# ---------------------------------------------------------------------------
# Hot numeric recompute (the paper's state-gated PtAP chain).
# ---------------------------------------------------------------------------

def level_state(ls: LevelSetup, a_data: Array,
                policy: PrecisionPolicy = None) -> LevelState:
    """Numeric level state from hierarchy-dtype payloads ``a_data``.

    The dense diagonal inversion runs at ``policy.factor_dtype`` (LAPACK
    has no sub-f32 kernels) and the D^{-1}A scaling accumulates at
    ``policy.accum_dtype``; everything is *stored* at the hierarchy dtype.
    A full-fp64 policy leaves every operation bitwise unchanged.

    Shared verbatim by the scalar baseline (``scalar_path``) and the
    distributed path's agglomerated levels (``repro.dist.solver``) — the
    rank-redundant replicated tail IS the single-device computation, which
    is what makes agglomerated-vs-single parity exact by construction.
    """
    policy = policy or PrecisionPolicy.double()
    h = jnp.dtype(policy.hierarchy_dtype)
    acc = jnp.promote_types(h, jnp.dtype(policy.accum_dtype))
    A = ls.A0.with_data(a_data)
    diag = A.diagonal_blocks()
    dinv = invert_diag_blocks(
        diag.astype(policy.factor_dtype)).astype(h)
    a_ell = ls.a_ell_plan.build(a_data)
    # D^{-1} A row scaling on lane-dense blocks (rows minor)
    dinva_ell = jnp.transpose(block_matmul_lanes(
        jnp.transpose(dinv.astype(acc), (1, 2, 0)),
        jnp.transpose(a_ell.data.astype(acc), (2, 3, 1, 0))),
        (3, 2, 0, 1)).astype(h)
    lam = lambda_max_dinv_a(a_ell.indices, dinva_ell, a_ell.mask,
                            A.nbr, A.br)
    r_ell = ls.r_ell.astype(h) if ls.r_ell is not None else None
    return LevelState(a_ell=a_ell, p_ell=ls.p_ell.astype(h),
                      r_ell=r_ell, dinv=dinv, lam_max=lam, p_t=ls.pt)


def jittered_cholesky(densef: Array, base_scale: float,
                      retry_scale: float) -> Array:
    """Dense Cholesky with a one-shot jitter-escalation retry (jittable).

    The base factorization adds ``base_scale * trace/n`` to the diagonal
    (the legacy guard, bitwise when it succeeds — ``lax.cond`` evaluates
    only the taken branch and adds no host sync).  A NaN factor — XLA's
    Cholesky reports an indefinite or rank-deficient matrix as NaNs, it
    never aborts — triggers one retry with the much larger
    ``retry_scale * |trace|/n`` shift, which lifts any eigenvalue the
    base jitter could not.  A factor that is NaN even after the retry
    (corrupted payloads) is returned as-is: the V-cycle propagates it,
    the Krylov health flags catch it within one iteration, and the
    recovery ladder escalates to a re-setup.

    Single source of truth for the coarse factorization — shared by
    ``coarse_cholesky`` here and the distributed ``_rank_coarse_chol``.
    """
    n = densef.shape[0]
    eye = jnp.eye(n, dtype=densef.dtype)
    jitter = base_scale * jnp.trace(densef) / n
    chol = jnp.linalg.cholesky(densef + jitter * eye)
    # |trace|: an indefinite operator can have a tiny or negative trace,
    # and a negative "jitter" would dig the retry deeper
    retry_jitter = retry_scale * jnp.abs(jnp.trace(densef)) / n
    return jax.lax.cond(
        jnp.isfinite(chol).all(),
        lambda: chol,
        lambda: jnp.linalg.cholesky(densef + retry_jitter * eye))


def coarse_cholesky(dense: Array, policy: PrecisionPolicy) -> Array:
    """Jittered dense Cholesky of the coarsest operator.

    fp64 keeps the legacy 1e-12 relative jitter bitwise; reduced-precision
    chains carry O(eps) rounding into the coarse operator, so the guard
    scales with the hierarchy eps (``PrecisionPolicy.coarse_jitter_scale``)
    and the factorization runs at ``factor_dtype``.  A NaN base factor
    (indefinite/rank-deficient coarse operator) is retried once at the
    escalated ``coarse_retry_scale`` jitter — see ``jittered_cholesky``.
    """
    fd = jnp.dtype(policy.factor_dtype)
    chol = jittered_cholesky(dense.astype(fd),
                             policy.coarse_jitter_scale(),
                             policy.coarse_retry_scale())
    return chol.astype(policy.hierarchy_dtype)


def recompute(setupd: GAMGSetup, a_fine_data: Array) -> Hierarchy:
    """Hot numeric hierarchy rebuild: pure function of the fine values.

    The hierarchy (level payloads, transfer payloads, dinv, coarse factor)
    is built and stored at ``setupd.precision.hierarchy_dtype``; the PtAP
    chain runs at that dtype too, so the value traffic of the whole
    recompute scales with the policy's width.  Mixed policies additionally
    keep a krylov-dtype copy of the *finest* operator
    (``Hierarchy.a_fine_ell``) for the outer iteration.

    Wrap with ``make_recompute`` for the jitted production entry point.
    """
    policy = setupd.precision
    h = jnp.dtype(policy.hierarchy_dtype)
    a_in = jnp.asarray(a_fine_data)
    states = []
    a_data = a_in.astype(h)
    scope = obs_trace.scope
    for li, ls in enumerate(setupd.levels):
        # level-gated payload-corruption site (trace-time identity unless
        # a fault schedule is installed — repro.robust.inject)
        a_data = inject.maybe("hierarchy", a_data, level=li)
        with scope(f"recompute/level{li}/smoother_data"):
            states.append(level_state(ls, a_data, policy))
        with scope(f"recompute/level{li}/ptap"):
            a_data = ptap_numeric_data(ls.ptap_cache, a_data,
                                       ls.P.data.astype(h),
                                       accum_dtype=policy.kernel_accum_dtype)
    a_data = inject.maybe("hierarchy", a_data, level=len(setupd.levels))
    Ac = setupd.coarse_struct.with_data(a_data)
    with scope("recompute/coarse_chol"):
        chol = coarse_cholesky(Ac.to_dense(), policy)
    a_fine_ell = None
    if policy.mixed and setupd.levels:
        with scope("recompute/fine_copy"):
            a_fine_ell = setupd.levels[0].a_ell_plan.build(
                a_in.astype(policy.krylov_dtype))
    return Hierarchy(levels=tuple(states), coarse_chol=chol,
                     a_fine_ell=a_fine_ell)


def make_recompute(setupd: GAMGSetup):
    """Jitted hot-recompute program ``a_fine_data -> Hierarchy``.

    The setup's plans and fixed prolongator payloads are arguments of the
    program (``repro.core.jit_args``), not constants baked into it, so the
    executable stays small enough for the persistent compilation cache.
    One per setup: every holder of the setup (``GAMGSolver``,
    ``AMGSolveServer``, the recovery ladder) shares the one compiled
    program instead of compiling its own copy."""
    fn = setupd.__dict__.get("_recompute_jit")
    if fn is None:
        fn = setupd.__dict__["_recompute_jit"] = jit_args.Program(
            recompute, setupd)
    return fn


def _check_assembler(setupd: GAMGSetup, assembler) -> None:
    nnzb = setupd.levels[0].A0.nnzb if setupd.levels \
        else setupd.coarse_struct.nnzb
    if assembler.plan.nnzb != nnzb:
        # out-of-range gathers clamp silently under jit — a mismatched
        # plan would "converge" against a garbage operator
        raise ValueError(
            f"assembler plan does not match the setup's fine operator: "
            f"plan has {assembler.plan.nnzb} output blocks, the fine "
            f"level has {nnzb}")


def _assemble(assembler, E, nu):
    """Device assembly of the fine operator's values, in its stage scope."""
    with obs_trace.scope("recompute/assemble"):
        return assembler.coo_data(E, nu)


def _coeff_recompute(objs, E, nu):
    setupd, assembler = objs
    return recompute(setupd, _assemble(assembler, E, nu))


def make_coeff_recompute(setupd: GAMGSetup, assembler):
    """Jitted coefficient hot path: ``(E, nu) -> Hierarchy``.

    Fuses device FEM assembly (element blocks -> cached blocked-COO
    scatter, ``repro.fem.device_stiffness.DeviceAssembler.coo_data``) with
    the state-gated PtAP recompute into ONE program — the whole
    ``update -> set_values_coo -> recompute`` step of the quasi-static hot
    loop runs device-resident with zero host transfers.  The assembler's
    plan and the setup's symbolic data are program arguments
    (``repro.core.jit_args``); the program retraces only if those
    structures change.
    """
    _check_assembler(setupd, assembler)
    return jit_args.Program(_coeff_recompute, (setupd, assembler))


def hier_solve(setupd: GAMGSetup, hier: Hierarchy, b: Array,
               x0: "Array | None" = None, *, rtol: float = 1e-8,
               maxiter: int = 200) -> CGResult:
    """Traceable AMG-PCG solve on a hierarchy — the body ``make_solve``
    jits, exposed unjitted so larger device programs can compose it (the
    ``repro.sim`` march fuses it with assembly + recompute inside one
    ``lax.scan`` segment).

    ``x0`` warm-starts CG from a prior iterate (``None`` = cold zero
    start) — the time-march knob: consecutive quasi-static steps solve
    nearby systems, so seeding with the previous step's solution starts
    from a small residual and saves iterations (``pcg`` docstring).
    """
    def apply_a(x):
        return spmv_ell(fine_operator(hier), x)

    def apply_m(r):
        return vcycle(hier, r, smoother=setupd.smoother,
                      degree=setupd.degree)

    return pcg(apply_a, apply_m, b, x0=x0, rtol=rtol, maxiter=maxiter,
               precond_dtype=setupd.precision.smoother_dtype)


def make_solve(setupd: GAMGSetup, rtol: float = 1e-8, maxiter: int = 200,
               obs=None):
    """Jitted hot KSPSolve: AMG-preconditioned CG on a Hierarchy pytree.

    The jitted closure's optional third argument warm-starts the solve:
    ``solve(hier, b, x0)`` seeds CG with a prior iterate (a previous
    time/Newton step's solution), ``solve(hier, b)`` is the cold start
    and stays bitwise the pre-warm-start closure (one jit cache entry
    per calling form).

    The outer CG runs at the policy's ``krylov_dtype`` (the dtype of
    ``b`` / the ``fine_operator`` copy); the V-cycle preconditioner runs
    at ``smoother_dtype`` with the cast at the ``pcg`` boundary —
    iterative refinement around a reduced-precision hierarchy.

    The observability mode (``obs=`` > ``use`` scope > ``REPRO_OBS``,
    resolved here at closure-build time, matching the knob's trace-time
    contract) selects the counted variant: under ``"counters"`` a
    ``repro.obs.trace.CycleTally`` rides the CG carry and the returned
    ``CGResult.counters`` reports level visits, smoother/operator/coarse
    applications and the modeled HBM bytes
    (``repro.obs.model.vcycle_traffic`` x V-cycle invocations).  Off
    (the default) this closure is bitwise the pre-obs one.
    """
    smoother, degree = setupd.smoother, setupd.degree
    precond_dtype = setupd.precision.smoother_dtype
    counted = obs_trace.counters_enabled(obs)
    if counted:
        from repro.obs.model import vcycle_traffic
        itemsize = jnp.dtype(setupd.precision.hierarchy_dtype).itemsize
        cycle_bytes = float(
            vcycle_traffic(setupd, itemsize=itemsize)["total"])
        n_levels = setupd.n_levels

    @partial(jax.jit, static_argnames=())
    def solve(hier: Hierarchy, b: Array,
              x0: "Array | None" = None) -> CGResult:
        if counted:
            def apply_a(x):
                return spmv_ell(fine_operator(hier), x)

            def apply_m(r, tl):
                return vcycle(hier, r, smoother=smoother, degree=degree,
                              tally=tl)
            res = pcg(apply_a, apply_m, b, x0=x0, rtol=rtol,
                      maxiter=maxiter, precond_dtype=precond_dtype,
                      tally=obs_trace.zero_tally(n_levels))
            return res._replace(counters=obs_trace.attach_model_bytes(
                res.counters, cycle_bytes))

        return hier_solve(setupd, hier, b, x0, rtol=rtol,
                          maxiter=maxiter)

    return solve


def make_coeff_solve(setupd: GAMGSetup, assembler, rtol: float = 1e-8,
                     maxiter: int = 200):
    """Jitted fused march step: ``(E, nu, b, x0) -> CGResult``.

    The segmented march's per-step primitive — device FEM assembly
    (``DeviceAssembler.coo_data``), the state-gated PtAP recompute and
    the warm-started AMG-PCG solve in ONE traced program with zero host
    transfers.  ``x0`` is the previous step's iterate (pass
    ``jnp.zeros_like(b)`` for a cold start — the signature keeps it
    positional so the jit cache stays at one entry across the march).
    The fully-fused scan/while segments (scenario law + staleness
    monitor riding along) live in ``repro.sim.driver``.
    """
    _check_assembler(setupd, assembler)

    def coeff_solve(E, nu, b, x0):
        hier = recompute(setupd, _assemble(assembler, E, nu))
        return hier_solve(setupd, hier, b, x0, rtol=rtol,
                          maxiter=maxiter)

    return jax.jit(coeff_solve)


# ---------------------------------------------------------------------------
# Convenience front door
# ---------------------------------------------------------------------------

class GAMGSolver:
    """PETSc-shaped convenience wrapper: setup once, re-solve many times."""

    def __init__(self, A: BlockCSR, B: Array, **opts):
        # "obs" rides along to make_solve/make_block_solve (counters mode)
        solve_opts = {k: opts.pop(k) for k in ("rtol", "maxiter", "obs")
                      if k in opts}
        with obs_trace.host_span("setup"):
            self.setup_data = setup(A, B, **opts)
            self._recompute = make_recompute(self.setup_data)
            self._solve = make_solve(self.setup_data, **solve_opts)
            self._solve_opts = solve_opts
            self._solve_many = None
            with obs_trace.host_span("setup/first_recompute") as done:
                self.hierarchy = done(self._recompute(A.data))
        self.n_recomputes = 0

    def update_operator(self, a_fine_data: Array) -> None:
        """Hot path: new operator values, same structure (Newton step)."""
        self.hierarchy = self._recompute(a_fine_data)
        self.n_recomputes += 1

    def bind_assembler(self, assembler) -> None:
        """Attach a ``repro.fem`` DeviceAssembler, enabling coefficient
        updates: ``update_coefficients(E, nu)`` then runs assembly +
        recompute as one jitted device program."""
        self.assembler = assembler
        self._coeff_recompute = make_coeff_recompute(self.setup_data,
                                                     assembler)

    def update_coefficients(self, E, nu) -> None:
        """Hot path: new *material fields* (per-element E/nu arrays or
        scalars), same mesh/structure — device assembly fused with the
        state-gated PtAP chain (``make_coeff_recompute``)."""
        if getattr(self, "assembler", None) is None:
            raise ValueError(
                "update_coefficients needs a bound DeviceAssembler: "
                "call bind_assembler(problem.assembler) (device assembly "
                "path) first")
        with obs_trace.host_span("update_coefficients"):
            E, nu = self.assembler.as_fields(E, nu)
            self.hierarchy = self._coeff_recompute(E, nu)
        self.n_recomputes += 1

    def solve(self, b: Array, x0: "Array | None" = None) -> CGResult:
        """Solve; ``x0`` warm-starts CG from a prior iterate (the
        time-march knob — pass the previous step's solution).  The cold
        form keeps its own single jit cache entry.  The host span
        ``repro/solve`` covers the dispatch only: it waits for nothing."""
        with obs_trace.host_span("solve"):
            if x0 is None:
                return self._solve(self.hierarchy, b)
            return self._solve(self.hierarchy, b, x0)

    def solve_many(self, B: Array, x0: "Array | None" = None):
        """Panel solve: ``B (n, k)`` -> ``BlockCGResult`` (per-column
        masked PCG, one operator stream for all k columns).  ``x0``
        warm-starts every column from a prior ``(n, k)`` iterate panel.

        Retraces once per distinct k — stream workloads should go through
        ``repro.multirhs.AMGSolveServer``, which buckets k statically.
        """
        if self._solve_many is None:
            from repro.multirhs.block_krylov import make_block_solve
            self._solve_many = make_block_solve(self.setup_data,
                                                **self._solve_opts)
        if x0 is None:
            return self._solve_many(self.hierarchy, B)
        return self._solve_many(self.hierarchy, B, x0)

    def march(self, prob, scenario, cfg, **kw):
        """Front door to the device-resident time march
        (``repro.sim.driver.march``): quasi-static coefficient evolution
        through fused assembly + recompute + warm-started solve steps,
        with adaptive re-coarsening at staleness boundaries.  ``prob``
        must be the assembled problem this solver was built from."""
        from repro.sim.driver import march as _march
        kw.setdefault("setup_opts", {})
        return _march(prob, scenario, cfg, **kw)
