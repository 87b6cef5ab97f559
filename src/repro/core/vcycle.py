"""Device-resident V-cycle — the paper's hot KSPSolve phase (Sec. 3.1).

The cycle is expressed entirely over the padded BlockELL layout: SpMV with
the level operator, restriction/prolongation with R/P (rectangular blocks,
one block per fine row), point-block Jacobi or pbjacobi-preconditioned
Chebyshev smoothing, and a dense Cholesky coarse solve.  Everything is
jittable with static level structure, so one ``jax.jit`` wraps the whole
hot solve, exactly matching the paper's "fully device-resident in blocks"
invariant.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.block_csr import BlockELL, EllTransposePlan
from repro.core.spmv import apply_ell, apply_ell_t, block_matvec
from repro.obs import trace as obs_trace
from repro.robust import inject

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LevelState:
    """Numeric per-level state (pytree).  Structure lives in the specs.

    Restriction is stored one of two ways (``apply_restriction`` picks):
    ``p_t`` — the transpose-free default — applies ``P^T`` straight off
    ``p_ell``'s blocks via the build-time plan, so the prolongator-side
    payload exists once; ``r_ell`` is the legacy explicit ``P^T`` copy
    (``gamg.setup(restriction="stored")``), kept for the scalar baseline
    and bitwise comparisons.
    """

    a_ell: BlockELL       # level operator (bs x bs blocks)
    p_ell: BlockELL       # prolongator (bs_f x bs_c blocks), fixed values
    r_ell: Optional[BlockELL]            # stored restriction = P^T, or None
    dinv: Array           # (nbr, bs, bs) inverted diagonal blocks
    lam_max: Array        # chebyshev upper bound for D^{-1}A
    p_t: Optional[EllTransposePlan] = None   # transpose-free P^T plan

    def tree_flatten(self):
        return (self.a_ell, self.p_ell, self.r_ell, self.dinv,
                self.lam_max, self.p_t), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Hierarchy:
    """Device-resident numeric hierarchy, stored at the policy's
    ``hierarchy_dtype``.

    ``a_fine_ell`` is only populated by mixed-precision policies
    (``PrecisionPolicy.mixed``): a krylov-dtype copy of the finest
    operator for the *outer* Krylov iteration, so the residual monitor
    never sees the reduced-precision rounding of ``levels[0].a_ell``
    (which the smoother keeps using).  ``fine_operator`` picks the right
    one.
    """

    levels: Tuple[LevelState, ...]
    coarse_chol: Array    # lower Cholesky factor of the coarsest operator
    a_fine_ell: Optional[BlockELL] = None   # krylov-dtype finest operator

    def tree_flatten(self):
        return (self.levels, self.coarse_chol, self.a_fine_ell), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def fine_operator(hier: Hierarchy) -> BlockELL:
    """The finest-level operator the Krylov loop should apply: the
    krylov-dtype copy under a mixed policy, else level 0's operator."""
    return hier.a_fine_ell if hier.a_fine_ell is not None \
        else hier.levels[0].a_ell


@jax.jit
def pbjacobi_apply(dinv: Array, r: Array) -> Array:
    """Point-block Jacobi apply; ``r`` is ``(n,)`` or a panel ``(n, k)``.

    The block-diagonal solve is column-independent, so the panel case is
    the same einsum with the panel axis broadcast along the ellipsis —
    this (together with ``apply_ell`` and the trailing-dim broadcast of
    ``cho_solve``) is what makes the whole V-cycle multi-RHS for free.
    """
    nbr, bs = dinv.shape[0], dinv.shape[1]
    rb = r.reshape((nbr, bs) + r.shape[1:])
    return block_matvec(dinv, rb).reshape((nbr * bs,) + r.shape[1:])


def chebyshev_recurrence(spmv, pbj, lam_max: Array, b: Array, x: Array,
                         degree: int = 2, lo_frac: float = 0.1,
                         hi_frac: float = 1.05) -> Array:
    """pbjacobi-preconditioned Chebyshev on [lo_frac, hi_frac]*lam_max.

    Shape-agnostic and closure-parameterized so the single-device path and
    the distributed path (``repro.dist.solver``) run the *same* recurrence
    with the same constants — the iteration-parity invariant the dist
    selftest asserts depends on this being the single source of truth.
    """
    lo = lo_frac * lam_max
    hi = hi_frac * lam_max
    theta = 0.5 * (hi + lo)
    delta = 0.5 * (hi - lo)
    sigma = theta / delta
    rho = 1.0 / sigma
    r = b - spmv(x)
    z = pbj(r)
    d = z / theta
    x = x + d
    for _ in range(degree - 1):
        rho_new = 1.0 / (2.0 * sigma - rho)
        r = r - spmv(d)
        z = pbj(r)
        d = (rho_new * rho) * d + (2.0 * rho_new / delta) * z
        x = x + d
        rho = rho_new
    return x


def pbjacobi_recurrence(spmv, pbj, b: Array, x: Array, its: int = 2,
                        omega: float = 0.6) -> Array:
    """Damped point-block Jacobi, closure-parameterized like Chebyshev."""
    for _ in range(its):
        r = b - spmv(x)
        x = x + omega * pbj(r)
    return x


def chebyshev_smooth(lv: LevelState, b: Array, x: Array,
                     degree: int = 2, lo_frac: float = 0.1,
                     hi_frac: float = 1.05) -> Array:
    """GAMG's default smoother; degree 2 matches the paper's production
    setup of cheap, SpMV-dominated smoothing (Sec. 4.2)."""
    return chebyshev_recurrence(lambda v: apply_ell(lv.a_ell, v),
                                lambda r: pbjacobi_apply(lv.dinv, r),
                                lv.lam_max, b, x, degree, lo_frac, hi_frac)


def pbjacobi_smooth(lv: LevelState, b: Array, x: Array,
                    omega: float = 0.6, its: int = 2) -> Array:
    """Plain damped point-block Jacobi (the paper's pbjacobi option)."""
    return pbjacobi_recurrence(lambda v: apply_ell(lv.a_ell, v),
                               lambda r: pbjacobi_apply(lv.dinv, r),
                               b, x, its, omega)


def _fused_step(lv: LevelState, b: Array, x: Array, d: Array, c1, c2):
    """One fused recurrence step ``d' = c1*d + c2*D^{-1}(b - A x);
    x' = x + d'`` through the single-pass Pallas kernel."""
    from repro.kernels.fused_smoother import ops as _fs
    return _fs.smoother_step(lv.a_ell, lv.dinv, b, x, d, c1, c2)


def chebyshev_smooth_fused(lv: LevelState, b: Array, x: Array,
                           degree: int = 2, lo_frac: float = 0.1,
                           hi_frac: float = 1.05) -> Array:
    """Chebyshev smoothing with each recurrence step as one fused pass.

    Same recurrence constants as ``chebyshev_recurrence``; the residual is
    formed fresh from the current iterate inside the kernel (``b - A x``,
    mathematically identical to the incremental ``r -= A d`` update), so
    the fused path differs from the unfused one only in rounding.
    """
    lo = lo_frac * lv.lam_max
    hi = hi_frac * lv.lam_max
    theta = 0.5 * (hi + lo)
    delta = 0.5 * (hi - lo)
    sigma = theta / delta
    rho = 1.0 / sigma
    x, d = _fused_step(lv, b, x, jnp.zeros_like(b), 0.0, 1.0 / theta)
    for _ in range(degree - 1):
        rho_new = 1.0 / (2.0 * sigma - rho)
        x, d = _fused_step(lv, b, x, d, rho_new * rho,
                           2.0 * rho_new / delta)
        rho = rho_new
    return x


def pbjacobi_smooth_fused(lv: LevelState, b: Array, x: Array,
                          omega: float = 0.6, its: int = 2) -> Array:
    """Damped point-block Jacobi with each step as one fused pass."""
    d = jnp.zeros_like(b)
    for _ in range(its):
        x, d = _fused_step(lv, b, x, d, 0.0, omega)
    return x


def apply_smoother(lv, b, x, smoother: str, degree: int,
                   path: str | None = None):
    """Smoother-name dispatch — the single source of truth shared by the
    V-cycle here and the distributed path's replicated (agglomerated)
    levels, whose exact-parity argument depends on running this verbatim.

    ``path`` selects the execution strategy via ``repro.kernels.backend
    .resolve_smooth_path`` (``REPRO_SMOOTH_PATH``): "fused" runs each
    recurrence step as one Pallas pass (``repro.kernels.fused_smoother``,
    TPU default — the ``r``/``z`` intermediates never touch HBM),
    "reference" the unfused jnp recurrences (CPU default, the bitwise
    legacy path).  Resolution happens at trace time, like the other knobs.
    """
    from repro.kernels.backend import resolve_smooth_path
    if resolve_smooth_path(path, lv.a_ell.data.dtype) == "fused":
        if smoother == "chebyshev":
            return chebyshev_smooth_fused(lv, b, x, degree=degree)
        return pbjacobi_smooth_fused(lv, b, x, its=degree)
    if smoother == "chebyshev":
        return chebyshev_smooth(lv, b, x, degree=degree)
    return pbjacobi_smooth(lv, b, x, its=degree)


def apply_restriction(lv: LevelState, r: Array) -> Array:
    """Restrict a fine-level residual: ``P^T r`` via the stored ``r_ell``
    when the level carries one, else transpose-free off ``p_ell``'s own
    blocks (``apply_ell_t``).  Shared by the single-device V-cycle and the
    dist replicated tail — the dispatch is structural (trace-time)."""
    if lv.r_ell is not None:
        return apply_ell(lv.r_ell, r)
    return apply_ell_t(lv.p_ell, lv.p_t, r)


def vcycle(hier: Hierarchy, b: Array, smoother: str = "chebyshev",
           degree: int = 2, tally: "obs_trace.CycleTally | None" = None):
    """One V(degree,degree) cycle with zero initial guess (preconditioner).

    The recursion is a static Python loop over levels — unrolled in the
    jitted graph, all device-resident.  ``b`` may be a single vector
    ``(n,)`` or a column panel ``(n, k)``: every stage is column-
    independent — ELL SpMV/SpMM via ``apply_ell``, the block-diagonal
    smoother einsums broadcast along the trailing axis, and the coarse
    ``cho_solve`` natively accepts matrix right-hand sides — so the
    panel cycle is per-column identical to k single cycles (tested in
    ``tests/test_multirhs.py``).

    Observability: every stage runs inside an always-on named
    scope (``vcycle/level{i}/smooth|residual|restrict|prolong`` and
    ``vcycle/coarse``, ``repro.obs.trace.scope``) so a profiler capture
    reads as a per-level timeline; with a ``tally`` (a
    ``repro.obs.trace.CycleTally``, ``REPRO_OBS=counters``) the
    cycle additionally returns ``(x, tally')`` with level visits, smoother
    applications and the coarse solve counted on device.  ``tally=None``
    (the default) leaves both signature and jaxpr exactly the pre-obs
    ones — zero residue, pinned by ``tests/test_obs.py``.
    """
    scope = obs_trace.scope
    counted = tally is not None
    bs_stack = []
    x_stack = []
    rhs = b
    if counted:
        tally = tally._replace(
            precond_applies=tally.precond_applies + 1)
    for li, lv in enumerate(hier.levels):
        with scope(f"vcycle/level{li}/smooth"):
            x = apply_smoother(lv, rhs, jnp.zeros_like(rhs), smoother,
                               degree)
        with scope(f"vcycle/level{li}/residual"):
            r = rhs - apply_ell(lv.a_ell, x)
        bs_stack.append(rhs)
        x_stack.append(x)
        # restrict; inject.maybe is a trace-time identity unless a fault
        # schedule is installed (repro.robust.inject)
        with scope(f"vcycle/level{li}/restrict"):
            rhs = inject.maybe("vcycle", apply_restriction(lv, r), level=li)
        if counted:
            tally = tally._replace(
                level_visits=tally.level_visits.at[li].add(1),
                smoother_applies=tally.smoother_applies.at[li].add(1))
    with scope("vcycle/coarse"):
        xc = inject.maybe(
            "coarse",
            jax.scipy.linalg.cho_solve((hier.coarse_chol, True), rhs))
    if counted:
        tally = tally._replace(coarse_solves=tally.coarse_solves + 1)
    nlev = len(hier.levels)
    for up, (lv, rhs_l, x) in enumerate(zip(reversed(hier.levels),
                                            reversed(bs_stack),
                                            reversed(x_stack))):
        li = nlev - 1 - up
        with scope(f"vcycle/level{li}/prolong"):
            x = x + apply_ell(lv.p_ell, xc)       # prolong + correct
        with scope(f"vcycle/level{li}/smooth"):
            xc = apply_smoother(lv, rhs_l, x, smoother, degree)
        if counted:
            tally = tally._replace(
                smoother_applies=tally.smoother_applies.at[li].add(1))
    return (xc, tally) if counted else xc


def vcycle_apply_op(hier: Hierarchy, x: Array) -> Array:
    """Finest-level operator application (for the Krylov wrapper)."""
    return apply_ell(fine_operator(hier), x)
