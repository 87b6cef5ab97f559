"""Preconditioned conjugate gradients (the paper's Krylov accelerator).

Convergence is monitored on the *unpreconditioned* residual norm, matching
the paper's Sec. 4.1 ("with this norm the two formats converge in the same
iteration count to the same true residual") — which makes the blocked/scalar
iteration-parity test exact.

Health monitoring (ISSUE 6): the while-loop carry additionally tracks
NaN/Inf, CG-breakdown and stagnation flags plus the best (minimum-residual)
iterate, surfaced as a structured ``SolveHealth`` on ``CGResult``.  All of
it is derived from reductions the recurrence already computes, so the
healthy path stays bitwise identical to the unmonitored loop (no extra
syncs, no retraces — pinned by ``tests/test_robust.py``).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace
from repro.robust import inject
from repro.robust.health import SolveHealth, status_of

Array = jax.Array


class CGResult(NamedTuple):
    x: Array
    iters: Array
    relres: Array
    converged: Array
    health: SolveHealth
    # device-side solve counters (repro.obs.trace.CycleTally) when the
    # solve ran under REPRO_OBS=counters; None otherwise.  None is an
    # empty pytree node, so the default changes no traced structure.
    counters: "obs_trace.CycleTally | None" = None


def wrap_precond(apply_m: Callable[[Array], Array], precond_dtype,
                 outer_dtype) -> Callable[[Array], Array]:
    """The mixed-precision preconditioner boundary, in one place.

    Casts the residual down to ``precond_dtype`` before ``apply_m`` and
    the preconditioned direction back to ``outer_dtype`` after —
    iterative-refinement style.  Returns ``apply_m`` unchanged when no
    cast is needed, so full-precision callers stay bitwise.  Shared by
    ``pcg``, ``block_pcg`` and the distributed ``_rank_pcg``.
    """
    if precond_dtype is None:
        return apply_m
    pd = jnp.dtype(precond_dtype)
    outer = jnp.dtype(outer_dtype)
    if pd == outer:
        return apply_m

    def wrapped(r):
        return apply_m(r.astype(pd)).astype(outer)

    return wrapped


def in_scope(name: str, fn: Callable) -> Callable:
    """``fn`` run inside the stage scope ``name`` (``obs_trace.scope``):
    op metadata only, the numerics unchanged."""
    def scoped(*args):
        with obs_trace.scope(name):
            return fn(*args)

    return scoped


def pcg(apply_a: Callable[[Array], Array],
        apply_m: Callable[[Array], Array],
        b: Array, x0: Array | None = None, rtol: float = 1e-8,
        maxiter: int = 200, record_history: bool = False,
        precond_dtype=None, stall_window: int = 40, tally=None):
    """Standard PCG; fixed SPD preconditioner (one AMG V-cycle).

    ``x0`` warm-starts the iteration from a prior iterate (``None`` is
    the cold zero start, bitwise the classic recurrence).  CG's theory
    is start-agnostic — only the initial residual ``b - A x0`` matters —
    so a good seed (the previous quasi-static/Newton step's solution,
    threaded by the ``repro.sim`` march) begins within a few digits of
    the tolerance and converges in a fraction of the cold count.  An
    exact-solution seed reports ``iters=0, converged=True``: the
    pre-loop residual check is the same monitor the loop uses.

    ``record_history=True`` (a static, trace-time switch — the default
    jitted hot path is unchanged) additionally returns the per-iteration
    unpreconditioned residual-norm trace as a fixed-size ``(maxiter,)``
    buffer: slot ``i`` holds ``||r||`` after iteration ``i+1``; slots past
    ``iters`` stay NaN.  Used by the benchmark/convergence plots.

    ``precond_dtype`` (static) is the mixed-precision boundary: when set,
    the residual is cast to that dtype before ``apply_m`` and the
    preconditioned direction cast back to ``b.dtype`` afterwards —
    iterative-refinement style, so the outer iteration (dots, updates,
    convergence monitor) stays at the Krylov dtype while the AMG V-cycle
    runs on a reduced-precision hierarchy (``PrecisionPolicy``).  ``None``
    or ``b.dtype`` leaves the call chain bitwise unchanged.

    Breakdown floor: the relative-residual denominator is floored at
    ``finfo(b.dtype).tiny`` — a *dtype-aware* floor, because a literal
    like 1e-300 underflows to 0 below f64 and turns the ``b == 0`` case
    into a 0/0 NaN ``relres``.  An all-zero right-hand side therefore
    reports ``converged=True, iters=0, relres=0`` at every Krylov dtype
    (``x = 0`` is its exact solution).

    Health (``CGResult.health``, a ``SolveHealth``): the loop exits early
    on a NaN/Inf residual, on CG breakdown (non-positive ``p·Ap`` or
    ``r·z`` on an active step — e.g. an indefinite reduced-precision
    preconditioner) or after ``stall_window`` iterations without a new
    best residual (stagnation/divergence).  A broken step's update is
    discarded, and any non-converged exit returns the *minimum-residual*
    iterate — never a diverged or NaN one.  On a clean converging run
    every flag stays false and the iterates, iteration count and relres
    are bitwise those of the unmonitored recurrence.

    Counters (``tally=``, ISSUE 7): pass a ``repro.obs.trace.CycleTally``
    to thread device-side solve counters through the carry — ``apply_m``
    must then have the threaded signature ``(r, tally) -> (z, tally)``
    (``vcycle(..., tally=...)`` is exactly that) and the result's
    ``counters`` field carries the totals.  ``tally=None`` (default)
    adds an *empty* pytree node to the carry — zero leaves, zero jaxpr
    residue, the recurrence bitwise unchanged (``tests/test_obs.py``).

    Stage scopes: every operator application runs inside ``pcg/apply_a``
    and every preconditioner application, its precision casts included,
    inside ``pcg/precond``; the dots and updates are in neither.
    """
    counted = tally is not None
    if counted:
        apply_m = obs_trace.wrap_threaded_precond(apply_m, precond_dtype,
                                                  b.dtype)
    else:
        apply_m = wrap_precond(apply_m, precond_dtype, b.dtype)
    apply_a = in_scope("pcg/apply_a", apply_a)
    apply_m = in_scope("pcg/precond", apply_m)
    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - apply_a(x)
    if counted:
        tally = tally._replace(operator_applies=tally.operator_applies + 1)
        z, tally = apply_m(r, tally)
    else:
        z = apply_m(r)
    tl0 = tally if counted else ()
    p = z
    rz = jnp.vdot(r, z)
    bnorm = jnp.maximum(jnp.linalg.norm(b), jnp.finfo(b.dtype).tiny)
    rnorm = jnp.linalg.norm(r)
    # a poison rhs / x0 or a NaN first preconditioner apply is flagged
    # before the first iteration; an indefinite M shows as r·z <= 0
    nonf0 = ~jnp.isfinite(rnorm) | ~jnp.isfinite(rz)
    brk0 = ~nonf0 & (rz <= 0) & (rnorm > rtol * bnorm)

    def cond(state):
        (x, r, z, p, rz, rnorm, k, hist, best, stall, brk, nonf, tl) = state
        return ((rnorm > rtol * bnorm) & (k < maxiter)
                & ~brk & ~nonf & (stall < stall_window))

    def body(state):
        (x, r, z, p, rz, rnorm, k, hist,
         (best_x, best_rnorm, best_k), stall, brk, nonf, tl) = state
        Ap = inject.maybe("spmv", apply_a(p), step=k)
        pAp = jnp.vdot(p, Ap)
        alpha = rz / pAp
        x_new = x + alpha * p
        r_new = r - alpha * Ap
        if counted:
            tl = tl._replace(operator_applies=tl.operator_applies + 1)
            z_new, tl = apply_m(r_new, tl)
            z_new = inject.maybe("precond", z_new, step=k)
        else:
            z_new = inject.maybe("precond", apply_m(r_new), step=k)
        rz_new = jnp.vdot(r_new, z_new)
        beta = rz_new / rz
        p_new = z_new + beta * p
        rnorm_new = jnp.linalg.norm(r_new)
        nonf_new = (~jnp.isfinite(pAp) | ~jnp.isfinite(rnorm_new)
                    | ~jnp.isfinite(rz_new))
        brk_new = ~nonf_new & ((pAp <= 0)
                               | ((rz_new <= 0)
                                  & (rnorm_new > rtol * bnorm)))
        ok_step = ~(nonf_new | brk_new)
        # a broken step's update is discarded — the carry keeps the last
        # healthy state and the loop exits through the flag
        x = jnp.where(ok_step, x_new, x)
        r = jnp.where(ok_step, r_new, r)
        z = jnp.where(ok_step, z_new, z)
        p = jnp.where(ok_step, p_new, p)
        rz = jnp.where(ok_step, rz_new, rz)
        rnorm = jnp.where(ok_step, rnorm_new, rnorm)
        if record_history:
            hist = hist.at[k].set(rnorm)
        improved = ok_step & (rnorm_new < best_rnorm)
        best_x = jnp.where(improved, x_new, best_x)
        best_rnorm = jnp.where(improved, rnorm_new, best_rnorm)
        best_k = jnp.where(improved, k + 1, best_k)
        stall = jnp.where(improved, 0, stall + 1)
        return (x, r, z, p, rz, rnorm, k + 1, hist,
                (best_x, best_rnorm, best_k), stall,
                brk | brk_new, nonf | nonf_new, tl)

    hist0 = (jnp.full((maxiter,), jnp.nan, rnorm.dtype) if record_history
             else jnp.zeros((0,), rnorm.dtype))
    # a NaN initial residual must not poison the best-so-far tracking
    # (identity when rnorm is finite, i.e. on every healthy run)
    best_rnorm0 = jnp.where(jnp.isfinite(rnorm), rnorm, jnp.inf)
    state = (x, r, z, p, rz, rnorm, jnp.asarray(0), hist0,
             (x, best_rnorm0, jnp.asarray(0)), jnp.asarray(0), brk0, nonf0,
             tl0)
    (x, r, z, p, rz, rnorm, k, hist,
     (best_x, best_rnorm, best_k), stall, brk, nonf, tl_out) = \
        jax.lax.while_loop(cond, body, state)
    converged = rnorm <= rtol * bnorm
    # early termination (breakdown, stagnation, max-iters) returns the
    # minimum-residual iterate, not the last one
    x_out = jnp.where(converged, x, best_x)
    rnorm_out = jnp.where(converged, rnorm, best_rnorm)
    stag = ~converged & ~brk & ~nonf & (stall >= stall_window)
    health = SolveHealth(
        status=status_of(converged, brk, nonf, stag),
        breakdown=brk, nonfinite=nonf, stagnation=stag,
        best_iter=jnp.asarray(best_k, jnp.int32),
        best_relres=best_rnorm / bnorm)
    res = CGResult(x=x_out, iters=k, relres=rnorm_out / bnorm,
                   converged=converged, health=health,
                   counters=tl_out if counted else None)
    return (res, hist) if record_history else res
