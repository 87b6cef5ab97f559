"""Batched PCG over column panels with per-column convergence masking.

One Krylov iteration on a ``(n, k)`` panel runs the operator and the AMG
preconditioner as SpMM — streaming A's values+indices once for all k
columns — while every CG scalar (``alpha``, ``beta``, ``rz``) becomes a
length-k vector of per-column reductions.  CG columns are mathematically
independent, so masking converged columns (their updates frozen at zero)
reproduces the looped single-RHS trajectories column by column: the same
iteration counts, the same solutions to fp tolerance
(``tests/test_multirhs.py`` + the property test assert both).

Convergence is monitored on the unpreconditioned residual norm per column,
matching ``repro.core.krylov.pcg`` — iteration-count parity with the
single-RHS path depends on the two monitors being identical.

Health monitoring rides the same per-column masks: a column whose
recurrence goes NaN/Inf, breaks down or stagnates is *quarantined* — its
updates freeze exactly like a converged column's, its flags are recorded
per column in ``BlockCGResult.health``, and its panel neighbours keep
iterating untouched.  This is the mechanism the solve server's per-request
``degraded``/``failed`` statuses are built on.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.krylov import in_scope, wrap_precond
from repro.core.vcycle import Hierarchy, fine_operator, vcycle
from repro.core.spmv import apply_ell
from repro.obs import trace as obs_trace
from repro.robust import inject
from repro.robust.health import SolveHealth, status_of

Array = jax.Array


class BlockCGResult(NamedTuple):
    x: Array          # (n, k) solutions
    iters: Array      # (k,)   iterations applied to each column
    relres: Array     # (k,)   final per-column relative residual
    converged: Array  # (k,)   bool
    health: SolveHealth  # per-column (k,) health record
    # device-side solve counters (repro.obs.trace.CycleTally) when the
    # panel ran under REPRO_OBS=counters; None (an empty pytree node —
    # no traced-structure change) otherwise.
    counters: "obs_trace.CycleTally | None" = None


def _col_dot(a: Array, b: Array) -> Array:
    """Per-column dot: reduce every axis but the trailing panel axis."""
    return jnp.sum(a * b, axis=tuple(range(a.ndim - 1)))


def _col_norm(a: Array) -> Array:
    return jnp.sqrt(jnp.sum(a * a, axis=tuple(range(a.ndim - 1))))


def block_pcg(apply_a: Callable[[Array], Array],
              apply_m: Callable[[Array], Array],
              B: Array, x0: Array | None = None, rtol: float = 1e-8,
              maxiter: int = 200, *,
              col_dot: Callable[[Array, Array], Array] = _col_dot,
              col_norm: Callable[[Array], Array] = _col_norm,
              precond_dtype=None, stall_window: int = 40,
              record_history: bool = False, tally=None):
    """PCG on a panel ``B: (..., k)`` with per-column masking.

    ``x0`` warm-starts every column from a prior ``(..., k)`` iterate
    panel (``None`` = cold zero start, bitwise unchanged); a column
    seeded within tolerance is inactive from iteration 0 — the same
    contract as ``core.krylov.pcg``'s warm start, column-wise.

    A column is *active* while its residual exceeds ``rtol * ||b_col||``
    and no health flag has tripped; frozen columns receive zero updates
    (``alpha = 0``) and keep their CG state, so the surviving columns'
    arithmetic is exactly the single-RHS recurrence.  The loop runs until
    every column converges or is flagged, or ``maxiter``.
    Zero columns (``||b|| ~ 0``) are inactive from the start (iters 0,
    converged, relres 0) — that is what makes the solve server's padding
    columns free.  Their denominator floor is ``finfo(B.dtype).tiny``
    (dtype-aware, like ``core.krylov.pcg``): a literal 1e-300 underflows
    to 0 below f64 and would NaN the zero columns' relres.

    ``col_dot`` / ``col_norm`` are the per-column reductions (everything
    but the trailing panel axis -> ``(k,)``).  The distributed path
    injects psum-reducing versions and runs this *same* recurrence over
    ``(rpad, bs, k)`` slabs inside shard_map — the dist-vs-single
    iteration-parity invariant depends on this body being the single
    source of truth (mirroring how ``core.vcycle`` shares the smoother
    recurrences).

    ``precond_dtype`` is the same mixed-precision boundary as
    ``core.krylov.pcg``: the panel residual is cast down before
    ``apply_m`` and the result cast back, so the masked outer recurrence
    stays at the Krylov dtype over a reduced-precision hierarchy.

    Health (``BlockCGResult.health``, per-column ``SolveHealth``): the
    operator and the V-cycle are column-independent, so corruption stays
    in its column; a flagged column is quarantined (frozen like a
    converged one, its broken step discarded) and its minimum-residual
    iterate is what the panel returns for it.  Clean columns' arithmetic,
    iteration counts and relres are bitwise unchanged.

    ``record_history=True`` (static, trace-time — parity with
    ``core.krylov.pcg``) additionally returns a ``(maxiter, k)`` buffer
    of per-column unpreconditioned residual norms: slot ``[i, c]`` holds
    column ``c``'s ``||r||`` after iteration ``i+1``, NaN once the column
    froze (converged, quarantined, or never active) — so a trace reads
    off each column's trajectory with its freeze point explicit.

    ``tally=`` (ISSUE 7) threads a ``repro.obs.trace.CycleTally`` through
    the carry exactly like ``pcg``; ``apply_m`` must then be the threaded
    ``(R, tally) -> (Z, tally)`` form.  The panel counts one operator /
    preconditioner application per *iteration* (SpMM streams A once for
    all columns — that is the point of the panel).  ``tally=None``
    (default) appends an empty pytree node: zero jaxpr residue.
    """
    counted = tally is not None
    if counted:
        apply_m = obs_trace.wrap_threaded_precond(apply_m, precond_dtype,
                                                  B.dtype)
    else:
        apply_m = wrap_precond(apply_m, precond_dtype, B.dtype)
    # the stage scopes of ``pcg``: operator, preconditioner (casts included)
    apply_a = in_scope("pcg/apply_a", apply_a)
    apply_m = in_scope("pcg/precond", apply_m)
    x = jnp.zeros_like(B) if x0 is None else x0
    r = B - apply_a(x)
    if counted:
        tally = tally._replace(operator_applies=tally.operator_applies + 1)
        z, tally = apply_m(r, tally)
    else:
        z = apply_m(r)
    tl0 = tally if counted else ()
    p = z
    rz = col_dot(r, z)
    bnorm = jnp.maximum(col_norm(B), jnp.finfo(B.dtype).tiny)
    rnorm = col_norm(r)
    nonf0 = ~jnp.isfinite(rnorm) | ~jnp.isfinite(rz)
    brk0 = ~nonf0 & (rz <= 0) & (rnorm > rtol * bnorm)

    def cond(state):
        (x, r, z, p, rz, rnorm, iters, k, best, stall, brk, nonf,
         hist, tl) = state
        active = ((rnorm > rtol * bnorm) & ~brk & ~nonf
                  & (stall < stall_window))
        return jnp.any(active) & (k < maxiter)

    def body(state):
        (x, r, z, p, rz, rnorm, iters, k,
         (best_x, best_rnorm, best_iter), stall, brk, nonf,
         hist, tl) = state
        active = ((rnorm > rtol * bnorm) & ~brk & ~nonf
                  & (stall < stall_window))
        Ap = inject.maybe("spmv", apply_a(p), step=k)
        pAp = col_dot(p, Ap)
        # frozen columns: guard the denominators, zero the step
        alpha = jnp.where(active, rz / jnp.where(active, pAp, 1.0), 0.0)
        x_new = x + alpha * p
        r_new = r - alpha * Ap
        if counted:
            tl = tl._replace(operator_applies=tl.operator_applies + 1)
            z_new, tl = apply_m(r_new, tl)
            z_new = inject.maybe("precond", z_new, step=k)
        else:
            z_new = inject.maybe("precond", apply_m(r_new), step=k)
        rz_new = col_dot(r_new, z_new)
        beta = jnp.where(active, rz_new / jnp.where(active, rz, 1.0), 0.0)
        rnorm_new = col_norm(r_new)
        nonf_new = active & (~jnp.isfinite(pAp) | ~jnp.isfinite(rnorm_new)
                             | ~jnp.isfinite(rz_new))
        brk_new = active & ~nonf_new & ((pAp <= 0)
                                        | ((rz_new <= 0)
                                           & (rnorm_new > rtol * bnorm)))
        ok_step = active & ~nonf_new & ~brk_new
        # a broken column's step is discarded — it keeps its last healthy
        # state, is quarantined by its flag, and its neighbours continue
        x = jnp.where(ok_step | ~active, x_new, x)
        r = jnp.where(ok_step | ~active, r_new, r)
        z = jnp.where(ok_step, z_new, z)
        p = jnp.where(ok_step, z_new + beta * p, p)
        rz = jnp.where(ok_step, rz_new, rz)
        rnorm = jnp.where(ok_step, rnorm_new, rnorm)
        improved = ok_step & (rnorm_new < best_rnorm)
        best_x = jnp.where(improved, x_new, best_x)
        best_rnorm = jnp.where(improved, rnorm_new, best_rnorm)
        best_iter = jnp.where(improved, k + 1, best_iter)
        stall = jnp.where(improved, 0, stall + active.astype(stall.dtype))
        iters = iters + active.astype(iters.dtype)
        if record_history:
            # frozen columns (converged / quarantined / broken step) stay
            # NaN — the trace shows exactly where each column stopped
            hist = hist.at[k].set(jnp.where(ok_step, rnorm_new, jnp.nan))
        return (x, r, z, p, rz, rnorm, iters, k + 1,
                (best_x, best_rnorm, best_iter), stall,
                brk | brk_new, nonf | nonf_new, hist, tl)

    iters0 = jnp.zeros(B.shape[-1], jnp.int32)
    # record_history=False contributes an *empty* carry node (like the
    # tally) — the default panel jaxpr is exactly the pre-obs one
    hist0 = (jnp.full((maxiter, B.shape[-1]), jnp.nan, rnorm.dtype)
             if record_history else ())
    # a NaN initial residual must not poison the best-so-far tracking
    best_rnorm0 = jnp.where(jnp.isfinite(rnorm), rnorm, jnp.inf)
    state = (x, r, z, p, rz, rnorm, iters0, jnp.asarray(0),
             (x, best_rnorm0, jnp.zeros(B.shape[-1], jnp.int32)),
             jnp.zeros(B.shape[-1], jnp.int32), brk0, nonf0, hist0, tl0)
    (x, r, z, p, rz, rnorm, iters, k,
     (best_x, best_rnorm, best_iter), stall, brk, nonf, hist, tl_out) = \
        jax.lax.while_loop(cond, body, state)
    converged = rnorm <= rtol * bnorm
    # a non-converged column reports its minimum-residual iterate
    x_out = jnp.where(converged, x, best_x)
    rnorm_out = jnp.where(converged, rnorm, best_rnorm)
    stag = ~converged & ~brk & ~nonf & (stall >= stall_window)
    health = SolveHealth(
        status=status_of(converged, brk, nonf, stag),
        breakdown=brk, nonfinite=nonf, stagnation=stag,
        best_iter=best_iter.astype(jnp.int32),
        best_relres=best_rnorm / bnorm)
    res = BlockCGResult(x=x_out, iters=iters, relres=rnorm_out / bnorm,
                        converged=converged, health=health,
                        counters=tl_out if counted else None)
    return (res, hist) if record_history else res


def make_block_solve(setupd, rtol: float = 1e-8, maxiter: int = 200,
                     record_history: bool = False, obs=None):
    """Jitted hot panel solve: ``(Hierarchy, B: (n, k)) -> BlockCGResult``
    (``(result, history)`` under ``record_history=True``).

    The multi-RHS twin of ``repro.core.gamg.make_solve`` — same smoother
    configuration, same hierarchy pytree, SpMM everywhere.  jax.jit traces
    once per distinct k; the solve server buckets request streams to a
    static k set precisely so this cache stays small.

    ``solve(hier, B, x0)`` warm-starts every column from a prior
    ``(n, k)`` iterate panel (the time-march knob — see
    ``core.krylov.pcg``); the two-argument cold form stays bitwise the
    pre-warm-start closure with its own single cache entry.

    The observability mode (``obs=`` > ``use`` scope > ``REPRO_OBS``) is
    resolved *here*, at closure-build time — matching the knob's
    trace-time contract.  Under ``"counters"`` the panel threads a
    ``CycleTally`` through the V-cycle and the result's ``counters``
    carries the totals plus the modeled HBM bytes
    (``repro.obs.model.vcycle_traffic`` x preconditioner applications).
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
    def solve(hier: Hierarchy, B: Array, x0: "Array | None" = None):
        def apply_a(X):
            return apply_ell(fine_operator(hier), X)

        if counted:
            def apply_m(R, tl):
                return vcycle(hier, R, smoother=smoother, degree=degree,
                              tally=tl)
            tally = obs_trace.zero_tally(n_levels)
        else:
            def apply_m(R):
                return vcycle(hier, R, smoother=smoother, degree=degree)
            tally = None

        out = block_pcg(apply_a, apply_m, B, x0=x0, rtol=rtol,
                        maxiter=maxiter, precond_dtype=precond_dtype,
                        record_history=record_history, tally=tally)
        if counted:
            res, hist = out if record_history else (out, None)
            res = res._replace(counters=obs_trace.attach_model_bytes(
                res.counters, cycle_bytes))
            return (res, hist) if record_history else res
        return out

    return solve
