"""Hierarchy-reusing solve server: request streams -> bucketed panel solves.

The production shape of the paper's reuse model: one cold ``GAMGSetup``
(aggregates, prolongators, PtAP plans) serves *many* solves — Newton
steps, load cases, client requests.  The server accepts a stream of
right-hand sides against the cached hierarchy and drains it in panels:

* requests are batched into column panels and padded up to a small static
  set of bucket widths (default k in {1, 2, 4, 8, 16}), so the jitted
  panel solve traces **once per bucket**, never per request count;
* padding columns are zero vectors — inactive from the first masked-PCG
  iteration, they cost VPU lanes but no extra iterations;
* each request gets back its own column, per-column iteration count and
  relative residual (the per-column masking keeps those identical to a
  dedicated single-RHS solve);
* ``update_operator`` refreshes the hierarchy through the state-gated hot
  recompute (new values, same structure) without touching the buckets.

Robustness contract (ISSUE 6): a malformed request — wrong shape, a
payload that cannot convert to the panel dtype, or non-finite values —
is rejected at ``submit`` with a ``ValueError`` before it can poison a
panel.  Corruption that arises *in flight* (a faulted kernel, a poisoned
hierarchy) is quarantined per column by the masked PCG's health flags:
the broken column freezes, its neighbours finish untouched, and its
report carries ``status="degraded"`` (usable best iterate) or
``status="failed"`` (solution zeroed — an explicit failure must never
look like an answer).  A flush therefore *never* raises because one
request went bad, and never returns an unflagged NaN.  With a
``recover=`` policy (or ``REPRO_RECOVER``), failed/degraded columns get
one bounded retry on freshly traced closures under
``inject.suppress_transient()`` — transient faults vanish from the fresh
traces, persistent ones keep the explicit failure.

``examples/serve_amg.py`` drives this end to end;
``benchmarks/table6_multirhs.py`` measures the per-RHS amortization the
bucketing buys.
"""
from __future__ import annotations

import time
from typing import Hashable, List, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import gamg
from repro.multirhs.block_krylov import make_block_solve
from repro.obs import trace as obs_trace
from repro.obs.server_metrics import ServerMetrics
from repro.robust import inject
from repro.robust.health import (
    BREAKDOWN,
    HEALTHY,
    NONFINITE,
    STATUS_NAMES,
)


class SolveReport(NamedTuple):
    request_id: Hashable
    x: np.ndarray         # (n,) solution for this request
    iters: int
    relres: float
    converged: bool
    k_bucket: int         # panel width the request was served in
    status: str = "ok"    # "ok" | "degraded" | "failed" | "recovered"
    health: int = HEALTHY  # raw health code (repro.robust.STATUS_NAMES)
    # observability (ISSUE 7): end-to-end submit->report latency (includes
    # any recovery retry this request triggered), submit->batch-start wait,
    # and — when the server records history — this request's per-iteration
    # residual-norm trace ((maxiter,), NaN past its final iteration).
    latency_s: float = 0.0
    queue_wait_s: float = 0.0
    history: "np.ndarray | None" = None


class AMGSolveServer:
    """Setup-once, serve-many front end over a cached GAMG hierarchy."""

    def __init__(self, setupd: gamg.GAMGSetup, a_fine_data, *,
                 buckets: Sequence[int] = (1, 2, 4, 8, 16),
                 rtol: float = 1e-8, maxiter: int = 200,
                 assembler=None, recover=None, record_history=None):
        from repro.kernels.backend import resolve_recover
        buckets_in = [int(k) for k in buckets]
        if not buckets_in:
            raise ValueError("buckets must be a non-empty sequence of "
                             "panel widths")
        if min(buckets_in) < 1:
            raise ValueError(f"bucket widths must be positive ints, got "
                             f"{buckets_in}")
        if len(set(buckets_in)) != len(buckets_in):
            raise ValueError(f"duplicate bucket widths in {buckets_in}: "
                             f"each width traces one panel solve, list "
                             f"each once")
        buckets = tuple(sorted(buckets_in))
        self.setupd = setupd
        self.buckets = buckets
        self.n = int(setupd.stats["level_rows"][0])
        # panels are assembled at the policy's *Krylov* dtype (fp64 under
        # every stock policy): every rhs is force-cast to it at submit
        # time, so a mixed-dtype burst can never have one request's dtype
        # decide the panel's — and a reduced-precision-resident hierarchy
        # (e.g. ``precision="f32"``) still serves full-fp64 requests, the
        # cast to the hierarchy dtype happening only at the masked PCG's
        # preconditioner boundary.
        self.dtype = np.dtype(setupd.precision.krylov_dtype)
        self._rtol = rtol
        self._maxiter = maxiter
        # per-request residual-history recording (the block PCG's
        # record_history parity, ISSUE 7): None defers to the obs knob —
        # on whenever REPRO_OBS (or a ``use`` scope) is not "off".
        if record_history is None:
            record_history = obs_trace.resolve() != "off"
        self._record_history = bool(record_history)
        self._recompute = gamg.make_recompute(setupd)
        self._solve = make_block_solve(setupd, rtol=rtol, maxiter=maxiter,
                                       record_history=self._record_history)
        self._a_fine_data = jnp.asarray(a_fine_data)
        self.hierarchy = self._recompute(self._a_fine_data)
        # bounded per-column retry on flagged columns (None disables);
        # resolve_recover honours the REPRO_RECOVER env knob
        self.recover = resolve_recover(recover)
        # optional device-assembly binding: coefficient updates (material
        # fields, not value streams) run assembly + recompute as one
        # jitted program; built at construction so a mismatched plan
        # fails here, not at the first update.
        self.assembler = assembler
        self._coeff_recompute = None if assembler is None else \
            gamg.make_coeff_recompute(setupd, assembler)
        self._coeff_fields = None       # last (E, nu), for clean retries
        self._pending: List[tuple] = []
        self._next_id = 0
        self.stats = {
            "requests": 0, "batches": 0, "padded_columns": 0,
            "recomputes": 0, "coefficient_updates": 0,
            "solves_per_k": {k: 0 for k in buckets},
            "rejected": 0, "degraded": 0, "failed": 0, "recovered": 0,
        }
        # always-on host-side instrumentation (repro.obs.server_metrics):
        # pure clocks and counters around work the server already does, so
        # the traced programs — and the REPRO_OBS=off bitwise contract —
        # are untouched.
        self._metrics = ServerMetrics(buckets)

    # ---- observability ---------------------------------------------------
    def metrics(self) -> ServerMetrics:
        """The server's measurement surface (latency/padding histograms,
        outcome counters; export via ``.to_prometheus()``/``.to_jsonl()``)."""
        return self._metrics

    def snapshot(self) -> dict:
        """One plain-dict health/throughput summary (dashboard poll)."""
        return self._metrics.snapshot()

    # ---- operator lifecycle ---------------------------------------------
    def update_operator(self, a_fine_data) -> None:
        """Hot path: new fine values, same structure (state-gated PtAP)."""
        self._a_fine_data = jnp.asarray(a_fine_data)
        self._coeff_fields = None
        with self._metrics.registry.timer("server/recompute_seconds") as t:
            self.hierarchy = t.block(self._recompute(self._a_fine_data))
        self.stats["recomputes"] += 1

    def update_coefficients(self, E, nu) -> None:
        """Hot path: new material fields (per-element arrays or scalars).

        Device assembly (element blocks through the cached COO plan)
        fused with the state-gated recompute — the server's quasi-static
        client contract: ship two small coefficient arrays, not an
        ``(nnzb, 3, 3)`` value stream.  Fields are force-cast to the
        assembler dtype, so mixed-dtype clients share one traced program.
        """
        if self.assembler is None:
            raise ValueError(
                "update_coefficients needs an assembler: construct the "
                "server with assembler=problem.assembler (device assembly "
                "path)")
        E, nu = self.assembler.as_fields(E, nu)
        self._coeff_fields = (E, nu)
        with self._metrics.registry.timer(
                "server/coeff_update_seconds") as t:
            self.hierarchy = t.block(self._coeff_recompute(E, nu))
        self.stats["recomputes"] += 1
        self.stats["coefficient_updates"] += 1

    # ---- request stream --------------------------------------------------
    def submit(self, b, request_id: Optional[Hashable] = None) -> Hashable:
        """Queue one right-hand side; returns its request id.

        The validation gate: a rhs that is the wrong shape, cannot convert
        to the panel dtype, or carries NaN/Inf is rejected HERE with a
        ``ValueError`` — one poison request must never reach a shared
        panel (where rejecting it would mean re-solving its neighbours).
        """
        try:
            b = np.asarray(b, dtype=self.dtype)
        except (TypeError, ValueError) as e:
            self.stats["rejected"] += 1
            self._metrics.rejected.inc()
            raise ValueError(
                f"rhs does not convert to the panel dtype "
                f"{self.dtype}: {e}") from e
        if b.shape != (self.n,):
            self.stats["rejected"] += 1
            self._metrics.rejected.inc()
            raise ValueError(f"rhs shape {b.shape} != ({self.n},)")
        if not np.isfinite(b).all():
            self.stats["rejected"] += 1
            self._metrics.rejected.inc()
            raise ValueError(
                f"rhs contains {int((~np.isfinite(b)).sum())} non-finite "
                f"values — rejected before panel assembly")
        if request_id is None:
            request_id = self._next_id
            self._next_id += 1
        self._pending.append((request_id, b, time.perf_counter()))
        self._metrics.requests.inc()
        self._metrics.pending.set(len(self._pending))
        return request_id

    def _bucket_for(self, count: int) -> int:
        """Smallest bucket width holding ``count`` columns.

        ``count > buckets[-1]`` raises: ``flush`` caps chunks at the
        largest bucket, so a bigger count is a caller/bookkeeping bug —
        silently truncating it would drop requests.
        """
        if count < 1:
            raise ValueError(f"chunk must hold at least one request, "
                             f"got {count}")
        if count > self.buckets[-1]:
            raise ValueError(f"chunk of {count} requests exceeds the "
                             f"largest bucket width {self.buckets[-1]}")
        for k in self.buckets:
            if k >= count:
                return k
        raise AssertionError("unreachable: count <= buckets[-1]")

    # ---- flagged-column recovery ----------------------------------------
    def _retry_column(self, b: np.ndarray):
        """One bounded retry of a flagged column: fresh jitted closures +
        fresh hierarchy under ``suppress_transient`` (one-off corruption
        vanishes from fresh traces; persistent faults survive and keep
        the explicit failure)."""
        with self._metrics.registry.timer("server/retry_seconds") as t, \
                inject.suppress_transient():
            recompute = gamg.make_recompute(self.setupd)
            solve = make_block_solve(self.setupd, rtol=self._rtol,
                                     maxiter=self._maxiter)
            if self._coeff_fields is not None:
                coeff = gamg.make_coeff_recompute(self.setupd,
                                                  self.assembler)
                hier = coeff(*self._coeff_fields)
            else:
                hier = recompute(self._a_fine_data)
            return t.block(solve(hier, jnp.asarray(b[:, None])))

    def _classify(self, code: int, converged: bool) -> str:
        if code == HEALTHY and converged:
            return "ok"
        if code in (BREAKDOWN, NONFINITE):
            return "failed"
        return "degraded"       # maxiter / stagnation: best iterate usable

    def flush(self) -> List[SolveReport]:
        """Drain the queue: bucketed, padded, batched solves; one report
        per request, in submission order.

        Per-column health classification — a flagged column degrades or
        fails *its own report only* (the masked PCG froze it without
        touching its panel neighbours).  Failed columns return zeros,
        degraded columns their best iterate; neither ever carries a NaN.
        With ``self.recover`` set, flagged columns get one retry via
        ``_retry_column`` first.

        Every report carries its timing (ISSUE 7): ``queue_wait_s`` from
        submit to its batch starting, ``latency_s`` from submit to the
        report existing — computed *after* any recovery retry, so a
        retried request's latency owns the retry it caused (previously a
        recovered request would have under-reported its latency by the
        whole retry).  The batch's blocked solve wall time and the
        per-request numbers also land in ``self.metrics()``.
        """
        reports: List[SolveReport] = []
        kmax = self.buckets[-1]
        while self._pending:
            chunk = self._pending[:kmax]
            del self._pending[:kmax]
            self._metrics.pending.set(len(self._pending))
            t_batch = time.perf_counter()
            k = self._bucket_for(len(chunk))
            B = np.zeros((self.n, k), self.dtype)
            for j, (_, b, _) in enumerate(chunk):
                B[:, j] = b
            out = self._solve(self.hierarchy, jnp.asarray(B))
            res, hist = out if self._record_history else (out, None)
            x = np.asarray(res.x)
            iters = np.asarray(res.iters)
            relres = np.asarray(res.relres)
            conv = np.asarray(res.converged)
            codes = np.asarray(res.health.status)
            hist_np = None if hist is None else np.asarray(hist)
            # every result array is on host now — the clock stop is honest
            solve_s = time.perf_counter() - t_batch
            for j, (rid, b_j, t_sub) in enumerate(chunk):
                code = int(codes[j])
                status = self._classify(code, bool(conv[j]))
                x_j, it_j = x[:, j], int(iters[j])
                rr_j = float(relres[j])
                if status != "ok" and self.recover is not None:
                    r1 = self._retry_column(b_j)
                    c1 = int(np.asarray(r1.health.status)[0])
                    if c1 == HEALTHY and bool(np.asarray(r1.converged)[0]):
                        status, code = "recovered", c1
                        x_j = np.asarray(r1.x)[:, 0]
                        it_j = int(np.asarray(r1.iters)[0])
                        rr_j = float(np.asarray(r1.relres)[0])
                if status == "failed":
                    # explicit failure: never hand back a maybe-iterate
                    x_j = np.zeros_like(x_j)
                elif not np.isfinite(x_j).all():  # pragma: no cover
                    # belt-and-braces: the masked PCG's best-iterate
                    # tracking keeps flagged columns finite by
                    # construction; if that invariant ever breaks,
                    # fail the report rather than leak a NaN
                    status, x_j = "failed", np.zeros_like(x_j)
                if status in ("degraded", "failed", "recovered"):
                    self.stats[status] += 1
                # latency clocked here, after any retry: the client waited
                # through it, so this request's latency includes it
                queue_wait = t_batch - t_sub
                latency = time.perf_counter() - t_sub
                self._metrics.record_request(status, it_j, queue_wait,
                                             latency)
                reports.append(SolveReport(
                    request_id=rid, x=x_j, iters=it_j,
                    relres=rr_j, converged=bool(conv[j]) or
                    status == "recovered",
                    k_bucket=k, status=status, health=code,
                    latency_s=latency, queue_wait_s=queue_wait,
                    history=None if hist_np is None else hist_np[:, j]))
            self.stats["requests"] += len(chunk)
            self.stats["batches"] += 1
            self.stats["padded_columns"] += k - len(chunk)
            self.stats["solves_per_k"][k] += 1
            self._metrics.record_batch(k, len(chunk), solve_s)
        return reports

    def serve(self, rhs_list: Sequence) -> List[SolveReport]:
        """Convenience: submit a batch of RHS vectors and flush."""
        for b in rhs_list:
            self.submit(b)
        return self.flush()


__all__ = ["AMGSolveServer", "SolveReport", "STATUS_NAMES"]
