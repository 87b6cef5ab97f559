"""Observe AMG: the full telemetry surface around one serving session.

Drives every layer of ``repro.obs`` (ISSUE 7) against a live solve
server:

* ``counters`` mode — a device-side ``CycleTally`` rides the CG carry,
  so the solve itself reports what it did (per-level visits, smoother /
  operator / coarse applications) and what the traffic model says it
  should have cost — compared here against the analytic expectation;
* per-request residual **histories** (NaN-padded per-column traces) from
  the panel solve, rendered as a convergence sketch;
* the server's always-on ``ServerMetrics``: queue wait, end-to-end
  latency, blocked solve wall time, padding efficiency, per-bucket and
  per-status counts — polled via ``snapshot()`` and exported both as
  Prometheus text and as a JSONL sink a dashboard could tail;
* the ``measure()`` compile/steady split on the hot recompute.

Run:  PYTHONPATH=src python examples/observe_amg.py [m]
"""
import sys

import numpy as np

import repro.core  # noqa: F401  (enables fp64)
from repro.core import gamg
from repro.fem.assemble import assemble_elasticity
from repro.multirhs import AMGSolveServer
from repro.obs import MetricsRegistry, describe_tally, use
from repro import compile_cache


def main(m: int = 6) -> None:
    print(f"assembling {m}^3 Q1 elasticity ...")
    prob = assemble_elasticity(m)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=40)
    print(f"hierarchy: {setupd.n_levels} levels, n = {prob.n}, "
          f"precision: {setupd.precision.describe()}")

    # ---- device-side counters on a single solve -------------------------
    # obs mode is consumed at trace time: build the closure inside the
    # scope (or set REPRO_OBS=counters before constructing the solver)
    with use("counters"):
        solve = gamg.make_solve(setupd, rtol=1e-8, maxiter=100)
    hier = gamg.make_recompute(setupd)(prob.A.data)
    res = solve(hier, prob.b)
    print(f"\nsolve: {int(res.iters)} iters, relres {float(res.relres):.2e}")
    print(f"tally: {describe_tally(res.counters)}")
    cycles = int(res.iters) + 1
    print(f"check: {cycles} cycles expected -> "
          f"{cycles} V-cycles, {2 * cycles} smoother sweeps/level, "
          f"{cycles} coarse solves")

    # ---- server metrics + per-request histories -------------------------
    # record_history defaults to "on when obs is on"; force it explicitly
    # so the demo works regardless of REPRO_OBS
    server = AMGSolveServer(setupd, prob.A.data, buckets=(1, 2, 4, 8),
                            rtol=1e-8, maxiter=100, record_history=True)
    rng = np.random.default_rng(0)
    for burst in (3, 8, 1):
        for _ in range(burst):
            server.submit(rng.standard_normal(prob.n))
        server.flush()
    reports = server.serve([np.asarray(prob.b)])
    r = reports[0]
    live = r.history[np.isfinite(r.history)]
    print(f"\nresidual history (request {r.request_id}, "
          f"{r.iters} iters, latency {r.latency_s * 1e3:.1f} ms):")
    marks = [0, len(live) // 2, len(live) - 1]
    print("  " + "  ".join(f"it{k:>3}: {live[k]:.2e}" for k in marks))

    snap = server.snapshot()
    print("\nserver snapshot:")
    for key in ("requests", "batches", "padded_columns",
                "padding_efficiency", "solves_per_k", "status"):
        print(f"  {key:>20}: {snap[key]}")
    print(f"  {'latency p50/p99':>20}: {snap['latency_p50_s'] * 1e3:.1f} / "
          f"{snap['latency_p99_s'] * 1e3:.1f} ms")
    print(f"  {'solve wall p50':>20}: {snap['solve_wall_p50_s'] * 1e3:.1f} ms")

    # ---- compile/steady split on the hot recompute ----------------------
    reg = MetricsRegistry()
    recompute = gamg.make_recompute(setupd)
    for scale in (1.0, 1.1, 1.2):
        reg.measure("recompute", recompute, scale * prob.A.data)
    cold = reg.get("recompute/compile").snapshot()
    hot = reg.get("recompute/steady").snapshot()
    print(f"\nrecompute: compile {cold['max'] * 1e3:.1f} ms (x{cold['count']})"
          f", steady {hot['max'] * 1e3:.1f} ms (x{hot['count']})")

    # ---- exporters ------------------------------------------------------
    prom = server.metrics().to_prometheus()
    wanted = ("server_request_latency_seconds_count",
              "server_padding_efficiency", "server_batches_total")
    print("\nprometheus exposition (excerpt):")
    for line in prom.splitlines():
        if line.startswith(wanted):
            print(f"  {line}")
    jsonl = server.metrics().to_jsonl()
    print(f"jsonl export: {len(jsonl.splitlines())} instrument lines "
          f"(tail one file per poll for a dashboard)")


if __name__ == "__main__":
    compile_cache.enable()
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 6)
