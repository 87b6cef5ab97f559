"""Observability layer (ISSUE 7), tier-1 contracts.

What this module pins:

* ``REPRO_OBS=off|counters`` resolution, and ``off`` is FREE: the
  counter plumbing leaves **zero jaxpr residue** (the off-mode trace is
  byte-identical before and after a counters scope);
* the always-on stage scopes are op metadata: the compiled solve and
  coefficient-recompute programs carry them in their ``op_name``s, and
  the solution is **bitwise** that of a build without them, with zero
  retraces;
* host spans: records with parent, count and the compile seconds that
  fell inside, and no name-stack entry in a program traced inside one;
* counter correctness: on a pinned 2-level problem the ``CycleTally``
  matches the analytic expectations of AMG-preconditioned CG exactly
  (one V-cycle per operator application, two smoother sweeps per visited
  level, one coarse solve per cycle), and the modeled bytes equal
  cycles x the exact traffic model;
* ``block_pcg`` ``record_history=`` parity: per-column residual traces,
  NaN-padded past each column's final iteration;
* ``MetricsRegistry`` bucket math, quantile estimates, compile/steady
  phase split, and the JSONL / Prometheus exporters (round-tripped
  through ``parse_prometheus``);
* ``AMGSolveServer`` end-to-end metrics: queue wait / latency / solve
  wall histograms, padding efficiency, per-bucket and per-status counts.
"""
import contextlib
import json
import math

import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on)
import jax
import jax.numpy as jnp

from repro.core import gamg
from repro.fem.assemble import assemble_elasticity
from repro.kernels.backend import resolve_obs
from repro.multirhs import AMGSolveServer
from repro.multirhs.block_krylov import make_block_solve
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry, parse_prometheus
from repro.obs.model import vcycle_traffic

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def prob():
    return assemble_elasticity(4)


@pytest.fixture(scope="module")
def setupd(prob):
    # coarse_size=40 pins a 2-level hierarchy: one smoothed level + the
    # direct coarse grid — the analytic counter expectations below assume
    # exactly this shape.
    sd = gamg.setup(prob.A, prob.B, coarse_size=40, precision="f64")
    assert sd.n_levels == 2
    return sd


@pytest.fixture(scope="module")
def hier(setupd, prob):
    return gamg.make_recompute(setupd)(prob.A.data)


# ---------------------------------------------------------------------------
# Knob resolution
# ---------------------------------------------------------------------------

def test_resolve_obs_knob(monkeypatch):
    for raw, want in (("off", "off"), ("0", "off"), ("", "off"),
                      ("none", "off"), ("False", "off"),
                      ("counters", "counters"), ("Counters", "counters")):
        assert resolve_obs(raw) == want
    # the stage scopes are always on: no mode switches them
    for raw in ("verbose", "spans", "on", "1"):
        with pytest.raises(ValueError, match="invalid observability mode"):
            resolve_obs(raw)
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert resolve_obs() == "off"
    monkeypatch.setenv("REPRO_OBS", "counters")
    assert resolve_obs() == "counters"
    assert obs_trace.resolve() == "counters"


def test_use_scope_overrides_env(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert obs_trace.resolve() == "off"
    assert not obs_trace.counters_enabled()
    with obs_trace.use("counters"):
        assert obs_trace.resolve() == "counters"
        assert obs_trace.counters_enabled()
        # explicit arg still wins over the scope
        assert obs_trace.resolve("off") == "off"
        assert not obs_trace.counters_enabled("off")
    assert obs_trace.resolve() == "off"
    with pytest.raises(ValueError):
        obs_trace.use("spans").__enter__()


# ---------------------------------------------------------------------------
# Off-mode contract: zero jaxpr residue, bitwise parity, zero retraces
# ---------------------------------------------------------------------------

def test_off_mode_zero_jaxpr_residue(setupd, hier, prob):
    """The ISSUE-7 acceptance pin.  Fresh closures per trace (jax caches
    traces on the function object, which would mask — or fake — residue
    differences): the off-mode jaxpr is identical before and after a
    counters scope, and a counters-mode closure genuinely changes the
    trace (the tally carry exists)."""
    b = jnp.asarray(prob.b)

    def mk(obs=None):
        solve = gamg.make_solve(setupd, rtol=1e-8, maxiter=50, obs=obs)

        def f(b):
            return solve(hier, b).x
        return f

    before = str(jax.make_jaxpr(mk())(b))
    with obs_trace.use("counters"):
        during = str(jax.make_jaxpr(mk())(b))
    after = str(jax.make_jaxpr(mk())(b))
    assert before == after, "an exited obs scope must leave zero residue"
    assert before != during, "counters mode must thread the tally carry"


def _null_scope(name):
    return contextlib.nullcontext()


def _compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def test_spans_mode_bitwise_matches_off(setupd, hier, prob, monkeypatch):
    """The always-on stage scopes are metadata: the solve is bitwise that
    of a build with ``scope`` replaced by a null context — same solution,
    same iteration count, same relres — and neither retraces."""
    b = jnp.asarray(prob.b)
    scoped = gamg.make_solve(setupd, rtol=1e-8, maxiter=100)
    res_scoped = scoped(hier, b)
    with monkeypatch.context() as mp:
        mp.setattr(obs_trace, "scope", _null_scope)
        bare = gamg.make_solve(setupd, rtol=1e-8, maxiter=100)
        res_bare = bare(hier, b)
        assert "pcg/precond" not in _compiled_text(bare, hier, b)
    assert "pcg/precond" in _compiled_text(scoped, hier, b)
    assert bool(res_scoped.converged) and bool(res_bare.converged)
    np.testing.assert_array_equal(np.asarray(res_scoped.x),
                                  np.asarray(res_bare.x))
    assert int(res_scoped.iters) == int(res_bare.iters)
    np.testing.assert_array_equal(np.asarray(res_scoped.relres),
                                  np.asarray(res_bare.relres))
    assert res_scoped.counters is None and res_bare.counters is None
    scoped(hier, 2.0 * b)
    assert scoped._cache_size() == 1 and bare._cache_size() == 1


def test_make_solve_hlo_carries_stage_scopes(setupd, hier, prob):
    """The compiled solve names its stages in its ops' ``op_name``s, the
    metadata a profiler trace reports as each op's ``tf_op``."""
    solve = gamg.make_solve(setupd, rtol=1e-8, maxiter=100)
    text = _compiled_text(solve, hier, jnp.asarray(prob.b))
    for stage in ("pcg/precond/vcycle/level0/smooth", "pcg/apply_a",
                  "vcycle/level0/residual", "vcycle/level0/restrict",
                  "vcycle/level0/prolong", "vcycle/coarse"):
        assert stage in text, stage


def test_coeff_recompute_hlo_carries_stage_scopes(setupd, prob):
    E, nu = prob.assembler.as_fields(1.0, 0.3)
    prog = gamg.make_coeff_recompute(setupd, prob.assembler)
    text = _compiled_text(prog, E, nu)
    for stage in ("recompute/assemble", "recompute/level0/ptap",
                  "recompute/level0/smoother_data",
                  "recompute/coarse_chol"):
        assert stage in text, stage


def test_counters_mode_matches_off_solution(setupd, hier, prob):
    """The tally rides the carry but never feeds back into the recurrence:
    counted iterates are bitwise the uncounted ones."""
    b = jnp.asarray(prob.b)
    res_off = gamg.make_solve(setupd, rtol=1e-8, maxiter=100)(hier, b)
    res_cnt = gamg.make_solve(setupd, rtol=1e-8, maxiter=100,
                              obs="counters")(hier, b)
    np.testing.assert_array_equal(np.asarray(res_off.x),
                                  np.asarray(res_cnt.x))
    assert int(res_off.iters) == int(res_cnt.iters)


def test_spans_solver_cache_stays_at_one(prob):
    """Zero retraces across repeated solves with the stage scopes and
    the solver's host spans on."""
    solver = gamg.GAMGSolver(prob.A, prob.B, coarse_size=40,
                             rtol=1e-8, maxiter=100, precision="f64")
    b = jnp.asarray(prob.b)
    r1 = solver.solve(b)
    r2 = solver.solve(2.0 * b)
    assert bool(r1.converged) and bool(r2.converged)
    assert solver._solve._cache_size() == 1
    assert solver._recompute._cache_size() == 1


# ---------------------------------------------------------------------------
# Counter correctness on the pinned 2-level problem
# ---------------------------------------------------------------------------

def _expected_tally(setupd, iters):
    """Analytic expectations for AMG-PCG on a 2-level hierarchy.

    CG applies the preconditioner once at init plus once per iteration:
    ``iters + 1`` V-cycles.  Every V-cycle visits the one smoothed level
    on the way down (pre-smooth) and again on the way up (post-smooth)
    and does one direct coarse solve.  The operator count matches the
    preconditioner count (one fine SpMV at init, one per iteration)."""
    cycles = iters + 1
    return {"precond": cycles, "op": cycles, "coarse": cycles,
            "level_visits": [cycles], "smoother": [2 * cycles]}


def test_cycle_tally_matches_analytic_counts(setupd, hier, prob):
    b = jnp.asarray(prob.b)
    res = gamg.make_solve(setupd, rtol=1e-8, maxiter=100,
                          obs="counters")(hier, b)
    assert bool(res.converged)
    tl = res.counters
    assert tl is not None
    want = _expected_tally(setupd, int(res.iters))
    assert int(tl.precond_applies) == want["precond"]
    assert int(tl.operator_applies) == want["op"]
    assert int(tl.coarse_solves) == want["coarse"]
    assert np.asarray(tl.level_visits).tolist() == want["level_visits"]
    assert np.asarray(tl.smoother_applies).tolist() == want["smoother"]
    # modeled bytes = cycles x the exact per-cycle traffic model
    itemsize = jnp.dtype(setupd.precision.hierarchy_dtype).itemsize
    cycle_bytes = vcycle_traffic(setupd, itemsize=itemsize)["total"]
    assert float(tl.modeled_bytes) == pytest.approx(
        want["precond"] * cycle_bytes)
    line = obs_trace.describe_tally(tl)
    assert f"precond={want['precond']}" in line and "modeled_MB=" in line


def test_block_tally_matches_single_rhs(setupd, hier, prob):
    """The panel solve counts cycles exactly like the single-RHS path
    (one V-cycle serves the whole panel)."""
    b = jnp.asarray(prob.b)
    B = jnp.stack([b, 2.0 * b, -0.5 * b], axis=1)
    solve = make_block_solve(setupd, rtol=1e-8, maxiter=100,
                             obs="counters")
    res = solve(hier, B)
    assert np.asarray(res.converged).all()
    tl = res.counters
    cycles = int(np.asarray(res.iters).max()) + 1
    assert int(tl.precond_applies) == cycles
    assert int(tl.coarse_solves) == cycles
    assert np.asarray(tl.smoother_applies).tolist() == [2 * cycles]


# ---------------------------------------------------------------------------
# block_pcg record_history parity
# ---------------------------------------------------------------------------

def test_block_record_history_nan_padding(setupd, hier, prob):
    b = jnp.asarray(prob.b)
    B = jnp.stack([b, 3.0 * b], axis=1)
    solve = make_block_solve(setupd, rtol=1e-8, maxiter=60,
                             record_history=True)
    res, hist = solve(hier, B)
    hist = np.asarray(hist)
    assert hist.shape == (60, 2)
    iters = np.asarray(res.iters)
    for j in range(2):
        k = int(iters[j])
        assert np.isfinite(hist[:k, j]).all(), "live steps must be finite"
        assert np.isnan(hist[k:, j]).all(), \
            "frozen/finished steps must be NaN-padded"
        assert hist[:k, j].min() > 0.0
        # the trace is the residual-norm recurrence: its last live entry
        # is the norm the reported relres was computed from
        bnorm = float(jnp.linalg.norm(B[:, j]))
        assert hist[k - 1, j] / bnorm == pytest.approx(
            float(np.asarray(res.relres)[j]))


def test_block_record_history_does_not_perturb_solution(setupd, hier, prob):
    b = jnp.asarray(prob.b)
    B = jnp.stack([b, 3.0 * b], axis=1)
    plain = make_block_solve(setupd, rtol=1e-8, maxiter=60)(hier, B)
    rec, _ = make_block_solve(setupd, rtol=1e-8, maxiter=60,
                              record_history=True)(hier, B)
    np.testing.assert_array_equal(np.asarray(plain.x), np.asarray(rec.x))
    np.testing.assert_array_equal(np.asarray(plain.iters),
                                  np.asarray(rec.iters))


# ---------------------------------------------------------------------------
# MetricsRegistry: instruments, bucket math, exporters
# ---------------------------------------------------------------------------

def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("t", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(106.0)
    assert snap["min"] == 0.5 and snap["max"] == 100.0
    # upper-bound (le) semantics: 1.0 lands in the <=1 bucket, 100 in +Inf
    assert snap["buckets"] == {1.0: 2, 2.0: 1, 4.0: 1, math.inf: 1}
    # quantiles: linear-in-bucket estimate, clamped to observed max
    assert h.quantile(0.0) == pytest.approx(0.0)
    assert 0.0 < h.quantile(0.5) <= 2.0
    assert h.quantile(1.0) == pytest.approx(100.0)
    assert math.isnan(reg.histogram("empty").quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError, match="duplicate"):
        reg.histogram("dup", buckets=(1.0, 1.0))


def test_counter_gauge_contracts():
    reg = MetricsRegistry()
    c = reg.counter("req")
    c.inc()
    c.inc(2.5, labels={"k": "4"})
    assert c.value() == 1.0
    assert c.value({"k": "4"}) == 2.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    g.set(1)
    assert g.value() == 1.0
    # one name, one kind
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req")
    # re-request returns the same instrument
    assert reg.counter("req") is c


def test_measure_splits_compile_from_steady():
    reg = MetricsRegistry()

    @jax.jit
    def f(x):
        return x * 2.0

    x = jnp.ones(8)
    for _ in range(3):
        reg.measure("phase", f, x)
    compile_h = reg.get("phase/compile")
    steady_h = reg.get("phase/steady")
    assert compile_h.snapshot()["count"] == 1
    assert steady_h.snapshot()["count"] == 2


def test_timer_blocks_and_records():
    reg = MetricsRegistry()
    with reg.timer("span") as t:
        out = t.block(jnp.arange(4) + 1)
    assert t.seconds is not None and t.seconds >= 0.0
    assert reg.get("span").snapshot()["count"] == 1
    assert int(out.sum()) == 10
    # a raising span must not record a bogus duration
    with pytest.raises(RuntimeError):
        with reg.timer("span"):
            raise RuntimeError("boom")
    assert reg.get("span").snapshot()["count"] == 1


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("server/requests_total", help="accepted").inc(7)
    reg.gauge("server/padding_efficiency").set(0.8125)
    h = reg.histogram("server/solve_wall_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert "# TYPE server_requests_total counter" in text
    assert "# HELP server_requests_total accepted" in text
    parsed = parse_prometheus(text)
    assert parsed["server_requests_total"][""] == 7
    assert parsed["server_padding_efficiency"][""] == 0.8125
    buckets = parsed["server_solve_wall_seconds_bucket"]
    # cumulative le convention survives the round trip
    assert buckets['{le="0.01"}'] == 1
    assert buckets['{le="0.1"}'] == 2
    assert buckets['{le="1"}'] == 3
    assert buckets['{le="+Inf"}'] == 4
    assert parsed["server_solve_wall_seconds_count"][""] == 4
    assert parsed["server_solve_wall_seconds_sum"][""] == pytest.approx(
        5.555)


def test_jsonl_export_parses():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    reg.histogram("b", buckets=(1.0,)).observe(0.5)
    lines = reg.to_jsonl(timestamp=123.0).splitlines()
    docs = [json.loads(ln) for ln in lines]
    assert {d["name"] for d in docs} == {"a", "b"}
    assert all(d["ts"] == 123.0 for d in docs)
    hdoc = next(d for d in docs if d["name"] == "b")
    assert hdoc["count"] == 1 and hdoc["buckets"]["1.0"] == 1


def test_host_span_records_parent_count_and_compile():
    obs_trace.reset_host_spans()

    def fresh(x):                 # a new function: its first call compiles
        return jnp.cos(x) * 3.0 + 1.0

    with obs_trace.host_span("outer"):
        with obs_trace.host_span("outer/inner") as wait:
            out = wait(jax.jit(fresh)(jnp.arange(5.0)))
    with obs_trace.host_span("outer"):
        pass
    assert float(out[0]) == 4.0
    inner, first, second = obs_trace.host_spans()
    assert [r.name for r in (inner, first, second)] == [
        "outer/inner", "outer", "outer"]
    assert inner.parent == "outer" and first.parent is None
    assert (inner.count, first.count, second.count) == (1, 1, 2)
    assert first.start <= inner.start <= inner.end <= first.end
    # the compile fell inside both open spans, and inside neither later
    assert inner.compile_s > 0.0
    assert first.compile_s == pytest.approx(inner.compile_s)
    assert second.compile_s == 0.0
    # a span that raises is not recorded
    with pytest.raises(RuntimeError):
        with obs_trace.host_span("outer"):
            raise RuntimeError("boom")
    assert len(obs_trace.host_spans()) == 3
    obs_trace.reset_host_spans()
    assert obs_trace.host_spans() == []


def test_host_span_adds_no_name_stack_entry():
    """A program first traced inside a host span carries no trace of the
    call site (the same name as a stage scope inside it does)."""
    x = jnp.ones(4)
    with obs_trace.host_span("caller_site"):
        text = _compiled_text(jax.jit(lambda v: jnp.sin(v) * 2.0), x)
    assert "caller_site" not in text and "repro/" not in text

    def staged(v):
        with obs_trace.scope("caller_site"):
            return jnp.sin(v) * 2.0

    assert "caller_site" in _compiled_text(jax.jit(staged), x)


def test_solver_host_spans_cover_setup_phases(prob):
    obs_trace.reset_host_spans()
    solver = gamg.GAMGSolver(prob.A, prob.B, coarse_size=40,
                             rtol=1e-8, maxiter=100, precision="f64")
    solver.solve(jnp.asarray(prob.b))
    recs = {r.name: r for r in obs_trace.host_spans()}
    setup = recs["setup"]
    for phase in ("strength", "aggregate", "tentative", "prolongator",
                  "ptap_symbolic", "ptap_numeric", "ell"):
        rec = recs[f"setup/level0/{phase}"]
        assert rec.parent == "setup"
        assert setup.start <= rec.start <= rec.end <= setup.end
    assert recs["setup/first_recompute"].parent == "setup"
    assert setup.compile_s > 0.0
    assert recs["solve"].parent is None and recs["solve"].count >= 1


def test_default_registry_reset():
    obs_metrics.reset_default_registry()
    reg = obs_metrics.default_registry()
    assert obs_metrics.default_registry() is reg
    obs_metrics.reset_default_registry()
    assert obs_metrics.default_registry() is not reg


# ---------------------------------------------------------------------------
# Server end-to-end metrics
# ---------------------------------------------------------------------------

def test_server_metrics_end_to_end(setupd, prob):
    server = AMGSolveServer(setupd, prob.A.data, buckets=(1, 2, 4),
                            record_history=True)
    b = np.asarray(prob.b)
    for i in range(5):
        server.submit((1.0 + 0.25 * i) * b)
    assert server.metrics().pending.value() == 5.0
    reports = server.flush()
    assert len(reports) == 5
    assert all(r.status == "ok" and r.converged for r in reports)

    snap = server.snapshot()
    assert snap["requests"] == 5
    assert snap["batches"] == 2            # chunks of 4 + 1
    assert snap["pending"] == 0
    assert snap["status"] == {"ok": 5, "degraded": 0, "failed": 0,
                              "recovered": 0}
    assert snap["solves_per_k"] == {1: 1, 2: 0, 4: 1}
    assert snap["padded_columns"] == 0
    assert snap["padding_efficiency"] == pytest.approx(1.0)
    assert snap["latency_p50_s"] > 0.0
    assert snap["latency_p99_s"] >= snap["latency_p50_s"]

    for r in reports:
        # end-to-end latency owns the whole submit->report window, so it
        # bounds the queue wait from above
        assert r.latency_s >= r.queue_wait_s > 0.0
        # recorded history: finite through the final iteration, NaN after
        assert r.history is not None and r.history.shape == (200,)
        assert np.isfinite(r.history[:r.iters]).all()
        assert np.isnan(r.history[r.iters:]).all()

    text = server.metrics().to_prometheus()
    assert "server_request_latency_seconds_count 5" in text
    assert "server_solve_wall_seconds_count 2" in text
    assert "server_queue_wait_seconds_count 5" in text
    parsed = parse_prometheus(text)
    assert parsed["server_requests_total"][""] == 5
    assert parsed["server_batches_total"][""] == 2


def test_server_padding_efficiency_and_rejects(setupd, prob):
    server = AMGSolveServer(setupd, prob.A.data, buckets=(1, 2, 4))
    b = np.asarray(prob.b)
    for i in range(3):
        server.submit((1.0 + i) * b)
    server.flush()                         # one k=4 panel, 1 padded column
    snap = server.snapshot()
    assert snap["padded_columns"] == 1
    assert snap["padding_efficiency"] == pytest.approx(3 / 4)
    with pytest.raises(ValueError):
        server.submit(np.full(server.n, np.nan))
    with pytest.raises(ValueError):
        server.submit(b[:-2])
    assert server.snapshot()["rejected"] == 2
    # stats mirror (legacy dict) agrees with the metrics surface
    assert server.stats["rejected"] == 2
    assert server.stats["padded_columns"] == 1


def test_server_history_off_by_default(setupd, prob, monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    server = AMGSolveServer(setupd, prob.A.data, buckets=(1, 2))
    server.submit(np.asarray(prob.b))
    (report,) = server.flush()
    assert report.history is None
    assert report.status == "ok"
    assert report.latency_s >= report.queue_wait_s > 0.0
