"""repro.obs — solver observability: metrics, tracing, regression tracking.

Four layers (ISSUE 7):

* ``metrics``        process-local ``MetricsRegistry`` (counters, gauges,
                     solver-scale histograms), compile/steady-aware
                     ``Timer`` spans, JSONL + Prometheus exporters;
* ``trace``          always-on ``jax.named_scope`` stage scopes
                     (``scope``) on every kernel family, Krylov, V-cycle
                     and recompute stage, read from a profiler trace's op
                     metadata; host spans on the profiler's clock with
                     in-memory records (``host_span``, ``host_spans``);
                     and the opt-in device-side ``CycleTally`` counter
                     carry, a trace-time no-op under ``REPRO_OBS=off``
                     (zero jaxpr residue);
* ``model``          the analytic HBM-traffic / dist-comm byte models
                     (moved from ``benchmarks/common``) the live counters
                     are validated against;
* ``server_metrics`` end-to-end ``AMGSolveServer`` instrumentation
                     (queue wait, solve wall, padding efficiency, health
                     statuses) behind ``server.metrics()``/``snapshot()``;
* ``bench``          the schema-versioned ``BENCH_*.json`` regression
                     tracker wrapping ``benchmarks/run.py``.

Knob: ``REPRO_OBS=off|counters`` (default off), resolved by
``repro.kernels.backend.resolve_obs`` at trace time; it governs the
counters only, the scopes and host spans are always on.
"""
from repro.obs.metrics import (          # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Timer,
    default_registry,
    parse_prometheus,
)
from repro.obs.server_metrics import ServerMetrics   # noqa: F401
from repro.obs.trace import (            # noqa: F401
    CycleTally,
    HostSpan,
    attach_model_bytes,
    counters_enabled,
    describe_tally,
    host_span,
    host_spans,
    reset_host_spans,
    scope,
    use,
    zero_tally,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "ServerMetrics",
    "Timer", "default_registry", "parse_prometheus", "CycleTally",
    "HostSpan", "attach_model_bytes", "counters_enabled", "describe_tally",
    "host_span", "host_spans", "reset_host_spans", "scope", "use",
    "zero_tally",
]
