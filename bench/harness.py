"""One run of one cell: set up, warm up, measure, check, report.

Everything that belongs to a configuration, a traffic mix or a metric is
found by name: ``configs/<config>.json``, ``mixes/<traffic>.json`` (whose
``"loop"`` names ``loops/<kind>.py``, the code that drives it) and
``metrics/<metric>.py`` (a metric ``a.b`` is read by ``metrics/a.py``;
the suffix only says which end-to-end metric it moves).  A configuration's
material kind is ``materials/<kind>.py``.  Adding a cell adds files and
``BENCHMARK.json`` entries; nothing here changes, and nothing here reads
the program: the loop reports its own limit, levels and counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Fail(SystemExit):
    """Stop the run with a message and a non-zero exit, printing no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Spec:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = root
        self.data = load_json(root / "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise Fail(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise Fail(f"no config {name!r} in BENCHMARK.json")

    def mix(self, traffic: str) -> dict:
        return load_json(BENCH / "mixes" / f"{traffic}.json")

    def metrics(self, workload: str, trace: bool) -> list:
        """The metric entries this cell reports in this kind of run."""
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]


def reader(metric: str):
    """The ``read(ctx)`` function of a metric, by the metric's name."""
    from problem import load_module
    return load_module("metrics", metric.split(".")[0]).read


def cell_parts(spec: Spec, workload: str, control: bool = False):
    """(cell, configuration, mix, loop class) of a workload.

    With ``control``, the configuration's ``"control"`` solver settings
    replace its own: the program one step below what the configuration
    states, which ``correct`` has to reject."""
    from problem import load_module
    cell = spec.workload(workload)
    cfg, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
    if control:
        cfg = {**cfg, "solver": {**cfg["solver"], **cfg["control"]}}
    return cell, cfg, mix, load_module("loops", mix["loop"]).Loop


class Spans:
    """Host-clock spans around the calls into each layer.

    Each is also a ``jax.profiler.TraceAnnotation`` named ``bench/<name>``,
    so a traced run sees them on the device trace's clock; annotations
    are host-side only and change no program."""

    def __init__(self):
        self.items = []          # (name, start_s, end_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation("bench/" + name):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> list:
        return [e - s for n, s, e in self.items
                if n == name and s >= lo and e <= hi]


class CompileCount:
    """Backend compiles (and persistent-cache loads) since start."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


@dataclasses.dataclass
class Context:
    """What a metric reader may read.  Unmeasured fields stay None."""

    workload: str
    unit: str                 # what one window step is: "solve", "step"
    setup_s: float
    window_s: float
    units: int                # steps completed in the window
    window: tuple             # (start_s, end_s) on the host clock
    spans: Spans
    counters: dict            # name -> per-solve numbers
    peak_bytes: int
    levels: list              # roofline.Level per level
    itemsize: int             # bytes per hierarchy value
    peaks: dict               # this device's row of peaks.json
    busy_s: "float | None" = None
    trace_window_s: "float | None" = None
    ops: "dict | None" = None     # op key -> (seconds, runs), per chip
    dims: "dict | None" = None    # op key -> output dims

    def window_spans(self, name: str) -> list:
        return self.spans.durations(name, *self.window)


def require_chips(jax, chips: int):
    """The devices, if JAX found enough TPUs; otherwise stop."""
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        found = devs[0].platform if devs else "no devices"
        raise Fail(f"needs a TPU, JAX found {found}")
    if len(devs) < chips:
        raise Fail(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise Fail(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def run_cell(spec: Spec, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, devices=None, *,
             control: bool = False, problem=None) -> dict:
    """One run; returns the result object the command prints.

    ``control`` runs the configuration's control in the program's place
    (``cell_parts``).  ``problem`` reuses a problem built by an earlier
    call with the same configuration, so that one process can read many
    seeds (``control.py``); the command never passes it."""
    import jax

    cell, cfg, mix, Loop = cell_parts(spec, workload, control)
    if devices is None:
        devices = require_chips(jax, int(cell["chips"]))
    dev = devices[0]
    peaks = peaks_for(dev.device_kind)
    compiles = CompileCount()
    spans = Spans()
    if problem is None:
        problem = Loop.build(cfg, spans)
    loop = Loop(problem, mix, seed, spans)
    loop.warm()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    n_before = compiles.n
    units = 0
    with spans("window"):
        t0 = time.perf_counter()
        while True:
            loop.step(units)
            units += 1
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    if compiles.n != n_before:
        raise Fail(f"{compiles.n - n_before} compilations inside the "
                   f"measured window")
    # the CPU reports no memory statistics (rehearsals only)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    ctx = Context(
        workload=workload, unit=loop.unit, setup_s=t0 - t_start,
        window_s=t1 - t0, units=units, window=(t0, t1),
        spans=spans, counters=loop.counters(), peak_bytes=peak,
        levels=loop.levels(), itemsize=loop.itemsize, peaks=peaks)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        import devtrace as tr
        t = tr.load(tr.find_xplane(str(TRACE_DIR)))
        ctx.busy_s, ctx.trace_window_s = tr.busy_window_s(t)
        ctx.ops = tr.op_stats(t)
        ctx.dims = {key: op.dims for key, op in t.info.items()}
        breakdown = tr.breakdown(t)
        result_device.update(busy_s=ctx.busy_s,
                             window_s=ctx.trace_window_s)
    metrics = {}
    for m in spec.metrics(workload, trace):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    answers = list(loop.answers())
    limit = loop.limit
    failed = [(label, v) for label, v in answers if not v <= limit]
    worst = max((v for _, v in answers), default=float("nan"))
    checks = {"true_relres_max": {"value": worst, "limit": limit},
              "answers_checked": {"value": len(answers), "limit": units}}
    print(f"window: {units} {loop.unit}s in {t1 - t0:.3f} s", file=sys.stderr)
    for name in ("recompute", "solve"):
        d = spans.durations(name, t0, t1)
        if d:
            print(f"window {name} s: {' '.join(f'{x:.4f}' for x in d)}",
                  file=sys.stderr)
    for label, v in answers:
        print(f"check {label}: true_relres {v:.6e} limit {limit:.3e}",
              file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    result = {"correct": bool(answers) and not failed
              and len(answers) == units,
              "attempted": len(answers), "failed": len(failed),
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
