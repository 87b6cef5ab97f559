"""Stage scopes, host spans and the solve counter carry.

The device half of the observability layer.  Three mechanisms:

``scope(name)``      a ``jax.named_scope`` around one device stage, always
                     on: every kernel family (``kernels/block_spmv``,
                     ``kernels/fused_smoother``, ...), the outer Krylov
                     stages (``pcg/apply_a``, ``pcg/precond``), every
                     V-cycle stage (``vcycle/level{i}/smooth|residual|
                     restrict|prolong``, ``vcycle/coarse``), every recompute
                     stage (``recompute/assemble``, ``recompute/level{i}/
                     smoother_data|ptap``, ``recompute/coarse_chol``,
                     ``recompute/fine_copy``) and the distributed stages
                     (``dist/assemble|recompute|pcg``).  A scope is op
                     metadata only: it lands in every HLO instruction's
                     ``op_name`` (the profiler's ``tf_op`` stat), costs
                     nothing at run time and leaves the numerics bitwise
                     unchanged (``tests/test_obs.py``).

``host_span(name)``  a ``jax.profiler.TraceAnnotation`` named
                     ``repro/<name>`` around one host stage (set-up phases,
                     ``GAMGSolver.solve``, ``update_coefficients``), on the
                     device trace's clock, plus an in-memory ``HostSpan``
                     record (``host_spans()``) that also holds the backend
                     compile and persistent-cache load seconds that fell
                     inside it.  It blocks on nothing unless handed outputs
                     to wait for.

``CycleTally``       the opt-in device-side counters, threaded through the
                     ``pcg``/``block_pcg``/``vcycle`` carries under
                     ``REPRO_OBS=counters``: per-level visit counts,
                     smoother applications, coarse solves,
                     operator/preconditioner applications and the modeled
                     HBM bytes of the cycle (``repro.obs.model.
                     vcycle_traffic``).  The tally changes the program (an
                     extra carry), so it is read at *trace* time and
                     ``REPRO_OBS=off`` (the default) leaves zero jaxpr
                     residue.  Set ``REPRO_OBS`` (or enter ``use(...)``)
                     before building the solver under observation.

The persistent compilation cache leaves op metadata out of its key
(``jax_compilation_cache_include_metadata_in_key`` is false), so an
executable cached before a scope was added or renamed is loaded without
it: clear ``.jax_cache`` after renaming a scope.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import monitoring

Array = jax.Array

MODES = ("off", "counters")

#: Explicit override (``use`` context manager); ``None`` defers to the
#: ``REPRO_OBS`` env knob via ``backend.resolve_obs``.
_MODE: Optional[str] = None


def resolve(mode: Optional[str] = None) -> str:
    """Active observability mode: explicit arg > ``use`` scope > env."""
    from repro.kernels import backend
    if mode is not None:
        return backend.resolve_obs(mode)
    if _MODE is not None:
        return _MODE
    return backend.resolve_obs()


def counters_enabled(mode: Optional[str] = None) -> bool:
    return resolve(mode) == "counters"


@contextlib.contextmanager
def use(mode: str):
    """Scoped mode override (tests and ad-hoc profiling runs).

    Only affects programs *traced* inside the scope — a closure jitted
    before entry keeps its cached trace, mirroring ``inject.active``.
    """
    from repro.kernels import backend
    global _MODE
    prev = _MODE
    _MODE = backend.resolve_obs(mode)
    try:
        yield
    finally:
        _MODE = prev


def scope(name: str):
    """Named scope around one device stage: ``jax.named_scope(name)``.

    Nests the stage under ``name`` in every HLO instruction's ``op_name``
    metadata, which a profiler trace carries as each op's ``tf_op``."""
    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

#: Span prefix on the profiler's clock: ``repro/<name>``.
HOST_PREFIX = "repro/"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Records kept; the oldest are dropped first in a long-running process.
MAX_RECORDS = 100_000


@dataclasses.dataclass
class HostSpan:
    """One closed host span."""

    name: str                 # without the ``repro/`` prefix
    parent: Optional[str]     # the enclosing host span's name, if any
    start: float              # ``time.perf_counter()`` seconds
    end: float
    count: int                # how often ``name`` was opened, this one included
    compile_s: float = 0.0    # backend compile + cache-load seconds inside

    @property
    def seconds(self) -> float:
        return self.end - self.start


_RECORDS: "collections.deque[HostSpan]" = collections.deque(
    maxlen=MAX_RECORDS)
_COUNTS: "collections.Counter[str]" = collections.Counter()
_COUNTS_LOCK = threading.Lock()
_OPEN = threading.local()          # each thread's stack of open spans


def _open_spans() -> List[HostSpan]:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        for rec in _open_spans():
            rec.compile_s += duration


monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def host_span(name: str):
    """Host span ``repro/<name>`` around one host stage.

    Yields ``wait(out) -> out``: outputs handed to it are waited for
    before the span closes, so an asynchronously dispatched stage's time
    is its own; a span given nothing blocks on nothing.  The span is a
    profiler ``TraceAnnotation`` only, never a ``named_scope``, so a
    program first traced inside it carries no trace of the call site.
    A span that raises is not recorded."""
    stack = _open_spans()
    with _COUNTS_LOCK:
        _COUNTS[name] += 1
        count = _COUNTS[name]
    rec = HostSpan(name=name, parent=stack[-1].name if stack else None,
                   start=time.perf_counter(), end=float("nan"), count=count)
    outs = []

    def wait(out):
        outs.append(out)
        return out

    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(HOST_PREFIX + name):
            yield wait
            if outs:
                jax.block_until_ready(outs)
    finally:
        stack.pop()
    rec.end = time.perf_counter()
    _RECORDS.append(rec)


def host_spans() -> List[HostSpan]:
    """The closed host spans of this process, oldest first."""
    return list(_RECORDS)


def reset_host_spans() -> None:
    _RECORDS.clear()
    with _COUNTS_LOCK:
        _COUNTS.clear()


# ---------------------------------------------------------------------------
# Device-side counter carry
# ---------------------------------------------------------------------------

class CycleTally(NamedTuple):
    """Device-side solve counters, threaded through the Krylov carries.

    All int32 except ``modeled_bytes``; per-level arrays are indexed by
    hierarchy level (0 = finest).  Lives inside the jitted programs as
    ordinary carry state — reading it costs one host transfer *after*
    the solve, never a sync inside the loop.
    """

    level_visits: Array      # (n_levels,) down-leg visits per level
    smoother_applies: Array  # (n_levels,) smoother calls (pre + post)
    coarse_solves: Array     # ()  direct coarse solves
    operator_applies: Array  # ()  fine-operator applications (Krylov)
    precond_applies: Array   # ()  V-cycle invocations
    modeled_bytes: Array     # ()  modeled HBM bytes (vcycle_traffic model)


def zero_tally(n_levels: int) -> CycleTally:
    """Fresh all-zero tally for an ``n_levels``-deep hierarchy (the count
    includes the coarse level; per-level arrays cover the smoothed ones)."""
    nl = max(int(n_levels) - 1, 0)
    z = jnp.zeros((), jnp.int32)
    return CycleTally(level_visits=jnp.zeros((nl,), jnp.int32),
                      smoother_applies=jnp.zeros((nl,), jnp.int32),
                      coarse_solves=z, operator_applies=z,
                      precond_applies=z,
                      modeled_bytes=jnp.zeros((), jnp.float64)
                      if jax.config.jax_enable_x64
                      else jnp.zeros((), jnp.float32))


def attach_model_bytes(tally: CycleTally, cycle_bytes: float) -> CycleTally:
    """Fill ``modeled_bytes`` = preconditioner applications x the modeled
    per-cycle traffic (``repro.obs.model.vcycle_traffic(...)["total"]``).
    Pure and jittable — the gamg solve closures call it on exit."""
    total = tally.precond_applies.astype(tally.modeled_bytes.dtype) \
        * cycle_bytes
    return tally._replace(modeled_bytes=total)


def describe_tally(tally: CycleTally) -> str:
    """One human line (host-side; forces the transfer)."""
    import numpy as np
    lv = np.asarray(tally.level_visits)
    sm = np.asarray(tally.smoother_applies)
    return (f"precond={int(tally.precond_applies)} "
            f"op={int(tally.operator_applies)} "
            f"coarse={int(tally.coarse_solves)} "
            f"level_visits={lv.tolist()} smoother={sm.tolist()} "
            f"modeled_MB={float(tally.modeled_bytes) / 1e6:.2f}")


def wrap_threaded_precond(apply_m: Callable, precond_dtype,
                          outer_dtype) -> Callable:
    """Tally-threaded twin of ``repro.core.krylov.wrap_precond``:
    ``apply_m`` has signature ``(r, tally) -> (z, tally)`` and the
    mixed-precision boundary casts around it exactly like the untallied
    wrapper (bitwise no-op when the dtypes already agree)."""
    if precond_dtype is None:
        return apply_m
    pd = jnp.dtype(precond_dtype)
    outer = jnp.dtype(outer_dtype)
    if pd == outer:
        return apply_m

    def wrapped(r, tally):
        z, tally = apply_m(r.astype(pd), tally)
        return z.astype(outer), tally

    return wrapped
