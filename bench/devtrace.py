"""Reduce a ``jax.profiler`` trace to device busy time, op times and gaps.

The trace is the ``.xplane.pb`` file the profiler writes.  A TPU's plane
is named ``/device:TPU:<n>``.  Its line ``XLA Modules`` holds one event
per program run; its line ``XLA Ops`` one event per operation run, named
by the operation's HLO text (``%fusion.12 = f32[...] fusion(...)``), with
control-flow operations (``while``, ``conditional``, ``call``) as events
that contain the operations of their bodies.  Host planes (``/host:...``)
hold the benchmark's ``jax.profiler.TraceAnnotation`` spans, named
``bench/<what>``, on the same clock.

Everything below ``load`` is plain arithmetic on ``(start, end)`` pairs,
checked by the tests on synthetic events and on a small trace recorded on
the chip.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# operations whose events contain the events of the operations they run
CONTAINERS = {"while", "conditional", "call"}

_NAME = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Op:
    """What a trace says of one HLO operation."""

    name: str           # instruction name, e.g. "fusion.12"
    opcode: str         # e.g. "fusion", "custom-call", "while"
    dims: tuple         # dims of its (first) output
    label: str          # short display name: "fusion.12 fusion f32[2571264]"


def parse_op(text: str) -> Op:
    """Instruction name, opcode and first output dims of an op's event
    name; names that are not HLO text are kept whole."""
    m = _NAME.match(text)
    if not m:
        return Op(text, "", (), text)
    rest = text[m.end():]
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else ""
    s = _SHAPE.search(rest)
    dims = tuple(int(d) for d in s.group(2).split(",") if d) if s else ()
    shape = f"{s.group(1)}[{s.group(2)}]" if s else ""
    return Op(m.group(1), opcode, dims, f"{m.group(1)} {opcode} {shape}")


@dataclasses.dataclass
class Trace:
    """Per device plane: operation events and program-run intervals; the
    benchmark's host spans; and what each operation name stands for."""

    ops: dict        # plane -> [(op key, start_ns, end_ns)], no containers
    busy: dict       # plane -> [(start_ns, end_ns)] of every op and run
    spans: list      # [(name, start_ns, end_ns)], names start "bench/"
    info: dict = dataclasses.field(default_factory=dict)  # op key -> Op

    def window(self):
        """(start_ns, end_ns) of the measured window's span."""
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if len(wins) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span in the "
                             f"trace, found {len(wins)}")
        return wins[0]


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read the device operations and host spans of one trace file.

    Operations are keyed ``<program>/<instruction>`` (the program run
    that contains the event), since instruction names repeat across
    programs."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    t = Trace(ops={}, busy={}, spans=[])
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(")[0])
                          for e in lines.get(MODULES_LINE, []))
            starts = [r[0] for r in runs]
            busy = [(s, e) for s, e, _ in runs]
            ops = []
            for e in lines.get(OPS_LINE, []):
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                busy.append(iv)
                i = bisect.bisect_right(starts, iv[0]) - 1
                prog = runs[i][2] if i >= 0 and iv[0] < runs[i][1] else "?"
                op = parse_op(e.name)
                if op.opcode in CONTAINERS:
                    continue
                key = f"{prog}/{op.name}"
                t.info.setdefault(key, op)
                ops.append((key, *iv))
            if busy:
                t.ops[plane.name], t.busy[plane.name] = ops, busy
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                t.spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events
                               if e.name.startswith(SPAN_PREFIX))
    return t


def merge(intervals, lo: float, hi: float):
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``, sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` during which some interval is open."""
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float):
    """Idle intervals ``(start, end)`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def covering_span(spans, t: float) -> str:
    """Name of the innermost benchmark span that covers time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e:
            if best is None or s >= best[1]:
                best = (name, s)
    return best[0] if best else "none"


def busy_window_s(trace: Trace):
    """(busy seconds averaged over chips, window seconds); busy is None
    where the trace holds no device plane."""
    lo, hi = trace.window()
    if not trace.busy:
        return None, (hi - lo) / 1e9
    busy = sum(busy_ns(b, lo, hi) for b in trace.busy.values())
    return busy / len(trace.busy) / 1e9, (hi - lo) / 1e9


def op_stats(trace: Trace) -> dict:
    """Op key -> (device seconds, runs) of the operations that ran wholly
    inside the window, per chip."""
    lo, hi = trace.window()
    sec, cnt = collections.Counter(), collections.Counter()
    for evs in trace.ops.values():
        for key, s, e in evs:
            if s >= lo and e <= hi:
                sec[key] += (e - s) / 1e9
                cnt[key] += 1
    k = max(len(trace.ops), 1)
    return {n: (sec[n] / k, cnt[n] / k) for n in sec}


def breakdown(trace: Trace, n: int = 10) -> dict:
    """The result line's ``breakdown``: the ``n`` operations with the most
    device time (seconds per chip, labelled by program, instruction,
    opcode and output shape) and the ``n`` longest idle gaps, each
    labelled by the benchmark span that covers its midpoint."""
    lo, hi = trace.window()
    stats = op_stats(trace)
    top = sorted(stats.items(), key=lambda kv: -kv[1][0])[:n]
    ops = [[key.split("/")[0] + "/" + trace.info[key].label, sec]
           for key, (sec, _) in top]
    gap_list = [g for b in trace.busy.values() for g in gaps(b, lo, hi)]
    gap_list.sort(key=lambda g: g[0] - g[1])
    idle = [[covering_span(trace.spans, (s + e) / 2), (e - s) / 1e9]
            for s, e in gap_list[:n]]
    return {"device_ops": ops, "idle_gaps": idle}
