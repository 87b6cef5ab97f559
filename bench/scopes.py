"""Read the program's stage scopes and host spans from a profiler trace.

    python3 bench/scopes.py [trace dir or .xplane.pb[.gz]] [--units N]

prints, for a traced run (default: ``.bench_trace``), the device seconds
per window step and the share of device time of every stage scope, the
operations with most device time with their scopes, the program's host
spans in the window and the longest idle gaps of the device with the
host spans that cover them.

The program names its device stages with ``jax.named_scope``
(``repro.obs.trace.scope``: ``pcg/apply_a``, ``pcg/precond``,
``vcycle/level0/smooth``, ``recompute/level1/ptap``, ...).  XLA keeps the
name stack in each instruction's ``op_name``, and the profiler writes it
into the trace as the ``tf_op`` stat of the operation's event metadata,
e.g. ``jit(solve)/while/body/pcg/apply_a/jit(spmv_ell)/gather:``.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
this reads the ``.xplane.pb`` wire format itself, for the few fields it
needs (``tsl/profiler/protobuf/xplane.proto``), without tensorflow.

An operation is attributed to its scope path: the named scopes of its
``tf_op``, innermost last, with the ``jit(...)``-style transforms, the
control-flow levels (``while``, ``body``, ``cond``, ...) and the
operation's own primitive left out.  A fusion carries the ``op_name`` of
its root instruction, so a fusion is attributed by its root's scopes.
Operations are timed as ``devtrace`` times them: control-flow containers
left out, only operations that ran wholly inside the ``bench/window``
span, seconds per chip.

The program's host stages are ``jax.profiler.TraceAnnotation`` spans
named ``repro/<stage>`` (``repro.obs.trace.host_span``) on the host
plane, on the device trace's clock.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import gzip
import os
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

HOST_PREFIX = "repro/"
SPAN_PREFIXES = (HOST_PREFIX, devtrace.SPAN_PREFIX)
SOLVE_PROGRAM = "jit_solve"
TF_OP = "tf_op"

# field numbers of tsl/profiler/protobuf/xplane.proto
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_LINES, XPLANE_EVENT_MD, XPLANE_STAT_MD = 2, 3, 4, 5
XLINE_NAME, XLINE_TIMESTAMP_NS, XLINE_EVENTS = 2, 3, 4
XEVENT_METADATA_ID, XEVENT_OFFSET_PS, XEVENT_DURATION_PS = 1, 2, 3
XEVENTMD_ID, XEVENTMD_NAME, XEVENTMD_STATS = 1, 2, 5
XSTATMD_ID, XSTATMD_NAME = 1, 2
XSTAT_METADATA_ID, XSTAT_STR_VALUE, XSTAT_REF_VALUE = 1, 5, 7
MAP_VALUE = 2       # a map field's entries: key = 1, value = 2

# name-stack levels that are not the program's scopes
_TRANSFORM = re.compile(r"^[\w.\-]+\(.*\)$")        # jit(f), vmap(f), ...
_CONTROL = {"while", "body", "cond", "scan", "checkpoint", "remat",
            "shard_map", "pjit", "closed_call", "core_call",
            "custom_jvp_call", "custom_vjp_call"}


class StaleExecutable(RuntimeError):
    """A program ran without the stage scopes its source gives it."""


# ---------------------------------------------------------------------------
# Protobuf wire format
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of one message in ``b[lo:hi]``: an int for a
    varint, a ``(lo, hi)`` slice for a length-delimited field; fixed-width
    fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _str(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(b: bytes, entries):
    """The value messages of a protobuf map field's entries."""
    for span in entries:
        for f, v in _fields(b, *span):
            if f == MAP_VALUE:
                yield v


# ---------------------------------------------------------------------------
# The trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceOp:
    """One run of one HLO operation on a device."""

    program: str        # the program run that contains it, e.g. "jit_solve"
    name: str           # instruction name, e.g. "fusion.1413"
    path: str           # scope path, e.g. "pcg/precond/vcycle/level0/smooth"
    start_ns: float
    end_ns: float

    @property
    def key(self) -> str:
        """``devtrace``'s op key, ``<program>/<instruction>``."""
        return f"{self.program}/{self.name}"


@dataclasses.dataclass
class ScopedTrace:
    """Per device plane: operation runs (containers left out) and busy
    intervals; the host spans named ``repro/`` or ``bench/``; and what each
    op key stands for (``devtrace.Op``)."""

    ops: dict           # plane -> [DeviceOp]
    busy: dict          # plane -> [(start_ns, end_ns)] of every op and run
    spans: list         # [(name, start_ns, end_ns)]
    info: dict = dataclasses.field(default_factory=dict)

    def window(self):
        return devtrace.Trace(ops={}, busy={}, spans=self.spans).window()

    def window_ops(self):
        """Every op run wholly inside the window, all planes."""
        lo, hi = self.window()
        return [op for ops in self.ops.values() for op in ops
                if op.start_ns >= lo and op.end_ns <= hi]

    @property
    def chips(self) -> int:
        return max(len(self.ops), 1)

    def seconds(self, keep) -> float:
        """Device seconds per chip of the window's ops for which
        ``keep(op)`` holds."""
        return sum(op.end_ns - op.start_ns for op in self.window_ops()
                   if keep(op)) / 1e9 / self.chips

    def op_stats(self) -> dict:
        """``devtrace.op_stats`` of this trace: op key -> (seconds, runs)
        per chip."""
        sec, cnt = collections.Counter(), collections.Counter()
        for op in self.window_ops():
            sec[op.key] += (op.end_ns - op.start_ns) / 1e9
            cnt[op.key] += 1
        k = self.chips
        return {n: (sec[n] / k, cnt[n] / k) for n in sec}


def scope_path(tf_op: str) -> str:
    """The named scopes of an op's ``tf_op``, outermost first.  Where XLA
    merged several operations into one it joins their names with ``;``,
    the later ones relative to the first: the first names the scopes."""
    first = tf_op.split(";")[0]
    stack = first.rpartition(":")[0] if ":" in first else first
    parts = stack.split("/")[:-1]
    return "/".join(p for p in parts
                    if p and not _TRANSFORM.match(p) and p not in _CONTROL
                    and not p.startswith("branch_"))


def under(path: str, pattern: str) -> bool:
    """Whether a scope path lies under ``pattern``: whole scope names, a
    ``*`` standing for one name's characters (``recompute/level*/ptap``)."""
    rx = "/".join(re.escape(p).replace(r"\*", "[^/]*")
                  for p in pattern.split("/"))
    return re.search(rf"(^|/){rx}(/|$)", path) is not None


def parse(data: bytes) -> ScopedTrace:
    """Decode one serialized ``XSpace``."""
    t = ScopedTrace(ops={}, busy={}, spans=[])
    for f, v in _fields(data, 0, len(data)):
        if f == XSPACE_PLANES:
            _plane(data, v, t)
    return t


def _plane(b: bytes, span, t: ScopedTrace) -> None:
    name, lines, ev_md, st_md = "", [], [], []
    for f, v in _fields(b, *span):
        if f == XPLANE_NAME:
            name = _str(b, v)
        elif f == XPLANE_LINES:
            lines.append(v)
        elif f == XPLANE_EVENT_MD:
            ev_md.append(v)
        elif f == XPLANE_STAT_MD:
            st_md.append(v)
    if name.startswith("/device:"):
        _device_plane(b, name, lines, ev_md, st_md, t)
    elif name.startswith("/host:"):
        names = _event_names(b, ev_md)
        for line in lines:
            for md, s, e in _line_events(b, line, None):
                n = names.get(md, "")
                if n.startswith(SPAN_PREFIXES):
                    t.spans.append((n, s, e))


def _event_names(b: bytes, ev_md) -> dict:
    out = {}
    for md in _map_values(b, ev_md):
        i, n = None, ""
        for f, v in _fields(b, *md):
            if f == XEVENTMD_ID:
                i = v
            elif f == XEVENTMD_NAME:
                n = _str(b, v)
        out[i] = n
    return out


def _line_events(b: bytes, line, want):
    """(metadata id, start_ns, end_ns) of a line's events; only the line
    named ``want`` when given, else every line."""
    name, ts, events = "", 0, []
    for f, v in _fields(b, *line):
        if f == XLINE_NAME:
            name = _str(b, v)
        elif f == XLINE_TIMESTAMP_NS:
            ts = v
        elif f == XLINE_EVENTS:
            events.append(v)
    if want is not None and name != want:
        return
    for ev in events:
        md = off = dur = 0
        for f, v in _fields(b, *ev):
            if f == XEVENT_METADATA_ID:
                md = v
            elif f == XEVENT_OFFSET_PS:
                off = v
            elif f == XEVENT_DURATION_PS:
                dur = v
        start = ts + off / 1000.0
        yield md, start, start + dur / 1000.0


def _device_plane(b, plane, lines, ev_md, st_md, t: ScopedTrace) -> None:
    stat_names = {}
    for md in _map_values(b, st_md):
        d = dict(_fields(b, *md))
        if XSTATMD_NAME in d:
            stat_names[d.get(XSTATMD_ID, 0)] = _str(b, d[XSTATMD_NAME])
    tf_op_id = next((i for i, n in stat_names.items() if n == TF_OP), None)
    meta = {}            # metadata id -> (HLO text, tf_op)
    for md in _map_values(b, ev_md):
        i, text, tf_op = None, "", ""
        for f, v in _fields(b, *md):
            if f == XEVENTMD_ID:
                i = v
            elif f == XEVENTMD_NAME:
                text = _str(b, v)
            elif f == XEVENTMD_STATS:
                st = dict(_fields(b, *v))
                if st.get(XSTAT_METADATA_ID) != tf_op_id:
                    continue
                if XSTAT_STR_VALUE in st:
                    tf_op = _str(b, st[XSTAT_STR_VALUE])
                elif XSTAT_REF_VALUE in st:
                    tf_op = stat_names.get(st[XSTAT_REF_VALUE], "")
        meta[i] = (text, tf_op)
    runs, op_events = [], []
    for line in lines:
        runs.extend((s, e, meta.get(md, ("?", ""))[0].split("(")[0])
                    for md, s, e in _line_events(b, line,
                                                 devtrace.MODULES_LINE))
        op_events.extend(_line_events(b, line, devtrace.OPS_LINE))
    runs.sort()
    starts = [r[0] for r in runs]
    busy = [(s, e) for s, e, _ in runs]
    ops = []
    for md, s, e in op_events:
        busy.append((s, e))
        text, tf_op = meta.get(md, ("", ""))
        op = devtrace.parse_op(text)
        if op.opcode in devtrace.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = runs[i][2] if i >= 0 and s < runs[i][1] else "?"
        dop = DeviceOp(prog, op.name, scope_path(tf_op), s, e)
        t.info.setdefault(dop.key, op)
        ops.append(dop)
    if busy:
        t.ops[plane], t.busy[plane] = ops, busy


def load(path) -> ScopedTrace:
    """Read a ``.xplane.pb`` file, gzipped or not."""
    data = Path(path).read_bytes()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return parse(data)


# ---------------------------------------------------------------------------
# What the metrics read
# ---------------------------------------------------------------------------

def program_has_scopes() -> bool:
    """Whether the program in this checkout names its stages (scopes and
    host spans); a program from before it did has nothing to read."""
    try:
        from repro.obs import trace
    except ImportError:
        return False
    return hasattr(trace, "scope") and hasattr(trace, "host_span")


_LOADED: dict = {}


def for_run(ctx):
    """The scoped trace of a traced run, or None where there is nothing to
    read: the run was not traced, or the program has no stage scopes."""
    if ctx.ops is None or not program_has_scopes():
        return None
    from harness import TRACE_DIR
    path = devtrace.find_xplane(str(TRACE_DIR))
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    return _LOADED[key]


def require_scope(t: ScopedTrace, root: str, program: str = None) -> bool:
    """Whether ops of ``program`` (any program if None) ran in the window;
    raises ``StaleExecutable`` where they did and none of them carries a
    ``root`` scope, as after a load from a compile cache written before
    the scope existed (the cache key leaves op metadata out)."""
    ops = [op for op in t.window_ops()
           if program is None or op.program == program]
    if not ops:
        return False
    if not any(under(op.path, root) for op in ops):
        progs = sorted({op.program for op in ops})
        raise StaleExecutable(
            f"the executables {progs} ran in the traced window, but none "
            f"of their ops carries a '{root}/' scope: they were compiled "
            f"without the program's stage scopes (a stale .jax_cache: "
            f"clear it and run again)")
    return True


def solve_split(t: ScopedTrace):
    """(outer CG seconds, V-cycle seconds) per chip of the solve program
    in the window: its ops outside and under ``pcg/precond``; None where
    it did not run."""
    if not require_scope(t, "pcg", SOLVE_PROGRAM):
        return None
    pre = t.seconds(lambda op: op.program == SOLVE_PROGRAM
                    and under(op.path, "pcg/precond"))
    total = t.seconds(lambda op: op.program == SOLVE_PROGRAM)
    return total - pre, pre


def recompute_seconds(t: ScopedTrace, pattern: str):
    """Device seconds per chip in the window of the ops under a recompute
    scope; None where no op ran in the window."""
    if not require_scope(t, "recompute"):
        return None
    return t.seconds(lambda op: under(op.path, pattern))


def program_idle_s(t: ScopedTrace):
    """Seconds per chip in the window during which the device is idle
    while the host is inside one of the program's ``repro/`` spans; None
    where the trace holds no device plane or no such span."""
    lo, hi = t.window()
    host = devtrace.merge([(s, e) for n, s, e in t.spans
                           if n.startswith(HOST_PREFIX)], lo, hi)
    if not t.busy or not host:
        return None
    total = 0.0
    for busy in t.busy.values():
        for gs, ge in devtrace.gaps(busy, lo, hi):
            total += devtrace.busy_ns(host, gs, ge)
    return total / len(t.busy) / 1e9


# ---------------------------------------------------------------------------
# The table
# ---------------------------------------------------------------------------

def overlap(spans, prefix: str, t0: float, t1: float):
    """(name, seconds) of the ``prefix`` span that overlaps ``[t0, t1]``
    most; ("none", 0) where none does."""
    best = ("none", 0.0)
    for n, s, e in spans:
        o = (min(e, t1) - max(s, t0)) / 1e9
        if n.startswith(prefix) and o > best[1]:
            best = (n, o)
    return best


def report(t: ScopedTrace, units: int = None, top: int = 15) -> str:
    lo, hi = t.window()
    if units is None:
        units = sum(1 for n, s, e in t.spans
                    if n == devtrace.SPAN_PREFIX + "solve"
                    and s >= lo and e <= hi)
    units = max(units, 1)
    ops = t.window_ops()
    total = sum(op.end_ns - op.start_ns for op in ops) / 1e9 / t.chips
    out = [f"window {(hi - lo) / 1e9:.3f} s, {units} steps, device op "
           f"time {total:.4f} s per chip ({total / units:.4f} s per step)",
           "", "s/step     share  program  scope path"]
    by = collections.Counter()
    for op in ops:
        by[(op.program, op.path or "(no scope)")] += (
            op.end_ns - op.start_ns) / 1e9 / t.chips
    for (prog, path), sec in sorted(by.items(), key=lambda kv: -kv[1]):
        out.append(f"{sec / units:9.5f} {100 * sec / total:6.2f}%  "
                   f"{prog}  {path}")
    out += ["", f"top {top} ops: s/step  op  scope path"]
    per_op, path_of = collections.Counter(), {}
    for op in ops:
        per_op[op.key] += (op.end_ns - op.start_ns) / 1e9 / t.chips
        path_of.setdefault(op.key, collections.Counter())[op.path] += 1
    for key, sec in per_op.most_common(top):
        label = key.split("/")[0] + "/" + t.info[key].label
        paths = " | ".join(p or "(no scope)"
                           for p, _ in path_of[key].most_common())
        out.append(f"{sec / units:9.5f}  {label}  {paths}")
    out += ["", "host spans in the window: count, mean s, total s"]
    spans = collections.defaultdict(list)
    for n, s, e in t.spans:
        if s >= lo and e <= hi and n.startswith(HOST_PREFIX):
            spans[n].append((e - s) / 1e9)
    for n, d in sorted(spans.items()):
        out.append(f"  {n}: {len(d)}, {sum(d) / len(d):.6f}, {sum(d):.6f}")
    idle = program_idle_s(t)
    if idle is not None:
        out.append(f"device idle inside repro/ spans: {idle:.6f} s "
                   f"({100 * idle * 1e9 / (hi - lo):.4f}% of the window)")
    out += ["", "longest idle gaps: s, the repro/ span overlapping most "
            "(s inside it), the benchmark span at its midpoint"]
    bench = [x for x in t.spans if x[0].startswith(devtrace.SPAN_PREFIX)]
    gap_list = [g for b in t.busy.values() for g in devtrace.gaps(b, lo, hi)]
    gap_list.sort(key=lambda g: g[0] - g[1])
    for s, e in gap_list[:top]:
        name, inside = overlap(t.spans, HOST_PREFIX, s, e)
        out.append(f"  {(e - s) / 1e9:.6f}  {name} ({inside:.6f})  "
                   f"{devtrace.covering_span(bench, (s + e) / 2)}")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", nargs="?",
                    default=str(BENCH.parent / ".bench_trace"))
    ap.add_argument("--units", type=int, default=None,
                    help="window steps (default: the bench/solve spans)")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = devtrace.find_xplane(path)
    print(report(load(path), args.units, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
