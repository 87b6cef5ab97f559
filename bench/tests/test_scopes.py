"""Stage scopes and host spans read from the trace's wire format."""
import gzip
import types
from pathlib import Path

import pytest

import devtrace
import harness
import scopes
from test_devtrace import M8_LEVELS

DATA = Path(__file__).resolve().parent / "data"


# ---------------------------------------------------------------------------
# A synthetic XSpace, written with the same field numbers
# ---------------------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _msg(field: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _fixed64(field: int) -> bytes:
    return _varint(field << 3 | 1) + bytes(8)


def _event_md(i, name, tf_op=None, ref=None, tf_op_id=7):
    stats = b""
    if tf_op is not None:
        stats = _msg(5, _int(1, tf_op_id) + _msg(5, tf_op))
    elif ref is not None:
        stats = _msg(5, _int(1, tf_op_id) + _int(7, ref))
    body = _int(1, i) + _msg(2, name) + stats
    return _msg(4, _int(1, i) + _msg(2, body))


def _stat_md(i, name):
    return _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, name)))


def _line(name, events, ts=0):
    evs = b"".join(_msg(4, _int(1, md) + _int(2, off) + _int(3, dur)
                        + _fixed64(9))           # a field the reader skips
                   for md, off, dur in events)
    return _msg(3, _msg(2, name) + _int(3, ts) + evs)


def _plane(name, body):
    return _msg(1, _msg(2, name) + body)


NS = 1000          # picoseconds per nanosecond


def synthetic(scoped=True):
    """One chip, a solve-program run [5, 50] and a recompute-program run
    [55, 95] in a window of [0, 100] ns:

    ops (ns)      scope path
    [10, 20]      pcg/apply_a            (tf_op given by reference)
    [20, 30]      pcg/precond/vcycle/level0/smooth
    [30, 35]      pcg/precond/vcycle/level0/residual
    [35, 40]      (none): a dot of the outer iteration
    [5, 45]       a while loop: a container, not timed
    [60, 70]      recompute/level0/ptap, in the program ``jit_run``
    [80, 90]      recompute/assemble, in ``jit_run``
    host: bench/window [0, 100], repro/solve [0, 12], bench/solve [0, 50],
    repro/update_coefficients [50, 62]."""
    def tf(s):
        return s if scoped else s.replace("pcg/", "").replace(
            "recompute/", "")
    md = (_stat_md(7, "tf_op") + _stat_md(8, "hlo_category")
          + _stat_md(9, tf("jit(solve)/while/body/pcg/apply_a/"
                           "jit(spmv_ell)/jit(gather_lanes)/gather:"))
          + _event_md(1, "jit_solve(123)")
          + _event_md(2, "%gather.1 = f32[8]{0} fusion(%a)", ref=9)
          + _event_md(3, "%fusion.2 = f32[8]{0} fusion(%b)",
                      tf("jit(solve)/while/body/pcg/precond/vcycle/level0/"
                         "smooth/kernels/fused_smoother/pallas_call:"))
          + _event_md(4, "%fusion.3 = f32[8]{0} fusion(%c)",
                      tf("jit(solve)/while/body/pcg/precond/vcycle/level0/"
                         "residual/jit(spmv_ell)/sub:"))
          + _event_md(5, "%dot.4 = f32[] fusion(%d)",
                      "jit(solve)/while/body/dot_general:")
          + _event_md(6, "%while.5 = (f32[8]{0}) while(%e)",
                      "jit(solve)/while:")
          + _event_md(10, "jit_run(456)")
          + _event_md(11, "%fusion.6 = f32[8]{0} fusion(%f)",
                      tf("jit(run)/recompute/level0/ptap/jit(segment_sum)/"
                         "add:"))
          + _event_md(12, "%fusion.7 = f32[8]{0} fusion(%g)",
                      tf("jit(run)/recompute/assemble/mul:")))
    device = _plane("/device:TPU:0", md + _line(
        "XLA Modules", [(1, 5 * NS, 45 * NS), (10, 55 * NS, 40 * NS)])
        + _line("XLA Ops", [(2, 10 * NS, 10 * NS), (3, 20 * NS, 10 * NS),
                            (4, 30 * NS, 5 * NS), (5, 35 * NS, 5 * NS),
                            (6, 5 * NS, 40 * NS), (11, 60 * NS, 10 * NS),
                            (12, 80 * NS, 10 * NS)]))
    host_md = (_event_md(1, "bench/window") + _event_md(2, "repro/solve")
               + _event_md(3, "bench/solve") + _event_md(4, "other")
               + _event_md(5, "repro/update_coefficients"))
    host = _plane("/host:CPU", host_md + _line(
        "python3", [(1, 0, 100 * NS), (2, 0, 12 * NS), (3, 0, 50 * NS),
                    (4, 50 * NS, 40 * NS), (5, 50 * NS, 12 * NS)], ts=0))
    return scopes.parse(device + host + _plane("/host:metadata", b""))


def test_scope_path_drops_transforms_control_and_the_primitive():
    assert scopes.scope_path(
        "jit(solve)/while/body/pcg/apply_a/jit(spmv_ell)/spmv_ell/"
        "jit(gather_lanes)/gather:") == "pcg/apply_a/spmv_ell"
    assert scopes.scope_path("jit(run)/mul:") == ""
    # operations XLA merged: the first name carries the scopes
    assert scopes.scope_path(
        "jit(solve)/while/body/pcg/apply_a/jit(spmv_ell)/spmv_ell/reshape;"
        "spmv_ell/broadcast_in_dim;spmv_ell/reshape:") == \
        "pcg/apply_a/spmv_ell"
    assert scopes.scope_path("b:") == ""
    assert scopes.scope_path(
        "jit(solve)/cond/branch_1_fun/vcycle/coarse/jit(_cho_solve)/"
        "triangular_solve:") == "vcycle/coarse"


def test_under_matches_whole_scope_names():
    path = "pcg/precond/vcycle/level10/smooth"
    assert scopes.under(path, "pcg/precond")
    assert scopes.under(path, "vcycle/level*/smooth")
    assert not scopes.under(path, "precond/vcycle/level1")
    assert not scopes.under("pcg/precondx", "pcg/precond")
    assert scopes.under("recompute/level0/ptap/kernels/fused_pair_gemm",
                        "recompute/level*/ptap")


def test_synthetic_ops_programs_and_spans():
    t = synthetic()
    ops = t.ops["/device:TPU:0"]
    assert [(op.program, op.name, op.path) for op in ops] == [
        ("jit_solve", "gather.1", "pcg/apply_a"),
        ("jit_solve", "fusion.2",
         "pcg/precond/vcycle/level0/smooth/kernels/fused_smoother"),
        ("jit_solve", "fusion.3", "pcg/precond/vcycle/level0/residual"),
        ("jit_solve", "dot.4", ""),
        ("jit_run", "fusion.6", "recompute/level0/ptap"),
        ("jit_run", "fusion.7", "recompute/assemble")]
    assert t.window() == (0, 100)
    assert sorted(n for n, _, _ in t.spans) == [
        "bench/solve", "bench/window", "repro/solve",
        "repro/update_coefficients"]
    # program runs [5, 50] and [55, 95]: the while container counts as
    # busy, not as an op
    assert devtrace.busy_ns(t.busy["/device:TPU:0"], 0, 100) == 45 + 40


def test_synthetic_split_and_recompute_seconds():
    t = synthetic()
    outer, pre = scopes.solve_split(t)
    assert outer == pytest.approx(15e-9)       # apply_a 10 + dot 5
    assert pre == pytest.approx(15e-9)         # smooth 10 + residual 5
    assert outer + pre == pytest.approx(
        t.seconds(lambda op: op.program == "jit_solve"))
    assert scopes.recompute_seconds(
        t, "recompute/level*/ptap") == pytest.approx(10e-9)
    assert scopes.recompute_seconds(
        t, "recompute/assemble") == pytest.approx(10e-9)


def test_synthetic_program_idle():
    # device idle [0, 5), [50, 55), [95, 100]; host in repro/ spans
    # [0, 12] and [50, 62]: idle inside them 5 + 5 ns
    t = synthetic()
    assert scopes.program_idle_s(t) == pytest.approx(10e-9)
    no_host = scopes.ScopedTrace(ops=t.ops, busy=t.busy, spans=[
        s for s in t.spans if not s[0].startswith("repro/")])
    assert scopes.program_idle_s(no_host) is None


def test_stale_executable_raises_and_never_reads_zero():
    t = synthetic(scoped=False)
    with pytest.raises(scopes.StaleExecutable, match="jit_solve"):
        scopes.solve_split(t)
    with pytest.raises(scopes.StaleExecutable, match="stale .jax_cache"):
        scopes.recompute_seconds(t, "recompute/assemble")
    # a program that did not run leaves nothing to read
    empty = scopes.ScopedTrace(ops={}, busy={}, spans=t.spans)
    assert scopes.solve_split(empty) is None
    assert scopes.recompute_seconds(empty, "recompute/assemble") is None


def _ctx(units=2, ops=True):
    return types.SimpleNamespace(ops={} if ops else None, units=units)


@pytest.mark.parametrize("metric,want", [
    ("outer_cg_s.solve", 7.5e-9), ("vcycle_s.solve", 7.5e-9),
    ("ptap_s.hot", 5e-9), ("device_assembly_s.hot", 5e-9),
    ("program_idle.solve", 10.0)])
def test_readers_on_the_synthetic_trace(monkeypatch, metric, want):
    t = synthetic()
    monkeypatch.setattr(scopes, "for_run", lambda ctx: t)
    assert harness.reader(metric)(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["outer_cg_s.solve", "vcycle_s.solve",
                                    "ptap_s.hot", "device_assembly_s.hot",
                                    "program_idle.solve"])
def test_readers_find_nothing_without_scopes_or_trace(monkeypatch, metric):
    """An untraced run, or a program that has no stage scopes (as before
    they were added), gives no value and raises nothing."""
    assert harness.reader(metric)(_ctx(ops=False)) is None
    monkeypatch.setattr(scopes, "program_has_scopes", lambda: False)
    assert harness.reader(metric)(_ctx()) is None


@pytest.mark.parametrize("metric", ["setup_compile_s", "setup_ptap_s"])
def test_host_span_readers(monkeypatch, metric):
    from repro.obs import trace as obs_trace
    recs = [obs_trace.HostSpan("setup", None, 10.0, 20.0, 1, 4.0),
            obs_trace.HostSpan("setup/level0/ptap_numeric", "setup",
                               11.0, 13.0, 1, 1.5),
            obs_trace.HostSpan("setup/level1/ptap_numeric", "setup",
                               14.0, 14.5, 1, 0.0),
            obs_trace.HostSpan("setup", None, 1.0, 2.0, 1, 9.0)]
    monkeypatch.setattr(obs_trace, "host_spans", lambda: recs)
    # the run started at 30 - 25 = 5: the record at 1.0 is another run's
    ctx = types.SimpleNamespace(window=(30.0, 40.0), setup_s=25.0)
    want = {"setup_compile_s": 4.0, "setup_ptap_s": 2.5}[metric]
    assert harness.reader(metric)(ctx) == pytest.approx(want)
    monkeypatch.setattr(obs_trace, "host_spans", lambda: [])
    assert harness.reader(metric)(ctx) is None


# ---------------------------------------------------------------------------
# Traces recorded on the chip
# ---------------------------------------------------------------------------

def _unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name.replace(".gz", "")
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return str(path)


def test_unscoped_recording_reads_as_stale(tmp_path_factory):
    """The trace recorded before the scopes existed: its programs ran, and
    the reader says so instead of reading zero."""
    t = scopes.load(DATA / "hot_m8.xplane.pb.gz")
    with pytest.raises(scopes.StaleExecutable):
        scopes.solve_split(t)
    with pytest.raises(scopes.StaleExecutable):
        scopes.recompute_seconds(t, "recompute/level*/ptap")
    # and the wire reader agrees with ProfileData on every op
    ref = devtrace.load(_unpacked(tmp_path_factory, "hot_m8.xplane.pb.gz"))
    mine, theirs = t.op_stats(), devtrace.op_stats(ref)
    assert set(mine) == set(theirs)
    for key, (sec, runs) in theirs.items():
        assert mine[key][0] == pytest.approx(sec, abs=1e-6)
        assert mine[key][1] == runs


# A hot step of q1_m32_incl.hot at m=8 recorded on one v5e with the stage
# scopes and host spans in the program: one recompute (``jit_run``) and
# one solve (``jit_solve``) inside one ``bench/window`` span.
SCOPED = "scoped_m8.xplane.pb.gz"


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    path = _unpacked(tmp_path_factory, SCOPED)
    return scopes.load(path), devtrace.load(path)


def _recorded_ctx(t, ops, dims):
    return types.SimpleNamespace(
        ops=ops, dims=dims, units=1, levels=M8_LEVELS, itemsize=4,
        peaks=harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"])


def test_scoped_recording_partitions_the_solve(scoped, monkeypatch):
    t, _ = scoped
    monkeypatch.setattr(scopes, "for_run", lambda ctx: t)
    ctx = _recorded_ctx(t, {}, {})
    outer = harness.reader("outer_cg_s.solve")(ctx)
    pre = harness.reader("vcycle_s.solve")(ctx)
    solve = t.seconds(lambda op: op.program == scopes.SOLVE_PROGRAM)
    assert outer > 0 and pre > 0
    assert outer + pre == pytest.approx(solve, rel=1e-9)
    # the fine operator's apply is the outer CG's, the residual the cycle's
    assert t.seconds(lambda op: scopes.under(op.path, "pcg/apply_a")) > 0
    assert t.seconds(lambda op: scopes.under(
        op.path, "pcg/precond/vcycle/level0/residual")) > 0
    for metric in ("ptap_s.hot", "device_assembly_s.hot",
                   "program_idle.hot"):
        assert harness.reader(metric)(ctx) > 0, metric
    assert {n for n, _, _ in t.spans if n.startswith("repro/")} == {
        "repro/solve", "repro/update_coefficients"}


@pytest.mark.parametrize("metric,kernel,scope", [
    ("fused_smoother_roofline", "_smoother_step_ell",
     "kernels/fused_smoother"),
    ("fused_pair_gemm_roofline", "fused_pair_gemm_lanes",
     "kernels/fused_pair_gemm")])
def test_scoped_recording_roofline_readers_agree(scoped, metric, kernel,
                                                 scope):
    """The roofline readers match kernels by instruction name; on a scoped
    trace they read the same from the wire reader's ops as from
    ``devtrace``'s, and the kernel calls they match are exactly the
    custom calls under the kernel's stage scope."""
    t, ref = scoped
    mine = harness.reader(metric)(_recorded_ctx(
        t, t.op_stats(), {k: op.dims for k, op in t.info.items()}))
    theirs = harness.reader(metric)(_recorded_ctx(
        ref, devtrace.op_stats(ref),
        {k: op.dims for k, op in ref.info.items()}))
    assert theirs is not None and 0 < theirs < 100
    # ProfileData rounds each op's times to whole nanoseconds, the wire
    # reader keeps picoseconds: under 1 ns per call on microsecond calls
    assert mine == pytest.approx(theirs, rel=1e-3)
    by_name = {op.key for op in t.window_ops() if op.name.startswith(kernel)}
    by_scope = {op.key for op in t.window_ops()
                if scopes.under(op.path, scope)
                and t.info[op.key].opcode == "custom-call"}
    assert by_name and by_name == by_scope
