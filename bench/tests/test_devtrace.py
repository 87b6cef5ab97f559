"""Trace reduction: busy time, gaps, op times and the breakdown."""
import gzip
import types
from pathlib import Path

import pytest

import devtrace
from devtrace import Trace
from roofline import Level, Product


def synthetic():
    ev0 = [("p/a", 10, 20), ("p/b", 15, 30), ("p/a", 50, 60),
           ("p/c", 95, 130)]
    ev1 = [("p/a", 0, 100)]
    info = {k: devtrace.parse_op(f"%{k[2:]} = f32[4]{{0}} fusion(%x)")
            for k in ("p/a", "p/b", "p/c")}
    return Trace(ops={"/device:TPU:0": ev0, "/device:TPU:1": ev1},
                 busy={"/device:TPU:0": [(s, e) for _, s, e in ev0],
                       "/device:TPU:1": [(s, e) for _, s, e in ev1]},
                 spans=[("bench/window", 10, 110), ("bench/solve", 10, 40),
                        ("bench/check", 40, 110), ("bench/load", 70, 90)],
                 info=info)


def test_parse_op_reads_name_opcode_and_output_dims():
    op = devtrace.parse_op(
        "%_smoother_step_ell.12 = (f32[3,1,31744]{2,1,0:T(1,128)S(1)}, "
        "f32[3,1,31744]{2,1,0:T(1,128)}) custom-call(s32[248,10]{1,0} "
        "%copy-done.152)")
    assert (op.name, op.opcode, op.dims) == (
        "_smoother_step_ell.12", "custom-call", (3, 1, 31744))
    op = devtrace.parse_op("%while.190 = (f32[95232]{0:T(1024)}, "
                           "u32[]{:T(128)}) while(%tuple.1)")
    assert op.opcode == "while" and op.opcode in devtrace.CONTAINERS
    assert devtrace.parse_op("plain name").name == "plain name"


def test_merge_busy_and_gaps():
    iv = synthetic().busy["/device:TPU:0"]
    assert devtrace.merge(iv, 10, 110) == [[10, 30], [50, 60], [95, 110]]
    assert devtrace.busy_ns(iv, 10, 110) == 45
    assert devtrace.gaps(iv, 10, 110) == [(30, 50), (60, 95)]
    assert devtrace.gaps([], 0, 5) == [(0, 5)]


def test_busy_window_and_op_stats_average_over_chips():
    t = synthetic()
    busy, window = devtrace.busy_window_s(t)
    assert window == pytest.approx(100e-9)
    assert busy == pytest.approx((45 + 90) / 2 * 1e-9)
    # only events wholly inside the window count; per chip
    assert devtrace.op_stats(t) == {
        "p/a": (pytest.approx(20e-9 / 2), 1.0),
        "p/b": (pytest.approx(15e-9 / 2), 0.5)}


def test_breakdown_labels_gaps_by_innermost_span():
    b = devtrace.breakdown(synthetic())
    assert [n for n, _ in b["device_ops"]] == ["p/a fusion f32[4]",
                                              "p/b fusion f32[4]"]
    assert [n for n, _ in b["idle_gaps"]] == ["bench/load", "bench/check",
                                             "bench/check"]
    assert b["idle_gaps"][0][1] == pytest.approx(35e-9)


def test_window_must_be_unique():
    with pytest.raises(ValueError):
        Trace(ops={}, busy={}, spans=[]).window()


# A hot step of q1_m32_incl.hot at m=8 (448 free nodes; levels of 448 and
# 27 block rows) traced on one v5e: the recompute program ``jit_run`` and
# the solve program ``jit_solve``, inside one ``bench/window`` span.
RECORDED = Path(__file__).resolve().parent / "data" / "hot_m8.xplane.pb.gz"
M8_LEVELS = [Level(448, 3, 9196, (
    Product(9196, (3, 3), 1736, (3, 6), 4056, (3, 6), 7646),
    Product(1736, (6, 3), 4056, (3, 6), 433, (6, 6), 1871))),
    Level(27, 6, 433)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "hot_m8.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return devtrace.load(str(path))


def test_recorded_trace_busy_and_kernels(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    busy, window = devtrace.busy_window_s(recorded)
    assert 0 < busy <= window
    stats = devtrace.op_stats(recorded)
    kernels = {k: recorded.info[k].dims for k in stats
               if "_smoother_step_ell" in k or "fused_pair_gemm" in k}
    assert {k.split("/")[0] for k in kernels} == {"jit_run", "jit_solve"}
    assert kernels["jit_run/fused_pair_gemm_lanes.2"] == (3, 6, 7646)
    assert kernels["jit_run/fused_pair_gemm_lanes.3"] == (6, 6, 1871)
    assert all(d == (3, 1, 448) for k, d in kernels.items()
               if "_smoother" in k)
    # device time of the ops cannot exceed the busy time by nesting: the
    # containers (while loops) are left out of the op times
    assert sum(sec for sec, _ in stats.values()) <= busy * 1.0001
    b = devtrace.breakdown(recorded)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
    assert all(label.startswith("bench/") for label, _ in b["idle_gaps"])


@pytest.mark.parametrize("metric", ["fused_smoother_roofline",
                                    "fused_pair_gemm_roofline"])
def test_recorded_trace_roofline_shares(recorded, metric):
    import harness
    ctx = types.SimpleNamespace(
        ops=devtrace.op_stats(recorded),
        dims={k: op.dims for k, op in recorded.info.items()},
        levels=M8_LEVELS, itemsize=4,
        peaks=harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"])
    share = harness.reader(metric)(ctx)
    assert share is not None and 0 < share < 100
