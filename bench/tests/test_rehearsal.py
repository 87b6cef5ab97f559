"""CPU rehearsal of every cell at a tiny grid, kernels in interpret mode,
and the faults a run must catch.

Drives ``harness.run_cell`` past its look for a chip: the rest of a run
(set-up, warm-up, window, trace reduction, metric readers, the check
against the plain reference) is the same code the chip runs."""
import time

import jax
import numpy as np
import pytest

import harness

M = 6
CELLS = [w["name"] for w in harness.Spec().data["workloads"]]
# metrics only the chip can give
DEVICE_ONLY = ("device_trace",)


@pytest.fixture
def spec(monkeypatch):
    s = harness.Spec()
    config = s.config
    monkeypatch.setattr(s, "config", lambda name: {**config(name), "m": M})
    v5e = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
    monkeypatch.setattr(harness, "peaks_for", lambda kind: v5e)
    return s


def run(spec, workload, trace=False, seconds=1.0, control=False):
    return harness.run_cell(spec, workload, 2 ** 31 + 12345, seconds, trace,
                            time.perf_counter(),
                            devices=jax.devices()[:1], control=control)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal(spec, workload, trace):
    res = run(spec, workload, trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in spec.metrics(workload, trace)
            if m["source"] not in DEVICE_ONLY}
    assert want <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    assert res["checks"]["answers_checked"]["value"] == res["attempted"]
    if trace:
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_fault_altered_answer(spec, monkeypatch):
    """Each solve's answer altered where it is produced."""
    from repro.core import gamg
    solve = gamg.GAMGSolver.solve

    def altered(self, b, x0=None):
        res = solve(self, b, x0)
        return res._replace(x=res.x * (1 + 1e-6))

    monkeypatch.setattr(gamg.GAMGSolver, "solve", altered)
    for workload in CELLS:
        res = run(spec, workload)
        assert not res["correct"] and res["failed"] == res["attempted"]


def test_fault_state_unchanged(spec, monkeypatch):
    """A hot step whose coefficient update leaves the hierarchy as it was."""
    from repro.core import gamg
    monkeypatch.setattr(gamg.GAMGSolver, "update_coefficients",
                        lambda self, E, nu: None)
    hot = [w["name"] for w in spec.data["workloads"]
           if harness.cell_parts(spec, w["name"])[3].coefficients]
    assert hot
    for workload in hot:
        res = run(spec, workload)
        assert not res["correct"] and res["failed"] == res["attempted"]
        assert res["checks"]["true_relres_max"]["value"] > 1e-4


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limit(spec, workload):
    """The control (the configuration's control settings: everything in
    f32) in the program's place: the run's own check says not correct."""
    res = run(spec, workload, control=True)
    assert not res["correct"] and res["failed"] == res["attempted"] >= 1
    worst = res["checks"]["true_relres_max"]
    assert np.isfinite(worst["value"]) and worst["value"] > 3 * worst["limit"]


def test_control_readings(spec):
    """control.py's readings go through the same run: the program's seeds
    come out correct, the control's not, on one reused problem each."""
    import control
    rows = control.readings(spec, CELLS[0], [11, 2 ** 40 + 7], [13], 1.0,
                            devices=jax.devices()[:1])
    s = control.summary(rows)
    limit = harness.cell_parts(spec, CELLS[0])[1]["true_relres_limit"]
    assert s["program_correct"] == s["program_seeds"] == 2
    assert s["control_correct"] == 0 and s["control_seeds"] == 1
    assert s["lower_reading"] <= limit < 3 * limit < s["upper_reading"]
