#!/usr/bin/env python3
"""Readings that set the limit of ``correct``: program and control.

    python3 bench/control.py --workload <cell> [--seeds 12] \\
        [--control-seeds 3] [--seconds 10] [--first-seed <s>] [--out <f>]

One process, since set-up is long.  The cell's problem is built once as
the configuration states it and once as its control (the configuration's
``"control"`` solver settings: the program one precision step below), and
each seed is one ``harness.run_cell`` on it, with a window of
``--seconds`` at the cell's own load, its answers checked as every run
checks them.  The lower reading is the largest true residual over the
program's seeds, the upper one the smallest over the control's.  Needs
the chips the cell asks for.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(spec, workload: str, seeds, control_seeds, seconds: float,
             devices=None) -> list:
    """One row per seed: path, correct, the compared number, iterations."""
    import harness
    rows = []
    for control, group in ((False, seeds), (True, control_seeds)):
        if not group:
            continue
        _, cfg, _, Loop = harness.cell_parts(spec, workload, control)
        problem = Loop.build(cfg, harness.Spans())
        for seed in group:
            res = harness.run_cell(spec, workload, seed, seconds, False,
                                   time.perf_counter(), devices,
                                   control=control, problem=problem)
            rows.append({"workload": workload, "seed": seed,
                         "path": "control" if control else "program",
                         "correct": res["correct"],
                         "true_relres_max":
                             res["checks"]["true_relres_max"]["value"],
                         "answers": res["attempted"]})
    return rows


def summary(rows: list) -> dict:
    prog = [r for r in rows if r["path"] == "program"]
    ctrl = [r for r in rows if r["path"] == "control"]
    return {
        "lower_reading": max((r["true_relres_max"] for r in prog),
                             default=None),
        "upper_reading": min((r["true_relres_max"] for r in ctrl),
                             default=None),
        "program_correct": sum(r["correct"] for r in prog),
        "program_seeds": len(prog),
        "control_correct": sum(r["correct"] for r in ctrl),
        "control_seeds": len(ctrl)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import harness
    from repro import compile_cache

    spec = harness.Spec(ROOT)
    harness.require_chips(jax, int(spec.workload(args.workload)["chips"]))
    compile_cache.enable()
    seeds = [args.first_seed + 7919 * k
             for k in range(args.seeds + args.control_seeds)]
    rows = readings(spec, args.workload, seeds[:args.seeds],
                    seeds[args.seeds:], args.seconds)
    lines = [json.dumps(r) for r in rows]
    lines.append(json.dumps({"workload": args.workload, **summary(rows)}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
