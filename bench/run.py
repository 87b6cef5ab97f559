#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell's configuration, warms the programs its window calls,
runs the closed loop of its traffic mix for ``--seconds`` (the window ends
with the first whole step that reaches it), checks every answer of the
window against the plain f64 reference, and prints one JSON object as
the last line of stdout.  With ``--trace 0`` it reports the cell's
end-to-end metrics; with ``--trace 1`` it traces the window and reports
the per-layer metrics.  Without a TPU it exits non-zero and prints no
result.  JAX's persistent compilation cache is kept in ``.jax_cache``
inside the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    spec = harness.Spec(ROOT)
    spec.workload(args.workload)
    try:
        from repro import compile_cache
    except ImportError as e:
        raise harness.Fail(f"the program is not in this checkout: {e}")
    import jax
    harness.require_chips(jax, int(spec.workload(args.workload)["chips"]))
    compile_cache.enable()
    result = harness.run_cell(spec, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
