"""CPU set-up for the benchmark's own tests (``pytest bench/tests``).

The program's Pallas kernels run in interpret mode on the CPU; the
kernel paths the chip takes under the ``f32`` policy are forced here, in
this test process only, before the program is first imported."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

os.environ.setdefault("JAX_PLATFORMS", "cpu")
for knob, path in (("REPRO_SMOOTH_PATH", "fused"),
                   ("REPRO_SPMM_PATH", "kernel"),
                   ("REPRO_SPGEMM_PATH", "fused")):
    os.environ.setdefault(knob, path)
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
