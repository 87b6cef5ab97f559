"""Device-resident quasi-static time march with adaptive re-coarsening.

Marches the built-in damage-softening scenario (``repro.sim``): each
step feeds the previous solution into the coefficient-update law, runs
the fused device assembly -> state-gated PtAP recompute -> warm-started
AMG-PCG step, and the device-side staleness monitor decides when the
frozen hierarchy has degraded enough to be worth a host rebuild.  The
three policies are run on the same trajectory:

* ``frozen``    one setup, the whole march one traced ``lax.scan``;
* ``adaptive``  frozen segments cut by the staleness monitor;
* ``resetup``   a full ``gamg.setup`` before every step (baseline).

Run:  PYTHONPATH=src python examples/march.py [m] [n_steps]
"""
import sys
import time

import numpy as np

import repro.core  # noqa: F401  (enables fp64)
from repro.fem.assemble import assemble_elasticity
from repro.sim import MarchConfig, SofteningScenario, StalenessConfig, march
from repro import compile_cache


def main(m: int = 5, n_steps: int = 8) -> None:
    print(f"assembling {m}^3 Q1 elasticity on device")
    prob = assemble_elasticity(m)
    scen = SofteningScenario.build(prob, rate=0.25, d_max=0.99)
    cfg = MarchConfig(n_steps=n_steps, seg_len=8, rtol=1e-8, maxiter=400,
                      staleness=StalenessConfig(iter_drift=2, ref_window=2,
                                                coeff_rtol=0.25))
    results = {}
    for mode in ("frozen", "adaptive", "resetup"):
        t0 = time.perf_counter()
        res = march(prob, scen, cfg, mode=mode,
                    setup_opts={"coarse_size": 8})
        dt = time.perf_counter() - t0
        results[mode] = res
        segs = " ".join(f"{s.steps}@setup{s.setup_id}({s.reason})"
                        for s in res.segments)
        print(f"{mode:>8}: {dt:6.1f} s | setups {res.n_setups} | "
              f"iters {res.iters.tolist()} (total {res.total_iters}) | "
              f"segments: {segs}")
        assert res.status == "ok", res.status

    frozen, adaptive, resetup = (results["frozen"], results["adaptive"],
                                 results["resetup"])
    x_ref = np.asarray(resetup.x)
    rel = (np.linalg.norm(np.asarray(adaptive.x) - x_ref)
           / np.linalg.norm(x_ref))
    print(f"adaptive vs per-step-resetup final state: rel diff {rel:.2e} "
          f"with {adaptive.n_setups}/{resetup.n_setups} of the setups")
    print(f"adaptive vs frozen total CG iterations: "
          f"{adaptive.total_iters} vs {frozen.total_iters}")


if __name__ == "__main__":
    compile_cache.enable()
    main(*(int(a) for a in sys.argv[1:3]))
