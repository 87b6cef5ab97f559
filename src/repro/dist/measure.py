"""Measured (traced) collective counts of the distributed V-cycle.

``repro.obs.model.dist_cycle_comm`` *predicts* the per-cycle message
traffic of the distributed hierarchy; this module *measures* it, by
staging the actual shard_map programs and counting the collective
equations in their jaxprs.  Every halo-exchanged slab is exactly one
``ppermute`` equation and every window/solve gather exactly one
``all_gather`` (``ndev - 1`` slab messages under recursive doubling), so
static equation counts of the *unrolled* V-cycle are the per-cycle
message counts — no timing, no devices doing real work, just traces.

The V-cycle is isolated by differencing: one trace runs the rank
recompute alone, a second runs recompute + one V-cycle; the recompute's
collectives (lambda-max power iterations, the stage-2 windows, the
coarse gather) cancel and the difference is one cycle.  The counts are
schedule-invariant — the overlapped split apply reorders the same
exchanges, it does not add or drop any — which is itself worth pinning.

CLI (``python -m repro.dist.measure m pr pc``) prints the comparison as
JSON; it needs ``XLA_FLAGS=--xla_force_host_platform_device_count=<pr>``
in the environment (the caller's job, exactly like the dist selftest),
which is why ``benchmarks/table1_weak_scaling.py`` runs it as a
subprocess for its model-vs-measured column.
"""
from __future__ import annotations

import json
import re
import sys

import numpy as np

_PRIMS = ("ppermute", "all_gather")


def count_collectives(jaxpr_text: str, ndev: int) -> dict:
    """Collective-equation counts of a jaxpr rendering -> message counts.

    ``msgs`` is per rank per execution: one slab message per ``ppermute``
    equation, ``ndev - 1`` per ``all_gather`` (each rank receives every
    other rank's slab).
    """
    counts = {p: len(re.findall(rf"\b{p}\[", jaxpr_text)) for p in _PRIMS}
    counts["msgs"] = (counts["ppermute"]
                      + (ndev - 1) * counts["all_gather"])
    return counts


def measured_cycle_comm(dg, mesh) -> dict:
    """Per-cycle collective counts of ``dg``'s V-cycle on ``mesh``.

    Returns ``{"cycle": {...}, "recompute": {...}}`` — the cycle entry is
    the recompute-differenced count (see module docstring).
    """
    import jax
    from jax.sharding import PartitionSpec

    from repro.dist import solver as ds

    P = PartitionSpec
    lv0 = dg.levels[0]
    nnzb = int(lv0.a_nnz_starts[-1])
    args = dg.sharded_args()
    a0 = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype),
        dg.scatter_fine_payloads(
            np.zeros((nnzb, lv0.bs, lv0.bs), np.float64)))
    b = jax.tree.map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype),
        dg.scatter_vector(np.zeros(lv0.n_fine * lv0.bs, np.float64)))
    overlap = ds.resolve_overlap() == "on"

    def recompute_only(args, a0):
        args, a0 = jax.tree.map(lambda t: t[0], (args, a0))
        _, chol = ds._rank_recompute(dg, args, a0, overlap)
        return chol[None]

    def recompute_and_cycle(args, a0, b):
        args, a0, b = jax.tree.map(lambda t: t[0], (args, a0, b))
        states, chol = ds._rank_recompute(dg, args, a0, overlap)
        return ds._rank_vcycle(dg, args, states, chol, b, overlap)[None]

    def trace(f, *xs):
        sm = jax.shard_map(f, mesh=mesh, in_specs=(P(ds.AXIS),) * len(xs),
                           out_specs=P(ds.AXIS), check_vma=False)
        return str(jax.make_jaxpr(sm)(*xs))

    rec = count_collectives(trace(recompute_only, args, a0), dg.ndev)
    full = count_collectives(trace(recompute_and_cycle, args, a0, b),
                             dg.ndev)
    cycle = {k: full[k] - rec[k] for k in full}
    return {"cycle": cycle, "recompute": rec}


def main(m: int, pr: int, pc: int) -> int:
    import jax

    from repro.core import gamg
    from repro.dist.partition import ProcessMesh
    from repro.dist.solver import build_dist_gamg, rank_mesh
    from repro.fem.assemble import assemble_elasticity
    from repro.obs.model import dist_cycle_comm

    assert len(jax.devices()) >= pr, \
        (f"need XLA_FLAGS=--xla_force_host_platform_device_count={pr}, "
         f"got {len(jax.devices())} devices")
    mesh = rank_mesh(jax.devices()[:pr])
    prob = assemble_elasticity(m)
    setupd = gamg.setup(prob.A, prob.B, coarse_size=30, precision="f64")
    dg = build_dist_gamg(setupd, ProcessMesh((pr, pc)))
    measured = measured_cycle_comm(dg, mesh)
    model_rows = dist_cycle_comm(dg)
    model_msgs = sum(r["msgs"] for r in model_rows)
    print(json.dumps({"m": m, "pr": pr, "pc": pc,
                      "measured": measured,
                      "model_msgs": model_msgs,
                      "model_rows": model_rows}))
    return 0


if __name__ == "__main__":
    # counts collectives on fake CPU devices; never takes an accelerator
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 5,
                  int(sys.argv[2]) if len(sys.argv) > 2 else 2,
                  int(sys.argv[3]) if len(sys.argv) > 3 else 1))
