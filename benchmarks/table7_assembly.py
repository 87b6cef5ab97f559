"""Table 7 (new workload): device-resident FEM assembly + the coefficient
hot loop — assemble/recompute time and update bytes vs the host path.

The paper's recurring-recompute scenario starts from "a new blocked COO
assembly path": with assembly itself device-resident, a quasi-static
operator update ships two per-element coefficient arrays (2 * ne * 8
bytes) instead of a host-assembled ``(n_input, 3, 3)`` value stream
(ne * nn^2 * 9 * 8 bytes) — a factor of ``nn^2 * 9 / 2`` (288x for Q1,
2916x for Q2) less host->device traffic per update, before counting the
host flops the device path sheds.

Timed on the real implementations at CPU scale:

* ``t7.device_assemble``         jitted fields -> assembled payload
  (element blocks + cached COO scatter)
* ``t7.device_update_recompute`` the fused hot loop: fields -> hierarchy
  (``gamg.make_coeff_recompute``) — ONE traced program, zero host bytes
* ``t7.host_assemble``           the numpy golden loop (per-element Ke)
  + the value-stream upload + the jitted recompute, the pre-ISSUE-5 path
"""
from __future__ import annotations

import time

import numpy as np

import repro.core  # noqa: F401
import jax
import jax.numpy as jnp

from repro.core import gamg
from repro.core.block_coo import set_values_coo_data
from repro.fem.assemble import assemble_elasticity, element_centroids

from benchmarks.common import emit, time_fn


def update_bytes(prob) -> tuple:
    """(device, host) host->device bytes of one coefficient update."""
    ne = prob.mesh.n_elements
    nn = prob.mesh.connectivity.shape[1]
    return 2 * ne * 8, ne * nn * nn * 9 * 8


def run(m: int = 8, order: int = 1) -> None:
    prob = assemble_elasticity(m, order=order)
    asm = prob.assembler
    ne = prob.mesh.n_elements
    c = element_centroids(prob.mesh)
    E = 1.0 + 4.0 * c[:, 0]
    nu = np.full(ne, 0.3)
    Ej, nuj = asm.as_fields(E, nu)

    # device assembly alone: fields -> (nnzb, 3, 3) payload
    assemble = jax.jit(asm.coo_data)
    us_dev = time_fn(assemble, Ej, nuj)
    dev_b, host_b = update_bytes(prob)
    emit(f"t7.device_assemble.m{m}.q{order}", us_dev,
         f"ne={ne};update_bytes={dev_b}")

    # the fused coefficient hot loop: fields -> hierarchy, one program
    setupd = gamg.setup(prob.A, prob.B, coarse_size=30)
    coeff_recompute = gamg.make_coeff_recompute(setupd, asm)
    us_loop = time_fn(coeff_recompute, Ej, nuj)
    emit(f"t7.device_update_recompute.m{m}.q{order}", us_loop,
         f"traced_programs=1;update_bytes={dev_b}")

    # host golden path: numpy per-element loop + value-stream upload +
    # jitted recompute (what the hot loop replaces)
    from repro.fem.assemble import _host_value_stream
    recompute = gamg.make_recompute(setupd)
    plan = prob.coo_plan

    def host_update():
        vals = _host_value_stream(prob.mesh, E, nu)     # host flops
        data = set_values_coo_data(plan, jnp.asarray(vals))  # upload+scatter
        return recompute(data)

    # steady state: warm the jitted recompute first (the device rows are
    # timed warm too), then best-of-n so the row measures the recurring
    # host assembly + upload cost, not one-time XLA compiles
    jax.block_until_ready(host_update().coarse_chol)
    us_host = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(host_update().coarse_chol)
        us_host = min(us_host, (time.perf_counter() - t0) * 1e6)
    emit(f"t7.host_assemble.m{m}.q{order}", us_host,
         f"update_bytes={host_b}")

    ratio = host_b / dev_b
    emit(f"t7.update_bytes_ratio.m{m}.q{order}", 0.0,
         f"host_over_device={ratio:.0f}x")
    assert dev_b * 100 < host_b, (dev_b, host_b)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
