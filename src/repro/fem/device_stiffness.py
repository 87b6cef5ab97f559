"""Device-resident element stiffness from per-element material fields.

The host golden path (``hex_elasticity.element_stiffness``) builds one
numpy ``Ke`` per distinct material and broadcasts it, which caps the
reachable operator updates at a global scalar ``reassemble(scale)``.  This
module computes **per-element** stiffness blocks in JAX from material
fields ``E(x), nu(x)`` given as per-element arrays, so the whole
quasi-static hot loop

    update_coefficients(E, nu) -> set_values_coo -> gamg.recompute -> solve

is one traced, zero-host-transfer device program (the paper's
recurring-recompute scenario with the *assembly* finally on device too).

Structure/value split mirrors the rest of the stack:

* ``DeviceAssembler`` is the cold, host-built symbolic object: the shared
  quadrature arrays (``hex_elasticity.element_quadrature`` — identical B
  matrices to the golden path), the element count and the cached
  ``BlockCOOPlan``.  Built once per mesh + boundary conditions.
* ``element_stiffness_blocks`` / ``DeviceAssembler.value_stream`` /
  ``DeviceAssembler.coo_data`` are pure jittable functions of the
  coefficient fields.  The constitutive matrix is linear in the Lame
  parameters (``D = lam*D_LAM + mu*D_MU``), so every element matrix is
  ``lam*K_LAM + mu*K_MU`` of two host-integrated basis matrices:
  heterogeneity costs two scaled adds per element, no quadrature.

Everything runs at the value dtype (f64 by default — the existing
precision policy casts *down* inside ``gamg.recompute``, never here, so
the assembled stream is a full-precision golden input under every
policy).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_coo import BlockCOOPlan, set_values_coo_data
from repro.fem.hex_elasticity import (
    D_LAM,
    D_MU,
    HexMesh,
    element_quadrature,
    lame_parameters,
)

Array = jax.Array
BS = 3  # displacement components per node


def stiffness_basis(Bq, wq):
    """``(K_LAM, K_MU)``: ``K_X = sum_q w_q B_q^T D_X B_q`` for the two
    constitutive basis matrices, integrated on the host and symmetrized
    (mirroring the host golden path), so that every element matrix is
    ``lam_e K_LAM + mu_e K_MU``."""
    Bq, wq = np.asarray(Bq), np.asarray(wq)
    out = []
    for d in (D_LAM, D_MU):
        k = np.einsum("q,qia,ij,qjb->ab", wq, Bq, d, Bq)
        out.append(0.5 * (k + k.T))
    return tuple(out)


def element_stiffness_blocks(Bq, wq, E: Array, nu: Array) -> Array:
    """Per-element stiffness matrices of the coefficient fields.

    ``Bq (nq, 6, 3*nn)`` / ``wq (nq,)`` are the shared (host) quadrature
    arrays; ``E``/``nu`` are per-element coefficient arrays ``(ne,)``.
    Returns ``(ne, 3*nn, 3*nn)`` symmetric element matrices:

        Ke_e = sum_q w_q B_q^T (lam_e D_LAM + mu_e D_MU) B_q
             = lam_e K_LAM + mu_e K_MU            (``stiffness_basis``)

    The quadrature runs once, on the host; the device work per element is
    two scaled adds.
    """
    k_lam, k_mu = stiffness_basis(Bq, wq)
    lam, mu = lame_parameters(E, nu)
    dt = np.asarray(Bq).dtype
    return (lam[:, None, None] * jnp.asarray(k_lam, dt)
            + mu[:, None, None] * jnp.asarray(k_mu, dt))


@dataclasses.dataclass(frozen=True, eq=False)
class DeviceAssembler:
    """Cold symbolic side of device assembly (host-built, hashable-by-id:
    ``eq=False`` keeps the identity hash — the array fields aren't
    field-hashable and two assemblers are never interchangeable anyway).

    Owns the quadrature arrays, the element/block bookkeeping and the
    cached ``BlockCOOPlan`` of the reduced (BC-eliminated) operator; the
    numeric side is the pure ``value_stream``/``coo_data`` functions of
    the coefficient fields.  Closures over an assembler (e.g.
    ``gamg.make_coeff_recompute``) bake the plan in as constants, exactly
    like the PtAP caches.
    """

    plan: BlockCOOPlan
    quad_b: np.ndarray      # (nq, 6, 3*nn) strain matrices
    quad_w: np.ndarray      # (nq,) weights * detJ
    n_elements: int
    nn: int                 # nodes per element
    dtype: np.dtype = np.dtype(np.float64)

    @staticmethod
    def build(mesh: HexMesh, plan: BlockCOOPlan,
              dtype=np.float64) -> "DeviceAssembler":
        Bq, wq = element_quadrature(mesh.order, mesh.h)
        return DeviceAssembler(plan=plan, quad_b=Bq, quad_w=wq,
                               n_elements=mesh.n_elements,
                               nn=mesh.connectivity.shape[1],
                               dtype=np.dtype(dtype))

    # ---- field plumbing -------------------------------------------------
    def as_fields(self, E, nu):
        """Scalars/arrays -> per-element ``(ne,)`` fields at the assembly
        dtype (force-cast, so callers at any dtype hit one traced program —
        the same no-retrace contract as the scatter staging in
        ``repro.dist``)."""
        ne = self.n_elements
        E = np.broadcast_to(np.asarray(E, self.dtype), (ne,))
        nu = np.broadcast_to(np.asarray(nu, self.dtype), (ne,))
        return jnp.asarray(E), jnp.asarray(nu)

    # ---- jittable numeric phase ----------------------------------------
    def value_stream(self, E: Array, nu: Array) -> Array:
        """(n_input, 3, 3) blocked COO value stream in declaration order
        (element-major, then row-node, then col-node) — exactly the
        MatSetValuesCOO stream ``self.plan`` was preallocated for.

        The two basis matrices are cut into node-pair blocks on the host,
        so the device only scales and adds them: reordering the element
        matrices into blocks on the device took the TPU compiler over two
        minutes at m=32."""
        nn = self.nn
        lam, mu = lame_parameters(E, nu)
        bl, bm = (np.asarray(k, self.dtype).reshape(nn, BS, nn, BS)
                  .transpose(0, 2, 1, 3).reshape(1, nn * nn, BS, BS)
                  for k in stiffness_basis(self.quad_b, self.quad_w))
        blocks = (lam[:, None, None, None] * jnp.asarray(bl)
                  + mu[:, None, None, None] * jnp.asarray(bm))
        return blocks.reshape(-1, BS, BS)

    def coo_data(self, E: Array, nu: Array) -> Array:
        """Assembled (nnzb, 3, 3) operator payload: value stream through
        the cached plan's scatter-sum.  Pure and jittable — compose with
        ``gamg.recompute`` for the one-program hot loop."""
        return set_values_coo_data(self.plan, self.value_stream(E, nu))
