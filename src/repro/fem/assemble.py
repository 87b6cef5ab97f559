"""Finite-element assembly through the blocked COO primitive (paper Sec. 5).

This is exactly the workload ``MatCOOUseBlockIndices`` was built for: every
element emits a dense grid of 3x3 node-pair blocks (duplicated across shared
nodes, unordered), declared once as block coordinates; each numeric assembly
is then a single device scatter-sum of the block value stream.

Two assembly paths share the one ``BlockCOOPlan``:

``path="device"`` (default)
    per-element stiffness blocks computed in JAX from Lame-parameter fields
    (``repro.fem.device_stiffness``) from per-element material fields
    ``E(x), nu(x)`` — heterogeneous and jittable.  The problem carries a
    ``DeviceAssembler`` whose ``coo_data(E, nu)`` composes with
    ``gamg.recompute`` into one zero-host-transfer hot-update program
    (``ElasticityProblem.update_coefficients`` /
    ``GAMGSolver.update_coefficients``).

``path="host"``
    the numpy golden reference: one ``element_stiffness`` matrix per
    distinct material, broadcast (constant fields) or looped (varying
    fields) on the host.  ``tests/test_assembly.py`` pins the device path
    against it to f64 tolerance.

Coefficient-update contract: fields are **per-element** arrays (constant
within an element, sampled e.g. at centroids via ``element_centroids``);
scalars broadcast.  Updates change *values only* — mesh, boundary
conditions and the COO plan are fixed, which is what keeps the update
inside the cached-plan / state-gated reuse model.

Dirichlet handling: clamped nodes are *eliminated* — the assembled operator
is restricted to free nodes so every remaining node carries a full 3x3
diagonal block and the operator stays SPD (the reduced system PETSc's ex56
effectively solves through MatZeroRowsColumns).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_coo import BlockCOOPlan, preallocate_coo, set_values_coo
from repro.core.block_csr import BlockCSR
from repro.fem.device_stiffness import DeviceAssembler
from repro.fem.hex_elasticity import (
    HexMesh,
    element_stiffness,
    hex_mesh,
    rigid_body_modes,
)

Array = jax.Array
BS = 3  # displacement components per node


@dataclasses.dataclass
class ElasticityProblem:
    """Assembled reduced system + everything AMG needs."""

    A: BlockCSR              # (n_free*3) x (n_free*3), 3x3 blocks
    b: Array                 # body-force load on free dofs
    B: Array                 # (n_free*3, 6) rigid-body near-null space
    mesh: HexMesh
    free_nodes: np.ndarray   # global ids of free nodes
    coo_plan: BlockCOOPlan   # cached: numeric reassembly is one scatter
    values: Array            # current block value stream (for reassembly)
    assembler: Optional[DeviceAssembler] = None   # device path only
    E_field: Optional[Array] = None   # current per-element coefficients
    nu_field: Optional[Array] = None

    @property
    def n(self) -> int:
        return self.A.shape[0]

    def reassemble(self, scale: float | Array = 1.0) -> BlockCSR:
        """Hot numeric re-assembly (new coefficients, same mesh) — a single
        MatSetValuesCOO scatter with the cached plan."""
        return set_values_coo(self.coo_plan, self.values * scale)

    # ---- coefficient updates (device path) ------------------------------
    def coefficient_operator(self, E, nu) -> BlockCSR:
        """Pure re-assembly from new per-element fields: element blocks
        -> cached COO scatter.  Does not mutate the problem."""
        if self.assembler is None:
            raise ValueError(
                "coefficient updates need the device assembly path: "
                "assemble with path='device' (the default)")
        E, nu = self.assembler.as_fields(E, nu)
        return set_values_coo(self.coo_plan,
                              self.assembler.value_stream(E, nu))

    def update_coefficients(self, E, nu) -> BlockCSR:
        """In-place coefficient update: new material fields, same mesh/plan.

        Refreshes ``A``/``values``/``E_field``/``nu_field`` and returns the
        new operator.  The solver-side hot loop
        (``GAMGSolver.update_coefficients``) skips this container entirely
        and jits ``assembler.coo_data`` straight into the recompute.
        """
        if self.assembler is None:
            raise ValueError(
                "coefficient updates need the device assembly path: "
                "assemble with path='device' (the default)")
        E, nu = self.assembler.as_fields(E, nu)
        stream = self.assembler.value_stream(E, nu)
        self.A = set_values_coo(self.coo_plan, stream)
        self.values = stream
        self.E_field, self.nu_field = E, nu
        return self.A


def _element_block_stream(mesh: HexMesh, Ke: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Block coordinates + values of every element contribution."""
    nn = mesh.connectivity.shape[1]
    conn = mesh.connectivity                        # (ne, nn)
    rows = np.repeat(conn, nn, axis=1).reshape(-1)   # e,a,b -> conn[e,a]
    cols = np.tile(conn, (1, nn)).reshape(-1)        # e,a,b -> conn[e,b]
    blocks = Ke.reshape(nn, BS, nn, BS).transpose(0, 2, 1, 3)  # (a,b,3,3)
    vals = np.broadcast_to(blocks.reshape(1, nn * nn, BS, BS),
                           (mesh.n_elements, nn * nn, BS, BS))
    return rows, cols, vals.reshape(-1, BS, BS)


def _host_value_stream(mesh: HexMesh, E: np.ndarray,
                       nu: np.ndarray) -> np.ndarray:
    """Golden numpy value stream for per-element fields (host loop)."""
    nn = mesh.connectivity.shape[1]
    ne = mesh.n_elements
    vals = np.empty((ne, nn * nn, BS, BS))
    for e in range(ne):
        Ke = element_stiffness(mesh.order, mesh.h, float(E[e]),
                               float(nu[e]))
        vals[e] = Ke.reshape(nn, BS, nn, BS).transpose(0, 2, 1, 3) \
                    .reshape(nn * nn, BS, BS)
    return vals.reshape(-1, BS, BS)


def element_centroids(mesh: HexMesh) -> np.ndarray:
    """(n_elements, 3) element centroid coordinates — sample material
    functions here to make per-element coefficient fields."""
    return mesh.coords[mesh.connectivity].mean(axis=1)


def inclusion_fields(mesh: HexMesh, *, E_matrix: float = 1.0,
                     E_inclusion: float = 10.0, nu_matrix: float = 0.3,
                     nu_inclusion: float = 0.2,
                     center=(0.7, 0.7, 0.7), radius: float = 0.3
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Two-material test problem: a stiff spherical inclusion in a softer
    matrix (the heterogeneous workload of the regression battery)."""
    c = element_centroids(mesh)
    inside = np.sum((c - np.asarray(center)) ** 2, axis=1) <= radius ** 2
    E = np.where(inside, E_inclusion, E_matrix)
    nu = np.where(inside, nu_inclusion, nu_matrix)
    return E, nu


def assemble_elasticity(m: int, order: int = 1, E=1.0, nu=0.3,
                        fix_face: bool = True, path: str = "device"
                        ) -> ElasticityProblem:
    """Assemble the reduced elasticity operator on an ``m^3`` grid.

    ``E``/``nu`` may be scalars or per-element ``(n_elements,)`` arrays
    (heterogeneous materials).  ``path`` selects where the element blocks
    are computed: ``"device"`` (JAX element blocks, default — carries a
    ``DeviceAssembler`` for jitted coefficient updates) or ``"host"`` (the
    numpy golden reference).
    """
    if path not in ("device", "host"):
        raise ValueError(f"invalid assembly path {path!r}: expected "
                         f"'device' or 'host'")
    mesh = hex_mesh(m, order)
    ne = mesh.n_elements
    E_f = np.broadcast_to(np.asarray(E, np.float64), (ne,))
    nu_f = np.broadcast_to(np.asarray(nu, np.float64), (ne,))

    # block coordinates (identical for both paths — one plan); values are
    # path-specific, so only the index streams are built here
    nn = mesh.connectivity.shape[1]
    conn = mesh.connectivity
    rows = np.repeat(conn, nn, axis=1).reshape(-1)   # e,a,b -> conn[e,a]
    cols = np.tile(conn, (1, nn)).reshape(-1)        # e,a,b -> conn[e,b]

    # clamp the z=0 face (eliminate those nodes)
    if fix_face:
        fixed = mesh.coords[:, 2] == 0.0
    else:
        fixed = np.zeros(mesh.n_nodes, dtype=bool)
    free = np.flatnonzero(~fixed)
    # renumber: global node -> free index, fixed -> -1 (COO drops them)
    renum = np.full(mesh.n_nodes, -1, dtype=np.int64)
    renum[free] = np.arange(len(free))
    r2, c2 = renum[rows], renum[cols]

    plan = preallocate_coo(r2, c2, nbr=len(free), nbc=len(free),
                           br=BS, bc=BS)
    assembler = None
    if path == "device":
        assembler = DeviceAssembler.build(mesh, plan)
        Ej, nuj = assembler.as_fields(E_f, nu_f)
        values = jax.jit(assembler.value_stream)(Ej, nuj)
    else:
        Ej = nuj = None
        if np.all(E_f == E_f[0]) and np.all(nu_f == nu_f[0]):
            Ke = element_stiffness(order, mesh.h, float(E_f[0]),
                                   float(nu_f[0]))
            _, _, vals = _element_block_stream(mesh, Ke)
        else:
            vals = _host_value_stream(mesh, E_f, nu_f)
        values = jnp.asarray(vals)
    A = set_values_coo(plan, values)

    # uniform body force (0, 0, -1) lumped to nodes
    b = np.zeros((len(free), BS))
    b[:, 2] = -mesh.h ** 3
    B = rigid_body_modes(mesh.coords[free])
    return ElasticityProblem(A=A, b=jnp.asarray(b.reshape(-1)),
                             B=jnp.asarray(B), mesh=mesh,
                             free_nodes=free, coo_plan=plan, values=values,
                             assembler=assembler, E_field=Ej, nu_field=nuj)
