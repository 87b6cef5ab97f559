"""Plain f64 numpy reference: Q1 hexahedral linear elasticity.

Written from the weak form alone and independent of the code under test:
nothing here imports the program.  Unit cube, ``m`` nodes per edge
(``m - 1`` trilinear elements per edge), the z=0 face clamped and
eliminated, isotropic material per element.

Layout conventions the benchmark shares with the program's interface:
node ``ix + m*(iy + m*iz)`` (x fastest), element ``ex + (m-1)*(ey +
(m-1)*ez)``, three displacement components per node, and the free nodes
(``iz > 0``) in increasing node order, so free dof ``3*(node - m*m) + c``.

The operator is applied matrix-free, element by element:

    a(u, v) = sum_e  lam_e * Klam + mu_e * Kmu

with, for shape functions N_i and components a, b,

    Klam[(i,a),(j,b)] = int d_a N_i d_b N_j
    Kmu [(i,a),(j,b)] = int delta_ab grad N_i . grad N_j + d_b N_i d_a N_j

integrated by 2x2x2 Gauss points, which is exact for trilinear elements.
"""
from __future__ import annotations

import numpy as np

# local node k = a0 + 2*(a1 + 2*a2) sits at corner (a0, a1, a2)
_CORNERS = np.array([[k & 1, (k >> 1) & 1, (k >> 2) & 1] for k in range(8)])


def lame(E, nu):
    """(lambda, mu) from Young's modulus and Poisson ratio."""
    E, nu = np.asarray(E, np.float64), np.asarray(nu, np.float64)
    return E * nu / ((1 + nu) * (1 - 2 * nu)), E / (2 * (1 + nu))


def element_basis(h: float):
    """(Klam, Kmu), each (24, 24) with dof 3*i + a, for a cube of edge h."""
    g = 1 / np.sqrt(3.0)
    klam = np.zeros((8, 3, 8, 3))
    kmu = np.zeros((8, 3, 8, 3))
    for q in np.array(np.meshgrid([-g, g], [-g, g], [-g, g])).reshape(3, -1).T:
        s = 2 * _CORNERS - 1                      # corner signs, (8, 3)
        f = 1 + s * q                             # 1-D factors, (8, 3)
        grad = np.empty((8, 3))
        for d in range(3):
            others = [o for o in range(3) if o != d]
            grad[:, d] = s[:, d] * f[:, others[0]] * f[:, others[1]] / 8
        grad *= 2 / h                             # reference -> physical
        w = (h / 2) ** 3                          # unit weights * det J
        klam += w * np.einsum("ia,jb->iajb", grad, grad)
        kmu += w * (np.einsum("ab,ic,jc->iajb", np.eye(3), grad, grad)
                    + np.einsum("ib,ja->iajb", grad, grad))
    return klam.reshape(24, 24), kmu.reshape(24, 24)


class Q1Elasticity:
    """The clamped-cube problem on an ``m^3`` node grid."""

    def __init__(self, m: int):
        self.m = m
        self.ne = m - 1
        self.h = 1.0 / self.ne
        e = np.arange(self.ne)
        ex, ey, ez = np.meshgrid(e, e, e, indexing="ij")
        base = np.stack([ex.ravel("F"), ey.ravel("F"), ez.ravel("F")], 1)
        corner = base[:, None, :] + _CORNERS[None]            # (ne^3, 8, 3)
        self.conn = corner[..., 0] + m * (corner[..., 1] + m * corner[..., 2])
        self.centroids = (base + 0.5) * self.h
        self.n_free = 3 * (m ** 3 - m * m)
        self.klam, self.kmu = element_basis(self.h)

    @property
    def n_elements(self) -> int:
        return self.ne ** 3

    def body_force(self) -> np.ndarray:
        """Load (0, 0, -1) lumped as h^3 on every free node."""
        b = np.zeros((self.n_free // 3, 3))
        b[:, 2] = -self.h ** 3
        return b.reshape(-1)

    def inclusion(self, E_matrix, E_inclusion, nu_matrix, nu_inclusion,
                  center, radius):
        """Per-element (E, nu) of a spherical inclusion, by centroid."""
        inside = (np.sum((self.centroids - np.asarray(center)) ** 2, 1)
                  <= radius ** 2)
        return (np.where(inside, float(E_inclusion), float(E_matrix)),
                np.where(inside, float(nu_inclusion), float(nu_matrix)))

    def apply(self, E, nu, x_free: np.ndarray) -> np.ndarray:
        """A x on the free dofs for per-element (or scalar) E, nu."""
        lam, mu = lame(np.broadcast_to(E, (self.n_elements,)),
                       np.broadcast_to(nu, (self.n_elements,)))
        m = self.m
        x = np.zeros((m ** 3, 3))
        x[m * m:] = np.asarray(x_free, np.float64).reshape(-1, 3)
        xe = x[self.conn].reshape(-1, 24)
        ye = (lam[:, None] * (xe @ self.klam.T)
              + mu[:, None] * (xe @ self.kmu.T)).reshape(-1, 3)
        nodes = np.repeat(self.conn.reshape(-1), 1)
        y = np.stack([np.bincount(nodes, ye[:, c], minlength=m ** 3)
                      for c in range(3)], 1)
        return y[m * m:].reshape(-1)

    def relres(self, E, nu, b: np.ndarray, x: np.ndarray) -> float:
        """||b - A x|| / ||b||, all in f64."""
        b = np.asarray(b, np.float64)
        r = b - self.apply(E, nu, x)
        return float(np.linalg.norm(r) / np.linalg.norm(b))
