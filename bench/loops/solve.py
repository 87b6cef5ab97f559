"""solve: one fixed hierarchy, each step solves one seeded load case.

Mix parameters (``bench/mixes/<traffic>.json``):

``cases``   load cases, each ``{"body": [bx, by, bz], "traction": [tx,
            ty, tz]}``: a body force per unit volume and a traction per
            unit area on the top face (z = 1), the two kinds of load a
            structural model of a clamped block carries.
``jitter``  each case's two vectors, plus ``jitter`` times a standard
            normal 3-vector each, drawn from the seed per solve.

Solve ``i`` takes case ``i mod len(cases)``, so every window holds the
same mix of cases whatever the seed.  The body force is lumped as h^3 on
every free node (the reference's ``body_force``); the traction with the
exact weights of a bilinear face, h^2 times 1/2 on each face edge it
lies on.
"""
from __future__ import annotations

import numpy as np

from problem import WARM_INDEX, ProblemLoop, host_rng


class Loop(ProblemLoop):
    unit = "solve"
    coefficients = False
    LOAD_STREAM = 2

    def __init__(self, problem, mix: dict, seed: int, spans):
        import jax

        self.p, self.seed, self.spans = problem, seed, spans
        self.cases = [np.array([c["body"], c["traction"]], np.float64)
                      for c in mix["cases"]]
        self.jitter = float(mix["jitter"])
        w_body, w_trac = (problem.rhs(w) for w in
                          self.nodal_weights(problem.ref.m))

        @jax.jit
        def make_load(bt):
            return (w_body[:, None] * bt[0]
                    + w_trac[:, None] * bt[1]).reshape(-1)

        self._make_load = make_load
        self.results = []

    @staticmethod
    def nodal_weights(m: int):
        """Per free node: body-force weight, top-face traction weight."""
        h = 1.0 / (m - 1)
        node = np.arange(m * m, m ** 3)
        ix, iy, iz = node % m, (node // m) % m, node // (m * m)

        def edge(i):
            return np.where((i == 0) | (i == m - 1), 0.5, 1.0)

        w_trac = np.where(iz == m - 1, h * h * edge(ix) * edge(iy), 0.0)
        return np.full(node.size, h ** 3), w_trac

    def vectors(self, i: int) -> np.ndarray:
        """Solve ``i``'s (body force, traction), a (2, 3) array."""
        noise = host_rng(self.seed, self.LOAD_STREAM, i).standard_normal(
            (2, 3))
        return self.cases[i % len(self.cases)] + self.jitter * noise

    def load(self, i: int):
        return self._make_load(self.p.rhs(self.vectors(i)))

    def warm(self):
        import jax
        with self.spans("warmup"):
            jax.block_until_ready(self.p.solver.solve(self.load(WARM_INDEX)))

    def step(self, i: int):
        import jax
        b = self.load(i)
        with self.spans("solve"):
            res = jax.block_until_ready(self.p.solver.solve(b))
        self.results.append((i, res))

    def answers(self):
        """(label, true relres) of every answer of the window."""
        for i, r in self.results:
            b = np.asarray(self.load(i), np.float64)
            yield (f"solve {i} ({int(r.iters)} iters)",
                   self.p.ref.relres(self.p.E, self.p.nu, b,
                                     np.asarray(r.x, np.float64)))
