"""hot: update -> assemble -> recompute -> solve, with perturbed moduli.

Each step scales every element's Young's modulus by ``1 + E_jitter * u``
(``u`` uniform on [-1, 1], drawn from the seed per step), rebuilds the
operator and hierarchy on the device (``update_coefficients``), and
solves the body-force load from a cold start.  The reference checks each
answer against that step's own moduli, so a wrong device assembly fails.
"""
from __future__ import annotations

import numpy as np

from problem import WARM_INDEX, ProblemLoop, host_rng


class Loop(ProblemLoop):
    unit = "step"
    coefficients = True
    FIELD_STREAM = 1

    def __init__(self, problem, mix: dict, seed: int, spans):
        self.p, self.seed, self.spans = problem, seed, spans
        self.jitter = float(mix["E_jitter"])
        self.b = problem.ref.body_force()
        self._b_dev = problem.rhs(self.b)
        self.results = []

    def field(self, i: int) -> np.ndarray:
        """Step ``i``'s Young's moduli."""
        u = host_rng(self.seed, self.FIELD_STREAM, i).uniform(
            -1.0, 1.0, self.p.ref.n_elements)
        return self.p.E * (1.0 + self.jitter * u)

    def _one(self, i: int):
        import jax
        s = self.p.solver
        E = self.field(i)
        with self.spans("recompute"):
            s.update_coefficients(E, self.p.nu)
            jax.block_until_ready(s.hierarchy)
        with self.spans("solve"):
            return jax.block_until_ready(s.solve(self._b_dev))

    def warm(self):
        with self.spans("warmup"):
            self._one(WARM_INDEX)

    def step(self, i: int):
        self.results.append((i, self._one(i)))

    def answers(self):
        for i, r in self.results:
            yield (f"step {i} ({int(r.iters)} iters)",
                   self.p.ref.relres(self.field(i), self.p.nu, self.b,
                                     np.asarray(r.x, np.float64)))
