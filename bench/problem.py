"""The configured problem a loop drives, and the seeded draws it uses.

A configuration file (``bench/configs/<config>.json``) gives the mesh, the
material (a kind found by name in ``bench/materials/<kind>.py``) and the
solver settings.  ``Problem`` assembles the operator, sets the GAMG
hierarchy up and holds the plain reference beside it.  Loops read the
program only through ``Problem``: the harness itself never touches it.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

from reference import Q1Elasticity

BENCH = Path(__file__).resolve().parent
# draws for warm-up calls come from this index, never reached in a window
WARM_INDEX = 2 ** 31


def seed_words(seed: int):
    """A seed of any size as two 32-bit words (low, high)."""
    seed = int(seed) % 2 ** 64
    return seed & 0xFFFFFFFF, seed >> 32


def host_rng(seed: int, stream: int, i: int) -> np.random.Generator:
    """Generator for draw ``i`` of one stream of a run's seed."""
    return np.random.default_rng([*seed_words(seed), stream, i])


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, found by name."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def policy(precision):
    """The program's precision policy from a stock name or four dtypes
    (``hierarchy``, ``smoother``, ``krylov``, ``accum``)."""
    from repro.core.precision import PrecisionPolicy
    if isinstance(precision, str):
        return PrecisionPolicy.from_name(precision)
    return PrecisionPolicy(**{f"{k}_dtype": v for k, v in precision.items()})


def levels_of(setupd) -> list:
    """roofline.Level per level of the program's setup."""
    from roofline import Level, Product
    out = []
    for ls in setupd.levels:
        A, P = ls.A0, ls.P
        ap, ac = ls.ptap_cache.ap_plan, ls.ptap_cache.ac_plan
        prods = (Product(A.nnzb, (A.br, A.bc), P.nnzb, (P.br, P.bc),
                         ap.nnzb, (ap.br, ap.bc), ap.tile_rows),
                 Product(P.nnzb, (P.bc, P.br), ap.nnzb, (ap.br, ap.bc),
                         ac.nnzb, (ac.br, ac.bc), ac.tile_rows))
        out.append(Level(A.nbr, A.br, A.nnzb, prods))
    c = setupd.coarse_struct
    out.append(Level(c.nbr, c.br, c.nnzb))
    return out


class Problem:
    """The configured system: reference, program problem and solver."""

    def __init__(self, cfg: dict, spans, *, coefficients: bool):
        import jax
        from repro.core import gamg
        from repro.fem.assemble import assemble_elasticity

        self.cfg = cfg
        self.ref = Q1Elasticity(cfg["m"])
        self.E, self.nu = self.fields(cfg["material"])
        # the material the hierarchy is set up on, where it differs from
        # the one the window solves
        E0, nu0 = self.fields(cfg.get("setup_material", cfg["material"]))
        s = cfg["solver"]
        self.policy = policy(s["precision"])
        with spans("assembly"):
            self.prob = assemble_elasticity(cfg["m"], order=cfg["order"],
                                            E=E0, nu=nu0, path="device")
            jax.block_until_ready(self.prob.A.data)
        with spans("gamg_setup"):
            self.solver = gamg.GAMGSolver(
                self.prob.A, self.prob.B, theta=s["theta"],
                smoother=s["smoother"], degree=s["degree"],
                coarse_size=s["coarse_size"], coarsener=s["coarsener"],
                rtol=s["rtol"], maxiter=s["maxiter"],
                precision=self.policy)
            if coefficients:
                self.solver.bind_assembler(self.prob.assembler)
            jax.block_until_ready(self.solver.hierarchy)
        self.limit = float(cfg["true_relres_limit"])

    def fields(self, mat: dict):
        """(E, nu): scalars or per-element arrays, by the material's kind."""
        return load_module("materials", mat["kind"]).fields(self.ref, mat)

    def rhs(self, b):
        """A load as the outer Krylov iteration takes it."""
        import jax.numpy as jnp
        return jnp.asarray(b, self.policy.krylov_dtype)

    @property
    def itemsize(self) -> int:
        """Bytes per stored hierarchy value."""
        return self.policy.hierarchy_dtype.itemsize

    def levels(self) -> list:
        return levels_of(self.solver.setup_data)


class ProblemLoop:
    """What every loop on a ``Problem`` shares: how it is built, and what
    the harness reads of it (its limit, levels and counters)."""

    coefficients = False      # the window calls update_coefficients

    @classmethod
    def build(cls, cfg: dict, spans) -> Problem:
        return Problem(cfg, spans, coefficients=cls.coefficients)

    @property
    def limit(self) -> float:
        return self.p.limit

    @property
    def itemsize(self) -> int:
        return self.p.itemsize

    def levels(self) -> list:
        return self.p.levels()

    def counters(self) -> dict:
        return {"cg_iters": [int(r.iters) for _, r in self.results]}
