"""The plain reference against the program's host assembly, and the
check it makes rejecting a wrong operator."""
import numpy as np
import pytest

from reference import Q1Elasticity

INCLUSION = dict(E_matrix=1.0, E_inclusion=1000.0, nu_matrix=0.3,
                 nu_inclusion=0.2, center=(0.7, 0.7, 0.7), radius=0.3)


def program_operator(m, E, nu):
    """The program's host-assembled operator as a dense f64 matrix."""
    from repro.fem.assemble import assemble_elasticity
    prob = assemble_elasticity(m, E=E, nu=nu, path="host")
    A = prob.A
    ip, ix = np.asarray(A.indptr), np.asarray(A.indices)
    data = np.asarray(A.data, np.float64)
    dense = np.zeros((prob.n, prob.n))
    rows = np.repeat(np.arange(len(ip) - 1), np.diff(ip))
    for r, c, blk in zip(rows, ix, data):
        dense[3 * r:3 * r + 3, 3 * c:3 * c + 3] += blk
    return dense, np.asarray(prob.b, np.float64)


@pytest.mark.parametrize("m", [3, 5])
@pytest.mark.parametrize("material", ["uniform", "inclusion"])
def test_reference_matches_program_assembly(m, material):
    ref = Q1Elasticity(m)
    if material == "uniform":
        E, nu = 1.0, 0.3
    else:
        E, nu = ref.inclusion(**INCLUSION)
    A, b = program_operator(m, E, nu)
    x = np.random.default_rng(m).standard_normal(ref.n_free)
    y = ref.apply(E, nu, x)
    assert np.abs(y - A @ x).max() <= 1e-12 * np.abs(y).max()
    np.testing.assert_array_equal(ref.body_force(), b)


def test_inclusion_fields_match_program():
    from repro.fem.assemble import assemble_elasticity, inclusion_fields
    ref = Q1Elasticity(6)
    mesh = assemble_elasticity(6, path="host").mesh
    E, nu = inclusion_fields(mesh, E_inclusion=1000.0)
    E2, nu2 = ref.inclusion(**INCLUSION)
    np.testing.assert_array_equal(E, E2)
    np.testing.assert_array_equal(nu, nu2)


def test_check_accepts_exact_and_rejects_wrong_operator():
    """An exact solve reads at rounding; a solve of a slightly wrong
    operator (one element's modulus 1% off, or nu 0.31) reads far above
    the configuration's limit."""
    m, limit = 5, 1.1e-8
    ref = Q1Elasticity(m)
    E, nu = ref.inclusion(**INCLUSION)
    b = ref.body_force() + np.random.default_rng(0).standard_normal(
        ref.n_free)
    A, _ = program_operator(m, E, nu)
    assert ref.relres(E, nu, b, np.linalg.solve(A, b)) < 1e-12
    E_off = E.copy()
    E_off[7] *= 1.01
    for E_w, nu_w in ((E_off, nu), (E, np.full_like(E, 0.31))):
        Aw, _ = program_operator(m, E_w, nu_w)
        assert ref.relres(E, nu, b, np.linalg.solve(Aw, b)) > 100 * limit
