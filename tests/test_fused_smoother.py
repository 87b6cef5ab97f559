"""Fused smoother kernel: one Pallas pass per recurrence step.

Covers ISSUE 8's smoother tentpole: kernel-vs-oracle exactness, fused vs
unfused recurrence parity (f64 tight, f32/bf16 at tolerance; vector and
panel RHS), the jaxpr zero-intermediates contract (no full-length
residual/gather arrays in the fused path — same style as the fused
Galerkin test), and the ``REPRO_SMOOTH_PATH`` knob resolution.
"""
import numpy as np
import pytest

import repro.core  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from helpers import jaxpr_outputs, spd_bcsr
from repro.core import gamg
from repro.core.vcycle import apply_smoother
from repro.fem.assemble import assemble_elasticity
from repro.kernels import backend
from repro.kernels.fused_smoother import ops as fs_ops
from repro.kernels.fused_smoother.fused_smoother import smoother_step_ell
from repro.kernels.fused_smoother.ref import smoother_step_ref, \
    smoother_step_seq

RNG = np.random.default_rng(11)


def _tol(dtype):
    return {"float64": 1e-12, "float32": 2e-5, "bfloat16": 5e-2}[
        jnp.dtype(dtype).name]


def _operands(nbr=17, bs=3, k=None, dtype=np.float64):
    A = spd_bcsr(RNG, nbr, bs)
    ell = A.to_ell().astype(dtype)
    dinv = jnp.asarray(
        np.linalg.inv(np.asarray(
            A.to_dense()).reshape(nbr, bs, nbr, bs)[
                np.arange(nbr), :, np.arange(nbr), :])).astype(dtype)
    shape = (nbr * bs,) if k is None else (nbr * bs, k)
    b = jnp.asarray(RNG.standard_normal(shape)).astype(dtype)
    x = jnp.asarray(RNG.standard_normal(shape)).astype(dtype)
    d = jnp.asarray(RNG.standard_normal(shape)).astype(dtype)
    return ell, dinv, b, x, d


@pytest.mark.parametrize("k", [None, 3])
@pytest.mark.parametrize("dtype", [np.float64, np.float32, jnp.bfloat16])
def test_kernel_matches_reference(dtype, k):
    """The tiled kernel vs the pure-jnp oracle, vector and panel RHS, at
    the family tolerance.  f64 must also be bitwise against the
    separately written sequential reference (same per-row reduction
    order)."""
    ell, dinv, b, x, d = _operands(k=k, dtype=dtype)
    nbr, bs = ell.nbr, ell.br
    coef = jnp.asarray([0.3, 0.7], ell.data.dtype)
    vshape = (nbr, bs) if k is None else (nbr, bs, k)
    args = (ell.indices, ell.data, dinv, b.reshape(vshape),
            x.reshape(vshape), d.reshape(vshape), coef)
    acc = jnp.float32 if jnp.dtype(dtype) == jnp.bfloat16 else None
    xr, dr = smoother_step_ref(*args, accum_dtype=acc)
    xs, ds = smoother_step_seq(*args, accum_dtype=acc)
    for tile in (4, 8, 32):
        xk, dk = smoother_step_ell(*args, tile_rows=tile, interpret=True,
                                   accum_dtype=acc)
        if jnp.dtype(dtype) == jnp.float64:
            np.testing.assert_array_equal(np.asarray(xk), np.asarray(xs))
            np.testing.assert_array_equal(np.asarray(dk), np.asarray(ds))
        for got, want in ((xk, xr), (dk, dr)):
            np.testing.assert_allclose(
                np.asarray(got, np.float64), np.asarray(want, np.float64),
                rtol=_tol(dtype), atol=_tol(dtype))


@pytest.mark.parametrize("k", [None, 3])
@pytest.mark.parametrize("smoother", ["chebyshev", "pbjacobi"])
def test_fused_matches_unfused_recurrence(smoother, k):
    """apply_smoother path parity on a real elasticity level.  pbjacobi is
    bitwise (both paths form the residual from scratch); Chebyshev's
    unfused recurrence updates the residual incrementally (r -= A d), so
    f64 agrees to rounding only — 'tight', not bitwise."""
    prob = assemble_elasticity(4)
    sd = gamg.setup(prob.A, prob.B, coarse_size=30)
    lv = gamg.recompute(sd, prob.A.data).levels[0]
    shape = prob.b.shape if k is None else (prob.b.shape[0], k)
    b = jnp.asarray(RNG.standard_normal(shape))
    x0 = jnp.zeros_like(b)
    xu = apply_smoother(lv, b, x0, smoother, 2, path="reference")
    xf = apply_smoother(lv, b, x0, smoother, 2, path="fused")
    assert xf.shape == xu.shape
    if smoother == "pbjacobi":
        np.testing.assert_array_equal(np.asarray(xf), np.asarray(xu))
    else:
        scale = float(jnp.abs(xu).max())
        np.testing.assert_allclose(np.asarray(xf), np.asarray(xu),
                                   rtol=0, atol=1e-13 * max(scale, 1.0))


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fused_low_precision_tolerance(dtype):
    ell, dinv, b, x, d = _operands(dtype=dtype)
    acc = jnp.float32
    x1, d1 = fs_ops.smoother_step(ell, dinv, b, x, d, 0.2, 0.5,
                                  interpret=True, accum_dtype=acc)
    nbr, bs = ell.nbr, ell.br
    xr, dr = smoother_step_ref(ell.indices, ell.data, dinv,
                               b.reshape(nbr, bs), x.reshape(nbr, bs),
                               d.reshape(nbr, bs),
                               jnp.asarray([0.2, 0.5], ell.data.dtype),
                               accum_dtype=acc)
    np.testing.assert_allclose(np.asarray(x1, np.float64),
                               np.asarray(xr.reshape(-1), np.float64),
                               rtol=_tol(dtype), atol=_tol(dtype))


def test_fused_path_has_no_full_length_intermediates():
    """The point of the fusion: the fused jaxpr must contain neither the
    full-length gathered-x array — row-major (nbr, kmax, bs) or lane-dense
    (bs, kmax, nbr) — nor any full-length residual subtraction — the
    kernel only ever touches (tile, ...) slices, so r and z never exist at
    HBM size."""
    ell, dinv, b, x, d = _operands(nbr=32, bs=3)
    nbr, kmax, bs = ell.nbr, ell.kmax, ell.br
    tile = 8
    assert tile < nbr

    fused = lambda bb, xx, dd: fs_ops.smoother_step(  # noqa: E731
        ell, dinv, bb, xx, dd, 0.3, 0.7, interpret=True, tile_rows=tile)
    shapes = jaxpr_outputs(jax.make_jaxpr(fused)(b, x, d).jaxpr)
    full_gathers = {(nbr, kmax, bs), (bs, kmax, nbr)}
    assert not full_gathers & {s for _, s in shapes}, \
        "fused path materialized the full gathered-x array"
    full_subs = [s for p, s in shapes
                 if p == "sub" and s in ((nbr * bs,), (nbr, bs))]
    assert not full_subs, \
        f"fused path materialized a full-length residual: {full_subs}"

    # sensitivity: the unfused recurrence does materialize both
    from repro.core.vcycle import LevelState, chebyshev_smooth
    lv = LevelState(a_ell=ell, p_ell=ell, r_ell=None, dinv=dinv,
                    lam_max=jnp.asarray(2.0), p_t=None)
    unfused = lambda bb, xx: chebyshev_smooth(lv, bb, xx)  # noqa: E731
    ushapes = jaxpr_outputs(jax.make_jaxpr(unfused)(b, x).jaxpr)
    assert full_gathers & {s for _, s in ushapes}, "oracle not sensitive"
    assert any(p == "sub" and s == (nbr * bs,) for p, s in ushapes)


def test_smooth_path_knob_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_SMOOTH_PATH", raising=False)
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert backend.resolve_smooth_path("fused") == "fused"
    assert backend.resolve_smooth_path("reference") == "reference"
    # default follows the accelerator rule
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    assert backend.resolve_smooth_path(None) == "fused"
    monkeypatch.setenv("REPRO_BACKEND", "cpu")
    assert backend.resolve_smooth_path(None) == "reference"
    monkeypatch.setenv("REPRO_SMOOTH_PATH", "fused")
    assert backend.resolve_smooth_path(None) == "fused"
    with pytest.raises(ValueError):
        backend.resolve_smooth_path("fast-ish")
