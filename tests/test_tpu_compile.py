"""TPU compile rehearsals at the paper's per-device size, no chip needed.

The TPU compiler is installed here and compiles for a v5e chip that is
described, not attached.  These tests compile every Pallas kernel of the
main path at m=32 shapes (98,304 unknowns: a 31,744-block-row fine level
with 27 ELL slots, 3x3 / 3x6 / 6x6 blocks, f32 payloads) and one whole
jitted AMG-PCG solve, and assert the compiled programs hold the Mosaic
kernels (``tpu_custom_call``).  They catch what interpret mode cannot:
unaligned or unsupported kernel ops, int64 index maps, and blocks that
overflow VMEM.  Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import, so that only
the worker given this file loads the TPU library.
"""
import re
import types

import pytest

import repro.core  # noqa: F401  (x64 on)
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.block_csr import BlockELL, EllTransposePlan
from repro.core.precision import PrecisionPolicy
from repro.core.vcycle import Hierarchy, LevelState

F32, I32 = jnp.float32, jnp.int32
NBR0 = 31744              # free nodes of the m=32 grid (one face clamped)
KMAX0 = 27
HBM_BYTES = 16 * 10 ** 9  # one v5e chip
N_WIN = 9                 # x windows per 128-row tile (the ELL gather plan)

# (nbr, kmax, bs) of each level operator and (kmax, n_coarse, tkmax) of its
# prolongator, as GAMG builds them at m=32 with ElasticityConfig's settings
LEVELS = ((NBR0, KMAX0, 3, 8, 1331, 146),
          (1331, 45, 6, 45, 836, 45),
          (836, 490, 6, 19, 19, 811))
N_COARSE = 114


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the persistent
    # cache, so keep it out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """Trace with the TPU's default dispatch (compiled kernels)."""
    for knob in ("REPRO_SMOOTH_PATH", "REPRO_SPMM_PATH", "REPRO_SPGEMM_PATH",
                 "REPRO_PRECISION", "REPRO_OBS"):
        monkeypatch.delenv(knob, raising=False)
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    monkeypatch.setenv("REPRO_TUNE", "off")


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _windows(nbr):
    return (-(-nbr // 128), N_WIN), I32


def _spmv(nbr, kmax, br, bc, nbc):
    from repro.kernels.block_spmv.block_spmv import block_spmv_ell
    fn = lambda i, d, x, w: block_spmv_ell(i, d, x,  # noqa: E731
                                           interpret=False, windows=w)
    return fn, ((nbr, kmax), I32), ((nbr, kmax, br, bc), F32), \
        ((nbc, bc), F32), _windows(nbr)


def _spmm(nbr, kmax, bs, k):
    from repro.kernels.block_spmm.block_spmm import block_spmm_ell
    fn = lambda i, d, x, w: block_spmm_ell(i, d, x,  # noqa: E731
                                           interpret=False, windows=w)
    return fn, ((nbr, kmax), I32), ((nbr, kmax, bs, bs), F32), \
        ((nbr, bs, k), F32), _windows(nbr)


def _pbjacobi(nbr, bs):
    from repro.kernels.pbjacobi.pbjacobi import pbjacobi_update
    fn = lambda d, r, x: pbjacobi_update(d, r, x, 0.6,  # noqa: E731
                                        interpret=False)
    return fn, ((nbr, bs, bs), F32), ((nbr, bs), F32), ((nbr, bs), F32)


def _smoother(nbr, kmax, bs, k):
    from repro.kernels.fused_smoother.fused_smoother import smoother_step_ell
    v = (nbr, bs) if k is None else (nbr, bs, k)

    def fn(i, d, dinv, b, x, dd, w):
        return smoother_step_ell(i, d, dinv, b, x, dd,
                                 jnp.asarray([0.3, 0.7], F32),
                                 interpret=False, windows=w)
    return fn, ((nbr, kmax), I32), ((nbr, kmax, bs, bs), F32), \
        ((nbr, bs, bs), F32), (v, F32), (v, F32), (v, F32), _windows(nbr)


def _pair_gemm(nslots, kmax, br, bk, bc):
    from repro.kernels.fused_pair_gemm.fused_pair_gemm import fused_pair_gemm
    fn = lambda a, b: fused_pair_gemm(a, b, interpret=False)  # noqa: E731
    return fn, ((nslots, kmax, br, bk), F32), ((nslots, kmax, bk, bc), F32)


KERNELS = {
    "block_spmv-A0-3x3": lambda: _spmv(NBR0, KMAX0, 3, 3, NBR0),
    "block_spmv-P0-3x6": lambda: _spmv(NBR0, 8, 3, 6, 1331),
    "block_spmv-A1-6x6": lambda: _spmv(1331, 45, 6, 6, 1331),
    "block_spmv-A2-6x6": lambda: _spmv(836, 490, 6, 6, 836),
    "block_spmv-P1-6x6": lambda: _spmv(1331, 45, 6, 6, 836),
    "block_spmv-P2-6x6": lambda: _spmv(836, 19, 6, 6, 19),
    "block_spmm-A0-3x3-k8": lambda: _spmm(NBR0, KMAX0, 3, 8),
    "block_spmm-A1-6x6-k8": lambda: _spmm(1331, 45, 6, 8),
    "block_spmm-A2-6x6-k16": lambda: _spmm(836, 490, 6, 16),
    "pbjacobi-3x3": lambda: _pbjacobi(NBR0, 3),
    "pbjacobi-6x6": lambda: _pbjacobi(1331, 6),
    "fused_smoother-A0-3x3": lambda: _smoother(NBR0, KMAX0, 3, None),
    "fused_smoother-A0-3x3-k8": lambda: _smoother(NBR0, KMAX0, 3, 8),
    "fused_smoother-A2-6x6": lambda: _smoother(836, 490, 6, None),
    "fused_pair_gemm-AP-3x3x6": lambda: _pair_gemm(95232, 8, 3, 3, 6),
    "fused_pair_gemm-RAP-6x3x6": lambda: _pair_gemm(47017, 64, 6, 3, 6),
    "fused_pair_gemm-6x6x6": lambda: _pair_gemm(234796, 16, 6, 6, 6),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(chip, name):
    fn, *shapes = KERNELS[name]()
    compiled = _compile(chip, fn, *shapes)
    assert _kernel_calls(compiled) >= 1, name


def _ell(nbr, kmax, br, bc, nbc, dtype, chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    return BlockELL(indices=s((nbr, kmax), I32),
                    data=s((nbr, kmax, br, bc), dtype),
                    mask=s((nbr, kmax), jnp.bool_), nbc=nbc,
                    windows=s(*_windows(nbr)))


def _m32_hierarchy(chip) -> Hierarchy:
    """Shape-only f32 hierarchy of the m=32 problem (f64 fine operator
    for the outer CG, as the ``f32`` policy keeps it)."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=chip)
    levels = []
    for i, (nbr, kmax, bs, pk, nc, tk) in enumerate(LEVELS):
        bsc = LEVELS[i + 1][2] if i + 1 < len(LEVELS) else 6
        levels.append(LevelState(
            a_ell=_ell(nbr, kmax, bs, bs, nbr, F32, chip),
            p_ell=_ell(nbr, pk, bs, bsc, nc, F32, chip),
            r_ell=None,
            dinv=s((nbr, bs, bs), F32),
            lam_max=s((), F32),
            p_t=EllTransposePlan(rows=s((nc, tk), I32),
                                 gather=s((nc, tk), I32),
                                 mask=s((nc, tk), jnp.bool_), nbr=nbr)))
    return Hierarchy(levels=tuple(levels),
                     coarse_chol=s((N_COARSE, N_COARSE), F32),
                     a_fine_ell=_ell(NBR0, KMAX0, 3, 3, NBR0, jnp.float64,
                                     chip))


def _kernel_scopes(compiled) -> list:
    """``op_name`` of every compiled Pallas call in the program text."""
    return re.findall(r'custom_call_target="tpu_custom_call".*?'
                      r'op_name="([^"]*)"', compiled.as_text())


def test_whole_solve_compiles_for_v5e(chip, tpu_dispatch):
    """One jitted AMG-PCG solve at m=32 under the f32 policy: the compiled
    program holds the fused smoother kernels, runs every level's residual
    and prolongation on the ``block_spmv`` kernel, and fits one chip's
    HBM."""
    from repro.core import gamg
    setupd = types.SimpleNamespace(
        smoother="chebyshev", degree=2,
        precision=PrecisionPolicy.from_name("f32"))
    solve = gamg.make_solve(setupd, rtol=1e-8, maxiter=200)
    b = jax.ShapeDtypeStruct((NBR0 * 3,), jnp.float64, sharding=chip)
    compiled = solve.lower(_m32_hierarchy(chip), b).compile()
    # Chebyshev degree 2: two fused steps per smoothing, pre and post,
    # on each of the three levels
    assert _kernel_calls(compiled) >= 2 * len(LEVELS)
    scopes = _kernel_scopes(compiled)
    for li in range(len(LEVELS)):
        for stage in ("residual", "prolong"):
            site = f"vcycle/level{li}/{stage}/kernels/block_spmv/"
            assert any(site in name for name in scopes), (site, scopes)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
