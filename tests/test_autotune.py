"""Kernel tile autotuner: cache round-trips, knob resolution, bitwise off.

Covers ISSUE 8's autotuner satellites: the on-disk winner cache
round-trips through ``record``/``clear_memo``/``lookup``, ``REPRO_TUNE``
resolves per the mode ladder, a tiny sweep records a winner that
subsequent resolution uses, and ``REPRO_TUNE=off`` is bitwise the
pre-tune path.
"""
import numpy as np
import pytest

import repro.core  # noqa: F401  (enables x64)
import jax.numpy as jnp

from helpers import random_bcsr
from repro.kernels import autotune, backend
from repro.kernels.block_spmv import ops as spmv_ops

RNG = np.random.default_rng(3)
SIG = {"br": 3, "bc": 3, "kmax": 4, "dtype": "float64"}


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    autotune.clear_memo()
    yield path
    autotune.clear_memo()


def test_cache_round_trip(tmp_cache):
    assert autotune.lookup("block_spmv", SIG, "tile_rows") is None
    p = autotune.record("block_spmv", SIG, {"tile_rows": 32}, best_us=12.5)
    assert p == tmp_cache and tmp_cache.exists()
    autotune.clear_memo()
    assert autotune.lookup("block_spmv", SIG, "tile_rows") == 32
    # merging a second signature keeps the first
    sig2 = dict(SIG, br=6, bc=6)
    autotune.record("block_spmv", sig2, {"tile_rows": 16})
    assert autotune.lookup("block_spmv", SIG, "tile_rows") == 32
    assert autotune.lookup("block_spmv", sig2, "tile_rows") == 16
    # winners are keyed per machine|backend — a different key misses
    assert autotune.machine_key() in autotune.load_cache()


def test_resolve_tune_modes(monkeypatch):
    monkeypatch.delenv("REPRO_TUNE", raising=False)
    assert backend.resolve_tune(None) == "cache"
    for val, want in (("off", "off"), ("0", "off"), ("cache", "cache"),
                      ("on", "cache"), ("sweep", "sweep")):
        monkeypatch.setenv("REPRO_TUNE", val)
        assert backend.resolve_tune(None) == want
    with pytest.raises(ValueError):
        backend.resolve_tune("fastest")


def test_resolve_param_mode_ladder(tmp_cache, monkeypatch):
    # explicit request always wins
    monkeypatch.setenv("REPRO_TUNE", "sweep")
    assert autotune.resolve_param("block_spmv", SIG, "tile_rows", 16, 8) \
        == 16
    # off -> static default even with a cached winner present
    autotune.record("block_spmv", SIG, {"tile_rows": 64})
    monkeypatch.setenv("REPRO_TUNE", "off")
    assert autotune.resolve_param("block_spmv", SIG, "tile_rows", None, 8) \
        == 8
    # cache -> the winner
    monkeypatch.setenv("REPRO_TUNE", "cache")
    assert autotune.resolve_param("block_spmv", SIG, "tile_rows", None, 8) \
        == 64
    # cache miss -> default (never sweeps)
    miss = dict(SIG, kmax=9)
    assert autotune.resolve_param("block_spmv", miss, "tile_rows", None, 8) \
        == 8
    assert autotune.lookup("block_spmv", miss, "tile_rows") is None


def test_tiny_sweep_records_winner_used_by_resolution(tmp_cache,
                                                      monkeypatch):
    won = autotune.sweep("block_spmv", SIG, nbr=16, repeats=1,
                         interpret=True)
    assert won["params"]["tile_rows"] in \
        autotune.CANDIDATES["block_spmv"]["tile_rows"]
    assert won["best_us"] > 0 and len(won["table"]) == len(
        autotune.CANDIDATES["block_spmv"]["tile_rows"])
    autotune.clear_memo()
    monkeypatch.setenv("REPRO_TUNE", "sweep")
    # the recorded winner satisfies sweep-mode resolution without
    # re-measuring (the cache hit short-circuits)
    assert autotune.resolve_param("block_spmv", SIG, "tile_rows", None, 8) \
        == won["params"]["tile_rows"]


def test_tune_off_is_bitwise_the_pretune_path(tmp_cache, monkeypatch):
    """REPRO_TUNE=off must reproduce the seed's hardcoded tiling exactly:
    resolving through the ladder with a (different) cached winner present
    changes nothing when the mode is off."""
    A = random_bcsr(RNG, 24, 24, 3, 3, density=0.3)
    ell = A.to_ell()
    x = jnp.asarray(RNG.standard_normal(A.shape[1]))
    pinned = spmv_ops.block_spmv(ell, x, interpret=True, tile_rows=8)
    autotune.record("block_spmv",
                    dict(br=3, bc=3, kmax=ell.kmax, dtype="float64"),
                    {"tile_rows": 16})
    monkeypatch.setenv("REPRO_TUNE", "off")
    off = spmv_ops.block_spmv(ell, x, interpret=True)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(pinned))
    # and the cached winner does engage in cache mode (same values —
    # tiling only repartitions the grid — but resolution must pick it up)
    monkeypatch.setenv("REPRO_TUNE", "cache")
    assert autotune.resolve_param(
        "block_spmv", dict(br=3, bc=3, kmax=ell.kmax, dtype="float64"),
        "tile_rows", None, 8) == 16
    cached = spmv_ops.block_spmv(ell, x, interpret=True)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(pinned),
                               rtol=1e-12, atol=1e-14)
