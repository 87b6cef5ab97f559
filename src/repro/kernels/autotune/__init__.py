"""Kernel tile autotuner: per-signature sweeps with an on-disk cache.

Every Pallas family in this package exposes tile parameters (row-tile
widths, pair-GEMM slot tiles, panel padding) that trade VMEM residency
against grid overhead.  The seed hardcoded one value per family; the right
value depends on the block shape, the ELL width, the dtype and the
machine.  This module closes that loop:

* each kernel front door accepts ``None`` for its tile knobs and calls
  ``resolve_param(family, signature, name, requested, default)``;
* the resolution mode comes from ``repro.kernels.backend.resolve_tune``
  (``REPRO_TUNE``): "off" -> always the static default (bitwise the
  pre-tune behaviour), "cache" (default) -> a cached winner when one
  exists, "sweep" -> measure on miss and record the winner;
* sweeps time each candidate on synthetic operands of the signature's
  shape through ``repro.obs.metrics.MetricsRegistry.measure`` — the
  compile/steady split the benchmarks use — and keep the best *steady*
  time (min over repeats);
* winners persist as JSON keyed by ``machine|backend`` then
  ``family|signature``, at ``REPRO_TUNE_CACHE`` or
  ``~/.cache/repro/autotune.json``.

CLI: ``python -m repro.kernels.autotune smoke|sweep|show`` (the nightly
workflow runs ``smoke``: one tiny interpret-mode sweep, cache written,
memo cleared, reloaded, winner asserted).
"""
from __future__ import annotations

import json
import os
import platform
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.kernels.backend import backend, resolve_interpret, resolve_tune

# candidate grids per family, keyed by the tile-parameter name: lanes
# (rows or slots) per grid step, multiples of the 128-lane tile; without
# a cached winner the front doors size the tile from the VMEM budget.  The
# ELL kernels gather x per 128-row tile, so their row tile is fixed.
CANDIDATES = {
    "block_spmv": {"tile_rows": (128,)},
    "block_spmm": {"tile_rows": (128,), "pad_k_to": (1, 4, 8)},
    "pbjacobi": {"tile_rows": (128, 256, 512, 1024, 2048)},
    "fused_smoother": {"tile_rows": (128,)},
    "fused_pair_gemm": {"tile_slots": (128, 256, 512, 1024)},
}

_memo: dict = {}


def cache_path() -> Path:
    """Cache file: ``REPRO_TUNE_CACHE`` or ``~/.cache/repro/autotune.json``.

    Re-read per call so tests can point the cache at a tmpdir.
    """
    p = os.environ.get("REPRO_TUNE_CACHE")
    if p:
        return Path(p)
    return Path.home() / ".cache" / "repro" / "autotune.json"


def machine_key() -> str:
    """Winners are per host *and* backend — an interpret-mode CPU sweep
    must never steer a TPU run."""
    return f"{platform.node()}|{backend()}"


def entry_key(family: str, signature: dict) -> str:
    """Stable text key: ``family|k=v,...`` with sorted signature items."""
    items = ",".join(f"{k}={signature[k]}" for k in sorted(signature))
    return f"{family}|{items}"


def clear_memo() -> None:
    """Drop the in-process cache memo (tests; the CLI smoke round-trip)."""
    _memo.clear()


def load_cache(path: Path | None = None) -> dict:
    """Parsed cache contents ({} when absent/corrupt), memoized on mtime."""
    path = path or cache_path()
    try:
        mtime = path.stat().st_mtime_ns
    except OSError:
        return {}
    key = (str(path), mtime)
    if key not in _memo:
        try:
            _memo.clear()           # one live file at a time
            _memo[key] = json.loads(path.read_text())
        except (OSError, ValueError):
            return {}
    return _memo[key]


def lookup(family: str, signature: dict, name: str):
    """Cached winner for one tile parameter, or None."""
    entry = load_cache().get(machine_key(), {}).get(
        entry_key(family, signature))
    if entry is None:
        return None
    return entry.get("params", {}).get(name)


def record(family: str, signature: dict, params: dict,
           best_us: float | None = None) -> Path:
    """Merge one signature's winning params into the cache (atomic write)."""
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    cache = dict(load_cache(path))
    mk = cache.setdefault(machine_key(), {})
    mk[entry_key(family, signature)] = {
        "params": dict(params),
        "best_us": best_us,
        "recorded": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    clear_memo()
    return path


def resolve_param(family: str, signature: dict, name: str, requested,
                  default):
    """One tile knob through the mode ladder.

    requested != None  -> the caller pinned it; use verbatim.
    mode "off"         -> the static default (bitwise pre-tune).
    mode "cache"       -> cached winner if present, else the default.
    mode "sweep"       -> cached winner if present, else sweep this
                          signature now, record, and use the winner.
    """
    if requested is not None:
        return requested
    mode = resolve_tune(None)
    if mode == "off":
        return default
    hit = lookup(family, signature, name)
    if hit is not None:
        return hit
    if mode == "sweep":
        won = sweep(family, signature)
        return won["params"].get(name, default)
    return default


# ---------------------------------------------------------------------------
# Sweeping
# ---------------------------------------------------------------------------

def _synthetic(family: str, signature: dict, nbr: int):
    """Deterministic operands of the signature's shape (rng seed 0)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    dt = np.dtype(signature["dtype"])
    if family == "fused_pair_gemm":
        br, bk, bc, kmax = (signature[k] for k in ("br", "bk", "bc", "kmax"))
        lhs = rng.standard_normal((nbr, kmax, br, bk)).astype(dt)
        rhs = rng.standard_normal((nbr, kmax, bk, bc)).astype(dt)
        return jnp.asarray(lhs), jnp.asarray(rhs)
    br, bc, kmax = signature["br"], signature["bc"], signature["kmax"]
    nbc = nbr                      # square-ish synthetic operator
    indices = jnp.asarray(
        rng.integers(0, nbc, size=(nbr, kmax)).astype(np.int32))
    data = jnp.asarray(rng.standard_normal((nbr, kmax, br, bc)).astype(dt))
    return indices, data, nbc


def _make_runner(family: str, signature: dict, params: dict,
                 interpret: bool, nbr: int):
    """Closure running one kernel call of the signature's shape."""
    import jax.numpy as jnp
    from repro.core.block_csr import BlockELL
    rng = np.random.default_rng(1)
    dt = np.dtype(signature["dtype"])
    if family == "fused_pair_gemm":
        lhs, rhs = _synthetic(family, signature, nbr)
        from repro.kernels.fused_pair_gemm import ops as _f
        return lambda: _f.fused_pair_gemm(lhs, rhs, interpret=interpret,
                                          **params)
    if family == "pbjacobi":
        bs = signature["bs"]
        dinv = jnp.asarray(
            rng.standard_normal((nbr, bs, bs)).astype(dt))
        r = jnp.asarray(rng.standard_normal(nbr * bs).astype(dt))
        x = jnp.asarray(rng.standard_normal(nbr * bs).astype(dt))
        from repro.kernels.pbjacobi import ops as _p
        return lambda: _p.pbjacobi_apply(dinv, r, x, 0.6,
                                         interpret=interpret, **params)
    indices, data, nbc = _synthetic(family, signature, nbr)
    br, bc = signature["br"], signature["bc"]
    mask = jnp.ones((nbr, signature["kmax"]), dtype=bool)
    ell = BlockELL(indices=indices, data=data, mask=mask, nbc=nbc)
    if family == "block_spmv":
        x = jnp.asarray(rng.standard_normal(nbc * bc).astype(dt))
        from repro.kernels.block_spmv import ops as _s
        return lambda: _s.block_spmv(ell, x, interpret=interpret, **params)
    if family == "block_spmm":
        X = jnp.asarray(
            rng.standard_normal((nbc * bc, signature["k"])).astype(dt))
        from repro.kernels.block_spmm import ops as _m
        return lambda: _m.block_spmm(ell, X, interpret=interpret, **params)
    if family == "fused_smoother":
        dinv = jnp.asarray(rng.standard_normal((nbr, br, br)).astype(dt))
        b = jnp.asarray(rng.standard_normal(nbr * br).astype(dt))
        x = jnp.asarray(rng.standard_normal(nbr * br).astype(dt))
        d = jnp.zeros_like(b)
        from repro.kernels.fused_smoother import ops as _fs
        return lambda: _fs.smoother_step(ell, dinv, b, x, d, 0.0, 0.5,
                                         interpret=interpret, **params)
    raise ValueError(f"unknown autotune family {family!r}")


def _param_grid(family: str):
    """Cartesian candidate grid as a list of param dicts."""
    import itertools
    cands = CANDIDATES[family]
    names = sorted(cands)
    return [dict(zip(names, vals))
            for vals in itertools.product(*(cands[n] for n in names))]


def sweep(family: str, signature: dict, *, nbr: int = 256, repeats: int = 3,
          interpret: bool | None = None, record_winner: bool = True) -> dict:
    """Time every candidate tiling for one signature; record the winner.

    Each candidate is measured through ``MetricsRegistry.measure`` — the
    first call files under ``.../compile``, the following ``repeats``
    under ``.../steady`` — and scored by its *min* steady seconds.
    Returns ``{"params", "best_us", "table"}`` (``table`` maps the
    candidate key to its best microseconds, for reporting).
    """
    from repro.obs.metrics import MetricsRegistry
    interpret = resolve_interpret(interpret)
    reg = MetricsRegistry()
    best = None
    table = {}
    for params in _param_grid(family):
        fn = _make_runner(family, signature, params, interpret, nbr)
        name = f"tune/{family}/" + ",".join(
            f"{k}={v}" for k, v in sorted(params.items()))
        for _ in range(repeats + 1):
            reg.measure(name, fn)
        us = reg.get(name + "/steady").snapshot()["min"] * 1e6
        table[",".join(f"{k}={v}" for k, v in sorted(params.items()))] = us
        if best is None or us < best[1]:
            best = (params, us)
    won = {"params": best[0], "best_us": best[1], "table": table}
    if record_winner:
        record(family, signature, best[0], best_us=best[1])
    return won
