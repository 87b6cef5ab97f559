"""Jitted programs over plan objects, with the plans' arrays as arguments.

The cold setup builds host objects — SpGEMM/ELL/COO plans, prolongators,
whole ``GAMGSetup``s — and the hot programs close over them.  A jitted
closure bakes every array it reads into the executable as a constant: the
m=32 recompute carried hundreds of megabytes of them, XLA constant-folded
gathers of one constant by another at compile time, and the executable
outgrew the persistent compilation cache.  ``Program`` jits ``fn(obj,
*args)`` with the large arrays of ``obj`` lifted out and passed as
arguments instead (uploaded to the device once); the structure — ints,
flags, static pytree aux data and small arrays — stays in the trace.

Objects are walked through dataclass fields, lists, tuples and dicts;
registered pytrees (``BlockCSR``, ``BlockELL``, ...) are flattened with
``jax.tree_util`` and only their leaves are lifted, so their static aux
data (e.g. a ``BlockCSR``'s host structure) keeps its trace-time use.
"""
from __future__ import annotations

import copy
import dataclasses

import jax
import numpy as np

# arrays with fewer elements stay in the trace as constants
MIN_ELEMENTS = 1 << 12


class _Slot:
    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i


class _Tree:
    __slots__ = ("treedef", "leaves")

    def __init__(self, treedef, leaves):
        self.treedef, self.leaves = treedef, leaves


def _split(obj, out: list, memo: dict):
    key = id(obj)
    if key in memo:
        return memo[key]
    if isinstance(obj, (np.ndarray, jax.Array)):
        if obj.size < MIN_ELEMENTS:
            return obj
        out.append(obj)
        res = _Slot(len(out) - 1)
    elif isinstance(obj, list):
        res = [_split(o, out, memo) for o in obj]
    elif isinstance(obj, tuple) and not hasattr(obj, "_fields"):
        res = tuple(_split(o, out, memo) for o in obj)
    elif isinstance(obj, dict):
        res = {k: _split(v, out, memo) for k, v in obj.items()}
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        leaves, treedef = jax.tree_util.tree_flatten(obj)
        if not (len(leaves) == 1 and leaves[0] is obj):
            res = _Tree(treedef, [_split(v, out, memo) for v in leaves])
        else:
            res = copy.copy(obj)
            for f in dataclasses.fields(obj):
                object.__setattr__(res, f.name,
                                   _split(getattr(obj, f.name), out, memo))
    else:
        return obj
    memo[key] = res
    return res


def _plant(skel, arrays, memo: dict):
    key = id(skel)
    if key in memo:
        return memo[key]
    if isinstance(skel, _Slot):
        return arrays[skel.i]
    if isinstance(skel, list):
        res = [_plant(o, arrays, memo) for o in skel]
    elif isinstance(skel, tuple) and not hasattr(skel, "_fields"):
        res = tuple(_plant(o, arrays, memo) for o in skel)
    elif isinstance(skel, dict):
        res = {k: _plant(v, arrays, memo) for k, v in skel.items()}
    elif isinstance(skel, _Tree):
        res = jax.tree_util.tree_unflatten(
            skel.treedef, [_plant(v, arrays, memo) for v in skel.leaves])
    elif dataclasses.is_dataclass(skel) and not isinstance(skel, type):
        res = copy.copy(skel)
        for f in dataclasses.fields(skel):
            object.__setattr__(res, f.name,
                               _plant(getattr(skel, f.name), arrays, memo))
    else:
        return skel
    memo[key] = res
    return res


class Program:
    """``fn(obj, *args)`` jitted once, ``obj``'s large arrays as arguments.

    Calling it runs ``fn(obj, *args)``; ``lower`` and ``_cache_size``
    mirror the jitted function's.  Keyword arguments go to ``jax.jit``.
    """

    def __init__(self, fn, obj, **jit_kw):
        arrays: list = []
        skel = _split(obj, arrays, {})
        self._arrays = jax.device_put(arrays)

        def run(arrays, *args):
            return fn(_plant(skel, arrays, {}), *args)

        self._jit = jax.jit(run, **jit_kw)

    def __call__(self, *args):
        return self._jit(self._arrays, *args)

    def lower(self, *args):
        return self._jit.lower(self._arrays, *args)

    def _cache_size(self) -> int:
        return self._jit._cache_size()


def call(fn, obj, *args):
    """One-shot ``Program(fn, obj)(*args)`` — one compile per call site
    and shape (the cold setup's per-level numeric phases)."""
    return Program(fn, obj)(*args)
