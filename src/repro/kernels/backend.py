"""Backend detection and kernel-dispatch defaults.

The seed hardcoded ``interpret=True`` on every Pallas entry point, so a run
on a real TPU would silently execute the kernels through the (slow, jax-level)
interpreter.  This module centralizes the decision:

* ``backend()``          — the active JAX platform ("tpu", "gpu", "cpu"),
                           overridable with ``REPRO_BACKEND`` for testing.
* ``resolve_interpret``  — ``None`` means "interpret only when no accelerator
                           can compile the kernel" (i.e. CPU).
* ``kernel_interpret``   — the same for a kernel front door, and refuses a
                           compiled call with an f64 payload (Mosaic has
                           no 64-bit floats) with a ``ValueError`` naming
                           the ``f32`` policy.
* ``resolve_use_kernel`` — ``None`` means "use the Pallas kernels exactly when
                           they compile natively": on TPU, for an f32/bf16
                           payload (``kernels_by_default``).  The path
                           resolvers below follow the same rule, so the
                           ``f64`` policy runs on XLA paths on a TPU.
* ``resolve_spgemm_path``— default numeric SpGEMM path: the fused tiled
                           kernel on TPU, the einsum+segment_sum reference
                           on CPU and GPU (interpret-mode Pallas is strictly
                           slower on CPU; Triton rejects these block tiles
                           on GPU).  ``REPRO_SPGEMM_PATH`` forces a path
                           globally ("fused" | "pairs" | "reference").
* ``resolve_spmm_path``  — multi-RHS block SpMM path: the Pallas panel
                           kernel on TPU, the jnp reference elsewhere;
                           forced globally with ``REPRO_SPMM_PATH``
                           ("kernel" | "reference").
* ``resolve_precision``  — the solver-stack ``PrecisionPolicy``:
                           ``None`` falls back to ``REPRO_PRECISION``
                           ("f64" | "f32" | "bf16"), default full fp64.
* ``resolve_faults``     — the fault-injection ``FaultSchedule``:
                           ``None`` falls back to ``REPRO_FAULTS``
                           (semicolon-separated
                           ``site:kind[@step][:level=N][:index=N]
                           [:persistent]`` specs), default no injection.
* ``resolve_recover``    — the breakdown-recovery ``RecoveryPolicy``:
                           ``None`` falls back to ``REPRO_RECOVER``
                           ("off" | "on" | max-attempts integer),
                           default off (``None``).
* ``resolve_obs``        — the observability mode (``repro.obs``):
                           ``None`` falls back to ``REPRO_OBS``
                           ("off" | "counters"), default off — the
                           zero-jaxpr-residue contract.
* ``resolve_smooth_path``— V-cycle smoother execution path: the fused
                           Pallas recurrence step (``repro.kernels.
                           fused_smoother``) on TPU, the unfused jnp
                           recurrences elsewhere; forced globally with
                           ``REPRO_SMOOTH_PATH`` ("fused" | "reference").
* ``resolve_tune``       — the kernel tile autotuner mode
                           (``repro.kernels.autotune``): ``None`` falls
                           back to ``REPRO_TUNE`` ("off" | "cache" |
                           "sweep"), default "cache" — use cached tuned
                           tiles when present, static defaults otherwise
                           ("off" is bitwise the pre-tune behaviour;
                           "sweep" measures and records on cache miss).
* ``resolve_overlap``    — distributed halo-exchange schedule
                           (``repro.dist``): ``None`` falls back to
                           ``REPRO_OVERLAP`` ("on" | "off"), default
                           "on" — split interior/boundary apply with the
                           exchange in flight; "off" is bitwise the
                           blocking pre-split path.

Every front door (``spmv``, ``spgemm_numeric_data``, ``set_values_coo``)
accepts ``None`` for these knobs and resolves them here, so the same call
site does the right thing on laptop CI and on a pod slice.
"""
from __future__ import annotations

import functools
import os

import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def _platform() -> str:
    # no fallback: a JAX that cannot start raises here rather than
    # passing for a CPU run
    import jax
    return jax.default_backend()


def backend() -> str:
    """Active platform name; honours the REPRO_BACKEND override.

    Only the jax platform probe is cached — the env override is re-read on
    every call so tests can flip it mid-process.
    """
    return os.environ.get("REPRO_BACKEND") or _platform()


def on_accelerator() -> bool:
    """True where the Pallas kernels compile natively.

    Deliberately TPU-only: the kernels' tiny rectangular block shapes
    violate Triton's power-of-2 tile constraint, so a compiled-by-default
    dispatch on GPU would crash at lowering.  GPU runs get the jnp
    reference paths until the Triton lowering is exercised.
    """
    return backend() == "tpu"


def kernel_dtype_ok(dtype) -> bool:
    """True for payload dtypes a compiled Pallas kernel takes: Mosaic has
    no 64-bit floats, so f64 payloads stay on the XLA paths on TPU."""
    return dtype is None or jnp.dtype(dtype) != jnp.float64


def kernels_by_default(dtype=None) -> bool:
    """Default dispatch rule of every front door: the Pallas kernel exactly
    where it compiles natively — on TPU, for a payload it can take."""
    return on_accelerator() and kernel_dtype_ok(dtype)


def resolve_interpret(interpret: bool | None = None) -> bool:
    """None -> interpret Pallas only where it cannot compile natively."""
    if interpret is None:
        return not on_accelerator()
    return interpret


def kernel_interpret(interpret: bool | None, dtype, family: str) -> bool:
    """``resolve_interpret`` for a Pallas front door, refusing a compiled
    call with an f64 payload (it cannot lower) with the policy to use."""
    interpret = resolve_interpret(interpret)
    if not interpret and not kernel_dtype_ok(dtype):
        raise ValueError(
            f"{family}: compiled Pallas kernels take f32/bf16 payloads, not "
            f"{jnp.dtype(dtype).name} (Mosaic has no 64-bit floats).  The "
            f"'f64' precision policy runs on the XLA paths on {backend()}; "
            f"use the 'f32' policy (precision='f32') for the kernel path.")
    return interpret


def resolve_use_kernel(use_kernel: bool | None = None, dtype=None) -> bool:
    """None -> dispatch to Pallas kernels exactly where they compile."""
    if use_kernel is None:
        return kernels_by_default(dtype)
    return use_kernel


def resolve_spgemm_path(path: str | None = None, dtype=None) -> str:
    """Default numeric SpGEMM path for this backend.

    "fused"     — tiled fused pair-GEMM + in-VMEM segment reduce (no
                  (npairs, br, bc) HBM intermediate); TPU default.
    "pairs"     — gather -> block_pair_gemm -> block_seg_sum (three
                  dispatches, materialized pair products).
    "reference" — einsum + sorted segment_sum oracle; CPU default.
    """
    if path is None:
        path = os.environ.get("REPRO_SPGEMM_PATH")
    if path is None:
        path = "fused" if kernels_by_default(dtype) else "reference"
    if path not in ("fused", "pairs", "reference"):
        # ValueError, not assert: the validation must survive `python -O`,
        # and a typo'd REPRO_SPGEMM_PATH should fail loudly either way.
        raise ValueError(
            f"invalid SpGEMM path {path!r}: expected 'fused', 'pairs' or "
            f"'reference' (from REPRO_SPGEMM_PATH or the path= knob)")
    return path


def resolve_spmm_path(path: str | None = None, dtype=None) -> str:
    """Default multi-RHS SpMM execution path for this backend.

    "kernel"    — the Pallas ``block_spmm`` panel kernel (compiled on TPU,
                  interpret-mode elsewhere when forced).
    "reference" — the jnp ``spmm_ell`` einsum; CPU/GPU default (same Triton
                  tile-shape exclusion as the other kernels).

    ``REPRO_SPMM_PATH`` forces a path globally, mirroring
    ``REPRO_SPGEMM_PATH``; re-read per call so tests can flip it
    mid-process.
    """
    if path is None:
        path = os.environ.get("REPRO_SPMM_PATH")
    if path is None:
        path = "kernel" if kernels_by_default(dtype) else "reference"
    if path not in ("kernel", "reference"):
        raise ValueError(
            f"invalid SpMM path {path!r}: expected 'kernel' or 'reference' "
            f"(from REPRO_SPMM_PATH or the path= knob)")
    return path


def resolve_smooth_path(path: str | None = None, dtype=None) -> str:
    """Default V-cycle smoother execution path for this backend.

    "fused"     — the Pallas ``fused_smoother`` kernel: one pass per
                  recurrence step computing ``d' = c1*d + c2*D^{-1}(b -
                  A x)``, ``x' = x + d'`` with no ``r``/``z`` HBM
                  intermediates (compiled on TPU, interpret-mode when
                  forced elsewhere).
    "reference" — the unfused jnp recurrences in ``repro.core.vcycle``
                  (SpMV + pbjacobi + axpys); CPU/GPU default.

    ``REPRO_SMOOTH_PATH`` forces a path globally, mirroring
    ``REPRO_SPMM_PATH``; re-read per call so tests can flip it
    mid-process (consumed at *trace* time for jitted solves).
    """
    if path is None:
        path = os.environ.get("REPRO_SMOOTH_PATH")
    if path is None:
        path = "fused" if kernels_by_default(dtype) else "reference"
    if path not in ("fused", "reference"):
        raise ValueError(
            f"invalid smoother path {path!r}: expected 'fused' or "
            f"'reference' (from REPRO_SMOOTH_PATH or the path= knob)")
    return path


def resolve_tune(mode: str | None = None) -> str:
    """Default autotuner mode; honours the ``REPRO_TUNE`` knob.

    "off"       — ignore the tuning cache entirely: every ``None`` tile
                  knob resolves to its static default.  Bitwise the
                  pre-autotuner behaviour.
    "cache"     (default) use a cached tuned tile when one exists for the
                  kernel signature on this machine/backend, else the
                  static default.  Never measures.
    "sweep"     — like "cache", but a miss triggers a timing sweep over
                  the candidate tiles on synthetic operands and records
                  the winner (``repro.kernels.autotune``).

    Re-read per call; like the path knobs it is consumed at *trace* time,
    so it must be set before the solver is built.  Invalid values raise
    ``ValueError``.
    """
    if mode is None:
        mode = os.environ.get("REPRO_TUNE")
    if mode is None:
        return "cache"
    key = str(mode).strip().lower()
    if key in ("", "0", "off", "false", "none"):
        return "off"
    if key in ("cache", "on", "1", "true"):
        return "cache"
    if key == "sweep":
        return "sweep"
    raise ValueError(
        f"invalid autotune mode {mode!r}: expected 'off', 'cache' or "
        f"'sweep' (from REPRO_TUNE or the mode= knob)")


def resolve_overlap(mode: str | None = None) -> str:
    """Distributed halo-exchange overlap mode; honours ``REPRO_OVERLAP``.

    "on"        (default) split apply: start the halo ``ppermute``s, run
                  the interior rows (no communication) while they fly,
                  finish the window, run the boundary rows.  Same per-row
                  summation order as blocking, so solutions are bitwise
                  identical — only the op *schedule* differs.
    "off"       — the blocking pre-refactor path: assemble the whole
                  window first, then one apply over all rows.  Bitwise
                  the pre-split jaxpr (zero residue).

    Re-read per call; consumed at *trace* time when the dist solver is
    staged, so it must be set before ``make_dist_solver``.  Invalid
    values raise ``ValueError``.
    """
    if mode is None:
        mode = os.environ.get("REPRO_OVERLAP")
    if mode is None:
        return "on"
    key = str(mode).strip().lower()
    if key in ("", "0", "off", "false", "blocking"):
        return "off"
    if key in ("on", "1", "true", "overlap"):
        return "on"
    raise ValueError(
        f"invalid overlap mode {mode!r}: expected 'on' or 'off' "
        f"(from REPRO_OVERLAP or the overlap= knob)")


def resolve_precision(precision=None):
    """Default precision policy; honours the REPRO_PRECISION override.

    ``precision`` may be a ``PrecisionPolicy``, a stock-policy name
    ("f64" | "f32" | "bf16"), or ``None`` — which reads
    ``REPRO_PRECISION`` (re-read per call, mirroring the path knobs) and
    falls back to full fp64, the paper's setting and the bitwise legacy
    behaviour.  Invalid names raise ``ValueError``.
    """
    from repro.core.precision import PrecisionPolicy
    if isinstance(precision, PrecisionPolicy):
        return precision
    if precision is None:
        precision = os.environ.get("REPRO_PRECISION")
    if precision is None:
        return PrecisionPolicy.double()
    return PrecisionPolicy.from_name(precision)


def resolve_faults(spec=None):
    """Default fault-injection schedule; honours ``REPRO_FAULTS``.

    ``spec`` may be a ``repro.robust.inject.FaultSchedule``, a spec string
    in the ``REPRO_FAULTS`` mini-language, or ``None`` — which reads
    ``REPRO_FAULTS`` (re-read per call, mirroring the other knobs) and
    falls back to no injection (``None``).  Invalid specs raise
    ``ValueError``.
    """
    from repro.robust import inject
    if spec is None:
        spec = os.environ.get("REPRO_FAULTS")
    if spec is None or isinstance(spec, inject.FaultSchedule):
        return spec
    return inject.parse_schedule(spec)


def resolve_obs(mode=None) -> str:
    """Default observability mode; honours the ``REPRO_OBS`` knob.

    "off"       (default) no counters — monitored hot paths are bitwise
                the unmonitored ones with zero jaxpr residue.
    "counters"  the device-side ``CycleTally`` carry threaded through
                ``pcg``/``block_pcg``/``vcycle``.

    The stage scopes (``repro.obs.trace.scope``) are op metadata and
    always on; no mode governs them.  Re-read per call (mirroring the
    path knobs); like them, the mode is consumed at *trace* time, so it
    must be set before the solver under observation is built.  Invalid
    values raise ``ValueError``.
    """
    if mode is None:
        mode = os.environ.get("REPRO_OBS")
    if mode is None:
        return "off"
    key = str(mode).strip().lower()
    if key in ("", "0", "off", "false", "none"):
        return "off"
    if key == "counters":
        return "counters"
    raise ValueError(
        f"invalid observability mode {mode!r}: expected 'off' or "
        f"'counters' (from REPRO_OBS or the obs= knob)")


def resolve_recover(policy=None):
    """Default breakdown-recovery policy; honours ``REPRO_RECOVER``.

    ``policy`` may be a ``repro.robust.recover.RecoveryPolicy``, a knob
    string ("off"/"0" -> disabled, "on"/"1" -> defaults, an integer ->
    that many ladder attempts), or ``None`` — which reads
    ``REPRO_RECOVER`` (re-read per call) and falls back to disabled
    (``None``).  Invalid values raise ``ValueError``.
    """
    from repro.robust.recover import RecoveryPolicy
    if isinstance(policy, RecoveryPolicy):
        return policy
    if policy is None:
        policy = os.environ.get("REPRO_RECOVER")
    if policy is None:
        return None
    key = str(policy).strip().lower()
    if key in ("0", "off", "false", "none", ""):
        return None
    if key in ("1", "on", "true", "default"):
        return RecoveryPolicy()
    try:
        return RecoveryPolicy(max_attempts=int(key))
    except ValueError as e:
        raise ValueError(
            f"invalid recovery knob {policy!r}: expected 'off', 'on' or a "
            f"max-attempts integer (from REPRO_RECOVER or the recover= "
            f"knob)") from e
