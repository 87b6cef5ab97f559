"""Distributed GAMG: device-resident hot recompute + solve over row slabs.

``build_dist_gamg(setupd, ndev)`` is the cold, host-side staging pass: it
takes the single-device ``GAMGSetup`` (global structure + plans) and remaps
every plan into per-rank slabs — the distributed analogue of the paper's
prolongator-side cache, including the pre-gathered off-process P rows
(P_oth).  ``make_dist_solver`` wraps the hot path in one jitted
``shard_map`` program over a 1-D ``"rank"`` mesh:

    recompute   chained distributed PtAP (stage 1 entirely local thanks to
                the cached P_oth operand; stage 2's off-process reduction is
                a neighbor ppermute window over the A·P payload slabs),
                smoother data (pbjacobi inverses, distributed power
                iteration for the Chebyshev bound), coarse Cholesky
                (replicated — the coarsest level is tiny by construction).
    solve       AMG-preconditioned CG with ``psum`` reductions and halo
                windows for every level SpMV.

Level placement (the coarse-grid agglomeration of PETSc GAMG's process
reduction): coarse levels hold a few thousand rows per rank, where halo
*latency* — not bandwidth — dominates, so sharding them across all ranks
is a net loss.  ``build_dist_gamg`` therefore assigns every level a
placement: levels above the ``coarse_eq_limit`` equations-per-rank
threshold stay slab-sharded as before; levels at or below it are
**agglomerated** — their operator payloads, P/R transfers and smoother
data are reassembled once per recompute into a *replicated* global
representation (``DistReplicatedLevel``) and the V-cycle runs them
rank-redundantly with zero ppermute traffic.  The sharded->replicated
boundary (``DistSwitch``) costs exactly one ``all_gather`` per V-cycle
(the restriction of the fine residual) and one per recompute (the
Galerkin payload of the first replicated operator); the prolongation
re-slices the replicated correction back into row slabs with a
zero-communication ``"replicated"``-halo operator.  The replicated tail
runs the *single-device* core functions (``gamg.level_state``,
``ptap_numeric_data``, ``vcycle``'s smoothers, dense ``cho_solve``)
verbatim, so agglomerated-vs-single-device f64 parity is exact by
construction — and therefore so is sharded-vs-agglomerated iteration
parity, which ``repro.dist.selftest`` asserts.

Halo schedule: every sharded operator apply routes through ``_rank_spmv``,
which renders the exchange either *blocking* (assemble the window, then
apply — bitwise the historical path, ``REPRO_OVERLAP=off``) or
*overlapped* (``REPRO_OVERLAP=on``, the default): start the ppermutes, run
the build-time **interior** rows against the rank's own slab while they
fly, finish the window, run the **boundary** rows, scatter the disjoint
partials back into slab order.  Per-row summation order is identical, so
the two schedules produce bitwise-equal iterates — which the selftest's
``REPRO_SELFTEST_OVERLAP=1`` section pins.  The knob is resolved at trace
time (``repro.kernels.backend.resolve_overlap``); the stage-2 PtAP
reduction overlaps the same way at pair granularity
(``dist_stage_apply_overlap``).

Parity with the single-device path is exact in structure (same contribution
order per row, same plans) and floating-point-tight in value (the only
reassociations are the ``psum`` dot products), which is what
``repro.dist.selftest`` asserts.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh, PartitionSpec

from repro.core.block_csr import BlockCSR, BlockELL, EllTransposePlan
from repro.core.gamg import GAMGSetup, LevelSetup, coarse_cholesky, \
    jittered_cholesky, level_state, restriction_bcsr
from repro.core.krylov import wrap_precond
from repro.core.precision import PrecisionPolicy
from repro.core.ptap import ptap_numeric_data
from repro.core.smooth import invert_diag_blocks
from repro.core.spmv import apply_ell, apply_ell_t, block_matvec
from repro.core.vcycle import (
    LevelState,
    apply_restriction,
    apply_smoother,
    chebyshev_recurrence,
    pbjacobi_recurrence,
)
from repro.dist.pamg import (
    AXIS,
    DistEll,
    DistPairStage,
    build_diag_sel,
    build_dist_ell,
    build_payload_gather,
    build_row_gather,
    build_stage1,
    build_stage2,
    combine_split,
    dist_ell_apply,
    dist_ell_apply_boundary,
    dist_ell_apply_interior,
    dist_stage_apply,
    dist_stage_apply_overlap,
    finish_halo_exchange,
    halo_window,
    start_halo_exchange,
)
from repro.dist.partition import ProcessMesh, RowPartition, as_mesh, \
    partition_rows
from repro.kernels.backend import resolve_overlap
from repro.multirhs.block_krylov import block_pcg
from repro.obs import trace as obs_trace
from repro.robust import inject
from repro.robust.health import status_of

#: Default agglomeration threshold, in equations per rank (the PETSc
#: ``-pc_gamg_process_eq_limit`` default): a level whose global equation
#: count divided by ``ndev`` is at or below this leaves the fully-sharded
#: path.  ``coarse_eq_limit=0`` disables agglomeration entirely.
DEFAULT_COARSE_EQ_LIMIT = 50

Array = jax.Array
P = PartitionSpec


def rank_mesh(devices) -> Mesh:
    """The 1-D ``"rank"`` mesh the dist programs run on, over ``devices``.

    The axis is ``Auto``: the per-rank programs are ``shard_map`` bodies,
    and their sharded outputs (status, iterate) must stay indexable by the
    caller, which an ``Explicit`` axis (``jax.make_mesh``'s default) does
    not allow.
    """
    return Mesh(np.asarray(devices), (AXIS,), axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
# Cold build
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DistLevel:
    """Per-level rank-sharded plans (host numpy, stacked (ndev, ...)).

    ``p_op``/``r_op`` are ``None`` on the last sharded level when a
    replicated tail follows — the transfers across the placement boundary
    live in ``DistSwitch`` instead.
    """

    a_op: DistEll
    p_op: Optional[DistEll]
    r_op: Optional[DistEll]
    stage1: DistPairStage
    stage2: DistPairStage
    diag_sel: np.ndarray
    diag_mask: np.ndarray
    row_mask: np.ndarray          # (ndev, rpad) valid fine rows
    a_nnz_starts: np.ndarray      # (ndev + 1,) A payload slab offsets
    a_pad: int                    # fine payload slab length (max nnz + 1)
    bs: int
    rpad: int                     # fine row slab pad
    n_fine: int


@dataclasses.dataclass
class DistCoarse:
    """Replicated coarsest-level solve data (the level is tiny).

    Only staged when *no* AMG level is agglomerated — with a replicated
    tail the coarsest payload is already global and the Cholesky needs no
    gather of its own.
    """

    part: RowPartition
    sel: np.ndarray               # (nnzb,) window ids into gathered payload
    rows: np.ndarray              # (nnzb,) global block coords
    cols: np.ndarray
    row_sel: np.ndarray           # (nbr,) window ids into gathered vectors
    nbr: int
    bs: int
    rpad: int
    ac_pad: int


@dataclasses.dataclass
class DistReplicatedLevel:
    """One agglomerated level: the rank-redundant global representation.

    The staging is deliberately thin — the level IS the single-device
    level.  ``ls`` carries the global plans (A-ELL, PtAP cache, P/R
    payloads) that ``gamg.level_state`` / ``ptap_numeric_data`` consume;
    the hot path closes over them as replicated constants, so the V-cycle
    on this level does zero communication.
    """

    ls: LevelSetup
    n_eqs: int                    # global equations (the placement metric)


@dataclasses.dataclass
class DistSwitch:
    """Gather-boundary staging where placement flips sharded->replicated.

    ``payload_sel``/``row_sel`` are the gather-boundary plans
    (``repro.dist.pamg.build_payload_gather`` / ``build_row_gather``):
    window ids into one ``all_gather`` of the last sharded level's padded
    slabs that reassemble the global Galerkin payload (recompute) and the
    global fine residual (restriction).  The boundary restriction is
    applied rank-redundantly after that gather — through the stored global
    ``r_ell`` when the setup carries one, else transpose-free off the
    global prolongator payload (``p_g`` + the ``p_t`` plan, the default).
    ``p_b`` is the boundary prolongator — sharded fine rows whose plan
    indices address the *replicated* coarse correction directly
    (``"replicated"`` halo, zero traffic).
    """

    payload_sel: np.ndarray       # (nnzb,) into gathered stage2 payload slabs
    row_sel: np.ndarray           # (nbr_fine,) into gathered residual slabs
    r_ell: Optional[BlockELL]     # stored global restriction, or None
    p_b: DistEll                  # slab rows <- replicated coarse vector
    nbr_c: int                    # replicated coarse vector block rows
    bs_c: int
    p_g: Optional[BlockELL] = None          # global prolongator payload
    p_t: Optional[EllTransposePlan] = None  # transpose-free P^T plan


@dataclasses.dataclass
class DistGAMG:
    """Cold distributed staging — valid while the setup's structures hold.

    ``precision`` mirrors the setup's ``PrecisionPolicy``: the staged
    constant payloads (P/R blocks, the cached P_oth operand) are baked at
    ``hierarchy_dtype``, the rank-local recompute/V-cycle runs at that
    dtype (halving the halo/ppermute payload for f32), and the outer
    distributed PCG stays at ``krylov_dtype`` with the boundary cast.

    Placement: ``levels`` holds only the slab-sharded levels; ``repl``
    the agglomerated (replicated) tail, ``switch`` the gather boundary
    between them (``None`` when nothing is agglomerated, in which case
    ``coarse`` carries the legacy replicated-Cholesky staging).  Level 0
    always stays sharded — the scatter/gather front doors and the outer
    Krylov iteration are slab contracts.
    """

    ndev: int
    parts: List[RowPartition]     # per level, + the coarsest
    levels: List[DistLevel]       # the slab-sharded prefix
    coarse: Optional[DistCoarse]  # legacy coarsest staging (no repl tail)
    smoother: str
    degree: int
    precision: PrecisionPolicy = dataclasses.field(
        default_factory=PrecisionPolicy.double)
    repl: List[DistReplicatedLevel] = dataclasses.field(default_factory=list)
    switch: Optional[DistSwitch] = None
    coarse_struct: Optional[BlockCSR] = None   # coarsest structure (repl tail)
    coarse_eq_limit: int = 0
    #: The device set as a ``ProcessMesh``.  The executable shard_map path
    #: consumes the row axis (``mesh.pr == ndev`` slabs); a 2-D mesh's
    #: column axis splits each slab's halo traffic ``pc`` ways, which
    #: ``repro.obs.model.dist_cycle_comm`` accounts.
    mesh: Optional[ProcessMesh] = None

    @property
    def n_levels(self) -> int:
        """AMG levels (sharded + replicated), excluding the coarsest."""
        return len(self.levels) + len(self.repl)

    @property
    def placement(self) -> List[str]:
        """Per-level placement tags (+ the coarsest, always replicated)."""
        return (["sharded"] * len(self.levels)
                + ["replicated"] * len(self.repl) + ["replicated"])

    # ---- args bundle (the sharded operands of the hot program) ----------
    def sharded_args(self, setupd: Optional[GAMGSetup] = None):
        del setupd  # staged at build time; kept for the call-site shape
        def split_args(pre: str, op: DistEll):
            """The interior/boundary split plan of one sharded DistEll."""
            return {pre + "loc": jnp.asarray(op.indices_local),
                    pre + "msk": jnp.asarray(op.int_mask)}

        lv_args = []
        for lv in self.levels:
            if lv.p_op is not None:
                transfers = dict(
                    p_idx=jnp.asarray(lv.p_op.indices),
                    p_data=jnp.asarray(lv.p_op.data),
                    r_idx=jnp.asarray(lv.r_op.indices),
                    r_data=jnp.asarray(lv.r_op.data),
                    **split_args("p_", lv.p_op),
                    **split_args("r_", lv.r_op))
            else:   # switch boundary: the re-slicing prolongator's slabs
                # (replicated halo — zero traffic, no split plan needed)
                transfers = dict(
                    pb_idx=jnp.asarray(self.switch.p_b.indices),
                    pb_data=jnp.asarray(self.switch.p_b.data))
            lv_args.append(dict(
                transfers,
                a_idx=jnp.asarray(lv.a_op.indices),
                a_gather=jnp.asarray(lv.a_op.gather),
                **split_args("a_", lv.a_op),
                s1_lhs=jnp.asarray(lv.stage1.lhs_gather),
                s1_rhs=jnp.asarray(lv.stage1.rhs_data),
                s1_seg=jnp.asarray(lv.stage1.seg),
                s2_lhs=jnp.asarray(lv.stage2.lhs_data),
                s2_rhs=jnp.asarray(lv.stage2.rhs_gather),
                s2_rhs_loc=jnp.asarray(lv.stage2.rhs_local),
                s2_msk=jnp.asarray(lv.stage2.local_mask),
                s2_seg=jnp.asarray(lv.stage2.seg),
                diag_sel=jnp.asarray(lv.diag_sel),
                diag_mask=jnp.asarray(lv.diag_mask),
                row_mask=jnp.asarray(lv.row_mask),
            ))
        return {"levels": lv_args}

    # ---- host-side scatter/gather (edges of the device-resident region) -
    @property
    def payload_stage_dtype(self) -> np.dtype:
        """Staging dtype of the fine payload slabs: wide enough for both
        the hierarchy chain (cast down once at the top of the rank
        recompute) and the mixed-policy krylov-dtype operator copy
        (``a_data_kr``).  Staging at the *policy's* dtype rather than the
        caller's means an fp64 operator update into an fp32-resident
        hierarchy neither retraces the jitted hot program nor poisons the
        staged dtype."""
        return np.dtype(jnp.promote_types(self.precision.hierarchy_dtype,
                                          self.precision.krylov_dtype))

    def scatter_fine_payloads(self, data: Array) -> Array:
        """Global (nnzb, bs, bs) fine values -> (ndev, a_pad, bs, bs).

        Slabs are allocated at ``payload_stage_dtype`` (policy-derived,
        never the caller's dtype) so repeat updates at varying caller
        dtypes hit the same compiled program.
        """
        data = np.asarray(data)
        lv = self.levels[0]
        out = np.zeros((self.ndev, lv.a_pad) + data.shape[1:],
                       self.payload_stage_dtype)
        for r in range(self.ndev):
            s, e = int(lv.a_nnz_starts[r]), int(lv.a_nnz_starts[r + 1])
            out[r, :e - s] = data[s:e]
        return jnp.asarray(out)

    def scatter_vector(self, b: Array) -> Array:
        """Global fine vector (n,) or panel (n, k) -> (ndev, rpad, bs[, k])
        padded slabs, staged at the policy's ``krylov_dtype`` (the dtype
        the outer distributed PCG runs at — never the caller's)."""
        lv, part = self.levels[0], self.parts[0]
        b = np.asarray(b)
        trailing = b.shape[1:]
        b2 = b.reshape((part.nrows, lv.bs) + trailing)
        out = np.zeros((self.ndev, lv.rpad, lv.bs) + trailing,
                       np.dtype(self.precision.krylov_dtype))
        for r in range(self.ndev):
            sl = part.slab(r)
            out[r, :sl.stop - sl.start] = b2[sl]
        return jnp.asarray(out)

    def gather_vector(self, x: Array) -> np.ndarray:
        """(ndev, rpad, bs[, k]) padded slabs -> global (n,) or (n, k)."""
        part = self.parts[0]
        xs = np.asarray(x)
        chunks = [xs[r, :part.counts[r]] for r in range(self.ndev)]
        cat = np.concatenate(chunks, axis=0)
        return cat.reshape((-1,) + xs.shape[3:])


@dataclasses.dataclass
class DistAssembly:
    """Per-rank device-assembly staging: the distributed rendering of the
    cached ``BlockCOOPlan``.

    ``plan.out_idx_sorted`` is monotone, so the contributions feeding rank
    ``r``'s fine payload slab (global output blocks
    ``a_nnz_starts[r]:a_nnz_starts[r+1]``) are one *contiguous* range of
    the globally sorted contribution stream — each rank owns a slice of
    the same scatter-sum the single-device ``set_values_coo`` runs, in the
    same order, which is what makes assembled-slab parity exact.

    A contribution is (element, node-pair); elements touching a slab
    boundary appear on both ranks, so each rank stages the ids of the
    elements it needs (``elem_ids``, padded) and recomputes their
    stiffness blocks rank-locally — the scatter front door
    (``scatter_fields``) then moves only two small per-element coefficient
    slabs, never a value stream.
    """

    elem_ids: np.ndarray      # (ndev, epad) global element ids (pad -> 0)
    contrib_elem: np.ndarray  # (ndev, cpad) rank-local element index
    contrib_pa: np.ndarray    # (ndev, cpad) row-node within element
    contrib_pb: np.ndarray    # (ndev, cpad) col-node within element
    contrib_seg: np.ndarray   # (ndev, cpad) local slot in the payload slab
    contrib_mask: np.ndarray  # (ndev, cpad) valid contributions
    quad_b: np.ndarray        # shared quadrature arrays (replicated consts)
    quad_w: np.ndarray
    nn: int                   # nodes per element
    bs: int
    a_pad: int                # fine payload slab length (dg.levels[0])
    n_elements: int
    stage_dtype: np.dtype     # dg.payload_stage_dtype (policy's, not caller's)

    @property
    def ndev(self) -> int:
        return self.elem_ids.shape[0]

    def sharded_args(self):
        """The (ndev, ...) stacked operands of the rank assembly."""
        return dict(elem=jnp.asarray(self.contrib_elem),
                    pa=jnp.asarray(self.contrib_pa),
                    pb=jnp.asarray(self.contrib_pb),
                    seg=jnp.asarray(self.contrib_seg),
                    mask=jnp.asarray(self.contrib_mask))

    def scatter_fields(self, E, nu):
        """Global per-element fields (or scalars) -> (ndev, epad) slabs.

        Staged at the policy-derived payload dtype (mirroring
        ``DistGAMG.scatter_fine_payloads``): repeat updates at varying
        caller dtypes hit the same compiled program.
        """
        ne = self.n_elements
        E = np.broadcast_to(np.asarray(E, self.stage_dtype), (ne,))
        nu = np.broadcast_to(np.asarray(nu, self.stage_dtype), (ne,))
        return (jnp.asarray(E[self.elem_ids]),
                jnp.asarray(nu[self.elem_ids]))


def build_dist_assembly(dg: DistGAMG, assembler) -> DistAssembly:
    """Cold staging of device FEM assembly over the fine payload slabs.

    ``assembler`` is the problem's ``repro.fem.device_stiffness
    .DeviceAssembler`` (its ``BlockCOOPlan`` must be the one the fine
    operator of ``dg``'s setup was assembled with).
    """
    plan = assembler.plan
    lv0 = dg.levels[0]
    nn = assembler.nn
    if int(lv0.a_nnz_starts[-1]) != plan.nnzb:
        raise ValueError(
            f"assembler plan does not match the staged fine operator: "
            f"plan has {plan.nnzb} output blocks, the fine level has "
            f"{int(lv0.a_nnz_starts[-1])}")
    sorted_input = plan.keep[plan.order]          # declared-coordinate ids
    elem = sorted_input // (nn * nn)
    pair = sorted_input % (nn * nn)
    seg = plan.out_idx_sorted                     # monotone output blocks
    starts = lv0.a_nnz_starts
    los = np.searchsorted(seg, starts[:-1], side="left")
    his = np.searchsorted(seg, starts[1:], side="left")
    per_elem, per_loc, per_uniq = [], [], []
    for r in range(dg.ndev):
        er = elem[los[r]:his[r]]
        uniq, local = np.unique(er, return_inverse=True)
        per_uniq.append(uniq)
        per_elem.append(er)
        per_loc.append(local)
    epad = max(1, max(len(u) for u in per_uniq))
    cpad = max(1, int((his - los).max()))
    ndev = dg.ndev
    elem_ids = np.zeros((ndev, epad), dtype=np.int64)
    c_elem = np.zeros((ndev, cpad), dtype=np.int32)
    c_pa = np.zeros((ndev, cpad), dtype=np.int32)
    c_pb = np.zeros((ndev, cpad), dtype=np.int32)
    # padded contributions land in the (always unused) last slab slot:
    # slab lengths are at most a_pad - 1 by construction
    c_seg = np.full((ndev, cpad), lv0.a_pad - 1, dtype=np.int32)
    c_mask = np.zeros((ndev, cpad), dtype=bool)
    for r in range(ndev):
        lo, hi = los[r], his[r]
        k = hi - lo
        elem_ids[r, :len(per_uniq[r])] = per_uniq[r]
        c_elem[r, :k] = per_loc[r]
        c_pa[r, :k] = pair[lo:hi] // nn
        c_pb[r, :k] = pair[lo:hi] % nn
        c_seg[r, :k] = seg[lo:hi] - starts[r]
        c_mask[r, :k] = True
    return DistAssembly(elem_ids=elem_ids, contrib_elem=c_elem,
                        contrib_pa=c_pa, contrib_pb=c_pb, contrib_seg=c_seg,
                        contrib_mask=c_mask,
                        quad_b=np.asarray(assembler.quad_b),
                        quad_w=np.asarray(assembler.quad_w),
                        nn=nn, bs=plan.br, a_pad=lv0.a_pad,
                        n_elements=assembler.n_elements,
                        stage_dtype=dg.payload_stage_dtype)


def _placement_split(setupd: GAMGSetup, ndev: int, limit: int) -> int:
    """First level index that leaves the fully-sharded path.

    A level is agglomerated when its global equation count per rank is at
    or below ``limit`` (PETSc's ``-pc_gamg_process_eq_limit`` rule).
    Level 0
    never qualifies — the fine level is the scatter/gather and outer-Krylov
    slab contract.  Level sizes shrink monotonically, so the split is a
    single index: ``levels[:split]`` sharded, ``levels[split:]`` replicated.
    """
    n = len(setupd.levels)
    if limit <= 0:
        return n
    for li in range(1, n):
        ls = setupd.levels[li]
        if ls.n_fine * ls.A0.br <= limit * ndev:
            return li
    return n


def build_dist_gamg(setupd: GAMGSetup, ndev, *,
                    coarse_eq_limit: Optional[int] = None) -> DistGAMG:
    """Cold distributed staging of a single-device GAMG setup.

    ``ndev`` is an int rank count (the legacy 1-D slab convention) or a
    ``ProcessMesh``: the executable slabs follow the mesh's *row* axis
    (``mesh.pr``), while a 2-D mesh's column axis is recorded for the
    communication model (each row group's ``pc`` ranks split its halo
    traffic — ``repro.obs.model.dist_cycle_comm``).

    Constant payloads (P, R, the cached P_oth) are staged at the policy's
    ``hierarchy_dtype`` — the distributed rendering of "the hierarchy is
    stored at hierarchy_dtype".

    ``coarse_eq_limit`` is the placement threshold in equations per rank:
    levels at or below it are agglomerated into the replicated tail (see module
    docstring).  ``None`` defers to ``setupd.coarse_eq_limit`` and then to
    ``DEFAULT_COARSE_EQ_LIMIT``; ``0`` keeps every level slab-sharded (the
    pre-placement behaviour).
    """
    assert setupd.levels, "distributed path needs at least one AMG level"
    mesh = as_mesh(ndev)
    mesh.row_partition(setupd.levels[0].A0.nbr)   # validate rows >= pr
    ndev = mesh.pr
    if coarse_eq_limit is None:
        coarse_eq_limit = setupd.coarse_eq_limit
    if coarse_eq_limit is None:
        coarse_eq_limit = DEFAULT_COARSE_EQ_LIMIT
    # the eq-per-rank placement rule counts every device of the mesh
    # (pr * pc), not just the row axis the slabs follow — a 2-D mesh
    # agglomerates exactly like the equally-sized 1-D one would
    n_sharded = _placement_split(setupd, mesh.ndev, coarse_eq_limit)
    h_np = setupd.precision.hierarchy_dtype
    parts = [partition_rows(ls.n_fine, ndev) for ls in setupd.levels]
    parts.append(partition_rows(setupd.coarse_struct.nbr, ndev))
    levels: List[DistLevel] = []
    for li, ls in enumerate(setupd.levels[:n_sharded]):
        fine, coarse = parts[li], parts[li + 1]
        boundary = li == n_sharded - 1 and n_sharded < len(setupd.levels)
        A0 = ls.A0
        a_nnz_starts = A0.indptr[fine.starts]
        a_pad = int(np.diff(a_nnz_starts).max()) + 1
        p_np = np.asarray(ls.P.data).astype(h_np)
        cache = ls.ptap_cache
        s1 = build_stage1(cache.ap_plan, fine, A0.indptr, p_np)
        s2 = build_stage2(cache.ac_plan, coarse, fine, cache.ap_plan.indptr,
                          s1.out_pad, p_np, cache.r_perm)
        diag_sel, diag_mask = build_diag_sel(A0.indptr, A0.indices, fine,
                                             a_pad)
        rpad = max(fine.max_count, 1)
        row_mask = (np.arange(rpad)[None, :]
                    < fine.counts[:, None])
        # the slab-sharded restriction slices a stored-form operand into
        # per-rank slabs; a transpose-free setup computes it here, cold,
        # at staging (it is never device-resident globally)
        R_sh = None if boundary else restriction_bcsr(ls)
        # at the switch boundary P/R are replaced by the gather-boundary
        # operators in DistSwitch; don't stage the unused sharded forms
        levels.append(DistLevel(
            a_op=build_dist_ell(A0, fine, fine, payload_pad=a_pad),
            p_op=None if boundary else
                build_dist_ell(ls.P, fine, coarse, const_data=p_np),
            r_op=None if boundary else
                build_dist_ell(R_sh, coarse, fine,
                               const_data=np.asarray(
                                   R_sh.data).astype(h_np)),
            stage1=s1, stage2=s2, diag_sel=diag_sel, diag_mask=diag_mask,
            row_mask=row_mask, a_nnz_starts=a_nnz_starts, a_pad=a_pad,
            bs=A0.br, rpad=rpad, n_fine=ls.n_fine))
    repl = [DistReplicatedLevel(ls=ls, n_eqs=ls.n_fine * ls.A0.br)
            for ls in setupd.levels[n_sharded:]]
    switch = None
    coarse_staging = None
    if repl:
        bls = setupd.levels[n_sharded - 1]       # last sharded level
        first = repl[0].ls                       # first replicated level
        fine = parts[n_sharded - 1]
        switch = DistSwitch(
            payload_sel=build_payload_gather(
                first.A0.indptr, parts[n_sharded],
                levels[-1].stage2.out_pad),
            row_sel=build_row_gather(fine, max(fine.max_count, 1)),
            r_ell=(bls.r_ell.astype(h_np)
                   if bls.r_ell is not None else None),
            p_g=(None if bls.r_ell is not None
                 else bls.p_ell.astype(h_np)),
            p_t=None if bls.r_ell is not None else bls.pt,
            p_b=build_dist_ell(bls.P, fine, parts[n_sharded],
                               const_data=np.asarray(
                                   bls.P.data).astype(h_np),
                               replicated_cols=True),
            nbr_c=first.A0.nbr, bs_c=first.A0.br)
    else:
        # legacy replicated coarsest-level maps (no agglomerated tail)
        Ac = setupd.coarse_struct
        c_part = parts[-1]
        ac_pad = levels[-1].stage2.out_pad
        c_rpad = max(c_part.max_count, 1)
        coarse_staging = DistCoarse(
            part=c_part,
            sel=build_payload_gather(Ac.indptr, c_part, ac_pad),
            rows=np.repeat(np.arange(Ac.nbr), np.diff(Ac.indptr)),
            cols=np.asarray(Ac.indices, dtype=np.int64),
            row_sel=build_row_gather(c_part, c_rpad),
            nbr=Ac.nbr, bs=Ac.br, rpad=c_rpad, ac_pad=ac_pad)
    return DistGAMG(ndev=ndev, parts=parts, levels=levels,
                    coarse=coarse_staging, smoother=setupd.smoother,
                    degree=setupd.degree, precision=setupd.precision,
                    repl=repl, switch=switch,
                    coarse_struct=setupd.coarse_struct if repl else None,
                    coarse_eq_limit=int(coarse_eq_limit), mesh=mesh)


# ---------------------------------------------------------------------------
# Hot path (per-rank functions, used inside shard_map)
# ---------------------------------------------------------------------------

def _pdot(a: Array, b: Array) -> Array:
    return lax.psum(jnp.vdot(a, b), AXIS)


def _pnorm(a: Array) -> Array:
    return jnp.sqrt(lax.psum(jnp.sum(a * a), AXIS))


def _pdot_cols(a: Array, b: Array) -> Array:
    """Per-column global dot over (rpad, bs, k) slabs -> (k,)."""
    return lax.psum(jnp.sum(a * b, axis=(0, 1)), AXIS)


def _pnorm_cols(a: Array) -> Array:
    return jnp.sqrt(lax.psum(jnp.sum(a * a, axis=(0, 1)), AXIS))


def _rank_lambda_max(lv: DistLevel, a, dinva_data: Array,
                     row_mask: Array, overlap: bool, iters: int = 10,
                     accum=None) -> Array:
    """Distributed power iteration — mirrors ``lambda_max_dinv_a``."""

    def spmv(x):
        return _rank_spmv(lv.a_op, a, "a_", dinva_data, x, overlap,
                          accum=accum)

    x0 = row_mask[:, None] * jnp.ones((lv.rpad, lv.bs), dinva_data.dtype)
    x0 = x0 / _pnorm(x0)

    def body(_, x):
        y = spmv(x)
        # finfo tiny, not a literal: 1e-300 underflows to 0 below f64
        return y / jnp.maximum(_pnorm(y), jnp.finfo(y.dtype).tiny)

    x = lax.fori_loop(0, iters, body, x0)
    return _pnorm(spmv(x))


def _rank_recompute(dg: DistGAMG, args, a_slab: Array, overlap: bool):
    """Distributed hot hierarchy rebuild: chained PtAP + smoother data.

    The payload chain runs at the policy's hierarchy dtype (the incoming
    fine slab is cast once at the top); under a mixed policy level 0
    additionally keeps a krylov-dtype payload gather (``a_data_kr``) for
    the outer CG's operator, mirroring ``Hierarchy.a_fine_ell``.

    With a replicated tail the sharded chain stops at the switch: the last
    sharded stage2 payload slabs are all-gathered once, the gather-boundary
    plan reassembles the first replicated operator's *global* payload, and
    the tail recompute is the single-device chain
    (``gamg.level_state`` + ``ptap_numeric_data``) run rank-redundantly —
    identical arithmetic to the single-device hot recompute.
    """
    policy = dg.precision
    h = jnp.dtype(policy.hierarchy_dtype)
    acc = policy.kernel_accum_dtype
    acc_p = jnp.promote_types(h, jnp.dtype(policy.accum_dtype))
    states = []
    a_cur = a_slab.astype(h)
    for li, lv in enumerate(dg.levels):
        a = args["levels"][li]
        a_ell_data = a_cur[a["a_gather"]]
        eye = jnp.eye(lv.bs, dtype=h)
        diag = jnp.where(a["diag_mask"][:, None, None], a_cur[a["diag_sel"]],
                         eye)
        dinv = invert_diag_blocks(
            diag.astype(policy.factor_dtype)).astype(h)
        dinva = jnp.einsum("rab,rkbc->rkac", dinv.astype(acc_p),
                           a_ell_data.astype(acc_p),
                           preferred_element_type=acc_p).astype(h)
        lam = _rank_lambda_max(lv, a, dinva, a["row_mask"], overlap,
                               accum=acc)
        st = dict(a_data=a_ell_data, dinv=dinv, lam=lam)
        if li == 0 and policy.mixed:
            st["a_data_kr"] = a_slab.astype(
                policy.krylov_dtype)[a["a_gather"]]
        states.append(st)
        # next-level payload: local A@P (cached P_oth), then the
        # off-process reduction window for R@(AP)
        ap = dist_stage_apply(a_cur[a["s1_lhs"]], a["s1_rhs"], a["s1_seg"],
                              lv.stage1.out_pad, accum_dtype=acc)
        s2 = lv.stage2
        if overlap and s2.halo.strategy not in ("local", "replicated"):
            a_cur = dist_stage_apply_overlap(
                a["s2_lhs"], ap, s2.halo, a["s2_rhs"], a["s2_rhs_loc"],
                a["s2_msk"], a["s2_seg"], s2.out_pad, accum_dtype=acc)
        else:
            ap_win = halo_window(ap, s2.halo)
            a_cur = dist_stage_apply(a["s2_lhs"], ap_win[a["s2_rhs"]],
                                     a["s2_seg"], s2.out_pad,
                                     accum_dtype=acc)
    if dg.repl:
        g = lax.all_gather(a_cur, AXIS, axis=0, tiled=True)
        a_data = g[jnp.asarray(dg.switch.payload_sel)]
        for rl in dg.repl:
            states.append(level_state(rl.ls, a_data, policy))
            a_data = ptap_numeric_data(rl.ls.ptap_cache, a_data,
                                       rl.ls.P.data.astype(h),
                                       accum_dtype=acc)
        Ac = dg.coarse_struct.with_data(a_data)
        chol = coarse_cholesky(Ac.to_dense(), policy)
    else:
        chol = _rank_coarse_chol(dg, a_cur)
    return states, chol


def _rank_coarse_chol(dg: DistGAMG, ac_slab: Array) -> Array:
    """Replicated dense Cholesky of the (tiny) coarsest operator.

    Shares ``gamg.jittered_cholesky`` — including its NaN-detect
    jitter-escalation retry — so the dist path hardens against an
    indefinite coarse operator exactly like the single-device one.
    """
    c = dg.coarse
    policy = dg.precision
    g = lax.all_gather(ac_slab, AXIS, axis=0, tiled=True)
    blocks = g[jnp.asarray(c.sel)]
    dense4 = jnp.zeros((c.nbr, c.nbr, c.bs, c.bs), ac_slab.dtype)
    dense4 = dense4.at[jnp.asarray(c.rows), jnp.asarray(c.cols)].add(blocks)
    n = c.nbr * c.bs
    dense = dense4.transpose(0, 2, 1, 3).reshape(n, n)
    chol = jittered_cholesky(dense.astype(jnp.dtype(policy.factor_dtype)),
                             policy.coarse_jitter_scale(),
                             policy.coarse_retry_scale())
    return chol.astype(policy.hierarchy_dtype)


def _rank_coarse_solve(dg: DistGAMG, chol: Array, rhs: Array) -> Array:
    """Replicated coarse solve; every rank slices its own slab back out.

    ``rhs`` is the (rpad, bs) coarse slab or its (rpad, bs, k) panel —
    ``cho_solve`` broadcasts over matrix right-hand sides natively.
    """
    c = dg.coarse
    trailing = rhs.shape[2:]
    g = lax.all_gather(rhs, AXIS, axis=0, tiled=True)     # (ndev*rpad, bs..)
    rhs_g = g[jnp.asarray(c.row_sel)]                     # (nbr, bs[, k])
    xc = jax.scipy.linalg.cho_solve(
        (chol, True), rhs_g.reshape((c.nbr * c.bs,) + trailing))
    xcb = jnp.pad(xc.reshape((c.nbr, c.bs) + trailing),
                  ((0, c.rpad), (0, 0)) + ((0, 0),) * len(trailing))
    r = lax.axis_index(AXIS)
    start = jnp.asarray(dg.coarse.part.starts)[r]
    zero = jnp.zeros_like(start)
    mine = lax.dynamic_slice(xcb, (start, zero) + (zero,) * len(trailing),
                             (c.rpad, c.bs) + trailing)
    mask = jnp.arange(c.rpad) < jnp.asarray(c.part.counts)[r]
    return mine * mask.reshape((c.rpad,) + (1,) * (mine.ndim - 1))


def _rank_assemble(da: DistAssembly, aargs, E: Array, nu: Array) -> Array:
    """Rank-local device assembly: coefficient slabs -> fine payload slab.

    Vmapped quadrature over this rank's (padded) element set, then the
    rank's contiguous slice of the global scatter-sum — same contribution
    order as the single-device ``set_values_coo``, so the assembled slabs
    match ``scatter_fine_payloads`` of the globally assembled stream.
    Padded elements compute element 0's block (valid arithmetic, no NaN)
    and their contributions are masked out of the segment sum.
    """
    from repro.fem.device_stiffness import element_stiffness_blocks
    dt = E.dtype
    blocks = element_stiffness_blocks(da.quad_b.astype(dt),
                                      da.quad_w.astype(dt), E, nu)
    nn, bs = da.nn, da.bs
    bl = blocks.reshape(-1, nn, bs, nn, bs).transpose(0, 1, 3, 2, 4)
    contrib = bl[aargs["elem"], aargs["pa"], aargs["pb"]]
    contrib = contrib * aargs["mask"][:, None, None].astype(dt)
    return jax.ops.segment_sum(contrib, aargs["seg"],
                               num_segments=da.a_pad,
                               indices_are_sorted=True)


def _rank_spmv(op: DistEll, a, pre: str, data: Array, x: Array,
               overlap: bool, accum=None) -> Array:
    """Per-rank SpMV through one of the two exchange renderings.

    ``a`` is the level's sharded-args dict, ``pre`` the operator's key
    prefix (``"a_"``/``"p_"``/``"r_"``/``"pb_"``).  Blocking
    (``overlap=False``) is exactly the pre-split apply: assemble the whole
    window, one apply over all rows — bitwise the historical jaxpr.
    Overlapped: issue the exchange, contract the full slab against the
    rank's own vector while it flies, finish the window, contract it
    again off the window, select per row (interior rows keep the
    exchange-free lane, boundary rows the windowed one).
    Halos that move no bytes (``local``/``replicated``) have nothing to
    hide and always take the blocking rendering.
    """
    idx = a[pre + "idx"]
    if not overlap or op.halo.strategy in ("local", "replicated"):
        return dist_ell_apply(idx, data, halo_window(x, op.halo),
                              accum_dtype=accum)
    pend = start_halo_exchange(x, op.halo)
    y_int = dist_ell_apply_interior(a[pre + "loc"], data, x,
                                    accum_dtype=accum)
    win = finish_halo_exchange(pend)
    y_bnd = dist_ell_apply_boundary(idx, data, win, accum_dtype=accum)
    return combine_split(a[pre + "msk"], y_int, y_bnd)


def _rank_smooth(dg: DistGAMG, spmv, st, b: Array, x: Array) -> Array:
    """Same recurrences as the single-device V-cycle (single source of
    truth in ``repro.core.vcycle``) with per-rank spmv/pbjacobi closures —
    iteration parity with the single-device path depends on this."""
    acc = jnp.promote_types(st["dinv"].dtype,
                            jnp.dtype(dg.precision.accum_dtype))

    def pbj(r):
        return block_matvec(st["dinv"].astype(acc),
                            r.astype(acc)).astype(r.dtype)

    if dg.smoother == "chebyshev":
        return chebyshev_recurrence(spmv, pbj, st["lam"], b, x, dg.degree)
    return pbjacobi_recurrence(spmv, pbj, b, x, dg.degree)


def _repl_smooth(dg: DistGAMG, st: LevelState, b: Array, x: Array) -> Array:
    """Smoother on a replicated level: literally the single-device one."""
    return apply_smoother(st, b, x, dg.smoother, dg.degree)


def _boundary_restrict(dg: DistGAMG, r: Array) -> Array:
    """Cross sharded->replicated: one all-gather of the fine residual
    slabs, reassemble the global vector, apply the global restriction
    rank-redundantly.  The only V-cycle communication the replicated tail
    costs."""
    sw = dg.switch
    g = lax.all_gather(r, AXIS, axis=0, tiled=True)   # (ndev*rpad, bs[, k])
    rg = g[jnp.asarray(sw.row_sel)]                   # (nbr_f, bs[, k])
    flat = rg.reshape((rg.shape[0] * rg.shape[1],) + rg.shape[2:])
    if sw.r_ell is not None:
        return apply_ell(sw.r_ell, flat)
    return apply_ell_t(sw.p_g, sw.p_t, flat)


def _boundary_prolong(dg: DistGAMG, a, xc: Array, overlap: bool,
                      accum=None) -> Array:
    """Cross replicated->sharded: the boundary prolongator's plan indices
    address the replicated correction directly (``"replicated"`` halo), so
    re-slicing the correction back into row slabs moves zero bytes — the
    split-apply router degenerates to the blocking rendering (nothing to
    hide) and the jaxpr is the historical one under either knob value.
    ``a`` is the boundary level's sharded-args dict (``pb_idx``/``pb_data``
    are this rank's slab of the re-slicing prolongator)."""
    sw = dg.switch
    xcb = xc.reshape((sw.nbr_c, sw.bs_c) + xc.shape[1:])
    return _rank_spmv(sw.p_b, a, "pb_", a["pb_data"], xcb, overlap,
                      accum=accum)


def _rank_vcycle(dg: DistGAMG, args, states, chol: Array, b: Array,
                 overlap: bool) -> Array:
    """One V-cycle over the placed hierarchy (zero initial guess).

    Sharded levels run the slab recurrences with halo-window SpMVs;
    replicated levels run the single-device core recurrences on global
    vectors, rank-redundantly, with zero communication.  The two layouts
    meet at the switch: restriction crosses it with one all-gather
    (``_boundary_restrict``), prolongation re-slices the replicated
    correction back into slabs for free (``_boundary_prolong``).

    Every sharded operator apply threads the policy's kernel accumulator
    so sub-fp32 hierarchies contract at ``accum_dtype`` (None — native —
    for the stock f64/f32 policies).
    """
    acc = dg.precision.kernel_accum_dtype
    ns = len(dg.levels)
    bs_stack, x_stack = [], []
    rhs = b
    for li, lv in enumerate(dg.levels):
        a = args["levels"][li]
        st = states[li]

        def spmv_a(v, a=a, st=st, lv=lv):
            return _rank_spmv(lv.a_op, a, "a_", st["a_data"], v, overlap,
                              accum=acc)

        x = _rank_smooth(dg, spmv_a, st, rhs, jnp.zeros_like(rhs))
        r = rhs - spmv_a(x)
        bs_stack.append(rhs)
        x_stack.append(x)
        if li == ns - 1 and dg.repl:
            rhs = _boundary_restrict(dg, r)
        else:
            rhs = _rank_spmv(lv.r_op, a, "r_", a["r_data"], r, overlap,
                             accum=acc)
    if dg.repl:
        # replicated tail: the single-device V-cycle on global vectors
        for li in range(ns, ns + len(dg.repl)):
            st = states[li]
            x = _repl_smooth(dg, st, rhs, jnp.zeros_like(rhs))
            r = rhs - apply_ell(st.a_ell, x)
            bs_stack.append(rhs)
            x_stack.append(x)
            rhs = apply_restriction(st, r)
        xc = jax.scipy.linalg.cho_solve((chol, True), rhs)
        for li in reversed(range(ns, ns + len(dg.repl))):
            st = states[li]
            x = x_stack[li] + apply_ell(st.p_ell, xc)
            xc = _repl_smooth(dg, st, bs_stack[li], x)
    else:
        xc = _rank_coarse_solve(dg, chol, rhs)
    for li in reversed(range(ns)):
        a = args["levels"][li]
        st = states[li]
        lv = dg.levels[li]

        def spmv_a(v, a=a, st=st, lv=lv):
            return _rank_spmv(lv.a_op, a, "a_", st["a_data"], v, overlap,
                              accum=acc)

        if li == ns - 1 and dg.repl:
            corr = _boundary_prolong(dg, a, xc, overlap, accum=acc)
        else:
            corr = _rank_spmv(lv.p_op, a, "p_", a["p_data"], xc, overlap,
                              accum=acc)
        x = x_stack[li] + corr
        xc = _rank_smooth(dg, spmv_a, st, bs_stack[li], x)
    return xc


def _rank_pcg(dg: DistGAMG, args, states, chol: Array, b: Array,
              rtol: float, maxiter: int, overlap: bool = False,
              stall_window: int = 40, x0: Array | None = None):
    """Distributed PCG — mirrors ``repro.core.krylov.pcg`` with psum dots.

    ``x0`` warm-starts from a prior iterate slab (``None`` = cold zero
    start, bitwise the classic recurrence) — the same contract as
    ``pcg(x0=...)``, threaded per rank by the warm dist march.

    Under a mixed policy the operator uses level 0's krylov-dtype payload
    copy and the V-cycle runs at the smoother dtype behind the same
    boundary cast as ``pcg(precond_dtype=...)``.

    Health mirrors ``pcg`` too: NaN/Inf, breakdown and stagnation flags
    folded into the int32 status the solver returns alongside
    (x, iters, relres, ok).  The flags read the psum reductions the
    recurrence already performs, and every rank computes them from the
    same replicated scalars — the exit decision is collective for free,
    no extra communication.  A faulted halo/spmv on ONE rank still trips
    every rank's flag within one iteration, because the corrupted value
    enters the global psum.  Clean runs are bitwise the pre-health loop.
    """
    a0 = args["levels"][0]
    st0 = states[0]
    a_data_kr = st0.get("a_data_kr", st0["a_data"])

    def apply_a(v):
        return _rank_spmv(dg.levels[0].a_op, a0, "a_", a_data_kr, v,
                          overlap)

    apply_m = wrap_precond(
        lambda r: _rank_vcycle(dg, args, states, chol, r, overlap),
        dg.precision.smoother_dtype, b.dtype)

    x = jnp.zeros_like(b) if x0 is None else x0
    r = b - apply_a(x)
    z = apply_m(r)
    p = z
    rz = _pdot(r, z)
    # dtype-aware breakdown floor (see core.krylov.pcg): an all-zero rhs
    # reports converged=True, iters=0, relres=0 at any krylov dtype
    bnorm = jnp.maximum(_pnorm(b), jnp.finfo(b.dtype).tiny)
    rnorm = _pnorm(r)
    nonf0 = ~jnp.isfinite(rnorm) | ~jnp.isfinite(rz)
    brk0 = ~nonf0 & (rz <= 0) & (rnorm > rtol * bnorm)

    def cond(state):
        (x, r, z, p, rz, rnorm, k, best, stall, brk, nonf) = state
        return ((rnorm > rtol * bnorm) & (k < maxiter)
                & ~brk & ~nonf & (stall < stall_window))

    def body(state):
        (x, r, z, p, rz, rnorm, k,
         (best_x, best_rnorm), stall, brk, nonf) = state
        Ap = inject.maybe("spmv", apply_a(p), step=k)
        pAp = _pdot(p, Ap)
        alpha = rz / pAp
        x_new = x + alpha * p
        r_new = r - alpha * Ap
        z_new = inject.maybe("precond", apply_m(r_new), step=k)
        rz_new = _pdot(r_new, z_new)
        beta = rz_new / rz
        p_new = z_new + beta * p
        rnorm_new = _pnorm(r_new)
        nonf_new = (~jnp.isfinite(pAp) | ~jnp.isfinite(rnorm_new)
                    | ~jnp.isfinite(rz_new))
        brk_new = ~nonf_new & ((pAp <= 0)
                               | ((rz_new <= 0)
                                  & (rnorm_new > rtol * bnorm)))
        ok_step = ~(nonf_new | brk_new)
        x = jnp.where(ok_step, x_new, x)
        r = jnp.where(ok_step, r_new, r)
        z = jnp.where(ok_step, z_new, z)
        p = jnp.where(ok_step, p_new, p)
        rz = jnp.where(ok_step, rz_new, rz)
        rnorm = jnp.where(ok_step, rnorm_new, rnorm)
        improved = ok_step & (rnorm_new < best_rnorm)
        best_x = jnp.where(improved, x_new, best_x)
        best_rnorm = jnp.where(improved, rnorm_new, best_rnorm)
        stall = jnp.where(improved, 0, stall + 1)
        return (x, r, z, p, rz, rnorm, k + 1, (best_x, best_rnorm),
                stall, brk | brk_new, nonf | nonf_new)

    best_rnorm0 = jnp.where(jnp.isfinite(rnorm), rnorm, jnp.inf)
    state = (x, r, z, p, rz, rnorm, jnp.asarray(0), (x, best_rnorm0),
             jnp.asarray(0), brk0, nonf0)
    (x, r, z, p, rz, rnorm, k, (best_x, best_rnorm), stall, brk, nonf) = \
        lax.while_loop(cond, body, state)
    converged = rnorm <= rtol * bnorm
    x_out = jnp.where(converged, x, best_x)
    rnorm_out = jnp.where(converged, rnorm, best_rnorm)
    stag = ~converged & ~brk & ~nonf & (stall >= stall_window)
    status = status_of(converged, brk, nonf, stag)
    return x_out, k, rnorm_out / bnorm, converged, status


def _rank_block_pcg(dg: DistGAMG, args, states, chol: Array, b: Array,
                    rtol: float, maxiter: int, overlap: bool = False,
                    stall_window: int = 40, x0: Array | None = None):
    """Distributed masked panel PCG over (rpad, bs, k) slabs.

    The recurrence body is ``repro.multirhs.block_krylov.block_pcg``
    itself (single source of truth, like the shared smoother
    recurrences in ``core.vcycle``) with the per-column reductions
    replaced by psum versions — the per-column iteration parity with the
    single-device batched path that the selftest's multi-RHS check
    asserts depends on the two paths sharing this body.
    """
    a0 = args["levels"][0]
    st0 = states[0]
    a_data_kr = st0.get("a_data_kr", st0["a_data"])

    def apply_a(v):
        return _rank_spmv(dg.levels[0].a_op, a0, "a_", a_data_kr, v,
                          overlap)

    def apply_m(r):
        return _rank_vcycle(dg, args, states, chol, r, overlap)

    res = block_pcg(apply_a, apply_m, b, x0=x0, rtol=rtol, maxiter=maxiter,
                    col_dot=_pdot_cols, col_norm=_pnorm_cols,
                    precond_dtype=dg.precision.smoother_dtype,
                    stall_window=stall_window)
    return res.x, res.iters, res.relres, res.converged, res.health.status


# ---------------------------------------------------------------------------
# Front door
# ---------------------------------------------------------------------------

def make_dist_solver(dg: DistGAMG, setupd: GAMGSetup, mesh, *,
                     rtol: float = 1e-8, maxiter: int = 200,
                     warm_start: bool = False):
    """Jitted distributed hot path:
    ``(args, a0, b) -> (x, iters, relres, ok, status)``.

    ``warm_start=True`` is a *build-time* knob that adds a trailing
    ``x0`` slab input (scattered like ``b``) to the signature —
    ``(args, a0, b, x0)`` — warm-starting each rank's CG from the prior
    iterate, the distributed twin of ``pcg(x0=...)``.  The default
    signature and its traced program are unchanged.

    ``args`` from ``dg.sharded_args``, ``a0`` from
    ``dg.scatter_fine_payloads`` (new fine operator values — the Newton
    step), ``b`` from ``dg.scatter_vector``.  One shard_map program:
    recompute the hierarchy, then CG-solve.  Outputs are stacked per rank;
    iters/relres/converged/status are replicated, take index 0.
    ``status`` is the int32 health code of ``repro.robust.health``
    (``STATUS_NAMES``), scalar for a vector solve, per-column for a panel.

    ``b`` may be a single scattered vector (slabs ``(rpad, bs)``) or a
    scattered panel (``(rpad, bs, k)`` — ``dg.scatter_vector`` on an
    ``(n, k)`` payload): the panel case runs the masked multi-RHS PCG and
    iters/relres/converged come back per column (shape ``(k,)``).

    Placement is baked into ``dg``: agglomerated levels (``dg.repl``) are
    closed over as replicated constants, so the same program serves any
    sharded/replicated split without signature changes.
    """
    del setupd  # structure is baked into dg; kept for call-site symmetry

    def rank_body(args, a0, b, x0):
        # consumed at trace time, like the kernel path knobs: every rank
        # traces the same Python, so the schedule choice is collective-safe
        overlap = resolve_overlap() == "on"
        # metadata-only stage scopes: identical on every rank, collective-safe
        with obs_trace.scope("dist/recompute"):
            states, chol = _rank_recompute(dg, args, a0, overlap)
        run_pcg = _rank_block_pcg if b.ndim == 3 else _rank_pcg
        with obs_trace.scope("dist/pcg"):
            x, k, relres, ok, status = run_pcg(dg, args, states, chol, b,
                                               rtol, maxiter, overlap,
                                               x0=x0)
        return (x[None], k[None], relres[None], ok[None], status[None])

    if warm_start:
        def rank_fn(args, a0, b, x0):
            args, a0, b, x0 = jax.tree.map(
                lambda t: t[0], (args, a0, b, x0))
            return rank_body(args, a0, b, x0)
        in_specs = (P(AXIS),) * 4
    else:
        def rank_fn(args, a0, b):
            args, a0, b = jax.tree.map(lambda t: t[0], (args, a0, b))
            return rank_body(args, a0, b, None)
        in_specs = (P(AXIS),) * 3

    sharded = jax.shard_map(rank_fn, mesh=mesh, in_specs=in_specs,
                            out_specs=P(AXIS), check_vma=False)
    return jax.jit(sharded)


def make_dist_coeff_solver(dg: DistGAMG, da: DistAssembly, mesh, *,
                           rtol: float = 1e-8, maxiter: int = 200,
                           warm_start: bool = False):
    """Jitted distributed *coefficient* hot path:
    ``(args, aargs, E, nu, b) -> (x, iters, relres, ok, status)``.

    The quasi-static front door: instead of a pre-assembled value stream
    (``make_dist_solver``'s ``a0``), each rank receives its coefficient
    slabs (``da.scatter_fields``) and runs device FEM assembly, the
    state-gated recompute and the CG solve as one shard_map program —
    the distributed twin of ``gamg.make_coeff_recompute``.  ``aargs``
    from ``da.sharded_args()``; everything else as ``make_dist_solver``
    (panel ``b`` supported the same way).

    ``warm_start=True`` (build-time) appends an ``x0`` slab input —
    ``(args, aargs, E, nu, b, x0)`` — so a time march can feed each
    rank's previous iterate straight back in: the slab-sharded twin of
    the ``repro.sim`` march step, exercised by the
    ``REPRO_SELFTEST_MARCH`` selftest section.
    """

    def rank_body(args, aargs, E, nu, b, x0):
        overlap = resolve_overlap() == "on"
        with obs_trace.scope("dist/assemble"):
            a_slab = _rank_assemble(da, aargs, E, nu)
        with obs_trace.scope("dist/recompute"):
            states, chol = _rank_recompute(dg, args, a_slab, overlap)
        run_pcg = _rank_block_pcg if b.ndim == 3 else _rank_pcg
        with obs_trace.scope("dist/pcg"):
            x, k, relres, ok, status = run_pcg(dg, args, states, chol, b,
                                               rtol, maxiter, overlap,
                                               x0=x0)
        return (x[None], k[None], relres[None], ok[None], status[None])

    if warm_start:
        def rank_fn(args, aargs, E, nu, b, x0):
            args, aargs, E, nu, b, x0 = jax.tree.map(
                lambda t: t[0], (args, aargs, E, nu, b, x0))
            return rank_body(args, aargs, E, nu, b, x0)
        in_specs = (P(AXIS),) * 6
    else:
        def rank_fn(args, aargs, E, nu, b):
            args, aargs, E, nu, b = jax.tree.map(
                lambda t: t[0], (args, aargs, E, nu, b))
            return rank_body(args, aargs, E, nu, b, None)
        in_specs = (P(AXIS),) * 5

    sharded = jax.shard_map(rank_fn, mesh=mesh, in_specs=in_specs,
                            out_specs=P(AXIS), check_vma=False)
    return jax.jit(sharded)


