"""Nekbone-equivalent problem setup: global operator, RHS, solve.

Composes the matrix-free pipeline of Algorithm 1 (scatter -> axhelm ->
gather) into a global SPD operator on unique dofs and runs PCG, mirroring the
Nekbone proxy app (Poisson with Dirichlet mask, or Helmholtz which is SPD
without masking).

With a `SolverShardCtx` (distributed.context) the same pipeline runs
element-sharded under `shard_map` over a 1-D device mesh: each device owns a
contiguous slab or Cartesian sub-box of elements (`make_solver_ctx(grid=)`
selects the shard-grid shape; boxes shrink the per-shard interface surface
to O((E/S)^(2/3))), the gather becomes a per-shard segment-sum plus one
psum over only the interface dofs — or per-neighbour ppermute rounds — and
PCG's dot products psum scalars; the whole while_loop stays inside the
sharded region.  See DESIGN.md.
"""

from __future__ import annotations

import functools
import itertools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import axhelm as axhelm_mod
from repro.core import gather_scatter as gs
from repro.core import geometry
from repro.core.mesh_gen import BoxMesh, MeshPartition, partition_elements
from repro.core.pcg import PCGResult, owned_dot, pcg, pcg_block, refine
from repro.core.spectral import SpectralBasis, basis as make_basis
from repro.resilience import inject as fault_inject

__all__ = ["NekboneProblem", "ShardedNekboneProblem", "setup_problem",
           "solve", "make_block_solver", "flop_count"]


class NekboneProblem(NamedTuple):
    """`op`/`diag` are ALWAYS full precision (the problem `dtype`): with
    ``precision="bf16_x32"`` the mixed-precision machinery lives in the
    extra ``op_lo`` field (the bfloat16 operator the inner refinement
    sweeps run on) while everything keyed off ``diag.dtype`` — tolerance
    eps, true-residual verification, serving casts — correctly reads the
    OUTER precision."""

    op: object                  # callable global operator A(x)
    diag: jnp.ndarray           # diag(A) on global dofs (for JACOBI)
    mask: Optional[jnp.ndarray]  # Dirichlet mask (None => no mask)
    mesh: BoxMesh
    basis: SpectralBasis
    d: int
    helmholtz: bool
    variant: str
    backend: str = "reference"
    precision: Optional[str] = None   # None (plain) or "bf16_x32"
    op_lo: object = None              # bf16 operator for the inner sweeps


class ShardedNekboneProblem(NamedTuple):
    """An element-sharded Nekbone problem (see `setup_problem(shard_ctx=)`).

    `op` has global-field semantics (Ng[, d] -> Ng[, d]) but runs the
    scatter -> axhelm -> gather pipeline under `shard_map`; `run_pcg` runs
    the whole PCG while_loop inside the sharded region and returns a
    `PCGResult` whose `x` has been reassembled onto global dofs (owner
    writes its dofs; interface values are identical on every shard by
    construction, so owner-wins is exact).
    """

    op: object                   # global-semantics A(x) via shard_map
    diag: jnp.ndarray            # diag(A) on global dofs
    mask: Optional[jnp.ndarray]  # Dirichlet mask on global dofs
    mesh: BoxMesh
    basis: SpectralBasis
    d: int
    helmholtz: bool
    variant: str
    backend: str
    shard_ctx: object            # distributed.context.SolverShardCtx
    partition: MeshPartition
    run_pcg: object              # (b, tol, max_iter, precond=) -> PCGResult
    precision: Optional[str] = None  # None (plain) or "bf16_x32"
    run_refined: object = None   # sharded fp32-outer/bf16-inner runner
    shard_arrays: object = None  # per-shard operands, split over the mesh


def _global_op(element_op, mesh: BoxMesh, mask):
    """A(x) = M Q^T A_e Q M x + (I - M) x  (M = Dirichlet zero-mask).

    The identity on masked dofs keeps the operator SPD on the full vector
    space so plain CG applies (the masked dofs just carry x through).

    Shape-polymorphic over batch axes: accepts (Ng,), (Ng, d), the
    RHS-batched (Ng, nrhs) and (Ng, d, nrhs).  Every axis after the dof
    axis is flattened into ONE component column (c = d*nrhs) so a single
    scatter/segment-sum serves the whole batch, the element kernel sees
    (E, c, N1^3) and amortizes its per-element geometry across all c
    columns, and the layout is restored on exit.
    """
    ids = jnp.asarray(mesh.global_ids)
    ng = mesh.n_global

    def apply(x):
        x_in = x
        bshape = x.shape[1:]
        if mask is not None:
            with obs.scope("vec.mask"):
                m = gs._expand_mask(mask, x)
                x = jnp.where(m, 0.0, x)
        xf = x.reshape((ng, -1)) if bshape else x
        xl = gs.scatter(xf, ids)                     # (E, N1,N1,N1[, c])
        with obs.scope("axhelm"):
            if bshape:
                xl = jnp.moveaxis(xl, -1, 1)         # (E, c, N1,N1,N1)
            yl = element_op(xl)
            if bshape:
                yl = jnp.moveaxis(yl, 1, -1)
        y = gs.gather(yl, ids, ng)
        if bshape:
            y = y.reshape((ng,) + bshape)
        if mask is not None:
            with obs.scope("vec.mask"):
                y = jnp.where(m, x_in, y)
        return y

    return apply


def _global_diag(mesh: BoxMesh, b: SpectralBasis, factors, lam0, lam1,
                 helmholtz: bool, d: int, mask, dtype) -> jnp.ndarray:
    """Jacobi diagonal on global dofs from per-element factor arrays."""
    lam0n = None if lam0 is None else jnp.broadcast_to(
        jnp.asarray(lam0, dtype=dtype), (len(mesh.verts),) + (b.n1,) * 3)
    lam1n = None if lam1 is None else jnp.broadcast_to(
        jnp.asarray(lam1, dtype=dtype), (len(mesh.verts),) + (b.n1,) * 3)
    dl = axhelm_mod.element_diagonal(factors,
                                     jnp.asarray(b.dhat, dtype=dtype),
                                     lam0=lam0n, lam1=lam1n,
                                     helmholtz=helmholtz)
    diag = gs.gather(dl, jnp.asarray(mesh.global_ids), mesh.n_global)
    if d > 1:
        diag = jnp.broadcast_to(diag[:, None], (mesh.n_global, d))
    if mask is not None:
        m = mask if d == 1 else mask[:, None]
        diag = jnp.where(m, 1.0, diag)
    return diag


PRECISIONS = (None, "bf16_x32")


@obs.span("setup.problem")
def setup_problem(mesh: BoxMesh, variant: str = "precomputed", d: int = 1,
                  helmholtz: bool = False, lam0=None, lam1=None,
                  dirichlet: bool | None = None,
                  dtype=jnp.float32,
                  backend: str | None = None,
                  block_elems=None,
                  interpret: bool | None = None,
                  shard_ctx=None,
                  nrhs: int | None = None,
                  precision: str | None = None) -> NekboneProblem:
    """Build the global operator + Jacobi diagonal for a mesh/variant.

    `backend` selects the element-kernel implementation ("reference",
    "pallas", or "auto"; see core.axhelm.make_axhelm) — with "pallas" the
    PCG while_loop drives the Pallas kernel every iteration.  `block_elems`
    and `interpret` are forwarded to the Pallas path ("auto" autotunes).

    `shard_ctx` (a `distributed.context.SolverShardCtx`, e.g. from
    `make_solver_ctx(devices=N)`) partitions the elements over a 1-D device
    mesh — as linear slabs, or as the Cartesian sub-boxes of
    `shard_ctx.grid` — and returns a `ShardedNekboneProblem` whose solve
    runs under `shard_map`.  `shard_ctx=None` — and any 1-device context,
    which `make_solver_ctx` already collapses to None — takes the
    single-device path below, bit-identical to previous behaviour.

    `nrhs` declares the RHS-batch width later `solve` calls will use
    (defaults to `shard_ctx.nrhs`, else 1).  The operator itself is
    shape-polymorphic — any batch width works at solve time — but the
    declaration matters for `block_elems="auto"`: the autotune sweep then
    runs at setup, outside any jit trace, with the VMEM feasibility model
    charged for the declared batch (an X window `nrhs`x larger, geometry
    unchanged).

    `precision="bf16_x32"` builds the mixed-precision solve: the problem's
    `op`/`diag` stay at full precision (`dtype` must be float32 — it IS
    the outer precision) and a SECOND bfloat16 operator is built over the
    same mesh/coefficients (`op_lo` here, a second sharded elem_ops set on
    the sharded path).  `solve` then dispatches to `core.pcg.refine`: the
    true residual and the correction accumulate in fp32, the inner PCG
    sweeps run the bf16 operator — MXU-width compute with a full-precision
    safety net (see DESIGN.md "Mixed precision").
    """
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; expected one "
                         f"of {PRECISIONS}")
    if precision == "bf16_x32" and jnp.dtype(dtype) != jnp.dtype(
            jnp.float32):
        raise ValueError(
            f"precision='bf16_x32' keeps the outer solve in float32 (the "
            f"bf16 operator is the separate inner machinery); pass "
            f"dtype=jnp.float32, got {jnp.dtype(dtype).name}")
    b = make_basis(mesh.order)
    verts = jnp.asarray(mesh.verts, dtype=dtype)
    if nrhs is None:
        nrhs = getattr(shard_ctx, "nrhs", None) or 1
    if helmholtz and lam1 is None:
        lam1 = jnp.asarray(0.1, dtype=dtype)  # Nekbone's h2-like shift
    if helmholtz and lam0 is None:
        lam0 = jnp.asarray(1.0, dtype=dtype)
    if dirichlet is None:
        dirichlet = not helmholtz  # Poisson needs the mask to be SPD
    mask = jnp.asarray(mesh.boundary) if dirichlet else None
    n_shards = shard_ctx.n_shards if shard_ctx is not None else 1
    part = None
    e_shard = len(mesh.verts)
    if n_shards > 1:
        with obs.span("setup.partition"):
            part = partition_elements(mesh, n_shards,
                                      grid=getattr(shard_ctx, "grid", None))
        e_shard = part.e_per_shard
        if getattr(shard_ctx, "exchange", "psum") == "neighbour":
            # overlapped exchange: ONE launch plan decides both the kernel
            # sub-batch split and the autotune clamp (see
            # `_neighbour_launch_plan` — the two used to be separate
            # conditions that could drift on the degenerate cases)
            split, _, e_shard = _neighbour_launch_plan(part)
            if not split:
                warnings.warn(
                    f"exchange='neighbour' has no interior elements to "
                    f"overlap the halo exchange with (every shard slot up "
                    f"to e_iface={part.e_iface} of e_per_shard="
                    f"{part.e_per_shard} is interface on some shard, grid="
                    f"{part.grid}): running the unsplit pipeline — the "
                    f"exchange is still point-to-point but nothing hides "
                    f"it.  A box decomposition (make_solver_ctx(grid="
                    f"'auto')) shrinks the interface surface and restores "
                    f"the overlap window.", UserWarning, stacklevel=2)
    block_arg = block_elems
    with obs.span("setup.block"):
        block_elems = _resolve_auto_block(variant, b, d, helmholtz, dtype,
                                          backend, block_elems, interpret,
                                          nrhs, e_shard)
        block_lo = None
        if precision == "bf16_x32":
            # the bf16 operator tunes its own block size: smaller windows,
            # but a full-width fp32 accumulator (see kernels/axhelm/tune.py)
            block_lo = _resolve_auto_block(variant, b, d, helmholtz,
                                           jnp.bfloat16, backend, block_arg,
                                           interpret, nrhs, e_shard)

    if part is not None:
        return _setup_problem_sharded(
            mesh, b, variant, d, helmholtz, lam0, lam1, mask, dtype,
            backend, block_elems, interpret, shard_ctx, part,
            precision, block_lo)

    with obs.span("setup.geometry"):
        op = axhelm_mod.make_axhelm(variant, b, verts, lam0=lam0, lam1=lam1,
                                    helmholtz=helmholtz, dtype=dtype,
                                    backend=backend, block_elems=block_elems,
                                    interpret=interpret)
    apply = _global_op(op.apply, mesh, mask)
    with obs.span("setup.diag"):
        diag = _global_diag(mesh, b, op.factors, lam0, lam1, helmholtz, d,
                            mask, dtype)
    op_lo_apply = None
    if precision == "bf16_x32":
        lo = jnp.bfloat16
        with obs.span("setup.geometry"):
            op_lo = axhelm_mod.make_axhelm(
                variant, b, verts.astype(lo), lam0=_cast_opt(lam0, lo),
                lam1=_cast_opt(lam1, lo), helmholtz=helmholtz, dtype=lo,
                backend=backend, block_elems=block_lo, interpret=interpret)
        op_lo_apply = _global_op(op_lo.apply, mesh, mask)
    return NekboneProblem(apply, diag, mask, mesh, b, d, helmholtz, variant,
                          op.backend, precision, op_lo_apply)


def _neighbour_launch_plan(part: MeshPartition):
    """The kernel launch plan for the overlapped neighbour exchange.

    Returns ``(split, cut, tune_elems)``: whether the element batch is run
    as two launches (interface slots ``[0, cut)`` first, interior
    ``[cut, EP)`` while the permutes fly), and the element count the block
    autotuner must clamp to.

    Split mode clamps to the SMALLER sub-batch — a block no launch pads up
    to (padding the interface launch would delay `neighbour_start`, the
    overlap window itself); the larger launch just takes more grid steps.

    Degenerate cases fall back to ONE unsplit launch of the full padded
    batch, clamped to its real size ``EP``: ``e_iface == e_per_shard``
    (some shard is all-interface — common for thin slabs at high shard
    counts — so no static split point can leave interior work) and the
    defensive ``e_iface == 0`` (no interface at all).  The solver body and
    the setup-time autotune clamp both read THIS plan, so they cannot
    disagree about which launches exist.
    """
    ep, ei = part.e_per_shard, part.e_iface
    split = 0 < ei < ep
    cut = ei if split else ep
    tune_elems = min(ei, ep - ei) if split else ep
    return split, cut, tune_elems


def _resolve_auto_block(variant: str, b: SpectralBasis, d: int,
                        helmholtz: bool, dtype, backend, block_elems,
                        interpret, nrhs: int, e_shard: int):
    """Resolve block_elems="auto" to a concrete block size at setup time.

    Runs the tune.py sweep (cache-backed) with the declared RHS-batch width
    NOW — outside jit and outside `shard_map` tracing — instead of on the
    first traced apply.  The kernel pins helmholtz per variant the same way
    ops.axhelm does, so the tune cache key matches the one the apply-time
    resolution would use; `e_shard` (elements per shard) keeps the
    per-shard clamp the lazy path applied from x.shape.  Anything other
    than "auto" passes through.
    """
    if block_elems != "auto":
        return block_elems
    if axhelm_mod._resolve_backend(backend, dtype) != "pallas":
        return None  # reference backend has no block knob
    from repro.kernels.axhelm import tune

    kernel_helm = {"merged": True, "partial": False}.get(variant, helmholtz)
    return tune.get_block_elems(variant, b.n1, d, dtype,
                                helmholtz=kernel_helm, autotune_now=True,
                                interpret=interpret, nrhs=nrhs,
                                e_total=e_shard)


def _cast_opt(lam, dtype):
    """Cast an optional scalar/field coefficient (None passes through)."""
    return None if lam is None else jnp.asarray(lam, dtype)


def _diag_factors(variant: str, b: SpectralBasis, verts: jnp.ndarray):
    """Per-element factor arrays for the Jacobi diagonal — the same choices
    `make_axhelm` makes, computed on the *unpartitioned* mesh so the sharded
    setup produces the identical diagonal to the single-device path."""
    if variant == "precomputed":
        return geometry.factors_discrete(geometry.node_coords(verts, b), b)
    if variant == "parallelepiped":
        return geometry.factors_parallelepiped(verts, b)
    return geometry.factors_trilinear(verts, b)


def _partition_lam_field(lam, part: MeshPartition, dtype) -> jnp.ndarray:
    """Partition + pad an (E, N1, N1, N1) lambda field into the per-shard
    element layout: `elem_perm` order (interface-first within each shard),
    dead padding slots filled with 1.0 (any finite value works — dead
    elements' outputs land masked in the trash slot), flattened over the
    (S * EP) axis the sharded runner partitions elem_ops on."""
    lam = np.asarray(lam)
    perm = part.elem_perm                      # (S, EP); -1 on dead slots
    vals = lam[np.where(perm >= 0, perm, 0)]
    vals[perm < 0] = 1.0
    return jnp.asarray(vals.reshape((-1,) + lam.shape[1:]), dtype=dtype)


def _setup_problem_sharded(mesh: BoxMesh, b: SpectralBasis, variant: str,
                           d: int, helmholtz: bool, lam0, lam1, mask, dtype,
                           backend, block_elems, interpret, shard_ctx,
                           part: MeshPartition, precision=None,
                           block_lo=None) -> "ShardedNekboneProblem":
    # Per-element lambda FIELDS are partitioned into the shard element
    # layout and travel as elem_ops operands; scalars pass through.  The
    # Jacobi diagonal below keeps the UNPARTITIONED fields — it is computed
    # on the whole mesh, identically to the single-device path.
    node_shape = (len(mesh.verts),) + (b.n1,) * 3
    lam_sh = []
    for name, lam in (("lam0", lam0), ("lam1", lam1)):
        if lam is not None and jnp.ndim(lam) > 0:
            if jnp.shape(lam) != node_shape:
                raise ValueError(
                    f"{name} must be a scalar or a per-node (E, N1, N1, N1) "
                    f"field of shape {node_shape} (the unpartitioned mesh "
                    f"layout), got {jnp.shape(lam)}")
            lam = _partition_lam_field(lam, part, dtype)
        lam_sh.append(lam)
    flat_verts = jnp.asarray(part.verts.reshape(-1, 8, 3), dtype=dtype)
    with obs.span("setup.geometry"):
        elem_ops, elem_apply, backend_used = axhelm_mod.make_axhelm_elem_ops(
            variant, b, flat_verts, lam0=lam_sh[0], lam1=lam_sh[1],
            helmholtz=helmholtz, dtype=dtype, backend=backend,
            block_elems=block_elems, interpret=interpret)
    verts = jnp.asarray(mesh.verts, dtype=dtype)
    with obs.span("setup.diag"):
        diag = _global_diag(mesh, b, _diag_factors(variant, b, verts), lam0,
                            lam1, helmholtz, d, mask, dtype)
    elem_ops_lo = elem_apply_lo = None
    if precision == "bf16_x32":
        # a SECOND operand set at bfloat16 over the same partition: the
        # inner refinement sweeps shard and exchange exactly like the
        # fp32 operator, just half-width (and codec-compressed on the
        # wire when ctx.compress says so)
        lo = jnp.bfloat16
        with obs.span("setup.geometry"):
            elem_ops_lo, elem_apply_lo, _ = axhelm_mod.make_axhelm_elem_ops(
                variant, b, flat_verts.astype(lo),
                lam0=_cast_opt(lam_sh[0], lo),
                lam1=_cast_opt(lam_sh[1], lo), helmholtz=helmholtz,
                dtype=lo, backend=backend, block_elems=block_lo,
                interpret=interpret)
    apply_global, run_pcg, run_refined, arrays = _build_sharded_runner(
        part, shard_ctx, elem_ops, elem_apply, mask, diag, d,
        mesh.n_global, elem_ops_lo=elem_ops_lo,
        elem_apply_lo=elem_apply_lo,
        compress=getattr(shard_ctx, "compress", None))
    return ShardedNekboneProblem(apply_global, diag, mask, mesh, b, d,
                                 helmholtz, variant, backend_used, shard_ctx,
                                 part, run_pcg, precision, run_refined,
                                 arrays)


def _build_sharded_runner(part: MeshPartition, ctx, elem_ops, elem_apply,
                          mask, diag, d: int, n_global: int, *,
                          elem_ops_lo=None, elem_apply_lo=None,
                          compress=None):
    """Wire the per-shard pipeline into `shard_map` over `ctx`'s 1-D mesh.

    Index sets are flattened over a leading (n_shards * per_shard) axis and
    sharded with P(axis) so every device receives exactly its shard's slice.
    With ctx.exchange == "psum" the only collectives inside the shard region
    are the interface-dof psum in `gather_sharded` and the scalar psums of
    `owned_dot`; with "neighbour" the interface psum is replaced by
    point-to-point `ppermute` rounds launched BEFORE the interior-element
    compute, so the exchange and the bulk of the axhelm work can overlap.

    `elem_ops_lo`/`elem_apply_lo` (the bfloat16 operand set of a
    ``precision="bf16_x32"`` problem) additionally wire `run_refined`: the
    whole `core.pcg.refine` loop inside ONE sharded region — fp32 true
    residual through the full-precision operator, bf16 inner sweeps
    through the lo operator, both sharing the same index sets and
    partition.  `compress` (ctx.compress) is the wire codec of the
    neighbour exchange; it applies to the operator that runs the INNER
    sweeps — the lo operator when one exists, else the plain operator —
    while a refined problem's fp32 outer operator always exchanges at
    full width (the outer residual is the safety net; compressing it
    would re-introduce the very floor the refinement removes).

    Every per-shard array (element operands, index sets, the local
    diagonal and mask) is placed split over the mesh at setup and reaches
    the jitted runners as an argument, never as a captured constant: each
    device holds its own shard at rest, and a call moves only the vectors.

    Returns ``(apply_global, run_pcg, run_refined, arrays)``: each runner
    is its jitted function with `arrays` — the pytree of placed per-shard
    operands — bound as the first argument (``.func`` lowers it alone);
    `run_refined` is None without a lo operand set.
    """
    axis = ctx.axis
    s, ep, nl, ns = (part.n_shards, part.e_per_shard, part.n_local,
                     part.n_shared)
    n1 = part.local_ids.shape[-1]
    pe = P(axis)
    split_over_mesh = NamedSharding(ctx.mesh, pe)

    def place(tree):
        return jax.device_put(tree, split_over_mesh)

    l2g = part.local_to_global.reshape(-1)
    mask_loc = mask[l2g] if mask is not None else np.zeros(s * nl, bool)
    has_mask = mask is not None
    neighbour = getattr(ctx, "exchange", "psum") == "neighbour"
    # static interface/interior launch plan (see _neighbour_launch_plan):
    # slots [0, cut) cover every interface element on every shard; the
    # degenerate all-interface case falls back to one unsplit launch
    split, cut, _ = _neighbour_launch_plan(part)
    nbr_args = ()
    if neighbour:
        nbr_args = tuple(
            t.reshape(-1)
            for j in range(len(part.nbr_offsets))
            for t in (part.nbr_lo_idx[j], part.nbr_lo_mask[j],
                      part.nbr_hi_idx[j], part.nbr_hi_mask[j]))
    with obs.span("setup.place"):
        arrays = place(dict(
            ops=elem_ops, ops_lo=elem_ops_lo, diag=diag[l2g], l2g=l2g,
            idx=(part.local_ids.reshape(s * ep, n1, n1, n1),
                 part.shared_idx.reshape(-1),
                 part.shared_present.reshape(-1),
                 part.owned_mask.reshape(-1), part.valid_mask.reshape(-1),
                 mask_loc) + nbr_args))

    ops_specs = jax.tree.map(lambda _: pe, elem_ops)
    idx_specs = (pe,) * len(arrays["idx"])
    expand = gs._expand_mask

    def localize(xg, a):
        with obs.scope("gs.q"):
            xl = xg[a["l2g"]]
            return jnp.where(expand(a["idx"][4], xl), xl, 0)  # valid slots

    def globalize(xl, a):
        with obs.scope("gs.qt"):
            w = expand(a["idx"][3], xl)                     # owned dofs
            shape = (n_global,) + xl.shape[1:]
            return jnp.zeros(shape, xl.dtype).at[a["l2g"]].add(
                jnp.where(w, xl, 0))

    def _make_a_op(apply_fn, wire):
        """The per-shard operator body for ONE element-kernel apply fn.

        `wire` is the halo codec its neighbour exchange sends with (None
        — full width).  The hi and lo operators of a refined problem are
        two instances of this factory over the same index sets.
        """

        def _elem_batch(xl, eo, lid, lo, hi, bshape):
            """axhelm + local gather on element slots [lo, hi)."""
            with obs.scope("axhelm"):
                xb = xl[lo:hi]
                eob = jax.tree.map(lambda a: a[lo:hi], eo)
                yb = apply_fn(xb, eob)
                if bshape:
                    yb = jnp.moveaxis(yb, 1, -1)
            with obs.scope("gs.qt"):
                return gs.gather(yb, lid[lo:hi], nl)

        def a_op_local(x, eo, lid, sidx, spres, own, val, m, *nbr,
                       it=None, fault=None, fdof=None):
            """Per-shard A(x): scatter -> axhelm -> sharded gather (+ mask).

            Shape-polymorphic like `_global_op`: trailing batch axes (d,
            nrhs, or both) are flattened into one component column, so the
            interface exchange is ONE (NS, c) psum — or one set of
            per-neighbour ppermutes — for the whole RHS batch.

            In neighbour mode the interface elements run FIRST: their
            local gather completes every shared-dof partial, the ppermute
            rounds launch, and the interior elements (which by
            construction touch no shared dof) compute while the permutes
            are in flight.

            `fault` (a static `resilience.inject.FaultSpec`, threaded from
            `run_pcg`) corrupts THIS shard pipeline when the traced
            iteration counter `it` hits its key: point faults
            (nan/bitflip) poison the precomputed local dof `fdof` after
            all masking, a drop_exchange fault makes the flagged shard
            keep its pre-exchange local partials (shared dofs lose every
            remote contribution for that application, exactly a lost
            neighbour message).  `fault=None` — the default and the
            `apply_global` path — traces the identical computation as
            before.
            """
            x_in = x
            bshape = x.shape[1:]
            if has_mask:
                with obs.scope("vec.mask"):
                    x = jnp.where(expand(m, x), 0.0, x)
            xf = x.reshape((x.shape[0], -1)) if bshape else x
            with obs.scope("gs.q"):
                xl = xf[lid]                          # (EP, N1,N1,N1[, c])
            if bshape:
                with obs.scope("axhelm"):
                    xl = jnp.moveaxis(xl, -1, 1)
            fire = None
            if fault is not None:
                fire = jnp.logical_and(
                    jnp.asarray(it, jnp.int32) == fault.iteration,
                    jax.lax.axis_index(axis) == fault.shard)
            if neighbour:
                rounds = gs.neighbour_rounds(part.nbr_offsets, s, nbr)
                y = _elem_batch(xl, eo, lid, 0, cut, bshape)
                recvs = gs.neighbour_start(y, rounds, axis,
                                           compress=wire)  # in flight
                if split:
                    y_interior = _elem_batch(xl, eo, lid, cut, ep, bshape)
                    with obs.scope("gs.qt"):
                        y = y + y_interior
                if wire is not None:
                    # interior elements touch no shared dof, so this still
                    # rounds exactly the partials the sends encoded; every
                    # sharer then sums the same codec-rounded set (see
                    # gs.halo_self_round — skipping it lets sharers drift)
                    y = gs.halo_self_round(y, sidx, spres, wire)
                y_pre = y
                y = gs.neighbour_finish(y, rounds, recvs, compress=wire)
            else:
                y_pre = _elem_batch(xl, eo, lid, 0, ep, bshape)
                y = gs.exchange_shared(y_pre, sidx, spres, axis)
            if fault is not None and fault.mode == "drop_exchange":
                y = jnp.where(fire, y_pre, y)
            if bshape:
                y = y.reshape((nl,) + bshape)
            with obs.scope("vec.mask"):
                if has_mask:
                    y = jnp.where(expand(m, y), x_in, y)
                # dead-element and padding slots must stay exactly zero:
                # anything accumulating there would feed inf/nan into
                # later iterations
                y = jnp.where(expand(val, y), y, 0)
            if fault is not None and fault.mode != "drop_exchange":
                y = fault_inject.poison(y, fdof, fire, fault)
            return y

        return a_op_local

    a_op_local = _make_a_op(elem_apply,
                            compress if elem_apply_lo is None else None)
    a_op_lo_local = (None if elem_apply_lo is None
                     else _make_a_op(elem_apply_lo, compress))

    # the replication check is off: the bodies psum to replicated outputs,
    # which the static varying-axes check cannot infer
    smap = functools.partial(jax.shard_map, mesh=ctx.mesh, check_vma=False)

    @jax.jit
    def _apply_global(a, xg):
        body = smap(a_op_local, in_specs=(pe, ops_specs) + idx_specs,
                    out_specs=pe)
        return globalize(body(localize(xg, a), a["ops"], *a["idx"]), a)

    apply_global = functools.partial(_apply_global, arrays)

    def pcg_body(b_loc, dg, tol, max_iter, x0_loc, eo, lid, sidx, spres, own,
                 val, m, *nbr, use_jacobi, batched, window, fault, fdof):
        if fault is None:
            def a_op(x):
                return a_op_local(x, eo, lid, sidx, spres, own, val, m, *nbr)
        else:
            # iteration-aware operator: pcg threads its loop counter so the
            # fault fires on exactly one application (it == -1 on the
            # initial residual, which is never corrupted)
            def a_op(x, it):
                return a_op_local(x, eo, lid, sidx, spres, own, val, m,
                                  *nbr, it=it, fault=fault, fdof=fdof)

            a_op.takes_iteration = True

        pre = None
        if use_jacobi:
            with obs.scope("vec.precond"):
                inv_diag = 1.0 / dg

            def pre(r):
                # the diagonal has no RHS axis; broadcast it over the batch
                return (inv_diag[..., None] if batched else inv_diag) * r
        if batched:
            res = pcg_block(a_op, b_loc, x0=x0_loc, precond=pre, tol=tol,
                            max_iter=max_iter,
                            dot=owned_dot(own, axis, batched=True),
                            stagnation_window=window)
        else:
            res = pcg(a_op, b_loc, x0=x0_loc, precond=pre, tol=tol,
                      max_iter=max_iter, dot=owned_dot(own, axis),
                      stagnation_window=window)
        # scalars (per-column vectors in the batched case) are replicated
        # across shards; emit one leading slot per shard so out_specs=
        # P(axis) reassembles them into an (S,)/(S, nrhs) array
        return (res.x, res.iterations[None], res.residual[None],
                res.initial_residual[None], res.breakdown[None],
                res.status[None])

    def _validate_fault(fault):
        """Static fault checks + the poisoned local dof (None for
        drop_exchange)."""
        if not 0 <= fault.shard < s:
            raise ValueError(
                f"fault.shard {fault.shard} out of range for {s} shards")
        if fault.mode == "drop_exchange":
            return None
        if part.elem_perm[fault.shard, fault.element] < 0:
            raise ValueError(
                f"fault.element {fault.element} is a dead padding "
                f"slot on shard {fault.shard}: pick a live element")
        return fault_inject.fault_dof(part.local_ids[fault.shard], fault)

    @functools.partial(jax.jit, static_argnames=("precond",
                                                 "stagnation_window",
                                                 "fault"))
    def _run_pcg(a, b_global, tol, max_iter, precond="jacobi", x0=None,
                 stagnation_window=0, fault=None):
        # trailing axes beyond the (Ng[, d]) base layout are the RHS batch
        batched = b_global.ndim > (2 if d > 1 else 1)
        fdof = _validate_fault(fault) if fault is not None else None
        b_loc = localize(b_global, a)
        # pcg treats a zero x0 identically to x0=None (the initial
        # residual applies A either way), so the restart path can always
        # thread an explicit iterate without a second trace shape
        x0_loc = localize(x0, a) if x0 is not None else jnp.zeros_like(b_loc)
        body = smap(
            functools.partial(pcg_body, use_jacobi=precond == "jacobi",
                              batched=batched, window=stagnation_window,
                              fault=fault, fdof=fdof),
            in_specs=(pe, pe, P(), P(), pe, ops_specs) + idx_specs,
            out_specs=(pe, pe, pe, pe, pe, pe))
        x_loc, it, rr, r0, brk, st = body(
            b_loc, a["diag"], jnp.asarray(tol),
            jnp.asarray(max_iter, jnp.int32), x0_loc, a["ops"], *a["idx"])
        with obs.scope("vec.update"):
            return PCGResult(globalize(x_loc, a), it[0], rr[0], r0[0],
                             brk[0], st[0])

    run_pcg = functools.partial(_run_pcg, arrays)

    run_refined = None
    if elem_apply_lo is not None:
        ops_specs_lo = jax.tree.map(lambda _: pe, elem_ops_lo)

        def refined_body(b_loc, dg, tol, max_iter, x0_loc, eo, eo_lo, lid,
                         sidx, spres, own, val, m, *nbr, use_jacobi,
                         batched, window, fault, fdof):
            """The whole refine loop on one shard: fp32 outer residual via
            the full-precision operator, bf16 inner sweeps via the lo one.
            A `fault` is threaded into the LO operator (iteration-aware
            like pcg_body's), so the corruption recurs in EVERY sweep at
            its inner-iteration key — a persistently-broken bf16 operator,
            exactly what the precision:float32 escape-hatch rung exists
            for."""

            def a_hi(x):
                return a_op_local(x, eo, lid, sidx, spres, own, val, m,
                                  *nbr)

            if fault is None:
                def a_lo(x):
                    return a_op_lo_local(x, eo_lo, lid, sidx, spres, own,
                                         val, m, *nbr)
            else:
                def a_lo(x, it):
                    return a_op_lo_local(x, eo_lo, lid, sidx, spres, own,
                                         val, m, *nbr, it=it, fault=fault,
                                         fdof=fdof)

                a_lo.takes_iteration = True

            pre = None
            if use_jacobi:
                # the inner iterates are bf16; so is their preconditioner
                with obs.scope("vec.precond"):
                    inv_lo = (1.0 / dg).astype(jnp.bfloat16)

                def pre(r):
                    return (inv_lo[..., None] if batched else inv_lo) * r
            res = refine(a_hi, a_lo, b_loc, x0=x0_loc, precond=pre,
                         tol=tol, max_iter=max_iter,
                         dot=owned_dot(own, axis, batched=batched),
                         batched=batched,
                         inner_window=window if window else 5)
            return (res.x, res.iterations[None], res.residual[None],
                    res.initial_residual[None], res.breakdown[None],
                    res.status[None])

        @functools.partial(jax.jit, static_argnames=("precond",
                                                     "stagnation_window",
                                                     "fault"))
        def _run_refined(a, b_global, tol, max_iter, precond="jacobi",
                         x0=None, stagnation_window=0, fault=None):
            batched = b_global.ndim > (2 if d > 1 else 1)
            fdof = _validate_fault(fault) if fault is not None else None
            b_loc = localize(jnp.asarray(b_global, jnp.float32), a)
            x0_loc = localize(jnp.asarray(x0, jnp.float32), a) \
                if x0 is not None else jnp.zeros_like(b_loc)
            body = smap(
                functools.partial(refined_body,
                                  use_jacobi=precond == "jacobi",
                                  batched=batched,
                                  window=stagnation_window,
                                  fault=fault, fdof=fdof),
                in_specs=(pe, pe, P(), P(), pe, ops_specs,
                          ops_specs_lo) + idx_specs,
                out_specs=(pe, pe, pe, pe, pe, pe))
            x_loc, it, rr, r0, brk, st = body(
                b_loc, a["diag"], jnp.asarray(tol),
                jnp.asarray(max_iter, jnp.int32), x0_loc, a["ops"],
                a["ops_lo"], *a["idx"])
            with obs.scope("vec.update"):
                return PCGResult(globalize(x_loc, a), it[0], rr[0], r0[0],
                                 brk[0], st[0])

        run_refined = functools.partial(_run_refined, arrays)

    return apply_global, run_pcg, run_refined, arrays


def rhs_from_solution(problem: NekboneProblem, x_true: jnp.ndarray) -> jnp.ndarray:
    """Manufactured RHS b = A x_true (x_true zeroed on the mask first).

    `x_true` may carry a trailing RHS-batch axis — (Ng, nrhs) or
    (Ng, d, nrhs) — producing a stacked RHS block for the batched solve.
    """
    if problem.mask is not None:
        x_true = jnp.where(gs._expand_mask(problem.mask, x_true), 0.0,
                           x_true)
    return problem.op(x_true)


_SOLVE_IDS = itertools.count()   # the `solve` attr of each solve.host span


def solve(problem: NekboneProblem, b_rhs: jnp.ndarray, precond: str = "jacobi",
          tol: float = 1e-8, max_iter: int = 200,
          x0: Optional[jnp.ndarray] = None, stagnation_window: int = 0,
          fault=None) -> PCGResult:
    """Solve A x = b (PCG).

    `b_rhs` is (Ng,) for d=1 or (Ng, d) for vector problems; ONE extra
    trailing axis stacks nrhs right-hand sides — (Ng, nrhs) / (Ng, d, nrhs)
    — solved together by block-PCG (`core.pcg.pcg_block`): one operator
    application, one gather exchange and one (batched) dot per iteration
    for the whole block, with per-column convergence.  The returned
    `PCGResult` then carries per-column iterations/residuals and an x with
    the same trailing axis.  A trailing axis of size 1 dispatches to the
    single-RHS path, so the degenerate batch is bit-identical to the
    unbatched solve.

    The result's ``status`` reports WHY each solve/column stopped (a
    `resilience.status.SolveStatus` code; detection runs inside the loop —
    see `core.pcg`).  `x0` warm-starts the iteration (the restart rung of
    `resilience.retry.solve_resilient` passes the frozen last-finite
    iterate); `stagnation_window` > 0 enables the stall detector.  `fault`
    (a `resilience.inject.FaultSpec`, static) deterministically corrupts
    one operator application — the fault-injection harness used by the
    resilience tests; leave None in production.

    The host work is the span ``solve.host``; on a sharded problem called
    outside any jit, ``solve.place`` puts the right-hand side (and `x0`)
    on every device and ``solve.launch`` dispatches the jitted runner.
    """
    with obs.span("solve.host", solve=next(_SOLVE_IDS)):
        return _solve(problem, b_rhs, precond, tol, max_iter, x0,
                      stagnation_window, fault)


def _solve(problem, b_rhs, precond, tol, max_iter, x0, stagnation_window,
           fault) -> PCGResult:
    if precond not in ("jacobi", "copy"):
        raise ValueError(f"unknown preconditioner {precond!r}")
    base = 1 if problem.d == 1 else 2
    if b_rhs.ndim not in (base, base + 1):
        raise ValueError(
            f"solve: b_rhs must be rank {base} (single RHS) or {base + 1} "
            f"(stacked RHS) for a d={problem.d} problem, got shape "
            f"{b_rhs.shape}")
    batched = b_rhs.ndim == base + 1
    if batched and b_rhs.shape[-1] == 1:
        # nrhs=1 degenerates to the exact single-RHS code path
        res = solve(problem, b_rhs[..., 0], precond=precond, tol=tol,
                    max_iter=max_iter,
                    x0=None if x0 is None else x0[..., 0],
                    stagnation_window=stagnation_window, fault=fault)
        return PCGResult(res.x[..., None], res.iterations[None],
                         res.residual[None], res.initial_residual[None],
                         res.breakdown[None], res.status[None])
    refined = getattr(problem, "precision", None) == "bf16_x32"
    if isinstance(problem, ShardedNekboneProblem):
        runner = problem.run_refined if refined else problem.run_pcg
        if not isinstance(b_rhs, jax.core.Tracer):
            # what the runner's dispatch would do: replicate the
            # arguments over the mesh
            with obs.span("solve.place"):
                everywhere = NamedSharding(problem.shard_ctx.mesh, P())
                b_rhs = jax.device_put(b_rhs, everywhere)
                if x0 is not None:
                    x0 = jax.device_put(x0, everywhere)
        with obs.span("solve.launch"):
            return runner(b_rhs, tol, max_iter, precond=precond, x0=x0,
                          stagnation_window=stagnation_window, fault=fault)
    if refined:
        # mixed precision: fp32 outer residual/correction through the
        # full-precision operator, bf16 inner sweeps through op_lo (a
        # fault corrupts the LO operator — recurring every sweep — the
        # case the precision:float32 resilience rung escapes)
        a_lo = problem.op_lo
        if fault is not None:
            a_lo = fault_inject.wrap_operator(a_lo, fault,
                                              problem.mesh.global_ids)
        pre = None
        if precond == "jacobi":
            with obs.scope("vec.precond"):
                inv_lo = (1.0 / problem.diag).astype(jnp.bfloat16)

            def pre(r):
                return (inv_lo[..., None] if batched else inv_lo) * r
        return refine(problem.op, a_lo, b_rhs, x0=x0, precond=pre, tol=tol,
                      max_iter=max_iter, batched=batched,
                      inner_window=stagnation_window or 5)
    a_op = problem.op
    if fault is not None:
        a_op = fault_inject.wrap_operator(a_op, fault,
                                          problem.mesh.global_ids)
    pre = None
    if precond == "jacobi":
        with obs.scope("vec.precond"):
            inv_diag = 1.0 / problem.diag

        def pre(r):
            return (inv_diag[..., None] if batched else inv_diag) * r
    runner = pcg_block if batched else pcg
    return runner(a_op, b_rhs, x0=x0, precond=pre, tol=tol,
                  max_iter=max_iter, stagnation_window=stagnation_window)


def make_block_solver(problem, *, precond: str = "jacobi", tol: float = 1e-8,
                      max_iter: int = 200, stagnation_window: int = 0,
                      on_trace=None):
    """A jit-wrapped, nrhs-polymorphic solve entry for padded RHS blocks.

    Returns ``solve_block(b_blk, x0_blk) -> PCGResult`` with the solver
    knobs closed over, jitted ONCE: jax keys its compilation cache on the
    abstract shapes, so each distinct nrhs (bucket) traces exactly once and
    every later call of that width replays the compiled executable.  `x0`
    is a required ARRAY argument (pass zeros for a cold start — `pcg`
    treats a zero ``x0`` identically to ``x0=None``): materializing it
    keeps one trace shape per bucket instead of a with/without-x0 pair.

    Zero-padded trailing columns are solve-neutral by construction: a zero
    RHS column has ``r0 = 0``, converges at iteration 0, and block-PCG's
    converged-column freeze (alpha masked to zero) keeps it from ever
    perturbing a live column — so callers may pad a block up to a bucket
    width and slice the result, which is what
    `serving.bucket_cache.BucketedSolveCache` does.

    ``on_trace(shape)``, if given, is called at TRACE time only (a Python
    side effect inside the traced function runs once per compilation, not
    per call) — the hook the serving layer's trace-count gate counts.
    """

    def solve_block(b_blk, x0_blk):
        if on_trace is not None:
            on_trace(tuple(b_blk.shape))
        return solve(problem, b_blk, precond=precond, tol=tol,
                     max_iter=max_iter, x0=x0_blk,
                     stagnation_window=stagnation_window)

    return jax.jit(solve_block)


def flop_count(mesh: BoxMesh, d: int, helmholtz: bool, iterations: int) -> float:
    """Nekbone-style useful-FLOP count for GFLOPS reporting (Table 6).

    Per CG iteration: one axhelm (F_ax per element) + vector ops
    (~7 flops/dof: 2 dots, 3 axpy-likes with fused mul-add counted as 2).
    """
    n1 = mesh.order + 1
    e = len(mesh.verts)
    is_helm = 1 if helmholtz else 0
    f_ax = d * (12.0 * n1**4 + (15.0 + 5.0 * is_helm) * n1**3) * e
    f_vec = 7.0 * mesh.n_global * d
    return (f_ax + f_vec) * iterations
