"""Gather-scatter: the actions of Q and Q^T (paper Algorithm 1, gslib role).

Q is the sparse binary global-to-local matrix (Eq. 2); it is never built.
  scatter (Q):   global field (Ng[, d])            -> local (E, N1,N1,N1[, d])
  gather  (Q^T): local  (E, N1,N1,N1[, d])         -> global (Ng[, d]) sum

On a sharded mesh the gather is the only cross-element (and cross-device)
communication of the solver.  The sharded primitives below implement it
owner-computes style: each shard gathers into its *local* dof space with a
plain segment-sum, then one collective (`lax.psum`) runs over only the
shared-face/edge/corner dofs of the element partition — never the full
field.  See `mesh_gen.partition_elements` for the index sets and DESIGN.md
for the exchange protocol.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.distributed.compression import halo_compress, halo_decompress

__all__ = [
    "scatter", "gather", "dssum", "multiplicity",
    "shared_contrib", "apply_shared", "exchange_shared", "gather_sharded",
    "NeighbourRound", "neighbour_rounds", "neighbour_start",
    "neighbour_finish", "halo_self_round", "exchange_neighbour",
    "gather_sharded_neighbour",
]


def scatter(x_global: jnp.ndarray, global_ids: jnp.ndarray) -> jnp.ndarray:
    """Q x: copy global dof values to element-local nodes."""
    with obs.scope("gs.q"):
        return x_global[global_ids]


def gather(y_local: jnp.ndarray, global_ids: jnp.ndarray,
           n_global: int) -> jnp.ndarray:
    """Q^T y: sum element-local values into global dofs.

    `y_local` must be shaped like `global_ids` (scalar field) or like
    `global_ids` plus one trailing component axis — a d-vector field or an
    nrhs RHS batch (the solver flattens a combined (d, nrhs) batch into one
    axis before gathering, so one segment-sum serves every column).
    """
    if y_local.shape[:global_ids.ndim] != global_ids.shape:
        raise ValueError(
            f"gather: y_local leading shape {y_local.shape} does not match "
            f"global_ids shape {global_ids.shape} — expected "
            f"{global_ids.shape} (scalar field) or {global_ids.shape} + (d,) "
            f"(vector field with one trailing component axis)")
    if y_local.ndim > global_ids.ndim + 1:
        raise ValueError(
            f"gather: y_local has {y_local.ndim - global_ids.ndim} trailing "
            f"axes beyond global_ids; vector fields must pack components "
            f"into a single trailing axis (got shape {y_local.shape} vs ids "
            f"{global_ids.shape})")
    ids = global_ids.reshape(-1)
    # The scatter-add must not accumulate at sub-fp32 width (shared dofs
    # collect up to 8 element contributions; the `AccumulationDtype`
    # contract forbids bf16 accumulation) — sum in f32, round once, like
    # `neighbour_finish` already does on the sharded path.
    dt = y_local.dtype
    acc_dt = jnp.promote_types(dt, jnp.float32) \
        if jnp.issubdtype(dt, jnp.floating) and jnp.finfo(dt).bits < 32 \
        else dt
    with obs.scope("gs.qt"):
        if y_local.ndim == global_ids.ndim:  # scalar field
            out = jax.ops.segment_sum(y_local.reshape(-1).astype(acc_dt),
                                      ids, num_segments=n_global)
        else:
            # vector field: trailing component axis
            d = y_local.shape[-1]
            vals = y_local.reshape(-1, d).astype(acc_dt)
            out = jax.ops.segment_sum(vals, ids, num_segments=n_global)
        return out.astype(dt)


def dssum(y_local: jnp.ndarray, global_ids: jnp.ndarray,
          n_global: int) -> jnp.ndarray:
    """Direct-stiffness summation: Q Q^T y (Nek's dssum)."""
    return scatter(gather(y_local, global_ids, n_global), global_ids)


def multiplicity(global_ids: jnp.ndarray, n_global: int) -> jnp.ndarray:
    """Number of elements sharing each global dof (gslib 'vmult')."""
    ones = jnp.ones(global_ids.size, dtype=jnp.float32)
    return jax.ops.segment_sum(ones, global_ids.reshape(-1),
                               num_segments=n_global)


# ---------------------------------------------------------------------------
# Sharded (owner-computes) gather: per-shard local segment-sum + one
# collective over the interface dofs only.  The three pieces are split so the
# exchange algebra is testable without a device mesh (see
# tests/test_gather_scatter.py) while `gather_sharded` wires them to
# `lax.psum` inside `shard_map`.
# ---------------------------------------------------------------------------


def _expand_mask(mask: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (L,)/(NS,) bool mask against trailing batch axes — one
    for a vector field (d) or RHS batch (nrhs), two for a batched vector
    field (d, nrhs)."""
    if y.ndim == mask.ndim:
        return mask
    return mask.reshape(mask.shape + (1,) * (y.ndim - mask.ndim))


def shared_contrib(y_dofs: jnp.ndarray, shared_idx: jnp.ndarray,
                   shared_present: jnp.ndarray) -> jnp.ndarray:
    """This shard's partial sums at the interface dofs, zero where absent.

    y_dofs: (L[, d]) per-shard local dof values; shared_idx: (NS,) local
    slots (trash where absent); shared_present: (NS,) bool.
    """
    with obs.scope("gs.iface"):
        vals = y_dofs[shared_idx]
        return jnp.where(_expand_mask(shared_present, vals), vals, 0.0)


def apply_shared(y_dofs: jnp.ndarray, shared_idx: jnp.ndarray,
                 summed: jnp.ndarray) -> jnp.ndarray:
    """Write the fully-summed interface values back into the local slots.

    Absent interface dofs carry the trash slot index, so their writes land
    in the trash slot (whose value is never read unmasked).
    """
    with obs.scope("gs.iface"):
        return y_dofs.at[shared_idx].set(summed)


def exchange_shared(y_dofs: jnp.ndarray, shared_idx: jnp.ndarray,
                    shared_present: jnp.ndarray,
                    axis_name: str) -> jnp.ndarray:
    """Sum interface-dof contributions across shards (the ONLY collective).

    The psum buffer is (NS[, c]) with c the flattened batch width (d, nrhs,
    or d*nrhs) — the shared-face/edge/corner dofs of the partition, not the
    full field.  A multi-RHS solve still pays exactly ONE exchange per
    operator application: the batch rides along as extra psum columns.
    """
    contrib = shared_contrib(y_dofs, shared_idx, shared_present)
    with obs.scope("exchange"):
        summed = jax.lax.psum(contrib, axis_name)
    return apply_shared(y_dofs, shared_idx, summed)


def gather_sharded(y_local: jnp.ndarray, local_ids: jnp.ndarray,
                   n_local: int, shared_idx: jnp.ndarray,
                   shared_present: jnp.ndarray,
                   axis_name: Optional[str]) -> jnp.ndarray:
    """Per-shard Q^T: local segment-sum, then the interface exchange.

    Runs inside `shard_map` over the element axis `axis_name`; with
    axis_name=None the exchange is skipped (single-shard debugging).
    After the exchange every real local slot holds the *full* global sum
    for its dof — interface dofs are consistent on every shard that has
    them, which is exactly gslib's post-gather state.
    """
    y_dofs = gather(y_local, local_ids, n_local)
    if axis_name is None:
        return y_dofs
    return exchange_shared(y_dofs, shared_idx, shared_present, axis_name)


# ---------------------------------------------------------------------------
# Neighbour-wise (ppermute) interface exchange: instead of one mesh-wide
# psum over ALL interface dofs, each shard trades per-pair buffers with the
# few shards it actually borders.  One exchange is a fixed set of ROUNDS —
# one per neighbour offset k, two `lax.ppermute` shifts each (+k and -k) —
# whose point-to-point permutes never serialize the mesh behind a global
# all-reduce and whose start can be hoisted before independent compute
# (the interior-element work) by the async collective scheduler.  The
# `neighbour_start` / `neighbour_finish` split exposes exactly that seam.
#
# The offsets are shard-LINEAR-index distances, so the same machinery
# serves 1-D slabs (a few small k) and 2-D/3-D box decompositions, where k
# is a linearized shard-grid shift |(dx*py + dy)*pz + dz| covering face,
# edge and corner neighbours: a dof shared by 4 or 8 shards sits in every
# pairwise table of its sharers, and receiving each other sharer's partial
# exactly once IS the full sum.  Pairs (s, s + k) that exist arithmetically
# but not geometrically (grid wrap-around) carry all-masked table rows —
# their sends are zeros and their receives land masked.
# ---------------------------------------------------------------------------


class NeighbourRound(NamedTuple):
    """One exchange round: the per-shard view of offset k's pair sets.

    fwd_perm / bwd_perm are STATIC (src, dst) device lists for the +k / -k
    `ppermute` shifts; the index/mask arrays are this shard's slices of the
    partition's per-offset tables (`mesh_gen.MeshPartition.nbr_*`):
    lo_idx/lo_mask — local slots of the dofs shared with shard s + k,
    hi_idx/hi_mask — local slots of the dofs shared with shard s - k, both
    enumerated in the same sorted-by-global-id order, trash-padded to the
    offset's static width M_k.
    """

    fwd_perm: tuple
    bwd_perm: tuple
    lo_idx: jnp.ndarray
    lo_mask: jnp.ndarray
    hi_idx: jnp.ndarray
    hi_mask: jnp.ndarray


def neighbour_rounds(offsets: Sequence[int], n_shards: int,
                     nbr_tables: Sequence[jnp.ndarray]
                     ) -> Sequence[NeighbourRound]:
    """Zip the static shift permutations with the per-shard table slices.

    `nbr_tables` holds the shard-local (lo_idx, lo_mask, hi_idx, hi_mask)
    quadruple for each offset, flattened in offset order (the layout the
    solver passes through `shard_map` operands).
    """
    rounds = []
    for j, k in enumerate(offsets):
        fwd = tuple((s, s + k) for s in range(n_shards - k))
        bwd = tuple((s + k, s) for s in range(n_shards - k))
        lo_idx, lo_mask, hi_idx, hi_mask = nbr_tables[4 * j:4 * j + 4]
        rounds.append(NeighbourRound(fwd, bwd, lo_idx, lo_mask,
                                     hi_idx, hi_mask))
    return rounds


def neighbour_start(y_dofs: jnp.ndarray, rounds: Sequence[NeighbourRound],
                    axis_name: str, compress: Optional[str] = None):
    """Launch every ppermute of the exchange; returns the in-flight recvs.

    All sends read from `y_dofs` — this shard's OWN partial sums — so the
    permutes depend on nothing but the interface-element gather.  Any
    compute issued between `neighbour_start` and `neighbour_finish` (the
    interior elements) is dataflow-independent of the permutes and can
    overlap them.

    `compress` (a `distributed.context.HALO_COMPRESS` method) encodes the
    send buffers with `distributed.compression.halo_compress` BEFORE the
    permutes, so the wire carries bf16 (or int8 + per-dof scale) instead
    of the solve dtype — `shared_contrib` has already zeroed trash-padded
    lanes, so the codec's per-row scales never see garbage.  Every part
    of the codec rides its own ppermute with the same static perm tables;
    `neighbour_finish` must be called with the same `compress`.
    """
    def permute(p, perm):
        with obs.scope("exchange"):
            return jax.lax.ppermute(p, axis_name, perm)

    recvs = []
    with obs.scope("gs.iface"):
        for r in rounds:
            send_lo = shared_contrib(y_dofs, r.lo_idx, r.lo_mask)
            send_hi = shared_contrib(y_dofs, r.hi_idx, r.hi_mask)
            if compress is not None:
                # each codec part (payload, scales, ...) rides its own
                # permute
                recv_hi = tuple(permute(p, r.fwd_perm)
                                for p in halo_compress(send_lo, compress))
                recv_lo = tuple(permute(p, r.bwd_perm)
                                for p in halo_compress(send_hi, compress))
            else:
                recv_hi = permute(send_lo, r.fwd_perm)
                recv_lo = permute(send_hi, r.bwd_perm)
            recvs.append((recv_hi, recv_lo))
    return recvs


def neighbour_finish(y_dofs: jnp.ndarray,
                     rounds: Sequence[NeighbourRound], recvs,
                     compress: Optional[str] = None) -> jnp.ndarray:
    """Accumulate the received neighbour partials into the local dofs.

    Each neighbour's partial is added exactly once, so a dof shared by m
    shards ends as own + (m - 1) received partials = the full global sum on
    every sharer (non-receiving shards got ppermute's zeros; padding lands
    masked in the trash slot).  With `compress` the received wire parts
    are decoded back to the `y_dofs` dtype first (the decode is arithmetic
    on the already-received buffers — no further communication).

    The accumulation runs at >= fp32 in CANONICAL SOURCE ORDER — round-k
    hi-side recvs (sources s-k) by descending k, then this shard's own
    partials (source s), then lo-side recvs (sources s+k) by ascending k
    — so every sharer of a dof sums the identical value sequence and
    lands on the bit-identical total, which one final cast rounds to the
    `y_dofs` dtype.  That order contract is what makes a reduced-
    precision exchange usable at all: the old own-partials-first order
    differs per shard, and for a dof with >= 3 sharers the sharers'
    independently-rounded bf16 sums drift by O(eps_bf16) per operator
    application — the sharded bf16 inner sweeps of a ``bf16_x32`` refined
    solve then converge on per-shard systems whose owner-wins assembly
    satisfies none of them (caught by
    ``tests/test_mixed_precision.py::test_sharded_refined_solve_every_wire``
    on 4 devices, where the block element partition shares corner dofs
    between up to 4 shards).  At fp32 the same reordering is the usual
    harmless 1-ulp-level associativity noise.
    """
    with obs.scope("gs.iface"):
        acc_dt = jnp.promote_types(y_dofs.dtype, jnp.float32)
        decoded = []
        for recv_hi, recv_lo in recvs:
            if compress is not None:
                recv_hi = halo_decompress(recv_hi, compress, y_dofs.dtype)
                recv_lo = halo_decompress(recv_lo, compress, y_dofs.dtype)
            decoded.append((recv_hi, recv_lo))
        acc = jnp.zeros(y_dofs.shape, acc_dt)
        for r, (recv_hi, _) in reversed(list(zip(rounds, decoded))):
            part = jnp.where(_expand_mask(r.hi_mask, recv_hi), recv_hi, 0.0)
            acc = acc.at[r.hi_idx].add(part.astype(acc_dt))
        acc = acc + y_dofs.astype(acc_dt)
        for r, (_, recv_lo) in zip(rounds, decoded):
            part = jnp.where(_expand_mask(r.lo_mask, recv_lo), recv_lo, 0.0)
            acc = acc.at[r.lo_idx].add(part.astype(acc_dt))
        return acc.astype(y_dofs.dtype)


def halo_self_round(y_dofs: jnp.ndarray, shared_idx: jnp.ndarray,
                    shared_present: jnp.ndarray,
                    compress: str) -> jnp.ndarray:
    """Round this shard's OWN interface partials through the wire codec.

    A lossy codec silently breaks the exchange's consistency contract.
    Every sharer of a dof must end the exchange holding the SAME value —
    owner-wins reassembly and the psum'd solver scalars both assume it.
    But with compression each sharer sums its own full-precision partial
    with the other sharers' DECODED partials, so two sharers of one dof
    accumulate different totals, their iterates drift apart, and the solve
    can report a residual its assembled solution does not satisfy.

    The fix is to make every sharer sum the identical set of codec-rounded
    partials: after the sends are captured (they must encode the original
    values — the int8 codec is not idempotent), replace the shard's own
    interface partials with their own decode(encode(·)) image.  The codec
    is per-dof (see `halo_compress`), so this self-rounding produces bit-
    for-bit the value every neighbour decodes from the wire.  Call between
    `neighbour_start` and `neighbour_finish`; a no-op when the field
    already lives at the wire precision (e.g. a bf16 operator on a bf16
    wire).
    """
    vals = shared_contrib(y_dofs, shared_idx, shared_present)
    with obs.scope("gs.iface"):
        dec = halo_decompress(halo_compress(vals, compress), compress,
                              y_dofs.dtype)
    return apply_shared(y_dofs, shared_idx, dec)


def exchange_neighbour(y_dofs: jnp.ndarray,
                       rounds: Sequence[NeighbourRound],
                       axis_name: str,
                       compress: Optional[str] = None,
                       shared_idx: Optional[jnp.ndarray] = None,
                       shared_present: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Sum interface-dof contributions pairwise across neighbour shards.

    Numerically equivalent to `exchange_shared` (same partials, summed in
    per-shard neighbour order instead of the psum's reduction order);
    `compress` additionally rounds the partials through the wire codec —
    the received ones on decode AND this shard's own via `halo_self_round`
    (which needs the full interface tables `shared_idx`/`shared_present`),
    so every sharer sums the identical codec-rounded set."""
    recvs = neighbour_start(y_dofs, rounds, axis_name, compress=compress)
    if compress is not None:
        if shared_idx is None or shared_present is None:
            raise ValueError(
                f"exchange_neighbour: compress={compress!r} requires "
                f"shared_idx/shared_present for the self-rounding pass "
                f"(halo_self_round) — a lossy wire without it leaves the "
                f"sharers of a dof holding different sums")
        y_dofs = halo_self_round(y_dofs, shared_idx, shared_present,
                                 compress)
    return neighbour_finish(y_dofs, rounds, recvs, compress=compress)


def gather_sharded_neighbour(y_local: jnp.ndarray, local_ids: jnp.ndarray,
                             n_local: int,
                             rounds: Sequence[NeighbourRound],
                             axis_name: Optional[str],
                             compress: Optional[str] = None,
                             shared_idx: Optional[jnp.ndarray] = None,
                             shared_present: Optional[jnp.ndarray] = None
                             ) -> jnp.ndarray:
    """Per-shard Q^T with the neighbour-wise exchange.

    Drop-in replacement for `gather_sharded`: identical post-gather state
    (every real local slot holds the full global sum) with the mesh-wide
    interface psum replaced by point-to-point ppermute rounds (optionally
    codec-compressed on the wire — see `neighbour_start`; `compress`
    requires the interface tables for the self-rounding pass).
    """
    y_dofs = gather(y_local, local_ids, n_local)
    if axis_name is None:
        return y_dofs
    return exchange_neighbour(y_dofs, rounds, axis_name, compress=compress,
                              shared_idx=shared_idx,
                              shared_present=shared_present)
