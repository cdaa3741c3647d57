"""Gradient compression for cross-pod data parallelism.

`compressed_crosspod_grads` computes per-pod gradients under a partially-
manual `shard_map` (manual over 'pod', automatic over 'data'/'model') and
mean-reduces them with an int8 all-gather + local sum: ~8x less inter-pod
traffic than the fp32 all-reduce XLA would otherwise insert.  The int8
all-gather is visible in the dry-run HLO (s8 all-gather over the pod groups).

Error feedback (1-bit-Adam style) is provided as a local utility
(`ef_compress`) and validated for convergence in tests; the cross-pod path
uses plain per-row int8 (per-pod error state at 1T parameters would cost
more HBM than it saves wire traffic — DESIGN.md §7).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["quantize_int8", "dequantize_int8", "ef_compress",
           "halo_compress", "halo_decompress", "compressed_crosspod_grads"]


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8; per-row scales for >=2D tensors."""
    x32 = x.astype(jnp.float32)
    if x.ndim >= 2:
        amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    else:
        amax = jnp.max(jnp.abs(x32), initial=0.0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(x32 / scale).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def ef_compress(g: jnp.ndarray, err: jnp.ndarray):
    """Quantize with error feedback: returns (q, scale, new_err)."""
    g32 = g.astype(jnp.float32) + err
    q, s = quantize_int8(g32)
    return q, s, g32 - dequantize_int8(q, s)


def halo_compress(vals: jnp.ndarray, method: str) -> Tuple[jnp.ndarray, ...]:
    """Encode one neighbour-halo buffer for the wire.

    Returns the tuple of arrays that must travel (each is ppermuted
    separately by `core.gather_scatter.neighbour_start`): ("bf16") one
    bfloat16 cast of the partials; ("int8") the `quantize_int8` pair —
    int8 codes plus the per-dof fp32 scale.  The buffer is (M[, c]) with
    trash-padded lanes already ZEROED by `shared_contrib` upstream, so an
    all-padding row quantizes to scale 1.0 / codes 0 and a real row's
    per-row amax never sees trash values — the codec needs no mask of its
    own.  `distributed.context.HALO_COMPRESS` names the valid methods.

    The codec is strictly PER-DOF — 1-D buffers quantize with per-element
    scales, not one global amax.  That is a correctness requirement, not
    a quality knob: a dof's encoding must come out identical whichever
    per-neighbour pair table (or the shard's own self-rounding pass — see
    `core.gather_scatter.halo_self_round`) slices it, and any scale
    computed over a whole buffer would differ between those slicings.
    """
    if method == "bf16":
        return (vals.astype(jnp.bfloat16),)
    if method == "int8":
        if vals.ndim == 1:
            q, s = quantize_int8(vals[:, None])
            return q[:, 0], s[:, 0]
        return quantize_int8(vals)
    raise ValueError(f"unknown halo compress method {method!r}")


def halo_decompress(parts: Tuple[jnp.ndarray, ...], method: str,
                    dtype) -> jnp.ndarray:
    """Decode the wire parts of `halo_compress` back to `dtype` partials."""
    if method == "bf16":
        return parts[0].astype(dtype)
    if method == "int8":
        return dequantize_int8(*parts).astype(dtype)
    raise ValueError(f"unknown halo compress method {method!r}")


def _compressed_mean(g: jnp.ndarray, axis: str) -> jnp.ndarray:
    q, s = quantize_int8(g)
    q_all = jax.lax.all_gather(q, axis)        # int8 on the wire
    s_all = jax.lax.all_gather(s, axis)
    n = q_all.shape[0]
    summed = jnp.sum(q_all.astype(jnp.float32) * s_all.astype(jnp.float32),
                     axis=0)
    return (summed / n).astype(g.dtype)


def compressed_crosspod_grads(loss_fn, params, batch, mesh,
                              pod_axis: str = "pod"):
    """Per-pod grads + compressed cross-pod mean.

    loss_fn(params, batch) -> (loss, metrics); batch leaves are sharded on
    dim 0 across pods (the usual batch sharding); params replicated across
    pods (their data/model sharding stays automatic).
    """
    def per_pod(p, b):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, b)
        grads = jax.tree.map(lambda g: _compressed_mean(g, pod_axis), grads)
        loss = jax.lax.pmean(loss, pod_axis)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, pod_axis), metrics)
        return loss, metrics, grads

    # partially manual: manual over 'pod', automatic over 'data'/'model'.
    # The replication check is off: the gather+sum makes the outputs
    # pod-replicated, which the static varying-axes check can't infer.
    shard = jax.shard_map(
        per_pod, mesh=mesh, axis_names={pod_axis}, check_vma=False,
        in_specs=(P(), P(pod_axis)), out_specs=(P(), P(), P()))
    return shard(params, batch)
