"""Mixture-of-experts layer with expert parallelism.

Dispatch is sort-based with a static per-expert capacity (GShard-style, all
shapes static) so the layer lowers cleanly on the production mesh:

  * train/prefill (`S` divisible by the EP axis): tokens are sequence-sharded
    over the EP ('model') axis and exchanged with two `all_to_all`s around
    the expert matmuls — classic EP, visible in the dry-run collectives.
  * decode (few tokens): dispatch is computed replicated over the EP axis,
    each device runs only its expert slice, outputs are `psum`-combined —
    cheaper than an all_to_all for tiny token counts.
  * no mesh (unit tests): same dispatch math, experts computed locally.

The router aux (load-balance) loss uses global statistics (psum over every
mesh axis that shards tokens).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import ShardCtx
from repro.models.config import ModelConfig
from repro.models.params import ParamSpec

__all__ = ["moe_spec", "moe_apply", "capacity_for"]


def moe_spec(cfg: ModelConfig, dtype):
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    spec = {
        "router": {"w": ParamSpec((d, e), ("fsdp", None))},  # router in fp32
        "experts": {
            "w_gate": ParamSpec((e, d, f), ("experts", "fsdp", None), dtype=dtype),
            "w_up": ParamSpec((e, d, f), ("experts", "fsdp", None), dtype=dtype),
            "w_down": ParamSpec((e, f, d), ("experts", None, "fsdp"), dtype=dtype),
        },
    }
    if cfg.num_shared_experts:
        fs = cfg.moe_d_ff * cfg.num_shared_experts
        spec["shared"] = {
            "w_gate": ParamSpec((d, fs), ("fsdp", "model"), dtype=dtype),
            "w_up": ParamSpec((d, fs), ("fsdp", "model"), dtype=dtype),
            "w_down": ParamSpec((fs, d), ("model", "fsdp"), dtype=dtype),
        }
    return spec


def capacity_for(tokens: int, cfg: ModelConfig) -> int:
    cap = int(tokens * cfg.experts_per_token / cfg.num_experts
              * cfg.capacity_factor)
    return max(4, -(-cap // 4) * 4)   # round up to a multiple of 4


class _Dispatch(NamedTuple):
    src_token: jnp.ndarray   # (T*k,) token index per assignment (sorted)
    expert: jnp.ndarray      # (T*k,) expert id per assignment (sorted)
    pos: jnp.ndarray         # (T*k,) slot within the expert
    keep: jnp.ndarray        # (T*k,) capacity mask
    gate: jnp.ndarray        # (T*k,) combine weight


def _route(xf: jnp.ndarray, router_w: jnp.ndarray, cfg: ModelConfig,
           capacity: int) -> Tuple[_Dispatch, jnp.ndarray, jnp.ndarray]:
    """Top-k routing + sort-based slot assignment (static shapes)."""
    t = xf.shape[0]
    k = cfg.experts_per_token
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)            # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    flat_e = expert_ids.reshape(-1)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < capacity
    disp = _Dispatch(src_token=order // k, expert=sorted_e, pos=pos,
                     keep=keep, gate=flat_g[order])
    return disp, probs, expert_ids


def _fill_buffer(xf: jnp.ndarray, disp: _Dispatch, num_experts: int,
                 capacity: int) -> jnp.ndarray:
    """Scatter tokens to the (E, C, D) dispatch buffer (dropped -> row E)."""
    d = xf.shape[-1]
    e_safe = jnp.where(disp.keep, disp.expert, num_experts)
    buf = jnp.zeros((num_experts + 1, capacity, d), xf.dtype)
    buf = buf.at[e_safe, disp.pos].set(xf[disp.src_token])
    return buf[:num_experts]


def _combine(out_buf: jnp.ndarray, disp: _Dispatch, t: int) -> jnp.ndarray:
    """Gather expert outputs back and weighted-sum per token."""
    d = out_buf.shape[-1]
    e_clip = jnp.minimum(disp.expert, out_buf.shape[0] - 1)
    vals = out_buf[e_clip, disp.pos]                    # (T*k, D)
    w = (disp.gate * disp.keep).astype(vals.dtype)[:, None]
    y = jnp.zeros((t, d), out_buf.dtype).at[disp.src_token].add(vals * w)
    return y


def _expert_ffn(buf: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """SwiGLU per expert: buf (E?, C, D) with matching leading expert dim."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _aux_loss(probs: jnp.ndarray, expert_ids: jnp.ndarray, cfg: ModelConfig,
              axes: Tuple[str, ...]) -> jnp.ndarray:
    """Switch load-balance loss with cross-device statistics."""
    e = cfg.num_experts
    counts = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0)
    p_sum = probs.sum(axis=0)
    n = jnp.asarray(probs.shape[0] * cfg.experts_per_token, jnp.float32)
    if axes:
        counts = jax.lax.psum(counts, axes)
        p_sum = jax.lax.psum(p_sum, axes)
        n = jax.lax.psum(n, axes)
    frac_tokens = counts / n
    frac_probs = p_sum / (n / cfg.experts_per_token)
    return e * jnp.sum(frac_tokens * frac_probs)


def _moe_core(xf, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
              capacity: int, ep_axis: Optional[str],
              token_axes: Tuple[str, ...], use_a2a: bool):
    """Per-device MoE body (runs under shard_map or standalone)."""
    t = xf.shape[0]
    disp, probs, expert_ids = _route(xf, router_w, cfg, capacity)
    buf = _fill_buffer(xf, disp, cfg.num_experts, capacity)     # (E, C, D)
    if ep_axis is None:
        out_buf = _expert_ffn(buf, w_gate, w_up, w_down)
    elif use_a2a:
        # (E, C, D) -> (E/ep, C*ep, D): tokens travel to their expert's device
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        out = _expert_ffn(buf, w_gate, w_up, w_down)
        out_buf = jax.lax.all_to_all(out, ep_axis, split_axis=1,
                                     concat_axis=0, tiled=True)
    else:
        # replicated dispatch, sliced experts, psum combine (decode path)
        ep = jax.lax.axis_size(ep_axis)
        e_loc = cfg.num_experts // ep
        idx = jax.lax.axis_index(ep_axis)
        buf_loc = jax.lax.dynamic_slice_in_dim(buf, idx * e_loc, e_loc, axis=0)
        out_loc = _expert_ffn(buf_loc, w_gate, w_up, w_down)
        pad = jnp.zeros((cfg.num_experts, capacity, xf.shape[-1]),
                        out_loc.dtype)
        out_buf = jax.lax.dynamic_update_slice_in_dim(pad, out_loc,
                                                      idx * e_loc, axis=0)
    y = _combine(out_buf, disp, t)
    if ep_axis is not None and not use_a2a:
        y = jax.lax.psum(y, ep_axis)
    aux = _aux_loss(probs, expert_ids, cfg, token_axes)
    return y, aux


def moe_apply(p, x: jnp.ndarray, cfg: ModelConfig,
              ctx: Optional[ShardCtx]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    dt = x.dtype
    router_w = p["router"]["w"]
    ex = p["experts"]
    wg, wu, wd = (ex["w_gate"].astype(dt), ex["w_up"].astype(dt),
                  ex["w_down"].astype(dt))

    if ctx is None:
        xf = x.reshape(-1, d)
        cap = capacity_for(xf.shape[0], cfg)
        y, aux = _moe_core(xf, router_w, wg, wu, wd, cfg, cap, None, (),
                           False)
    else:
        ep_axis = ctx.model_axis
        ep = ctx.ep_size
        batch_ok = b % ctx.dp_size == 0
        use_a2a = (s % ep == 0) and batch_ok
        if use_a2a:
            t_loc = (b // ctx.dp_size) * (s // ep)
            x_spec = P(ctx.data_axes, ep_axis, None)
        elif batch_ok:
            t_loc = (b // ctx.dp_size) * s
            x_spec = P(ctx.data_axes, None, None)
        else:  # tiny batches: fully replicated dispatch
            t_loc = b * s
            x_spec = P(None, None, None)
        cap = capacity_for(t_loc, cfg)
        token_axes = tuple(ctx.data_axes) + ((ep_axis,) if use_a2a else ())
        body = functools.partial(_moe_core, cfg=cfg, capacity=cap,
                                 ep_axis=ep_axis, token_axes=token_axes,
                                 use_a2a=use_a2a)
        shard = jax.shard_map(
            lambda xx, rw, g, u, dn: _shard_body(body, xx, rw, g, u, dn),
            mesh=ctx.mesh, check_vma=False,
            in_specs=(x_spec, P(None, None), P(ep_axis, None, None),
                      P(ep_axis, None, None), P(ep_axis, None, None)),
            out_specs=(x_spec, P()))
        y, aux = shard(x, router_w, wg, wu, wd)
        y = y.reshape(b, s, d)
        aux = aux  # already psum'd to a replicated scalar
        if "shared" in p:
            y = y + _shared_expert(p["shared"], x, dt)
        return y, aux

    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + _shared_expert(p["shared"], x, dt)
    return y, aux


def _shard_body(body, xx, rw, g, u, dn):
    bl, sl, d = xx.shape
    y, aux = body(xx.reshape(-1, d), rw, g, u, dn)
    return y.reshape(bl, sl, d), aux


def _shared_expert(ps, x, dt):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, ps["w_gate"].astype(dt)))
    h = h * jnp.einsum("bsd,df->bsf", x, ps["w_up"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", h, ps["w_down"].astype(dt))
