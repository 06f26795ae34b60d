"""Mixture-of-Experts with sort-based capacity dispatch.

Two execution paths sharing the same math:

  * `moe_apply` (single-shard): sort token-expert pairs by expert, pack
    per-expert capacity buffers with gather (no one-hot dispatch tensors),
    run all experts as one batched einsum, combine with segment-sum. Used
    by smoke tests and as the per-shard body of the EP path.

  * `moe_apply_ep` (expert-parallel): shard_map over the `model` mesh axis.
    Tokens are sequence-sharded across the EP group; each shard packs
    per-GLOBAL-expert buffers, an all_to_all routes them to their owner
    shard, local experts run, a reverse all_to_all returns outputs, and
    each shard combines its own tokens. This is the production EP path the
    dry-run exercises (deepseek-moe: 64/16 = 4 experts/shard; kimi-k2:
    384/16 = 24 experts/shard).

Capacity: per (source-shard, expert) buffer of
C = ceil(cf * T_local * k / E) slots; overflow drops (standard MoE
contract), and the gate normalization keeps dropped tokens' residual path
intact. DeepSeek-style shared experts run densely on every token.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import Params, _dtype


def moe_init(key, cfg: ModelConfig) -> Params:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    std = 0.02
    p = {
        "router": (jax.random.normal(ks[0], (d, E)) * std).astype(jnp.float32),
        "wg": (jax.random.normal(ks[1], (E, d, f)) * std).astype(dt),
        "wu": (jax.random.normal(ks[2], (E, d, f)) * std).astype(dt),
        "wd": (jax.random.normal(ks[3], (E, f, d)) * std).astype(dt),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": (jax.random.normal(k1, (d, fs)) * std).astype(dt),
            "wu": (jax.random.normal(k2, (d, fs)) * std).astype(dt),
            "wd": (jax.random.normal(k3, (fs, d)) * std).astype(dt),
        }
    return p


def _gate(router_w: jax.Array, x: jax.Array, k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """x: (T, d) -> (gates (T,k) f32 normalized, ids (T,k) int32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32)


def _pack_dispatch(x: jax.Array, ids: jax.Array, n_experts: int,
                   capacity: int):
    """Sort-based capacity packing (no one-hot dispatch tensor).

    x: (T, d); ids: (T, k) expert per pair. Returns:
      buf      (E, C, d): per-expert token buffers (zero-padded)
      pair_slot (T*k,)   : flat buffer slot of each pair (-1 if dropped)
    """
    T, k = ids.shape
    flat_e = ids.reshape(-1)                         # (T*k,)
    pair_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e)                      # expert-major
    e_s = flat_e[order]
    tok_s = pair_tok[order]
    cnt = jax.ops.segment_sum(jnp.ones_like(e_s), e_s,
                              num_segments=n_experts)
    offset = jnp.concatenate([jnp.zeros((1,), cnt.dtype),
                              jnp.cumsum(cnt)[:-1]])
    rank = jnp.arange(T * k, dtype=jnp.int32) - offset[e_s].astype(jnp.int32)
    kept = rank < capacity
    slot_s = jnp.where(kept, e_s * capacity + rank, 0)

    # each kept pair owns a unique slot, so scatter-add never collides;
    # dropped pairs add zeros at slot 0 (harmless)
    buf = jnp.zeros((n_experts * capacity, x.shape[1]), x.dtype)
    buf = buf.at[slot_s].add(jnp.where(kept[:, None], x[tok_s], 0.0))

    pair_slot = jnp.full((T * k,), -1, jnp.int32).at[order].set(
        jnp.where(kept, slot_s, -1))
    return buf.reshape(n_experts, capacity, x.shape[1]), pair_slot


def _expert_ffn(wg, wu, wd, buf):
    """buf: (E, C, d) -> (E, C, d), batched over experts."""
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


def _combine(out_buf: jax.Array, pair_slot: jax.Array, gates: jax.Array,
             T: int) -> jax.Array:
    """Gather expert outputs back to tokens and weight by gates."""
    E, C, d = out_buf.shape
    flat = out_buf.reshape(E * C, d)
    safe = jnp.clip(pair_slot, 0, E * C - 1)
    vals = jnp.where((pair_slot >= 0)[:, None], flat[safe], 0.0)
    k = pair_slot.shape[0] // T
    vals = vals * gates.reshape(-1)[:, None].astype(vals.dtype)
    return vals.reshape(T, k, d).sum(axis=1)


def capacity_of(cfg: ModelConfig, tokens: int) -> int:
    c = int(math.ceil(cfg.capacity_factor * tokens
                      * cfg.experts_per_token / cfg.n_experts))
    return max(8, -(-c // 8) * 8)   # pad to 8 for TPU-friendly shapes


def moe_apply(p: Params, x: jax.Array, cfg: ModelConfig,
              capacity: Optional[int] = None) -> jax.Array:
    """Single-shard MoE on (B, S, d)."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    T = B * S
    cap = capacity or capacity_of(cfg, T)
    gates, ids = _gate(p["router"], xt, cfg.experts_per_token)
    buf, pair_slot = _pack_dispatch(xt, ids, cfg.n_experts, cap)
    out_buf = _expert_ffn(p["wg"], p["wu"], p["wd"], buf)
    out = _combine(out_buf, pair_slot, gates, T)
    if "shared" in p:
        sh = p["shared"]
        g = jnp.einsum("td,df->tf", xt, sh["wg"])
        u = jnp.einsum("td,df->tf", xt, sh["wu"])
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, sh["wd"])
    return out.reshape(B, S, d).astype(x.dtype)


# ----------------------------------------------------------------------
# expert-parallel path (shard_map over the `model` axis)
# ----------------------------------------------------------------------

def _moe_ep_shard(xt, router_w, wg, wu, wd, *, cfg: ModelConfig,
                  axis: str, cap: int, fsdp_axis: Optional[str] = None):
    """Per-shard body. xt: (T_loc, d) local tokens; wg/wu/wd: local experts
    (E_loc, ...). Routes via all_to_all over `axis`.

    fsdp_axis: expert weights arrive additionally sharded over this axis on
    their d_model dim (FSDP); we all-gather them here explicitly — the
    backward pass then reduce-scatters the expert grads over the same axis,
    keeping the f32 grad tree sharded over (model x data). Letting GSPMD
    reshard at the shard_map boundary instead replicates the grads on the
    multi-pod mesh (measured +2 TiB/device — EXPERIMENTS §Perf H3)."""
    if fsdp_axis is not None:
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
    n_shards = jax.lax.axis_size(axis)
    E = cfg.n_experts
    E_loc = E // n_shards
    T_loc = xt.shape[0]

    gates, ids = _gate(router_w, xt, cfg.experts_per_token)
    # pack per-GLOBAL-expert buffers: (E, cap, d)
    buf, pair_slot = _pack_dispatch(xt, ids, E, cap)
    # (E, cap, d) -> (n_shards, E_loc, cap, d) -> a2a -> each shard holds
    # its E_loc experts' tokens from every source shard
    buf = buf.reshape(n_shards, E_loc, cap, xt.shape[1])
    recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    # recv: (n_shards_src, E_loc, cap, d) -> merge src into capacity axis
    recv = recv.transpose(1, 0, 2, 3).reshape(E_loc, n_shards * cap, -1)
    out_loc = _expert_ffn(wg, wu, wd, recv)
    # reverse: (E_loc, n_src*cap, d) -> (n_src, E_loc, cap, d) -> a2a back
    out_loc = out_loc.reshape(E_loc, n_shards, cap, -1).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(out_loc, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    # back: (E=n_shards*E_loc, cap, d) in global expert order
    out_buf = back.reshape(E, cap, -1)
    return _combine(out_buf, pair_slot, gates, T_loc)


def moe_apply_ep(p: Params, x: jax.Array, cfg: ModelConfig, mesh,
                 ep_axis: str = "model",
                 dp_axes: Tuple[str, ...] = ("data",),
                 capacity: Optional[int] = None,
                 fsdp_axis: Optional[str] = None) -> jax.Array:
    """Expert-parallel MoE: tokens sequence-sharded over ep_axis within
    each data shard; experts sharded over ep_axis (+ FSDP over
    fsdp_axis)."""
    B, S, d = x.shape
    ep = mesh.shape[ep_axis]
    T_loc = B * S // math.prod(mesh.shape[a] for a in dp_axes) // ep
    cap = capacity or capacity_of(cfg, T_loc)

    body = functools.partial(_moe_ep_shard, cfg=cfg, axis=ep_axis, cap=cap,
                             fsdp_axis=fsdp_axis)
    # tokens sharded over (dp..., ep) jointly on the leading axis
    tok_spec = P(tuple(dp_axes) + (ep_axis,), None)
    f = fsdp_axis
    wgu_spec = P(ep_axis, f, None)
    wd_spec = P(ep_axis, None, f)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), wgu_spec, wgu_spec, wd_spec),
        out_specs=tok_spec,
    )(x.reshape(B * S, d), p["router"], p["wg"], p["wu"], p["wd"])
    out = out.reshape(B, S, d)
    if "shared" in p:
        sh = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sh["wg"])
        u = jnp.einsum("bsd,df->bsf", x, sh["wu"])
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, sh["wd"])
    return out.astype(x.dtype)
