"""Mixture-of-experts FFN with TPU-native expert parallelism.

Design (DESIGN.md §5): experts are sharded over the ``model`` axis
(E_loc = E / |model|); each expert's FFN dim is further sharded over the
data axes for storage AND compute (``expert_mlp`` logical axis).  Tokens
stay on their data shard; per MoE layer the collectives are

  1. tiled all-gather of the gathered expert batches over the data axes
     (token-slot bytes — small at decode, bounded at train),
  2. reduce-scatter of the F-partial expert outputs back (same bytes),
  3. psum of the combined token outputs over the model axis.

No weight gathers, no (T, E, C) one-hot dispatch matmuls (those dominate
HLO FLOPs and wreck the roofline).  Dispatch is sort-free: a cumsum over a
(slots, E_loc) one-hot builds the (E_loc, capacity) token table; overflow
tokens are dropped (standard capacity-factor semantics).

With ``ctx.mesh is None`` the same inner function runs unsharded (smoke
tests).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import MeshCtx
from repro.models import layers
from repro.nn.module import Param

Array = jax.Array


def moe_specs(cfg: ModelConfig) -> Dict[str, Param]:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    specs = {
        "router": Param((d, e), ("embed", "experts"), init="fan_in"),
        # NOTE: expert D dims deliberately unnamed (replicated); the FFN dim
        # carries "expert_mlp" -> data axes.  See module docstring.
        "w_gate": Param((e, d, f), ("experts", None, "expert_mlp"), init="fan_in"),
        "w_up": Param((e, d, f), ("experts", None, "expert_mlp"), init="fan_in"),
        "w_down": Param((e, f, d), ("experts", "expert_mlp", None), init="fan_in"),
    }
    if cfg.n_shared_experts:
        specs["shared"] = layers.mlp_specs(
            cfg, cfg.moe_d_ff * cfg.n_shared_experts)
    return specs


def _dispatch_tables(top_ids: Array, top_probs: Array, e_start, e_loc: int,
                     capacity: int, n_tokens: int
                     ) -> Tuple[Array, Array]:
    """Build (E_loc, C) token-index and prob tables for local experts."""
    k = top_ids.shape[-1]
    flat_e = top_ids.reshape(-1)                       # (T*k,)
    flat_t = jnp.repeat(jnp.arange(n_tokens, dtype=jnp.int32), k)
    flat_p = top_probs.reshape(-1)
    local = (flat_e >= e_start) & (flat_e < e_start + e_loc)
    le = jnp.where(local, flat_e - e_start, e_loc)     # e_loc = trash bucket
    onehot = (le[:, None] == jnp.arange(e_loc, dtype=le.dtype)[None, :])
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    pos = jnp.sum(pos * onehot, axis=1)                # slot position in expert
    le = jnp.where(local & (pos < capacity), le, e_loc)  # drop overflow
    table = jnp.full((e_loc, capacity), n_tokens, jnp.int32)
    table = table.at[le, pos].set(flat_t, mode="drop")
    ptable = jnp.zeros((e_loc, capacity), flat_p.dtype)
    ptable = ptable.at[le, pos].set(flat_p, mode="drop")
    return table, ptable


def _moe_inner(cfg: ModelConfig, e_loc: int, capacity: int,
               data_axes: Optional[Tuple[str, ...]], model_axis: Optional[str],
               tokens_sharded: bool,
               xt: Array, top_ids: Array, top_probs: Array,
               w_gate: Array, w_up: Array, w_down: Array) -> Array:
    """Per-device body.  xt (T_loc, D) local tokens; weights local shards
    (E_loc, D, F_loc) / (E_loc, F_loc, D)."""
    t_loc, d = xt.shape
    if model_axis is not None:
        e_start = jax.lax.axis_index(model_axis) * e_loc
    else:
        e_start = 0
    table, ptable = _dispatch_tables(top_ids, top_probs, e_start, e_loc,
                                     capacity, t_loc)

    x_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xg = x_pad[table]                                  # (E_loc, C, D)

    gather_data = data_axes and tokens_sharded
    if gather_data:
        # Expert batch must meet every F-shard: gather over data axes.
        xg = jax.lax.all_gather(xg, data_axes, axis=1, tiled=True)

    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w_gate))
         * jnp.einsum("ecd,edf->ecf", xg, w_up))       # (E_loc, C*, F_loc)
    yg = jnp.einsum("ecf,efd->ecd", h, w_down)         # F-partial

    if data_axes:
        if tokens_sharded:
            # Sum F-partials AND return only this shard's token slots.
            yg = jax.lax.psum_scatter(yg, data_axes, scatter_dimension=1,
                                      tiled=True)
        else:
            yg = jax.lax.psum(yg, data_axes)

    y = jnp.zeros((t_loc + 1, d), yg.dtype)
    y = y.at[table].add(yg * ptable[..., None].astype(yg.dtype), mode="drop")
    y = y[:t_loc]
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    return y


def load_balance_loss(probs: Array, top_ids: Array, n_experts: int) -> Array:
    """Switch-style aux loss: E * sum_e f_e * p_e  (f_e = routed-token
    fraction over the top-k assignments, p_e = mean router prob).
    Minimized (=1) by a uniform router."""
    f = jnp.mean(jax.nn.one_hot(top_ids, n_experts, dtype=jnp.float32),
                 axis=(0, 1))
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)


def moe_forward(params, cfg: ModelConfig, ctx: MeshCtx, x: Array,
                with_aux: bool = False):
    """x (B, S, D) -> (B, S, D) [, aux load-balance loss].
    Router in f32; top-k renormalized."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = (xt.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_probs = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
    top_probs = top_probs.astype(x.dtype)
    aux = (load_balance_loss(probs, top_ids, cfg.n_experts)
           if with_aux else None)

    if ctx.mesh is None:
        cap = max(1, math.ceil(b * s * cfg.top_k * cfg.capacity_factor
                               / cfg.n_experts))
        y = _moe_inner(cfg, cfg.n_experts, cap, None, None, False,
                       xt, top_ids, top_probs,
                       params["w_gate"], params["w_up"], params["w_down"])
    else:
        tokens_rule = ctx.axis_rule("moe_tokens")
        tokens_sharded = tokens_rule is not None
        n_data = ctx.n_data if tokens_sharded else 1
        e_loc = cfg.n_experts // ctx.n_model
        t_loc = (b * s) // (n_data if tokens_sharded else 1)
        cap = max(1, math.ceil(t_loc * cfg.top_k * cfg.capacity_factor
                               / cfg.n_experts))
        tok_spec = P(tokens_rule) if tokens_sharded else P()
        dp = tuple(ctx.data_axes)
        body = functools.partial(
            _moe_inner, cfg, e_loc, cap, dp, ctx.model_axis, tokens_sharded)
        y = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(tokens_rule, None) if tokens_sharded else P(None, None),
                      tok_spec, tok_spec,
                      P("model", None, dp), P("model", None, dp),
                      P("model", dp, None)),
            out_specs=P(tokens_rule, None) if tokens_sharded else P(None, None),
            check_vma=False,
        )(xt, top_ids, top_probs,
          params["w_gate"], params["w_up"], params["w_down"])

    y = y.reshape(b, s, d)
    if cfg.n_shared_experts:
        y = y + layers.mlp(params["shared"], cfg, ctx, x)
    if with_aux:
        return y, aux
    return y
