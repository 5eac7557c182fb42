"""Mixture-of-Experts FFN with fine-grained routed experts + shared experts
(DeepSeekMoE-style) and top-k routing (GShard-style capacity, sort-based dispatch).

Distribution: expert-parallel over the ``model`` mesh axis using shard_map with
*replicated activations* — each model shard computes only its local experts for the
tokens routed to them and the outputs are combined with a single psum. On TPU this
replaces the GPU all-to-all with the all-reduce Megatron-style TP already pays,
which is ICI-friendly (see DESIGN.md §5).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.models.layers import act_fn, dense_init, mlp_init, apply_mlp

CAPACITY_FACTOR = 1.25


def moe_init(key, cfg: ArchConfig, dtype=jnp.float32):
    d, de, E = cfg.d_model, cfg.d_expert or cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 6)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),  # router kept fp32
        "wg": (jax.random.normal(ks[1], (E, d, de)) * scale).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, d, de)) * scale).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, de, d)) / math.sqrt(de)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d, de * cfg.n_shared_experts, dtype)
    return p


def _capacity(n_tokens: int, n_experts: int, top_k: int) -> int:
    c = int(math.ceil(n_tokens * top_k / n_experts * CAPACITY_FACTOR))
    return max(4, -(-c // 4) * 4)


def _route(cfg: ArchConfig, router_w, x):
    """x (N,d) -> gates (N,E) fp32, topk_idx (N,k), topk_w (N,k) renormalized."""
    logits = x.astype(jnp.float32) @ router_w
    gates = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(gates, cfg.moe_top_k)
    topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)
    return gates, topk_idx, topk_w


def _dispatch_local(x, topk_idx, topk_w, e_lo, n_local, capacity):
    """Sort-based dispatch to the local expert slice [e_lo, e_lo+n_local).

    Returns xg (E_loc, C, d), weight (E_loc, C), token ids (E_loc, C) into x
    (value N = padding). Tokens routed to non-local experts are dropped here —
    their owners handle them on other shards.
    """
    N, k = topk_idx.shape
    flat_e = topk_idx.reshape(-1) - e_lo                       # (N*k,)
    flat_w = topk_w.reshape(-1)
    local = (flat_e >= 0) & (flat_e < n_local)
    sort_key = jnp.where(local, flat_e, n_local)               # non-local last
    order = jnp.argsort(sort_key, stable=True)
    se = sort_key[order]                                       # sorted expert ids
    start = jnp.searchsorted(se, jnp.arange(n_local))
    slot = jnp.arange(N * k) - start[jnp.clip(se, 0, n_local - 1)]
    keep = (se < n_local) & (slot < capacity)
    tok = order // k
    e_idx = jnp.where(keep, se, n_local)                       # drop row
    s_idx = jnp.where(keep, slot, 0)
    tok_mat = jnp.full((n_local + 1, capacity), N, jnp.int32)
    tok_mat = tok_mat.at[e_idx, s_idx].set(jnp.where(keep, tok, N).astype(jnp.int32),
                                           mode="drop")
    w_mat = jnp.zeros((n_local + 1, capacity), flat_w.dtype)
    w_mat = w_mat.at[e_idx, s_idx].set(jnp.where(keep, flat_w[order], 0.0),
                                       mode="drop")
    tok_mat, w_mat = tok_mat[:n_local], w_mat[:n_local]
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)
    return x_pad[tok_mat], w_mat, tok_mat


def _expert_ffn(cfg: ArchConfig, wg, wu, wd, xg):
    h = jnp.einsum("ecd,edh->ech", xg, wu)
    if cfg.gated_mlp:
        h = act_fn(cfg.act)(jnp.einsum("ecd,edh->ech", xg, wg)) * h
    else:
        h = act_fn(cfg.act)(h)
    return jnp.einsum("ech,ehd->ecd", h, wd)


def _moe_shard(cfg: ArchConfig, x, router_w, wg, wu, wd, e_lo, capacity):
    """Single-shard MoE over a local expert slice. x: (N, d)."""
    N, d = x.shape
    n_local = wg.shape[0]
    gates, topk_idx, topk_w = _route(cfg, router_w, x)
    xg, w_mat, tok_mat = _dispatch_local(x, topk_idx, topk_w, e_lo, n_local, capacity)
    out = _expert_ffn(cfg, wg, wu, wd, xg)                      # (E_loc, C, d)
    # accumulate in the compute dtype: each token receives <= top_k adds, and
    # the f32 (N, d) accumulator dominates the train_4k backward carry
    y = jnp.zeros((N + 1, d), x.dtype)
    y = y.at[tok_mat].add(out * w_mat[..., None].astype(x.dtype))
    y = y[:N]
    # load-balance aux loss (per-token so the caller can take a global mean)
    E = cfg.n_experts
    onehot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(1)  # (N,E)
    f = jnp.mean(onehot, axis=0)
    p_mean = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(f * p_mean) / cfg.moe_top_k
    return y, jnp.full((N,), aux, jnp.float32)


def apply_moe(cfg: ArchConfig, params, x, mesh=None, data_axes=None,
              ep_axis="model"):
    """x: (B,T,d) -> (y (B,T,d), aux (B,T)). EP via shard_map when mesh given."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    if mesh is None or ep_axis not in mesh.axis_names \
            or cfg.n_experts % mesh.shape[ep_axis] != 0:
        cap = _capacity(B * T, cfg.n_experts, cfg.moe_top_k)
        y, aux = _moe_shard(cfg, xf, params["router"], params["wg"], params["wu"],
                            params["wd"], 0, cap)
    else:
        ep = mesh.shape[ep_axis]
        n_local = cfg.n_experts // ep
        if data_axes is None:
            data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        d_axes = tuple(a for a in data_axes if a in mesh.axis_names)
        n_data = math.prod(mesh.shape[a] for a in d_axes) if d_axes else 1
        if (B * T) % max(n_data, 1) != 0:      # tiny batches: replicate tokens
            d_axes, n_data = (), 1
        n_loc_tokens = (B * T) // max(n_data, 1)
        cap = _capacity(n_loc_tokens, cfg.n_experts, cfg.moe_top_k)

        def shard_fn(xl, rw, wg, wu, wd):
            j = jax.lax.axis_index(ep_axis)
            y, aux = _moe_shard(cfg, xl, rw, wg, wu, wd, j * n_local, cap)
            # combine in bf16: the f32 (N, d) psum buffer is 2x the size and
            # shows up replicated in the train_4k memory analysis
            y = jax.lax.psum(y.astype(x.dtype), ep_axis)
            return y, aux

        bspec = P(d_axes if d_axes else None, None)
        y, aux = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(bspec, P(None, None), P(ep_axis, None, None),
                      P(ep_axis, None, None), P(ep_axis, None, None)),
            out_specs=(bspec, P(d_axes if d_axes else None)),
            check_vma=False,
        )(xf, params["router"], params["wg"], params["wu"], params["wd"])
    y = y.astype(x.dtype).reshape(B, T, d)
    if "shared" in params:
        y = y + apply_mlp(cfg, params["shared"], x)
    return y, aux.reshape(B, T)


def capacity_keep_mask(topk_idx, n_experts: int, capacity: int):
    """Which (token, k) routing assignments survive the capacity cut.

    Mirrors ``_dispatch_local``'s arrival order exactly: assignments are
    ranked per expert by flat (token, k) index (the stable sort key), and
    ranks >= capacity are dropped. Returns (N, k) bool."""
    N, k = topk_idx.shape
    flat_e = topk_idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    arrival = jnp.cumsum(onehot, axis=0) - onehot          # exclusive rank
    slot = jnp.take_along_axis(arrival, flat_e[:, None], axis=1)[:, 0]
    return (slot < capacity).reshape(N, k)


def moe_dense_reference(cfg: ArchConfig, params, x):
    """O(E) dense oracle: every expert computes every token (tests only).

    Capacity-aware: assignments ``_dispatch_local`` would drop (per-expert
    arrival rank >= capacity) contribute zero here too, so the oracle matches
    the sort-based dispatch exactly — including under imbalanced routing."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    gates, topk_idx, topk_w = _route(cfg, params["router"], xf)
    cap = _capacity(B * T, cfg.n_experts, cfg.moe_top_k)
    keep = capacity_keep_mask(topk_idx, cfg.n_experts, cap)
    full_w = jnp.zeros_like(gates).at[
        jnp.arange(xf.shape[0])[:, None], topk_idx].set(
        jnp.where(keep, topk_w, 0.0))
    outs = _expert_ffn(cfg, params["wg"], params["wu"], params["wd"],
                       jnp.broadcast_to(xf, (cfg.n_experts,) + xf.shape))
    y = jnp.einsum("ne,end->nd", full_w, outs.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, T, d)
    if "shared" in params:
        y = y + apply_mlp(cfg, params["shared"], x)
    return y
