"""Pallas TPU kernel: decode attention over per-KV-head selected pages.

This is FreeKV's decode hot spot: one query token per request attends to the
budget-resident pages (sink + window + speculatively recalled), laid out NHD
(page-major (p, d) blocks). Flash-style online softmax over a token grid:

  grid = (B, kv, ceil(L / blk)); each step loads a (blk, d) run of K and V
  tokens into VMEM and updates running (m, l, acc) scratch for all G group
  queries; the last step writes acc/l. Pallas pipelines the grid, so block
  j+1's HBM->VMEM DMA overlaps block j's compute.

Tiling: the pages are passed flattened to (L = N·p, d) per head and their
positions as a (1, L) row. A resident set of up to ``max_block`` tokens (the
paper's 2048-token budget plus sink and window) is one whole-axis block per
(b, kv): few grid steps, whose fixed cost outweighs a block's DMA at decode
sizes. Larger sets stream in ``max_block``-token blocks (a multiple of 128,
so the position row stays lane-aligned) and VMEM stays bounded; a ragged
last block is masked by token index. The G query heads of a GQA group ride
the sublane dimension of the q block. The current position arrives by
scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref,
            acc_ref, *, scale, softcap, n_tokens):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (blk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (blk, d)
    pos = pos_ref[0, 0]                            # (1, blk) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    ok = (pos >= 0) & (pos <= cur_ref[b])
    blk = k.shape[0]
    if n_tokens % blk:
        # ragged last block: lanes past L hold stale VMEM (maybe NaN)
        start = j * blk
        ok &= start + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) \
            < n_tokens
        v = jnp.where(start + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
                      < n_tokens, v, 0.0)
    s = jnp.where(ok, s, NEG_INF)                  # (G, blk)

    m_prev = m_ref[...]                            # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    w = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        w, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_pos, cur_pos, *, scale,
                    softcap=None, interpret=True, max_block=2304):
    """q (B,kv,G,d); k/v_pages (B,kv,N,p,d); page_pos (B,kv,N,p);
    cur_pos (B,) -> (B,kv,G,d). ``max_block`` (a multiple of 128) bounds the
    tokens held in VMEM per grid step."""
    B, kv, G, d = q.shape
    L = k_pages.shape[2] * k_pages.shape[3]
    blk = L if L <= max_block else max_block
    kern = functools.partial(_kernel, scale=scale, softcap=softcap,
                             n_tokens=L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, kv, pl.cdiv(L, blk)),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, k, j, cur: (b, k, 0, 0)),
            pl.BlockSpec((1, 1, blk, d), lambda b, k, j, cur: (b, k, j, 0)),
            pl.BlockSpec((1, 1, blk, d), lambda b, k, j, cur: (b, k, j, 0)),
            pl.BlockSpec((1, 1, 1, blk), lambda b, k, j, cur: (b, k, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, k, j, cur: (b, k, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, d), jnp.float32)],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, G, d), q.dtype),
        interpret=interpret, name="paged_attention",
    )(cur_pos.astype(jnp.int32), q, k_pages.reshape(B, kv, L, d),
      v_pages.reshape(B, kv, L, d), page_pos.reshape(B, kv, 1, L))
