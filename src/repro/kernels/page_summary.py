"""Pallas kernel: fused min/max page summaries over post-RoPE keys.

Runs at page-offload time (off the critical path): one grid step reduces one
(p, d) key page to its (2, d) bounding box (Quest-style summary, §3.2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(k_ref, o_ref):
    k = k_ref[0, 0]                              # (p, d)
    o_ref[0, 0, 0, 0] = jnp.min(k, axis=0)
    o_ref[0, 0, 0, 1] = jnp.max(k, axis=0)


def page_summary(k, *, page_size, interpret=True):
    """k (B, T, kv, d) with T = n_pages * p -> (B, n_pages, kv, 2, d).

    The keys enter KV-head-major, (B, kv, T, d), so each grid step reads one
    (p, d) page tile."""
    B, T, kv, d = k.shape
    p = page_size
    assert T % p == 0
    N = T // p
    return pl.pallas_call(
        _kernel,
        grid=(B, N, kv),
        in_specs=[pl.BlockSpec((1, 1, p, d), lambda b, n, h: (b, h, n, 0))],
        out_specs=pl.BlockSpec((1, 1, 1, 2, d),
                               lambda b, n, h: (b, n, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, kv, 2, d), k.dtype),
        interpret=interpret, name="page_summary",
    )(k.transpose(0, 2, 1, 3))
