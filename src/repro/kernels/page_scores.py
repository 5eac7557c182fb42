"""Pallas kernel: Quest min-max page scoring for selection (§3.2).

score[g, n] = scale * sum_d max(q[g,d] * lo[n,d], q[g,d] * hi[n,d])

Grid tiles the page axis in blocks of 128 (lane-aligned) where 128 divides
it, and takes the whole axis otherwise; q's GQA group rides the sublane dim.
The summaries are read in their state layout, (B, n_pages, kv, 2, d), with
the KV head squeezed out of the block: a grid step reads one head's
(pages, 2, d) tile, so the compiled program moves the summaries once, with
no transpose or relayout copy in HBM. This runs off the critical path under
speculative retrieval but on it for corrected heads, so it is a genuine hot
spot at 16K+ pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, s_ref, o_ref, *, scale):
    q = q_ref[0, 0].astype(jnp.float32)            # (G, d)
    lo = s_ref[0, :, 0, :].astype(jnp.float32)     # (NB, d)
    hi = s_ref[0, :, 1, :].astype(jnp.float32)
    # sum_d max(q*lo, q*hi) == relu(q) @ hi^T + min(q,0) @ lo^T  (lo <= hi)
    dot = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = dot(jnp.maximum(q, 0), hi) + dot(jnp.minimum(q, 0), lo)
    o_ref[0, 0] = (s * scale).astype(o_ref.dtype)


def default_interpret() -> bool:
    """Backend-derived kernel execution mode: compiled (Mosaic) on TPU,
    interpret on every other backend — the single source of truth
    (``kernels.ops`` builds its wrappers and ``resolve_interpret`` on it)."""
    return jax.default_backend() != "tpu"


def page_scores(q, summ, *, scale, block_pages=128, interpret=None):
    """q (B, kv, G, d); summ (B, n_pages, kv, 2, d) -> (B, kv, G, n_pages) f32.

    ``interpret=None`` derives the execution mode from the backend
    (``default_interpret``) — override per call or globally via
    ``FreeKVConfig.kernel_interpret`` (see ``kernels.ops.resolve_interpret``).
    """
    if interpret is None:
        interpret = default_interpret()
    B, kv, G, d = q.shape
    N = summ.shape[1]
    NB = block_pages if N % block_pages == 0 else N
    kern = functools.partial(_kernel, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(B, kv, N // NB),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, k, j: (b, k, 0, 0)),
            pl.BlockSpec((1, NB, None, 2, d),
                         lambda b, k, j: (b, j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, NB), lambda b, k, j: (b, k, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, kv, G, N), jnp.float32),
        interpret=interpret, name="page_scores",
    )(q, summ)
