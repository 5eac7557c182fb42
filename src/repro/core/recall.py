"""Recall primitives: gather selected KV pages from the HND host pool into
NHD device buffers. This is the pure-jnp reference path for the
``(pool, idx) -> (k, v)`` contract; the chunked double-buffered Pallas kernel
(``kernels/recall_gather.py``) implements the same contract with an explicit
2-deep VMEM ring and per-chunk DMA overlap.

Scheduling — *which* pages transfer on vs off the decode critical path
(speculative staging, correction top-up, resident-buffer reuse) — lives one
level up in ``core/recall_pipeline.RecallExecutor``; every retriever routes
its transfers through that executor.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import shard_map


def recall_pages(pool, idx):
    """pool: (B, n_pages, kv, 2, p, d) HND; idx: (B, kv, n_sel) int32 (-1 invalid)
    -> (sel_k, sel_v) each (B, kv, n_sel, p, d) NHD-per-head."""
    B, n_pages, kv, _, p, d = pool.shape
    safe = jnp.clip(idx, 0, n_pages - 1)
    bI = jnp.arange(B)[:, None, None]
    kI = jnp.arange(kv)[None, :, None]
    blk = pool[bI, safe, kI]                      # (B,kv,n_sel,2,p,d)
    blk = jnp.where((idx >= 0)[..., None, None, None], blk, 0)
    return blk[..., 0, :, :], blk[..., 1, :, :]


def recall_values_only(pool, idx):
    """ShadowKV-style: only the V half is transferred (K reconstructed)."""
    B, n_pages, kv, _, p, d = pool.shape
    safe = jnp.clip(idx, 0, n_pages - 1)
    bI = jnp.arange(B)[:, None, None]
    kI = jnp.arange(kv)[None, :, None]
    v = pool[bI, safe, kI, 1]                     # (B,kv,n_sel,p,d)
    return jnp.where((idx >= 0)[..., None, None], v, 0)


def _local_gather(pool, idx):
    B, n_pages, kv = pool.shape[0], pool.shape[1], pool.shape[2]
    safe = jnp.clip(idx, 0, n_pages - 1)
    bI = jnp.arange(B)[:, None, None]
    kI = jnp.arange(kv)[None, :, None]
    blk = pool[bI, safe, kI]
    return jnp.where((idx >= 0)[..., None, None, None], blk, 0)


def recall_pages_sharded(pool, idx, mesh, batch_ok: bool, kv_div: bool):
    """shard_map recall: the GSPMD partitioner turns the fancy gather over a
    sharded pool into a pool-sized masked all-reduce (measured: ~8.6 GB/dev at
    64 devices); doing it shard-local brings collectives to ~0 for
    (batch, kv)-sharded pools and to one selected-pages-sized psum for
    page-sharded pools (long_500k / kv-indivisible archs).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_spec = ba if batch_ok else None
    if kv_div:
        pool_spec = P(b_spec, None, "model", None, None, None)
        idx_spec = P(b_spec, "model", None)
        out_spec = P(b_spec, "model", None, None, None, None)

        def f(pool_l, idx_l):
            return _local_gather(pool_l, idx_l)

        blk = shard_map(f, mesh=mesh, in_specs=(pool_spec, idx_spec),
                            out_specs=out_spec, check_vma=False)(pool, idx)
    else:
        page_axes = ("model",) if batch_ok else tuple(
            a for a in ("pod", "data", "model") if a in mesh.axis_names)
        pool_spec = P(b_spec, page_axes, None, None, None, None)
        idx_spec = P(b_spec, None, None)
        out_spec = P(b_spec, None, None, None, None, None)

        def f(pool_l, idx_l):
            n_loc = pool_l.shape[1]
            lin = 0
            for a in page_axes:
                lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
            lo = lin * n_loc
            rel = idx_l - lo
            mask = (idx_l >= 0) & (rel >= 0) & (rel < n_loc)
            blk = _local_gather(pool_l, jnp.where(mask, rel, -1))
            return jax.lax.psum(blk, page_axes)

        blk = shard_map(f, mesh=mesh, in_specs=(pool_spec, idx_spec),
                            out_specs=out_spec, check_vma=False)(pool, idx)
    return blk[..., 0, :, :], blk[..., 1, :, :]


def recall_bytes(idx, p, d, itemsize=2, kv_and_v=True):
    """Bytes moved host->device for a recall (cost-model input)."""
    import numpy as np
    n = int(np.sum(np.asarray(idx) >= 0))
    return n * (2 if kv_and_v else 1) * p * d * itemsize
