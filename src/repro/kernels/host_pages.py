"""Pallas DMA kernels for the host-resident KV pool (``offload="host"``).

With the pool in ``pinned_host`` memory the decode step reaches it only by
DMA: XLA cannot gather or scatter in host memory, and the TPU cannot DMA from
host memory into VMEM. So both kernels move whole page blocks between host
memory and HBM, and nothing else:

* ``recall_gather_host`` — the streamed recall: one host->HBM DMA per
  selected (kv-head, page) block, all in flight at once per grid step.
* ``write_host`` — page offload: one HBM->host DMA per batch row whose page
  completed this step, into the pool aliased in place.

Blocks travel in the word format of ``core/paging.hnd_to_words`` (rows of
uint32 words), the only shape the host DMA accepts. The pool may carry a
leading layer axis (the period-stacked decode state): ``layer`` then picks
the layer inside the kernel, so the scan over layers never slices — and so
never copies — the host buffer.

Output buffers are declared in HBM (``pltpu.HBM``): left unconstrained, XLA
may place a small kernel output in VMEM, which the host DMA cannot reach.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _space(x):
    """The kernel-side memory space of a pool operand: host for a
    ``pinned_host`` array, anywhere (HBM) otherwise — e.g. a device-resident
    pool under interpret-mode tests."""
    if jax.typeof(x).memory_space == jax.memory.Space.Host:
        return pltpu.HOST
    return pl.ANY


def _layer_operand(layer):
    return jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                   jnp.int32), (1,))


def recall_gather_host(pool, idx, *, layer=None, interpret=False):
    """Gather selected page blocks from the word-format pool.

    pool ([L,] B, n_pages, kv, rows, lanes) uint32; idx (B, kv, n_sel) int32
    (-1 pad); ``layer`` indexes L when the pool is layer-stacked. Returns
    (B, kv, n_sel, rows, lanes) in HBM. Lanes with ``idx < 0`` issue no DMA
    and hold unspecified words: callers mask them."""
    stacked = layer is not None
    B, n_pages, kv, rows, lanes = pool.shape[-5:]
    n_sel = idx.shape[2]

    def kernel(layer_ref, idx_ref, pool_ref, out_ref, sems):
        b, h = pl.program_id(0), pl.program_id(1)

        def copy(i):
            page = jnp.clip(idx_ref[b, h, i], 0, n_pages - 1)
            src = (pool_ref.at[layer_ref[0], b, page, h] if stacked
                   else pool_ref.at[b, page, h])
            return pltpu.make_async_copy(src, out_ref.at[b, h, i], sems.at[i])

        for i in range(n_sel):                # every DMA in flight at once
            @pl.when(idx_ref[b, h, i] >= 0)
            def _():
                copy(i).start()

        for i in range(n_sel):
            @pl.when(idx_ref[b, h, i] >= 0)
            def _():
                copy(i).wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, kv),
        in_specs=[pl.BlockSpec(memory_space=_space(pool))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((n_sel,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=pltpu.HBM((B, kv, n_sel, rows, lanes), pool.dtype),
        interpret=interpret, name="recall_gather_host",
    )(_layer_operand(layer), idx, pool)


def write_host(pool, blk, tgt, done, *, layer=None, interpret=False):
    """Write completed page blocks into the word-format pool in place.

    pool ([L,] B, n_pages, kv, rows, lanes) uint32; blk (B, kv, rows, lanes)
    the page each row just completed; tgt (B,) its page index; done (B,)
    whether row b completed a page (rows that did not write nothing).
    Returns the updated pool (aliased to the input)."""
    stacked = layer is not None
    B = blk.shape[0]

    def kernel(layer_ref, tgt_ref, done_ref, blk_ref, _pool_in, pool_ref,
               sem):
        for b in range(B):
            dst = (pool_ref.at[layer_ref[0], b, tgt_ref[b]] if stacked
                   else pool_ref.at[b, tgt_ref[b]])

            @pl.when(done_ref[b] != 0)
            def _():
                cp = pltpu.make_async_copy(blk_ref.at[b], dst, sem.at[0])
                cp.start()
                cp.wait()

    space = _space(pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=space)],
        out_specs=pl.BlockSpec(memory_space=space),
        scratch_shapes=[pltpu.SemaphoreType.DMA((1,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0}, interpret=interpret, name="write_host",
    )(_layer_operand(layer), tgt.astype(jnp.int32),
      done.astype(jnp.int32), blk.astype(pool.dtype), pool)


def write_slot(dst, src, slot, *, axis=0, interpret=False):
    """Splice a batch-1 state leaf into batch row ``slot`` of a host-resident
    leaf, in place: ``dst[..., slot, ...] = src[..., 0, ...]`` with the batch
    on ``axis`` (1 for period-stacked leaves). One DMA per leading index —
    XLA's own host update cannot write along a non-major dimension."""
    n_lead = dst.shape[0] if axis == 1 else 1

    def kernel(slot_ref, src_ref, _dst_in, dst_ref, sem):
        for l in range(n_lead):
            s_ref, d_ref = ((src_ref.at[l, 0], dst_ref.at[l, slot_ref[0]])
                            if axis == 1 else
                            (src_ref.at[0], dst_ref.at[slot_ref[0]]))
            cp = pltpu.make_async_copy(s_ref, d_ref, sem.at[0])
            cp.start()
            cp.wait()

    space = _space(dst)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=space)],
        out_specs=pl.BlockSpec(memory_space=space),
        scratch_shapes=[pltpu.SemaphoreType.DMA((1,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        input_output_aliases={2: 0}, interpret=interpret, name="write_slot",
    )(jnp.reshape(slot, (1,)).astype(jnp.int32), src.astype(dst.dtype), dst)
