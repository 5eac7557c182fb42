"""Pallas kernel: chunked double-buffered streamed recall (§4.2, TPU).

Gathers the selected KV pages out of the HND pool into NHD device buffers
with an explicit two-deep VMEM ring: while chunk *c*'s pages drain from the
ring slot into the outputs (layout conversion + store), chunk *c+1*'s DMAs
stream into the alternate slot. This is the paper's double buffering
expressed with manual ``pltpu.make_async_copy`` descriptors — one DMA per
selected page, because selected pages are scattered in the pool; each DMA
moves the maximal contiguous unit, the ``(2, p, d)`` HND K+V block
(16 KiB at p=32, d=128, bf16). The page ids arrive as a SCALAR-PREFETCH
operand so the copy source addresses are computable before the body runs.

The pool stays in device memory (``pl.ANY``); a ``pinned_host`` pool is read
by ``kernels/host_pages.recall_gather_host`` instead, since the TPU cannot
DMA from host memory into VMEM (``core/offload.py``). The *staging*
footprint is the ring alone (2 chunks of pages), independent of the
selection budget. The per-(b, h) output blocks
are ``(n_sel, p, d)`` and do scale with the budget — at production shapes
(n_sel=32, p=32, d=128, bf16) that is 256 KiB per output, well under VMEM.

Invalid (``-1``-padded) lanes issue no DMA at all — the masked split the
recall executor plans (top-up vs staged vs reused) is a physical traffic
split, not just accounting. ``values_only=True`` transfers just the V half
of each block (ShadowKV-style recall, half the bytes); the K output is then
all zeros.

Contract (shared with ``core/recall.recall_pages`` and
``kernels/ref.recall_gather_ref``): ``(pool, idx) -> (k, v)``, invalid pages
(``idx < 0``) produce zeros. Interpret-mode parity on CPU is covered by
``tests/test_recall_pipeline.py``; orchestration of *which* pages transfer
on vs off the decode critical path lives in ``core/recall_pipeline.py``.

``recall_gather_quant`` is the quantized-pool variant (``src/repro/quant``):
the packed int8/int4 page rides the same ring, its fp32 scales arrive as one
pre-gathered block, and dequantization to the output dtype is fused into the
drain —
the transfer moves 2-4x fewer bytes and the fp page never exists outside
VMEM. Parity vs the jnp dequant reference: ``tests/test_quant.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, pool_ref, k_ref, v_ref, scratch, sems, *,
            n_sel, n_pages, chunk, n_chunks, values_only):
    b, h = pl.program_id(0), pl.program_id(1)

    def lane_valid(i):
        # invalid (-1 padded) and tail lanes issue NO DMA at all — the
        # transfer truly skips them, matching the telemetry's block counts
        return (i < n_sel) & (idx_ref[b, h, jnp.minimum(i, n_sel - 1)] >= 0)

    def page_of(i):
        return jnp.clip(idx_ref[b, h, jnp.minimum(i, n_sel - 1)],
                        0, n_pages - 1)

    def dma(slot, j, i):
        src = pool_ref.at[b, page_of(i), h]
        if values_only:
            src = src.at[1]                    # V half of the (2, p, d) block
        return pltpu.make_async_copy(src, scratch.at[slot, j],
                                     sems.at[slot, j])

    def start_chunk(slot, c):
        for j in range(chunk):                 # one DMA per scattered page
            i = c * chunk + j

            @pl.when(lane_valid(i))
            def _():
                dma(slot, j, i).start()

    start_chunk(0, 0)                          # warm-up: fill ring slot 0

    def body(c, _):
        slot = jax.lax.rem(c, 2)
        nxt = jax.lax.rem(c + 1, 2)

        @pl.when(c + 1 < n_chunks)             # stream chunk c+1 into the
        def _():                               # alternate ring slot
            start_chunk(nxt, c + 1)

        for j in range(chunk):                 # drain chunk c
            i = c * chunk + j
            valid = lane_valid(i)

            @pl.when(valid)                    # same predicate as the start
            def _():
                dma(slot, j, i).wait()

            @pl.when(i < n_sel)
            def _():
                blk = scratch[slot, j]
                if values_only:
                    zero = jnp.zeros_like(blk)
                    k_ref[0, 0, i] = zero
                    v_ref[0, 0, i] = jnp.where(valid, blk, zero)
                else:
                    zero = jnp.zeros_like(blk[0])
                    k_ref[0, 0, i] = jnp.where(valid, blk[0], zero)
                    v_ref[0, 0, i] = jnp.where(valid, blk[1], zero)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


def _quant_kernel(idx_ref, pool_ref, scale_ref, k_ref, v_ref,
                  scratch, sems, *,
                  n_sel, n_pages, chunk, n_chunks, values_only, bits,
                  out_dtype):
    """Quantized-pool variant: DMA the packed int page through the 2-deep
    VMEM ring and dequantize on drain (fused — the fp page never exists in
    host or HBM, only in VMEM on its way to the output buffer). The selected
    pages' fp32 scales arrive pre-gathered as one aligned (n_sel, 2, n_g)
    block per (b, h): a per-lane DMA of a (2, n_g) scale row would slice the
    lane dimension below its 128-wide tiling. Dequant math matches
    ``repro.quant.quantizers.dequant_block`` exactly: int -> f32 * scale ->
    out_dtype."""
    from repro.quant import quantizers as qz

    b, h = pl.program_id(0), pl.program_id(1)

    def lane_valid(i):
        return (i < n_sel) & (idx_ref[b, h, jnp.minimum(i, n_sel - 1)] >= 0)

    def page_of(i):
        return jnp.clip(idx_ref[b, h, jnp.minimum(i, n_sel - 1)],
                        0, n_pages - 1)

    def dma(slot, j, i):
        src = pool_ref.at[b, page_of(i), h]
        if values_only:
            src = src.at[1]                    # V half of the packed block
        return pltpu.make_async_copy(src, scratch.at[slot, j],
                                     sems.at[slot, j])

    def start_chunk(slot, c):
        for j in range(chunk):                 # one page DMA per lane
            i = c * chunk + j

            @pl.when(lane_valid(i))
            def _():
                dma(slot, j, i).start()

    start_chunk(0, 0)

    def body(c, _):
        slot = jax.lax.rem(c, 2)
        nxt = jax.lax.rem(c + 1, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start_chunk(nxt, c + 1)

        for j in range(chunk):
            i = c * chunk + j
            valid = lane_valid(i)

            @pl.when(valid)
            def _():
                dma(slot, j, i).wait()

            @pl.when(i < n_sel)
            def _():
                sc = scale_ref[0, 0, jnp.minimum(i, n_sel - 1)]
                if values_only:
                    sc = sc[1]
                deq = qz.dequant_block(scratch[slot, j], sc, bits, out_dtype)
                zero = jnp.zeros_like(deq[..., 0, :, :] if not values_only
                                      else deq)
                if values_only:
                    k_ref[0, 0, i] = zero
                    v_ref[0, 0, i] = jnp.where(valid, deq, zero)
                else:
                    k_ref[0, 0, i] = jnp.where(valid, deq[0], zero)
                    v_ref[0, 0, i] = jnp.where(valid, deq[1], zero)
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)


def recall_gather_quant(pool, scales, idx, *, bits, values_only=False,
                        out_dtype=jnp.float32, chunk=None, interpret=True):
    """Fused dequant-on-recall gather from the packed host pool.

    pool (B, n_pages, kv, 2, p, d_packed) int8 (packed int4 when bits=4);
    scales (B, n_pages, kv, 2, n_groups) float32; idx (B, kv, n_sel) int32
    (-1 pad) -> (k, v) each (B, kv, n_sel, p, d) in ``out_dtype``. Matches
    ``repro.quant.quantizers.dequant_recall_pages`` bit-for-bit."""
    B, n_pages, kv, _, p, dp = pool.shape
    d = dp * (8 // bits)
    n_g = scales.shape[-1]
    n_sel = idx.shape[2]
    chunk = max(1, min(chunk or 8, n_sel))
    n_chunks = -(-n_sel // chunk)

    ring = ((2, chunk, p, dp) if values_only else (2, chunk, 2, p, dp))
    safe = jnp.clip(idx, 0, n_pages - 1)
    sel_scales = scales[jnp.arange(B)[:, None, None], safe,
                        jnp.arange(kv)[None, :, None]]    # (B, kv, n_sel, 2, n_g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, kv),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((1, 1, n_sel, 2, n_g),
                               lambda b, h, idx_ref: (b, h, 0, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, n_sel, p, d), lambda b, h, idx_ref: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, n_sel, p, d), lambda b, h, idx_ref: (b, h, 0, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM(ring, pool.dtype),
                        pltpu.SemaphoreType.DMA((2, chunk))],
    )
    out_shape = [jax.ShapeDtypeStruct((B, kv, n_sel, p, d), out_dtype),
                 jax.ShapeDtypeStruct((B, kv, n_sel, p, d), out_dtype)]
    kernel = functools.partial(
        _quant_kernel, n_sel=n_sel, n_pages=n_pages, chunk=chunk,
        n_chunks=n_chunks, values_only=values_only, bits=bits,
        out_dtype=out_dtype)
    k, v = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        name="recall_gather_quant",
    )(idx, pool, sel_scales)
    return k, v


def recall_gather(pool, idx, *, values_only=False, chunk=None, interpret=True):
    """pool (B, n_pages, kv, 2, p, d) HND; idx (B, kv, n_sel) int32 (-1 pad)
    -> (k, v) each (B, kv, n_sel, p, d)."""
    B, n_pages, kv, _, p, d = pool.shape
    n_sel = idx.shape[2]
    chunk = max(1, min(chunk or 8, n_sel))
    n_chunks = -(-n_sel // chunk)

    ring = ((2, chunk, p, d) if values_only else (2, chunk, 2, p, d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, kv),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((1, 1, n_sel, p, d), lambda b, h, idx_ref: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, n_sel, p, d), lambda b, h, idx_ref: (b, h, 0, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM(ring, pool.dtype),
                        pltpu.SemaphoreType.DMA((2, chunk))],
    )
    out_shape = [jax.ShapeDtypeStruct((B, kv, n_sel, p, d), pool.dtype),
                 jax.ShapeDtypeStruct((B, kv, n_sel, p, d), pool.dtype)]
    kernel = functools.partial(
        _kernel, n_sel=n_sel, n_pages=n_pages, chunk=chunk,
        n_chunks=n_chunks, values_only=values_only)
    k, v = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        name="recall_gather",
    )(idx, pool)
    return k, v
