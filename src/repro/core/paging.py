"""KV paging + the paper's hybrid layouts (§4.2).

Host pool uses the **HND** layout ``(B, n_pages, n_kv, 2, p, d)`` — for one
(KV-head, page) the ``(2, p, d)`` K+V block is contiguous, the paper's maximal
transfer unit (2·p·d elements, 16 KiB at p=32, d=128, bf16).

Device-side caches use the **NHD** layout ``(..., p, n_kv, d)`` (token-major) so
appending freshly projected K/V needs no transpose; the NHD→HND transpose happens
once per page at offload time (amortized, off the critical path).

With the quantized host tier (``fkv.kv_quant`` in {"int8", "int4"} —
``src/repro/quant``), the pool stores packed integers and a ``pool_scale``
leaf carries the fp32 per-page scales; pages are quantized exactly where the
NHD→HND transpose already happens (page completion in ``append_token``, bulk
insert in ``prefill_fill_pool``) so quantization cost is amortized off the
decode critical path too. Page *summaries* are computed from the raw keys
before quantization — selection quality is unaffected. The quant parameters
are inferred from the state itself (presence/shape of ``pool_scale``), so
every downstream consumer keeps its signature, and ``kv_quant="none"`` states
carry no extra leaves and trace the exact same graph as before.

All state is a flat dict of arrays so it scans over layers and shards under
pjit. Every leaf keeps the KV-head dim explicit (never folded into another
axis), which is what lets tensor-parallel serving shard the whole dict per
KV-head group (``sharding/rules.decode_state_spec``) and run every op here —
ring append, page completion, quantize-at-offload, the pool scatter —
shard-local inside the TP ``shard_map`` with bit-identical results
(``core/sharded_retrieval.TPGroupShardedRetriever``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, FreeKVConfig
from repro.quant import quantizers as qz


def state_dims(cfg: ArchConfig, fkv: FreeKVConfig, max_len: int):
    p = fkv.page_size
    n_pages = -(-max_len // p)
    m = fkv.pool_pad_pages
    n_pages = -(-n_pages // m) * m
    n_sink = fkv.n_sink
    n_win = fkv.n_window + p          # ring slack so a completing page is present
    n_sel = max(1, (fkv.budget - fkv.n_sink - fkv.n_window) // p)
    if fkv.sharded_retrieval and fkv.sharded_overselect > 1:
        # §Perf opt2 mitigation: extra (invalid-padded) slots so a shard can
        # hold up to overselect x its fair share of globally chosen pages
        n_sel *= fkv.sharded_overselect
    return p, n_pages, n_sink, n_win, n_sel


def quant_info(state):
    """(bits, group_size) of a quantized-pool state, or None when fp.

    Inferred from the state alone: packed int4 pools have half the channel
    width of the device-side buffers, and the scale leaf's group count fixes
    the channel-group size — no config needs threading through the decode
    step."""
    if "pool_scale" not in state:
        return None
    d = state["win_k"].shape[-1]
    bits = 8 if state["pool"].shape[-1] == d else 4
    return bits, d // state["pool_scale"].shape[-1]


def state_bytes(state) -> int:
    """Physical bytes of every leaf of a decode state (packed int8/int4 pool
    payload at its packed width, fp32 scales included) — the unit the serving
    preemption swap accounts, since a swap round-trips the state verbatim."""
    return sum(leaf.nbytes for leaf in jax.tree.leaves(state)
               if hasattr(leaf, "nbytes"))


def init_kv_state(cfg: ArchConfig, fkv: FreeKVConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    """Per-layer FreeKV decode state."""
    p, n_pages, n_sink, n_win, n_sel = state_dims(cfg, fkv, max_len)
    kv, d, H = cfg.n_kv_heads, cfg.d_head, cfg.n_heads
    bits = fkv.quant_bits
    if bits:
        d_packed = d * bits // 8
        n_g = d // qz.effective_group(fkv.quant_group_size, d)
        pool = {"pool": jnp.zeros((batch, n_pages, kv, 2, p, d_packed),
                                  jnp.int8),
                "pool_scale": jnp.zeros((batch, n_pages, kv, 2, n_g),
                                        jnp.float32)}
    else:
        pool = {"pool": jnp.zeros((batch, n_pages, kv, 2, p, d), dtype)}
    return {
        # host pool, HND hybrid layout (offloaded; memory-kind applied by
        # launcher), packed int8/int4 + fp32 scales when kv_quant is on
        **pool,
        # min/max pooled key summaries per page (paper: Quest-style min-max)
        "summ": jnp.zeros((batch, n_pages, kv, 2, d), dtype),
        # device-resident regions (NHD)
        "sink_k": jnp.zeros((batch, n_sink, kv, d), dtype),
        "sink_v": jnp.zeros((batch, n_sink, kv, d), dtype),
        "win_k": jnp.zeros((batch, n_win, kv, d), dtype),
        "win_v": jnp.zeros((batch, n_win, kv, d), dtype),
        "win_pos": jnp.full((batch, n_win), -1, jnp.int32),
        # speculatively recalled pages, per KV head (group-consistent => n_kv)
        "sel_k": jnp.zeros((batch, kv, n_sel, p, d), dtype),
        "sel_v": jnp.zeros((batch, kv, n_sel, p, d), dtype),
        "sel_idx": jnp.full((batch, kv, n_sel), -1, jnp.int32),
        # previous decode step's query vectors (for correction, §3.3)
        "qprev": jnp.zeros((batch, H, d), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# per-slot state surgery (continuous batching)
# ---------------------------------------------------------------------------
# The decode state's page tables (pool/summ/sel_idx/win_pos/length) carry the
# batch dimension on axis 0 per layer — or axis 1 for period-stacked pattern
# layers. Continuous batching maps logical requests onto physical batch slots
# by functionally splicing one row in or out; XLA lowers these to in-place
# dynamic-update-slices so a slot refill never copies the other slots' pools.
def slot_write_leaf(dst, src, slot, axis=0, interpret=None, sharding=None):
    """Write ``src``'s singleton batch row into ``dst``'s row ``slot``.

    dst (..., B, ...) with batch on ``axis``; src identical but batch size 1;
    ``slot`` may be a traced scalar (one compile serves every slot). A
    host-resident ``dst`` (the offloaded pool) is written by DMA in its word
    format (``offload.write_slot``, kernel mode ``interpret``, per shard of
    ``sharding`` under a mesh)."""
    from repro.core import offload
    if offload.is_host(dst):
        return offload.write_slot(dst, src, slot, axis, interpret, sharding)
    upd = jax.lax.index_in_dim(src, 0, axis, keepdims=False).astype(dst.dtype)
    return jax.lax.dynamic_update_index_in_dim(dst, upd, slot, axis)


def slot_read_leaf(arr, slot, axis=0):
    """Extract row ``slot`` as a singleton-batch array (inverse of write)."""
    return jax.lax.dynamic_index_in_dim(arr, slot, axis, keepdims=True)


# ---------------------------------------------------------------------------
# layout conversions
# ---------------------------------------------------------------------------
def nhd_pages_to_hnd(k_pages, v_pages):
    """(B, n, p, kv, d) K and V -> pool block (B, n, kv, 2, p, d) (HND)."""
    k = k_pages.transpose(0, 1, 3, 2, 4)   # (B,n,kv,p,d)
    v = v_pages.transpose(0, 1, 3, 2, 4)
    return jnp.stack([k, v], axis=3)       # (B,n,kv,2,p,d)


def hnd_to_nhd_kv(block):
    """pool block (B, ..., kv, 2, p, d) -> (k, v) each (B, ..., kv, p, d)."""
    return block[..., 0, :, :], block[..., 1, :, :]


# Host-resident pools hold each (2, p, d) K+V block as rows of 32-bit words:
# the TPU's host DMA moves whole (8, 512)-word tiles only, and refuses 16-bit
# element types outright. A 16-bit block packs K into the low and V into the
# high half of each word (elementwise, so no lane relayout); a 32-bit block
# is K's rows then V's. Exact both ways, and one contiguous DMA per block.
WORD_LANES = 512


def word_rows(p: int, d: int, itemsize: int) -> int:
    """Rows of WORD_LANES uint32 words that hold one (2, p, d) block; raises
    when the block does not fill whole (8, WORD_LANES) DMA tiles."""
    nbytes = 2 * p * d * itemsize
    if itemsize not in (2, 4) or nbytes % (8 * WORD_LANES * 4):
        raise NotImplementedError(
            f"host pool blocks must be whole {8 * WORD_LANES * 4}-byte DMA "
            f"tiles of 16/32-bit elements; (2, p={p}, d={d}) at {itemsize} "
            f"bytes is {nbytes}")
    return nbytes // (4 * WORD_LANES)


def hnd_to_words(block):
    """(..., 2, p, d) 16/32-bit blocks -> (..., rows, WORD_LANES) uint32."""
    *lead, _, p, d = block.shape
    rows = word_rows(p, d, block.dtype.itemsize)
    if block.dtype.itemsize == 4:
        words = jax.lax.bitcast_convert_type(block, jnp.uint32)
    else:
        k, v = (jax.lax.bitcast_convert_type(h, jnp.uint16).astype(jnp.uint32)
                for h in hnd_to_nhd_kv(block))
        words = k | (v << 16)
    return words.reshape(*lead, rows, WORD_LANES)


def words_to_kv(words, dtype, p: int, d: int):
    """Inverse of ``hnd_to_words``: (..., rows, WORD_LANES) -> (k, v), each
    (..., p, d) of ``dtype``."""
    lead = words.shape[:-2]
    if jnp.dtype(dtype).itemsize == 4:
        blk = jax.lax.bitcast_convert_type(words, dtype).reshape(*lead, 2, p, d)
        return hnd_to_nhd_kv(blk)
    w = words.reshape(*lead, p, d)
    half = lambda x: jax.lax.bitcast_convert_type(  # noqa: E731
        x.astype(jnp.uint16), dtype)
    return half(w & 0xFFFF), half(w >> 16)


# ---------------------------------------------------------------------------
# bulk (prefill) pool construction
# ---------------------------------------------------------------------------
def prefill_fill_pool(state, k, v, length):
    """Insert a prefill's K/V (B, T, kv, d) into pool + window + sink.

    T must be the (static) prefill length; ``length`` (B,) <= T gives per-row
    valid lengths (rows are right-aligned at position length-1).
    For simplicity rows share T in this framework (continuous batching pads).
    """
    B, T, kv, d = k.shape
    n_pages_total = state["pool"].shape[1]
    p = state["pool"].shape[4]
    n_full = T // p
    kp = k[:, : n_full * p].reshape(B, n_full, p, kv, d)
    vp = v[:, : n_full * p].reshape(B, n_full, p, kv, d)
    hnd = nhd_pages_to_hnd(kp, vp)
    qi = quant_info(state)
    if qi is None:
        pool = jax.lax.dynamic_update_slice(
            state["pool"], hnd.astype(state["pool"].dtype), (0, 0, 0, 0, 0, 0))
        scale_update = {}
    else:
        bits, g = qi
        qblk, qsc = qz.quantize_block(hnd, bits, g)
        pool = jax.lax.dynamic_update_slice(
            state["pool"], qblk, (0, 0, 0, 0, 0, 0))
        scale_update = {"pool_scale": jax.lax.dynamic_update_slice(
            state["pool_scale"], qsc, (0, 0, 0, 0, 0))}
    summ = jnp.stack([kp.min(axis=2), kp.max(axis=2)], axis=3)  # (B,n,kv,2,d)
    summaries = jax.lax.dynamic_update_slice(
        state["summ"], summ.astype(state["summ"].dtype), (0, 0, 0, 0, 0))

    n_sink = state["sink_k"].shape[1]
    n_win = state["win_k"].shape[1]
    sink_k = k[:, :n_sink]
    sink_v = v[:, :n_sink]
    win_k = k[:, T - n_win: T]
    win_v = v[:, T - n_win: T]
    # ring layout: token at absolute position q lives in slot q % n_win
    tail_pos = jnp.arange(T - n_win, T)
    slots = tail_pos % n_win
    wk = jnp.zeros_like(state["win_k"]).at[:, slots].set(win_k.astype(state["win_k"].dtype))
    wv = jnp.zeros_like(state["win_v"]).at[:, slots].set(win_v.astype(state["win_v"].dtype))
    wpos = jnp.full_like(state["win_pos"], -1).at[:, slots].set(
        jnp.broadcast_to(tail_pos, (B, n_win)).astype(jnp.int32))
    return dict(state, pool=pool, summ=summaries, **scale_update,
                sink_k=sink_k.astype(state["sink_k"].dtype),
                sink_v=sink_v.astype(state["sink_v"].dtype),
                win_k=wk, win_v=wv, win_pos=wpos,
                length=jnp.broadcast_to(length, (B,)).astype(jnp.int32))


# ---------------------------------------------------------------------------
# decode-time append + page offload (NHD -> HND transpose amortized here)
# ---------------------------------------------------------------------------
def append_token(state, k_new, v_new, interpret=None):
    """Append one token's K/V (B, kv, d); offload a page when one completes.

    The page completion test is per-row; the pool scatter is masked so rows not
    at a page boundary write nothing (a no-op row writes to its current page
    position with zero-effect data is avoided via index clamping + where).
    A host-resident pool is written by DMA (``offload.write_page``, kernel
    mode ``interpret``).
    """
    from repro.core import offload
    B, n_win, kv, d = state["win_k"].shape
    host = offload.is_host(state["pool"])
    p = state["sel_k"].shape[3] if host else state["pool"].shape[4]
    pos = state["length"]                          # (B,) position of new token
    slot = pos % n_win
    bidx = jnp.arange(B)
    win_k = state["win_k"].at[bidx, slot].set(k_new.astype(state["win_k"].dtype))
    win_v = state["win_v"].at[bidx, slot].set(v_new.astype(state["win_v"].dtype))
    win_pos = state["win_pos"].at[bidx, slot].set(pos)

    new_len = pos + 1
    page_done = (new_len % p) == 0                 # (B,)
    page_idx = new_len // p - 1                    # page just completed
    # gather the completed page's tokens from the ring: positions
    # [page_idx*p, page_idx*p + p) -> slots (pos % n_win)
    tok_pos = page_idx[:, None] * p + jnp.arange(p)[None, :]      # (B,p)
    tok_slot = tok_pos % n_win
    pk = jnp.take_along_axis(win_k, tok_slot[:, :, None, None], axis=1)  # (B,p,kv,d)
    pv = jnp.take_along_axis(win_v, tok_slot[:, :, None, None], axis=1)
    hnd = nhd_pages_to_hnd(pk[:, None], pv[:, None])[:, 0]        # (B,kv,2,p,d)
    summ = jnp.stack([pk.min(axis=1), pk.max(axis=1)], axis=2)    # (B,kv,2,d)

    tgt = jnp.where(page_done, page_idx, 0)
    summaries = state["summ"].at[bidx, tgt].set(jnp.where(
        page_done[:, None, None, None], summ.astype(state["summ"].dtype),
        jnp.take_along_axis(state["summ"], tgt[:, None, None, None, None],
                            axis=1)[:, 0]))
    rest = dict(win_k=win_k, win_v=win_v, win_pos=win_pos, summ=summaries,
                length=new_len)
    if host:                  # masked DMA write; rows without a page skip it
        pool = offload.write_page(state["pool"], hnd.astype(state["sel_k"].dtype),
                                  tgt, page_done, state.get("pool_layer"),
                                  interpret)
        return dict(state, pool=pool, **rest)
    qi = quant_info(state)
    if qi is None:
        blk = hnd.astype(state["pool"].dtype)
        scale_update = {}
    else:
        bits, g = qi
        blk, qsc = qz.quantize_block(hnd, bits, g)        # (B,kv,2,p,dp)
        old_sc_row = jnp.take_along_axis(
            state["pool_scale"], tgt[:, None, None, None, None], axis=1)[:, 0]
        scale_update = {"pool_scale": state["pool_scale"].at[bidx, tgt].set(
            jnp.where(page_done[:, None, None, None], qsc, old_sc_row))}
    old_pool_row = jnp.take_along_axis(
        state["pool"], tgt[:, None, None, None, None, None], axis=1)[:, 0]
    pool = state["pool"].at[bidx, tgt].set(
        jnp.where(page_done[:, None, None, None, None], blk, old_pool_row))
    return dict(state, pool=pool, **scale_update, **rest)
