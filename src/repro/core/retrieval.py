"""Retrievers: FreeKV (the paper) + the baselines it compares against, behind a
uniform functional API so the model stack and serving engine are method-agnostic:

    r = make_retriever(cfg, fkv)
    state = r.init_state(batch, max_len, dtype)
    state = r.prefill(state, k, v, q_last)         # bulk-insert a prompt
    o, state, info = r.decode(state, q, k_new, v_new[, q_proxy])

Shapes: k/v (B,T,kv,dh) post-RoPE; q (B,H,dh) single decode token.
``info`` carries per-step statistics for the latency cost model (bytes recalled
on/off the critical path, correction counts, similarities).

Methods:
  freekv     speculative retrieval + fine-grained correction (the paper)
  arkvale    fresh selection + blocking recall each step (tau=inf ~ always correct)
  infinigen  selection from a proxy query (prev layer), token-wise recall
  quest      per-q-head (non-group-consistent) selection, no offload
  shadowkv   low-rank keys on device, V-only recall
  raas       dynamic dropping with recency timestamps (no pool)
  streaming  sink + window only (StreamingLLM / Razor-style static)
  full       exact dense cache (oracle)
  centroid   centroid-then-token two-level selection (CTkvr-style) inside
             the FreeKV speculative + correction machinery
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, FreeKVConfig
from repro.core import centroid_index, offload, paging, recall, selection
from repro.core.correction import corrected_heads
from repro.core.recall_pipeline import RecallExecutor, match_resident
from repro.models.layers import softcap
from repro.obs.trace import (SPAN_ATTN_COMPUTE, SPAN_RECALL_CORRECTION,
                             SPAN_RECALL_SELECT, annotate)
from repro.quant import quantizers as qz

NEG_INF = -1e30


def _scale(cfg):
    return cfg.attn_scale if cfg.attn_scale is not None else 1.0 / (cfg.d_head ** 0.5)


def _attend(cfg, q, k_cat, v_cat, pos_cat, cur_pos, window=None,
            fkv=None, use_kernels=False):
    """q (B,H,d); k/v_cat (B,kv,L,d); pos_cat (B,kv,L) -> (B,H,d).

    With ``use_kernels`` (single-device path) this dispatches to the Pallas
    paged-attention kernel (interpret-mode on CPU, Mosaic on TPU)."""
    B, H, d = q.shape
    if (use_kernels and window is None and fkv is not None
            and k_cat.shape[2] % fkv.page_size == 0):
        from repro.kernels import ops
        p = fkv.page_size
        kv_ = k_cat.shape[1]
        G_ = H // kv_
        L = k_cat.shape[2]
        o = ops.paged_attention(
            q.reshape(B, kv_, G_, d),
            k_cat.reshape(B, kv_, L // p, p, d),
            v_cat.reshape(B, kv_, L // p, p, d),
            pos_cat.reshape(B, kv_, L // p, p), cur_pos,
            scale=_scale(cfg), softcap=cfg.attn_logit_softcap,
            interpret=ops.resolve_interpret(fkv))
        return o.reshape(B, H, d)
    kv = k_cat.shape[1]
    G = H // kv
    qg = q.reshape(B, kv, G, d)
    s = jnp.einsum("bkgd,bkld->bkgl", qg, k_cat).astype(jnp.float32) * _scale(cfg)
    s = softcap(s, cfg.attn_logit_softcap)
    ok = (pos_cat >= 0) & (pos_cat <= cur_pos[:, None, None])
    if window is not None:
        ok &= pos_cat > (cur_pos[:, None, None] - window)
    s = jnp.where(ok[:, :, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,bkld->bkgd", w.astype(v_cat.dtype), v_cat)
    return o.reshape(B, H, d)


def _window_floor(fkv, length):
    """First position attended via the window ring. Tokens in
    [n_sink, window_floor) are attended via selected pages; the partition
    sink / selected / window is exact (no double counting, no gaps):
    selectable pages are exactly [n_sink//p, window_floor//p)."""
    p = fkv.page_size
    return jnp.maximum(fkv.n_sink // p, (length - fkv.n_window) // p) * p


def _cat_regions(fkv, state, sel_k, sel_v, sel_idx, p):
    """Concatenate sink + window + selected pages per KV head, with the
    three-region position partition applied via pos = -1 masking."""
    B, n_sink, kv, d = state["sink_k"].shape
    n_win = state["win_k"].shape[1]
    length = state["length"]
    wfloor = _window_floor(fkv, length)[:, None, None]           # (B,1,1)
    ks = state["sink_k"].transpose(0, 2, 1, 3)                   # (B,kv,S,d)
    vs = state["sink_v"].transpose(0, 2, 1, 3)
    pos_s = jnp.broadcast_to(jnp.arange(n_sink)[None, None, :], (B, kv, n_sink))
    pos_s = jnp.where(pos_s < length[:, None, None], pos_s, -1)
    kw = state["win_k"].transpose(0, 2, 1, 3)
    vw = state["win_v"].transpose(0, 2, 1, 3)
    pos_w = jnp.broadcast_to(state["win_pos"][:, None, :], (B, kv, n_win))
    pos_w = jnp.where((pos_w >= n_sink) & (pos_w >= wfloor), pos_w, -1)
    n_sel = sel_idx.shape[2]
    kp = sel_k.reshape(B, kv, n_sel * p, d)
    vp = sel_v.reshape(B, kv, n_sel * p, d)
    pos_p = (sel_idx[..., None] * p + jnp.arange(p)[None, None, None, :])
    pos_p = jnp.where(sel_idx[..., None] >= 0, pos_p, -1).reshape(B, kv, n_sel * p)
    pos_p = jnp.where((pos_p >= n_sink) & (pos_p < wfloor), pos_p, -1)
    k_cat = jnp.concatenate([ks, kw, kp], axis=2)
    v_cat = jnp.concatenate([vs, vw, vp], axis=2)
    pos = jnp.concatenate([pos_s, pos_w, pos_p], axis=2).astype(jnp.int32)
    return k_cat, v_cat, pos


def ring_snapshot(state, n_rows: int):
    """Save the ``n_rows`` window-ring slots a drafted block will write.

    A verify pass (``models.serve_step_verify``) appends every drafted row
    into the ring before knowing which rows commit; rows ``>= m`` must then
    be undone so the ring is bitwise what ``m`` sequential appends leave.
    Appends at positions ``length + j`` land in slots ``(length + j) %
    n_win`` — distinct while ``n_rows <= n_win`` — so saving those slots'
    (k, v, pos) beforehand is a complete undo log. Works for any state with
    the ``win_k/win_v/win_pos`` ring contract (FreeKV and streaming)."""
    n_win = state["win_k"].shape[1]
    slots = (state["length"][:, None] + jnp.arange(n_rows)[None]) % n_win
    k = jnp.take_along_axis(state["win_k"], slots[:, :, None, None], axis=1)
    v = jnp.take_along_axis(state["win_v"], slots[:, :, None, None], axis=1)
    pos = jnp.take_along_axis(state["win_pos"], slots, axis=1)
    return slots, k, v, pos


def ring_restore(state, snap, keep):
    """Undo the ring writes of rejected drafted rows.

    ``snap`` is ``ring_snapshot`` taken before the block; ``keep`` (B,) is
    the per-slot committed row count m. Slots written by rows < m keep the
    new content (identical to sequential appends); slots written by rows
    >= m revert to the snapshot. Pool/summary writes by rejected rows are
    deliberately NOT undone: a stale page is never selectable before the
    genuine append rewrites it (selection admits pages < length//p only,
    and the crossing append rewrites first)."""
    slots, k, v, pos = snap
    B, S = slots.shape
    rej = jnp.arange(S)[None, :] >= keep[:, None]                  # (B, S)
    bidx = jnp.arange(B)[:, None]
    cur_k = jnp.take_along_axis(state["win_k"], slots[:, :, None, None], 1)
    cur_v = jnp.take_along_axis(state["win_v"], slots[:, :, None, None], 1)
    cur_p = jnp.take_along_axis(state["win_pos"], slots, axis=1)
    r4 = rej[:, :, None, None]
    return dict(
        state,
        win_k=state["win_k"].at[bidx, slots].set(jnp.where(r4, k, cur_k)),
        win_v=state["win_v"].at[bidx, slots].set(jnp.where(r4, v, cur_v)),
        win_pos=state["win_pos"].at[bidx, slots].set(
            jnp.where(rej, pos, cur_p)))


class FreeKVRetriever:
    """FreeKV (and, by flags, ArkVale / InfiniGen-style baselines)."""

    def __init__(self, cfg: ArchConfig, fkv: FreeKVConfig,
                 speculative: bool = True, proxy_query: bool = False,
                 token_wise_recall: bool = False, mesh=None):
        self.cfg, self.fkv = cfg, fkv
        self.speculative = speculative          # False => ArkVale-style blocking
        self.proxy_query = proxy_query          # True  => InfiniGen-style
        self.token_wise_recall = token_wise_recall
        self.offloaded = True
        self.mesh = mesh                        # enables shard-local recall
        self.use_kernels = fkv.use_kernels and mesh is None
        self.executor = RecallExecutor(recall_fn=self._recall,
                                       values_fn=self._recall_values)

    def _overlap(self):
        """Pipelined (double-buffered) recall applies to the speculative
        single-device path; the sharded path keeps its own fused step."""
        return (self.fkv.recall_overlap and self.speculative
                and self.mesh is None)

    def _interpret(self):
        from repro.kernels import ops
        return ops.resolve_interpret(self.fkv)

    def _pool_view(self, state):
        """The opaque pool reference the recall executor threads through:
        the fp pool array, a ``HostPool`` view of a host-resident pool, or a
        (packed pool, fp32 scales) pair under the quantized host tier —
        every gather backend unpacks the same way."""
        host = offload.host_view(state, self._interpret())
        if host is not None:
            return host
        if "pool_scale" in state:
            return (state["pool"], state["pool_scale"])
        return state["pool"]

    def _recall_values(self, pool, idx):
        if isinstance(pool, offload.HostPool):        # DMA is the only way in
            return offload.recall_host(pool, idx)[1]
        if isinstance(pool, tuple):                   # quantized host tier
            pool_q, scales = pool
            if self.use_kernels:
                from repro.kernels import ops
                return ops.recall_values_quant(
                    pool_q, scales, idx, bits=self.fkv.quant_bits,
                    chunk=self.fkv.recall_chunk_pages or None,
                    interpret=ops.resolve_interpret(self.fkv))
            return qz.dequant_recall_values(pool_q, scales, idx,
                                            self.fkv.quant_bits)
        if self.use_kernels:
            from repro.kernels import ops
            return ops.recall_values(
                    pool, idx, chunk=self.fkv.recall_chunk_pages or None,
                    interpret=ops.resolve_interpret(self.fkv))
        return recall.recall_values_only(pool, idx)

    def _recall(self, pool, idx):
        if isinstance(pool, offload.HostPool):        # DMA is the only way in
            return offload.recall_host(pool, idx)
        if isinstance(pool, tuple):                   # quantized host tier
            # fused dequant-on-recall: packed page + scales move, bf16/fp
            # never does. The page-sharded shard_map gather is fp-only; under
            # a mesh the jnp dequant gather runs (correct under pjit, the
            # partitioner handles it) — see docs/methods.md.
            pool_q, scales = pool
            if self.use_kernels and self.mesh is None:
                from repro.kernels import ops
                return ops.recall_gather_quant(
                    pool_q, scales, idx, bits=self.fkv.quant_bits,
                    chunk=self.fkv.recall_chunk_pages or None,
                    interpret=ops.resolve_interpret(self.fkv))
            return qz.dequant_recall_pages(pool_q, scales, idx,
                                           self.fkv.quant_bits)
        mesh = self.mesh
        if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
            if self.use_kernels:
                from repro.kernels import ops
                return ops.recall_gather(
                    pool, idx, chunk=self.fkv.recall_chunk_pages or None,
                    interpret=ops.resolve_interpret(self.fkv))
            return recall.recall_pages(pool, idx)
        import math as _math
        ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        nb = _math.prod(mesh.shape[a] for a in ba) if ba else 1
        batch_ok = pool.shape[0] % max(nb, 1) == 0 and pool.shape[0] >= nb
        kv_div = self.cfg.n_kv_heads % mesh.shape["model"] == 0
        return recall.recall_pages_sharded(pool, idx, mesh, batch_ok, kv_div)

    # -- state ---------------------------------------------------------
    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        return paging.init_kv_state(self.cfg, self.fkv, batch, max_len, dtype)

    def _n_sel(self, state):
        return state["sel_idx"].shape[2]

    # -- prefill -------------------------------------------------------
    def prefill(self, state, k, v, q_last):
        """k/v (B,T,kv,d); q_last (B,H,d): the prompt's final query, used for
        the initial speculative selection + recall."""
        B, T = k.shape[:2]
        length = jnp.full((B,), T, jnp.int32)
        state = paging.prefill_fill_pool(state, k, v, length)
        idx, _ = selection.select_pages(
            self.cfg, self.fkv, q_last, state["summ"], state["length"],
            self._n_sel(state))
        sk, sv = self._recall(self._pool_view(state), idx)
        return dict(state, sel_k=sk.astype(state["sel_k"].dtype),
                    sel_v=sv.astype(state["sel_v"].dtype), sel_idx=idx,
                    qprev=q_last.astype(state["qprev"].dtype))

    def _use_sharded(self, state):
        mesh = self.mesh
        if not (self.fkv.sharded_retrieval and mesh is not None
                and "model" in getattr(mesh, "axis_names", ())):
            return False
        if self.fkv.kv_quant != "none":
            # the fused shard-local step reads the fp pool directly; the
            # quantized tier falls back to the plain (pjit-partitioned) path
            return False
        mp = mesh.shape["model"]
        n_sel = state["sel_idx"].shape[2]
        n_pages = state["pool"].shape[1]
        return n_sel % mp == 0 and n_pages % mp == 0

    # -- decode --------------------------------------------------------
    def decode(self, state, q, k_new, v_new, q_proxy=None):
        cfg, fkv = self.cfg, self.fkv
        p = fkv.page_size
        cur_pos = state["length"]                    # position of the new token

        if self._use_sharded(state):             # beyond-paper (§Perf)
            from repro.core.sharded_retrieval import sharded_decode_step
            if self.speculative:
                corr, sim = corrected_heads(cfg, fkv, q, state["qprev"])
                corr = corr | jnp.all(state["qprev"].astype(jnp.float32) == 0)
            else:
                corr = jnp.ones((q.shape[0], cfg.n_kv_heads), bool)
                sim = jnp.zeros((q.shape[0], cfg.n_kv_heads), jnp.float32)
            prev_idx = state["sel_idx"]
            # NOTE: append happens INSIDE the shard body (the page write is
            # masked to its owning shard) — state here is pre-append
            o, updates, new_k, new_v, new_idx = sharded_decode_step(
                cfg, fkv, self.mesh, state, q, k_new, v_new, corr)
            state = dict(state, **updates,
                         sel_k=new_k.astype(state["sel_k"].dtype),
                         sel_v=new_v.astype(state["sel_v"].dtype),
                         sel_idx=new_idx,
                         qprev=q.astype(state["qprev"].dtype))
            n_sel = new_idx.shape[2]
            # speculation quality: the fused step fetches fresh regardless,
            # but selection overlap vs the previous step is still the
            # telemetry of interest (docs/observability.md)
            sel_pages = jnp.sum(new_idx >= 0, axis=(1, 2))
            spec_hit = jnp.sum(match_resident(new_idx, prev_idx)[0],
                               axis=(1, 2))
            info = {"corrected": corr, "similarity": sim,
                    "sync_pages": jnp.sum(corr, axis=1) * n_sel,
                    "async_pages": jnp.sum(~corr, axis=1) * n_sel,
                    "sel_pages": sel_pages,
                    "spec_hit_pages": spec_hit,
                    "churn_pages": sel_pages - spec_hit,
                    "granularity": "page"}
            return o, state, info

        state = paging.append_token(state, k_new, v_new,
                                    interpret=self._interpret())
        state = self._post_append(state)
        B = q.shape[0]

        if self.speculative:
            with annotate(SPAN_RECALL_CORRECTION):
                corr, sim = corrected_heads(cfg, fkv, q, state["qprev"])
            first_step = state["qprev"].astype(jnp.float32)
            is_cold = jnp.all(first_step == 0)       # no prefill qprev -> correct
            corr = corr | is_cold
        else:                                        # ArkVale/InfiniGen: always fresh
            corr = jnp.ones((B, cfg.n_kv_heads), bool)
            sim = jnp.zeros((B, cfg.n_kv_heads), jnp.float32)

        # --- selection (off critical path for FreeKV: overlaps compute) ----
        q_sel = q
        if self.proxy_query and q_proxy is not None:
            q_sel = q_proxy
        with annotate(SPAN_RECALL_SELECT):
            new_idx, sel_info = self._select_indices(state, q_sel, corr)
        n_sel = new_idx.shape[2]
        reused = jnp.zeros((B,), jnp.int32)
        # speculation quality (repro.obs): how much of the new selection the
        # previous step's speculative buffer already holds
        sel_pages = jnp.sum(new_idx >= 0, axis=(1, 2))
        spec_hit = jnp.sum(match_resident(new_idx, state["sel_idx"])[0],
                           axis=(1, 2))

        if self._overlap():
            # --- pipelined (§4): correction top-up on the critical path,
            # staged double-buffer refill off it (core/recall_pipeline) ----
            pr = self.executor.step(self._pool_view(state), new_idx,
                                    state["sel_idx"],
                                    state["sel_k"], state["sel_v"], corr)
            use_k, use_v, use_idx = pr.use_k, pr.use_v, pr.use_idx
            new_k, new_v = pr.staged_k, pr.staged_v
            sync_pages, async_pages = pr.topup_blocks, pr.staged_blocks
            reused = pr.reused_blocks
        else:
            # --- synchronous reference: full blocking recall every step ----
            new_k, new_v = self.executor.recall(self._pool_view(state),
                                                new_idx)
            new_k = new_k.astype(state["sel_k"].dtype)
            new_v = new_v.astype(state["sel_v"].dtype)
            if self.speculative:                     # correction merge (§3.3)
                m = corr[:, :, None, None, None]
                use_k = jnp.where(m, new_k, state["sel_k"])
                use_v = jnp.where(m, new_v, state["sel_v"])
                use_idx = jnp.where(corr[:, :, None], new_idx, state["sel_idx"])
            else:
                use_k, use_v, use_idx = new_k, new_v, new_idx
            sync_pages = jnp.sum(corr, axis=1) * n_sel
            async_pages = jnp.sum(~corr, axis=1) * n_sel

        k_cat, v_cat, pos = _cat_regions(fkv, state, use_k, use_v, use_idx, p)
        with annotate(SPAN_ATTN_COMPUTE):
            o = _attend(cfg, q, k_cat, v_cat, pos, cur_pos, fkv=fkv,
                        use_kernels=self.use_kernels)

        state = dict(state, sel_k=new_k, sel_v=new_v, sel_idx=new_idx,
                     qprev=q.astype(state["qprev"].dtype))
        info = {
            "corrected": corr, "similarity": sim,
            # (kv-head, page) blocks on the critical path (blocking recall)
            "sync_pages": sync_pages,
            # blocks recalled off the critical path (speculative, overlapped)
            "async_pages": async_pages,
            # blocks served from the resident double buffer (no transfer)
            "reused_pages": reused,
            # speculation quality: selected page slots / buffer hits /
            # pages entering the top-k this step
            "sel_pages": sel_pages,
            "spec_hit_pages": spec_hit,
            "churn_pages": sel_pages - spec_hit,
            "granularity": "token" if self.token_wise_recall else "page",
        }
        info.update(sel_info)
        return o, state, info

    # -- subclass hooks ------------------------------------------------
    def _post_append(self, state):
        """Retriever-owned index maintenance after the token append (the
        centroid retriever keeps its two-level index in sync here)."""
        return state

    def _select_indices(self, state, q_sel, corr):
        """Selection hook -> (new_idx (B, kv, n_sel), extra info). ``corr``
        lets subclasses route corrected heads to an exact scan."""
        new_idx, _ = selection.select_pages(
            self.cfg, self.fkv, q_sel, state["summ"], state["length"],
            self._n_sel(state))
        return new_idx, {}

    # -- speculative-decoding rollback (models.serve_step_verify) -------
    def draft_probe(self, state):
        """Per-row rewind probe the verify scan stacks: the post-step lanes
        (beyond ``length`` and the ring, which have their own undo paths)
        needed to restore an arbitrary committed row's state."""
        return (state["qprev"], state["sel_idx"])

    def draft_rewind(self, state, keep_len, probe):
        """Roll a drafted block back to ``keep_len`` committed tokens.

        ``probe`` is this layer's ``draft_probe`` gathered at the last
        committed row. The selection buffers are rebuilt with ONE staged
        recall of that row's ``sel_idx`` — bitwise what the sequential path
        stored, because pool pages are write-once and both the overlap and
        blocking paths store exactly ``recall(pool, sel_idx)`` content
        (core/recall_pipeline: ``staged == fresh`` holds bit-exactly). That
        recall is simultaneously the draft-ahead prefetch: the next drafted
        block's first verify row reuses it as its resident buffer. Stale
        pool/summary pages written by rejected rows stay (never selectable
        before the genuine append rewrites them); the ring is restored
        separately via ``ring_restore``."""
        qprev, sel_idx = probe
        nk, nv = self.executor.recall(self._pool_view(state), sel_idx)
        return dict(state, length=keep_len, qprev=qprev, sel_idx=sel_idx,
                    sel_k=nk.astype(state["sel_k"].dtype),
                    sel_v=nv.astype(state["sel_v"].dtype))


class CentroidRetriever(FreeKVRetriever):
    """Centroid-then-token selection (CTkvr-style two-level index over the
    page summaries, ``core/centroid_index``): per-step selection scans the
    C cluster bounding boxes plus a bounded candidate set instead of every
    page summary — the ~1M-token regime where the exact scan dominates.

    Runs inside the same speculative-recall + correction machinery:
    speculative selection is two-stage (approximate), while **corrected
    heads always re-select with the exact full scan**, so mis-clustered
    heads are corrected rather than lost. With correction on the greedy
    output is bit-identical to ``freekv`` whenever the candidate set covers
    the exact top-k (structural for the non-softmax pooling modes; see
    docs/methods.md for the softmax-pooling caveat)."""

    def __init__(self, cfg, fkv, mesh=None):
        assert not fkv.sharded_retrieval, \
            "method='centroid' composes with tp_serving, not sharded_retrieval"
        super().__init__(cfg, fkv, speculative=True, mesh=mesh)

    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        st = super().init_state(batch, max_len, dtype)
        st.update(centroid_index.init_index(
            st["length"].shape[0], st["pool"].shape[1],
            self.fkv.centroid_count, self.cfg.n_kv_heads, self.cfg.d_head,
            st["summ"].dtype))
        return st

    def prefill(self, state, k, v, q_last):
        st = super().prefill(state, k, v, q_last)
        st.update(centroid_index.build(
            st["summ"], st["length"], self.fkv.centroid_count,
            self.fkv.page_size, st["cent"].dtype))
        return st

    def _post_append(self, state):
        return centroid_index.update_on_append(state, self.fkv)

    def _select_indices(self, state, q_sel, corr):
        # Corrected heads re-select via the exact full scan (its cost is
        # charged to corrected heads only — the jnp path computes full-width
        # with masking per repo convention, counts are the source of truth);
        # uncorrected heads take the two-stage centroid selection.
        exact_idx, _ = selection.select_pages(
            self.cfg, self.fkv, q_sel, state["summ"], state["length"],
            self._n_sel(state))
        cent_idx, cand_idx = centroid_index.centroid_select(
            self.cfg, self.fkv, q_sel, state, self._n_sel(state),
            use_kernels=self.use_kernels)
        new_idx = jnp.where(corr[:, :, None], exact_idx, cent_idx)
        return new_idx, {"cand_pages": jnp.sum(cand_idx >= 0, axis=(1, 2))}


class QuestRetriever(FreeKVRetriever):
    """Quest: no offload, per-q-head (non-group-consistent) selection -> G x
    memory traffic; selection+recall are on the critical path."""

    def __init__(self, cfg, fkv):
        super().__init__(cfg, fkv, speculative=False)
        self.offloaded = False

    def decode(self, state, q, k_new, v_new, q_proxy=None):
        cfg, fkv = self.cfg, self.fkv
        p = fkv.page_size
        B, H, d = q.shape
        kv, G = cfg.n_kv_heads, cfg.group_size
        cur_pos = state["length"]
        state = paging.append_token(state, k_new, v_new)
        n_sel = self._n_sel(state)
        scores = selection.page_scores_minmax(q, state["summ"], _scale(cfg))
        valid = selection.selectable_mask(cfg, fkv, state["summ"].shape[1],
                                          state["length"])
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        _, idx_h = jax.lax.top_k(scores, n_sel)                   # (B,H,n_sel)
        idx_h = idx_h.astype(jnp.int32)
        # per-q-head gather: emulate by gathering per KV *group member* (G x)
        idx_g = idx_h.reshape(B, kv, G, n_sel)
        outs = []
        for g in range(G):
            sk, sv = self._recall(self._pool_view(state), idx_g[:, :, g])
            k_cat, v_cat, pos = _cat_regions(fkv, state, sk.astype(q.dtype),
                                             sv.astype(q.dtype),
                                             idx_g[:, :, g], p)
            qh = q.reshape(B, kv, G, d)[:, :, g].reshape(B, kv, d)
            outs.append(_attend(cfg, qh, k_cat, v_cat, pos, cur_pos))
        o = jnp.stack(outs, axis=2).reshape(B, kv, G, d).reshape(B, H, d)
        state = dict(state, qprev=q.astype(state["qprev"].dtype))
        info = {"corrected": jnp.ones((B, kv), bool),
                "sync_pages": jnp.full((B,), H * n_sel),
                "async_pages": jnp.zeros((B,), jnp.int32),
                "similarity": jnp.zeros((B, kv)), "granularity": "page"}
        return o, state, info


class StreamingRetriever:
    """Sink + sliding window only (StreamingLLM; Razor-like static dropping).
    Also used for ATTN_LOCAL layers (gemma2) with window = cfg.sliding_window."""

    def __init__(self, cfg, fkv, window=None, n_sink=None):
        self.cfg, self.fkv = cfg, fkv
        self.window = window or fkv.n_window
        self.n_sink = fkv.n_sink if n_sink is None else n_sink
        self.offloaded = False

    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        kv, d = cfg.n_kv_heads, cfg.d_head
        n_win = self.window
        return {
            "sink_k": jnp.zeros((batch, self.n_sink, kv, d), dtype),
            "sink_v": jnp.zeros((batch, self.n_sink, kv, d), dtype),
            "win_k": jnp.zeros((batch, n_win, kv, d), dtype),
            "win_v": jnp.zeros((batch, n_win, kv, d), dtype),
            "win_pos": jnp.full((batch, n_win), -1, jnp.int32),
            "length": jnp.zeros((batch,), jnp.int32),
        }

    def prefill(self, state, k, v, q_last):
        B, T = k.shape[:2]
        n_win = state["win_k"].shape[1]
        dt = state["win_k"].dtype
        tail = jnp.arange(max(T - n_win, 0), T)
        slots = tail % n_win
        st = dict(state)
        st["sink_k"] = k[:, : self.n_sink].astype(dt)
        st["sink_v"] = v[:, : self.n_sink].astype(dt)
        st["win_k"] = state["win_k"].at[:, slots].set(k[:, tail].astype(dt))
        st["win_v"] = state["win_v"].at[:, slots].set(v[:, tail].astype(dt))
        st["win_pos"] = state["win_pos"].at[:, slots].set(
            jnp.broadcast_to(tail, (B, tail.shape[0])).astype(jnp.int32))
        st["length"] = jnp.full((B,), T, jnp.int32)
        return st

    def decode(self, state, q, k_new, v_new, q_proxy=None):
        cfg = self.cfg
        B, H, d = q.shape
        kv = cfg.n_kv_heads
        n_win = state["win_k"].shape[1]
        cur_pos = state["length"]
        slot = cur_pos % n_win
        bidx = jnp.arange(B)
        st = dict(state)
        st["win_k"] = state["win_k"].at[bidx, slot].set(k_new.astype(state["win_k"].dtype))
        st["win_v"] = state["win_v"].at[bidx, slot].set(v_new.astype(state["win_v"].dtype))
        st["win_pos"] = state["win_pos"].at[bidx, slot].set(cur_pos)
        st["length"] = cur_pos + 1
        n_sink = st["sink_k"].shape[1]
        ks = st["sink_k"].transpose(0, 2, 1, 3)
        vs = st["sink_v"].transpose(0, 2, 1, 3)
        pos_s = jnp.broadcast_to(jnp.arange(n_sink)[None, None, :], (B, kv, n_sink))
        pos_s = jnp.where(pos_s < st["length"][:, None, None], pos_s, -1)
        kw = st["win_k"].transpose(0, 2, 1, 3)
        vw = st["win_v"].transpose(0, 2, 1, 3)
        pos_w = jnp.broadcast_to(st["win_pos"][:, None, :], (B, kv, n_win))
        pos_w = jnp.where(pos_w >= n_sink, pos_w, -1)
        k_cat = jnp.concatenate([ks, kw], axis=2)
        v_cat = jnp.concatenate([vs, vw], axis=2)
        pos = jnp.concatenate([pos_s, pos_w], axis=2)
        o = _attend(cfg, q, k_cat, v_cat, pos, cur_pos)
        info = {"corrected": jnp.zeros((B, kv), bool),
                "sync_pages": jnp.zeros((B,), jnp.int32),
                "async_pages": jnp.zeros((B,), jnp.int32),
                "similarity": jnp.zeros((B, kv)), "granularity": "page"}
        return o, st, info

    # -- speculative-decoding rollback (models.serve_step_verify) -------
    def draft_probe(self, state):
        """Sink + ring only: nothing beyond length/ring needs restoring."""
        return ()

    def draft_rewind(self, state, keep_len, probe):
        del probe
        return dict(state, length=keep_len)


class FullRetriever:
    """Exact dense KV cache — the accuracy oracle / no-compression baseline."""

    def __init__(self, cfg, fkv):
        self.cfg, self.fkv = cfg, fkv
        self.offloaded = False

    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        cfg = self.cfg
        return {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dtype),
            "length": jnp.zeros((batch,), jnp.int32),
        }

    def prefill(self, state, k, v, q_last):
        B, T = k.shape[:2]
        dt = state["k"].dtype
        return dict(
            state,
            k=jax.lax.dynamic_update_slice(state["k"], k.astype(dt), (0, 0, 0, 0)),
            v=jax.lax.dynamic_update_slice(state["v"], v.astype(dt), (0, 0, 0, 0)),
            length=jnp.full((B,), T, jnp.int32))

    def decode(self, state, q, k_new, v_new, q_proxy=None):
        cfg = self.cfg
        B, H, d = q.shape
        kv = cfg.n_kv_heads
        cur_pos = state["length"]
        bidx = jnp.arange(B)
        st = dict(state)
        st["k"] = state["k"].at[bidx, cur_pos].set(k_new.astype(state["k"].dtype))
        st["v"] = state["v"].at[bidx, cur_pos].set(v_new.astype(state["v"].dtype))
        st["length"] = cur_pos + 1
        L = st["k"].shape[1]
        k_cat = st["k"].transpose(0, 2, 1, 3)
        v_cat = st["v"].transpose(0, 2, 1, 3)
        pos = jnp.broadcast_to(jnp.arange(L)[None, None, :], (B, kv, L))
        pos = jnp.where(pos < st["length"][:, None, None], pos, -1)
        o = _attend(cfg, q, k_cat, v_cat, pos, cur_pos)
        info = {"corrected": jnp.zeros((B, kv), bool),
                "sync_pages": jnp.zeros((B,), jnp.int32),
                "async_pages": jnp.zeros((B,), jnp.int32),
                "similarity": jnp.zeros((B, kv)), "granularity": "page"}
        return o, st, info


class RaaSRetriever:
    """RaaS-like dynamic dropping: pages without recent significant attention
    are evicted permanently (timestamp-based, budget-bounded, no pool)."""

    def __init__(self, cfg, fkv):
        self.cfg, self.fkv = cfg, fkv
        self.offloaded = False

    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        cfg, fkv = self.cfg, self.fkv
        kv, d, p = cfg.n_kv_heads, cfg.d_head, fkv.page_size
        n_keep = max(1, (fkv.budget - fkv.n_sink - fkv.n_window) // p)
        base = StreamingRetriever(cfg, fkv).init_state(batch, max_len, dtype)
        base.update({
            "keep_k": jnp.zeros((batch, kv, n_keep, p, d), dtype),
            "keep_v": jnp.zeros((batch, kv, n_keep, p, d), dtype),
            "keep_idx": jnp.full((batch, kv, n_keep), -1, jnp.int32),
            "last_used": jnp.full((batch, kv, n_keep), -(10 ** 9), jnp.int32),
        })
        return base

    def prefill(self, state, k, v, q_last):
        cfg, fkv = self.cfg, self.fkv
        p = fkv.page_size
        B, T = k.shape[:2]
        st = StreamingRetriever(cfg, fkv).prefill(state, k, v, q_last)
        # seed kept pages with the top pages under the last query (like snapKV)
        n_keep = state["keep_idx"].shape[2]
        n_pages = T // p
        kp = k[:, : n_pages * p].reshape(B, n_pages, p, cfg.n_kv_heads, cfg.d_head)
        summ = jnp.stack([kp.min(2), kp.max(2)], axis=3)
        length = jnp.full((B,), T, jnp.int32)
        scores = selection.page_scores_minmax(q_last, summ, _scale(cfg))
        valid = selection.selectable_mask(cfg, fkv, n_pages, length)
        pooled = selection.group_consistent_scores(cfg, scores, valid,
                                                   fkv.group_pool)
        _, idx = jax.lax.top_k(pooled, n_keep)
        idx = idx.astype(jnp.int32)
        vp = v[:, : n_pages * p].reshape(B, n_pages, p, cfg.n_kv_heads, cfg.d_head)
        pool = paging.nhd_pages_to_hnd(kp, vp)
        kk, vv = recall.recall_pages(pool, idx)
        return dict(st, keep_k=kk.astype(state["keep_k"].dtype),
                    keep_v=vv.astype(state["keep_v"].dtype), keep_idx=idx,
                    last_used=jnp.full_like(state["last_used"], T))

    def decode(self, state, q, k_new, v_new, q_proxy=None):
        cfg, fkv = self.cfg, self.fkv
        p = fkv.page_size
        B, H, d = q.shape
        kv = cfg.n_kv_heads
        cur_pos = state["length"]
        stream = StreamingRetriever(cfg, fkv)
        # attention over sink + window + kept pages
        st = dict(state)
        n_win = st["win_k"].shape[1]
        slot = cur_pos % n_win
        bidx = jnp.arange(B)
        st["win_k"] = st["win_k"].at[bidx, slot].set(k_new.astype(st["win_k"].dtype))
        st["win_v"] = st["win_v"].at[bidx, slot].set(v_new.astype(st["win_v"].dtype))
        st["win_pos"] = st["win_pos"].at[bidx, slot].set(cur_pos)
        st["length"] = cur_pos + 1
        k_cat, v_cat, pos = _cat_regions(
            fkv, {**st}, st["keep_k"], st["keep_v"], st["keep_idx"], p)
        # need attention weights to update timestamps: recompute scores per page
        o = _attend(cfg, q, k_cat, v_cat, pos, cur_pos)
        # page-level attention mass for kept pages (group-mean, like selection)
        n_keep = st["keep_idx"].shape[2]
        G = cfg.group_size
        qg = q.reshape(B, kv, G, d)
        s = jnp.einsum("bkgd,bkld->bkgl", qg, k_cat).astype(jnp.float32) * _scale(cfg)
        s = jnp.where((pos >= 0)[:, :, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        off = k_cat.shape[2] - n_keep * p
        wp = w[..., off:].reshape(B, kv, G, n_keep, p).sum(-1).mean(2)
        significant = wp > (1.0 / jnp.maximum(st["length"], 1))[:, None, None]
        last_used = jnp.where(significant & (st["keep_idx"] >= 0),
                              st["length"][:, None, None], st["last_used"])
        # when a page completes, insert it by evicting the stalest kept page
        page_done = (st["length"] % p) == 0
        page_idx = st["length"] // p - 1
        tok_pos = page_idx[:, None] * p + jnp.arange(p)[None, :]
        tok_slot = tok_pos % n_win
        pk = jnp.take_along_axis(st["win_k"], tok_slot[:, :, None, None], axis=1)
        pv = jnp.take_along_axis(st["win_v"], tok_slot[:, :, None, None], axis=1)
        evict = jnp.argmin(last_used, axis=2)                      # (B,kv)
        kI = jnp.arange(kv)[None, :]
        bI = bidx[:, None]
        sel = page_done[:, None, None, None]
        newp_k = pk.transpose(0, 2, 1, 3)                          # (B,kv,p,d)
        newp_v = pv.transpose(0, 2, 1, 3)
        keep_k = st["keep_k"].at[bI, kI, evict].set(
            jnp.where(sel, newp_k, st["keep_k"][bI, kI, evict]))
        keep_v = st["keep_v"].at[bI, kI, evict].set(
            jnp.where(sel, newp_v, st["keep_v"][bI, kI, evict]))
        keep_idx = st["keep_idx"].at[bI, kI, evict].set(
            jnp.where(page_done[:, None], page_idx[:, None],
                      st["keep_idx"][bI, kI, evict]).astype(jnp.int32))
        last_used = last_used.at[bI, kI, evict].set(
            jnp.where(page_done[:, None], st["length"][:, None],
                      last_used[bI, kI, evict]))
        st.update(keep_k=keep_k, keep_v=keep_v, keep_idx=keep_idx,
                  last_used=last_used)
        info = {"corrected": jnp.zeros((B, kv), bool),
                "sync_pages": jnp.zeros((B,), jnp.int32),
                "async_pages": jnp.zeros((B,), jnp.int32),
                "similarity": jnp.zeros((B, kv)), "granularity": "page"}
        return o, st, info


class ShadowKVRetriever(FreeKVRetriever):
    """ShadowKV-like: rank-r key representation resident on device (keys are
    reconstructed, not transferred); only V pages are recalled from the pool.
    SVD is computed at prefill (the paper notes ShadowKV does not natively
    support long generation; decoded tokens here stay in the window/sink or are
    recalled normally)."""

    def __init__(self, cfg, fkv):
        super().__init__(cfg, fkv, speculative=False)
        self.rank = min(fkv.svd_rank, cfg.d_head)

    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        st = super().init_state(batch, max_len, dtype)
        cfg = self.cfg
        n_pages = st["pool"].shape[1]
        p = self.fkv.page_size
        st["k_u"] = jnp.zeros((batch, cfg.n_kv_heads, n_pages * p, self.rank),
                              dtype)
        st["k_w"] = jnp.zeros((batch, cfg.n_kv_heads, self.rank, cfg.d_head),
                              dtype)
        return st

    def prefill(self, state, k, v, q_last):
        st = super().prefill(state, k, v, q_last)
        B, T, kv, d = k.shape
        kf = k.transpose(0, 2, 1, 3).astype(jnp.float32)           # (B,kv,T,d)
        u, s, vt = jnp.linalg.svd(kf, full_matrices=False)
        r = self.rank
        ur = u[..., :r] * s[..., None, :r]                         # (B,kv,T,r)
        wr = vt[..., :r, :]                                        # (B,kv,r,d)
        k_u = jax.lax.dynamic_update_slice(
            st["k_u"], ur.astype(st["k_u"].dtype), (0, 0, 0, 0))
        return dict(st, k_u=k_u, k_w=wr.astype(st["k_w"].dtype))

    def decode(self, state, q, k_new, v_new, q_proxy=None):
        cfg, fkv = self.cfg, self.fkv
        p = fkv.page_size
        B, H, d = q.shape
        kv = cfg.n_kv_heads
        cur_pos = state["length"]
        state = paging.append_token(state, k_new, v_new)
        n_sel = self._n_sel(state)
        with annotate(SPAN_RECALL_SELECT):
            idx, _ = selection.select_pages(
                cfg, fkv, q, state["summ"], state["length"], n_sel)
        # speculation quality: selection overlap vs the previous resident set
        sel_pages = jnp.sum(idx >= 0, axis=(1, 2))
        spec_hit = jnp.sum(match_resident(idx, state["sel_idx"])[0],
                           axis=(1, 2))
        # keys: reconstruct selected pages from the low-rank factors
        safe = jnp.clip(idx, 0, state["pool"].shape[1] - 1)
        tok = safe[..., None] * p + jnp.arange(p)[None, None, None, :]
        bI = jnp.arange(B)[:, None, None, None]
        kI = jnp.arange(kv)[None, :, None, None]
        u_sel = state["k_u"][bI, kI, tok]                          # (B,kv,n_sel,p,r)
        k_rec = jnp.einsum("bkspr,bkrd->bkspd", u_sel.astype(jnp.float32),
                           state["k_w"].astype(jnp.float32))
        k_rec = jnp.where((idx >= 0)[..., None, None], k_rec, 0).astype(q.dtype)
        # values: genuine recall (V half only — ShadowKV's saving)
        if fkv.recall_overlap and self.mesh is None:
            # executor delta-fetch: V pages already resident in the previous
            # step's buffer are reused bit-exactly; only misses transfer
            pr = self.executor.step_values(self._pool_view(state), idx,
                                           state["sel_idx"], state["sel_v"])
            v_sel = pr.staged_v.astype(q.dtype)
            sync_pages = pr.topup_blocks // 2                       # V-only
            reused = pr.reused_blocks // 2
            state = dict(state, sel_v=pr.staged_v)
        else:
            v_sel = self._recall_values(self._pool_view(state),
                                        idx).astype(q.dtype)
            sync_pages = jnp.sum(idx >= 0, axis=(1, 2)) // 2        # V-only
            reused = jnp.zeros((B,), jnp.int32)
        k_cat, v_cat, pos = _cat_regions(fkv, state, k_rec, v_sel, idx, p)
        o = _attend(cfg, q, k_cat, v_cat, pos, cur_pos)
        state = dict(state, sel_idx=idx, qprev=q.astype(state["qprev"].dtype))
        info = {"corrected": jnp.ones((B, kv), bool),
                "sync_pages": sync_pages,
                "async_pages": jnp.zeros((B,), jnp.int32),
                "reused_pages": reused,
                "sel_pages": sel_pages,
                "spec_hit_pages": spec_hit,
                "churn_pages": sel_pages - spec_hit,
                "similarity": jnp.zeros((B, kv)), "granularity": "page"}
        return o, state, info


METHODS = ("freekv", "arkvale", "infinigen", "quest", "shadowkv", "raas",
           "streaming", "full", "centroid")


def make_retriever(cfg: ArchConfig, fkv: FreeKVConfig, mesh=None):
    from repro.core.sharded_retrieval import (TPGroupShardedRetriever,
                                              tp_serving_active)
    if tp_serving_active(cfg, fkv, mesh):
        # serving TP: the plain (mesh-free) retriever for the local KV-head
        # group runs inside a per-layer shard_map — overlap pipeline, quant
        # pool views and kernels all shard-local (core/sharded_retrieval)
        return TPGroupShardedRetriever(
            cfg, fkv, mesh, lambda c: make_retriever(c, fkv, mesh=None))
    m = fkv.method
    if m == "freekv":
        return FreeKVRetriever(cfg, fkv, speculative=True, mesh=mesh)
    if m == "centroid":
        return CentroidRetriever(cfg, fkv, mesh=mesh)
    if m == "arkvale":
        return FreeKVRetriever(cfg, fkv, speculative=False, mesh=mesh)
    if m == "infinigen":
        return FreeKVRetriever(cfg, fkv, speculative=False, proxy_query=True,
                               token_wise_recall=True, mesh=mesh)
    if m == "quest":
        return QuestRetriever(cfg, fkv)
    if m == "shadowkv":
        return ShadowKVRetriever(cfg, fkv)
    if m == "raas":
        return RaaSRetriever(cfg, fkv)
    if m == "streaming":
        return StreamingRetriever(cfg, fkv, window=fkv.budget - fkv.n_sink)
    if m == "full":
        return FullRetriever(cfg, fkv)
    raise ValueError(f"unknown method {m!r}")
