"""Sharded retrieval: two multi-device execution schemes for the FreeKV
decode step.

1. **KV-head-group tensor parallelism** (``TPGroupShardedRetriever``, used by
   ``ServeEngine(tp>1)``): every retrieval-side state leaf is sharded over
   the GQA KV-head dim on a 1-D ``('model',)`` mesh and the entire per-layer
   retrieval step — append/offload, selection, recall (incl. the overlapped
   double-buffer pipeline and the quantized pool view), correction,
   attention — runs shard-local inside one ``shard_map``. Selection stays
   the exact per-head top-k, the only cross-shard transfer is the tiny
   per-head-group attention-output all-gather, and greedy outputs are
   **bit-identical** to the unsharded path (``tests/test_sharded_serving``).

2. **Page-sharded fused decode step** (``sharded_decode_step``, beyond-paper
   §Perf optimization, ``fkv.sharded_retrieval``): described below —
   approximate shard-local selection + LSE-merged partial attention for
   meshes where the KV-head count cannot absorb the model axis
   (long_500k-style sequence parallelism). The two schemes are mutually
   exclusive per config.

The paper's FreeKV runs selection globally, recalls selected pages to one
device, and appends/offloads pages with batch-indexed scatters. Distributed
over a page-sharded pool, the faithful port pays per-layer collectives for
(a) the cross-shard recall gather (masked psum of selected pages),
(b) the pool append scatter (the partitioner emits pool-block all-reduces for
    batch-fancy-indexed updates), and
(c) replicated budget attention on every model shard.

This module keeps the ENTIRE retrieval pipeline shard-local inside one
shard_map over the 'model' axis:

  * window-ring append is computed redundantly (it is model-replicated state);
  * the completed page is written ONLY by its owning page shard (masked);
  * each shard scores only ITS pages and selects top-(n_sel / n_shards)
    locally (an approximation of global top-k: forced spread across shards);
  * recall is a purely local gather;
  * decode attention runs as partials (num, den, max) over the local pages —
    sink/window attended on shard 0 only — merged with one small LSE combine
    (a psum of (B, H, d) + (B, H) instead of page-sized collectives);
  * speculative reuse + per-KV-head correction semantics are preserved
    shard-locally (stale slices live on their owning shard).

Measured on granite-3-8b x decode_32k (16x16 mesh): collective bytes/step
drop from 20.3 GB -> 0.45 GB per device (§Perf log in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ArchConfig, FreeKVConfig
from repro.core import selection
from repro.models.layers import softcap as _softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV-head-group tensor parallelism (serving TP)
# ---------------------------------------------------------------------------
def tp_group_size(mesh) -> int:
    """Size of the 'model' axis, or 1 when the mesh has none."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return 1
    return mesh.shape["model"]


def tp_serving_active(cfg: ArchConfig, fkv: FreeKVConfig, mesh) -> bool:
    """Should retrieval run as KV-head-group TP on this (cfg, fkv, mesh)?

    Requires the head counts to divide the model axis (every shard owns an
    integral group of KV heads and their G query heads); mutually exclusive
    with the page-sharded fused step."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return False
    mp = tp_group_size(mesh)
    return (fkv.tp_serving and not fkv.sharded_retrieval
            and cfg.n_kv_heads % mp == 0 and cfg.n_heads % mp == 0)


def tp_state_specs(cfg: ArchConfig, mesh, state):
    """PartitionSpec tree for one layer's retrieval state under serving TP —
    the single source of truth is ``sharding/rules.decode_state_spec`` (its
    KV-head branch), so the shard_map in_specs can never diverge from the
    slot pool's storage shardings."""
    from repro.sharding import rules

    def f(path, leaf):
        key = rules._path_str(path)
        if key == "pool_layer":
            return P()
        if key == "pool" and "pool_layer" in state:
            # the layer-stacked host pool rides whole (``core/offload.
            # layer_state``): period axis leading, like a pattern leaf
            key = "pattern/pool"
        return rules.decode_state_spec(cfg, mesh, key, leaf)

    return jax.tree_util.tree_map_with_path(f, state)


class TPGroupShardedRetriever:
    """Wrap any pool-backed retriever in a per-layer shard_map over 'model'.

    ``make_inner`` builds the wrapped retriever for a given ArchConfig; it is
    called twice — once with a *local view* config (head counts divided by
    the model-axis size) whose instance runs inside the shard body, and once
    with the global config for state construction. Because every retrieval
    op is per-KV-head (selection top-k, recall gather, correction masks, the
    overlap executor's resident matching, quant dequant, attention softmax),
    the local instance computes exactly the corresponding slice of the
    global computation: outputs are bit-identical to the unwrapped
    retriever, shard count notwithstanding.

    Cross-shard traffic per decode step: one all-gather of the (B, H, d)
    attention output (forced replicated so the following out-projection runs
    as a full replicated matmul — a partial-contraction psum would break
    bit-identity) plus integer psums of the transfer counters. Host->device
    recall traffic is per-head-group: each shard only ever touches its own
    slice of the (possibly host-resident, possibly quantized) pool.

    Works unchanged inside the host-sync-free decode window
    (``models.model.decode_window``): the per-layer shard_map is pure in
    its sharded state, so the while-loop carry donates/aliases the sharded
    leaves in place, the psum'ed counters land in the window's (k, B) stat
    blocks, and — the backbone (hence logits) being replicated — the fused
    on-device sampler draws identical tokens on every shard.
    """

    def __init__(self, cfg: ArchConfig, fkv: FreeKVConfig, mesh, make_inner):
        mp = tp_group_size(mesh)
        assert cfg.n_kv_heads % mp == 0 and cfg.n_heads % mp == 0, (
            f"{cfg.name}: the model axis ({mp}) must divide both head "
            f"counts ({cfg.n_heads}/{cfg.n_kv_heads}) for KV-head-group TP")
        self.cfg, self.fkv, self.mesh, self.mp = cfg, fkv, mesh, mp
        self.local_cfg = dataclasses.replace(
            cfg, n_heads=cfg.n_heads // mp, n_kv_heads=cfg.n_kv_heads // mp)
        self.inner = make_inner(self.local_cfg)
        self._global = make_inner(cfg)
        self.offloaded = getattr(self._global, "offloaded", False)

    # counters summed over (local) KV heads inside the shard body — psum'ed
    # to their exact global integer values (includes the speculation-quality
    # telemetry so per-step hit/churn counts stay exact under tp>1)
    _COUNTERS = ("sync_pages", "async_pages", "reused_pages", "sel_pages",
                 "spec_hit_pages", "churn_pages", "cand_pages")

    def _hspec(self):
        return P(None, "model", None)          # (B, H|kv, d) head-dim shard

    def init_state(self, batch, max_len, dtype=jnp.bfloat16):
        return self._global.init_state(batch, max_len, dtype)

    def prefill(self, state, k, v, q_last):
        sspec = tp_state_specs(self.cfg, self.mesh, state)
        kv_spec = P(None, None, "model", None)            # (B, T, kv, d)

        def body(st, k_l, v_l, q_l):
            return self.inner.prefill(st, k_l, v_l, q_l)

        return shard_map(
            body, mesh=self.mesh,
            in_specs=(sspec, kv_spec, kv_spec, self._hspec()),
            out_specs=sspec, check_vma=False)(state, k, v, q_last)

    def decode(self, state, q, k_new, v_new, q_proxy=None):
        sspec = tp_state_specs(self.cfg, self.mesh, state)
        hq = self._hspec()
        kn_spec = P(None, "model", None)                   # (B, kv, d)
        # q_proxy=None must stay None for the inner retriever (proxy_query
        # methods fall back to q_sel=q on None); a placeholder array rides
        # the shard_map signature but is never consumed in that case
        has_proxy = q_proxy is not None
        if not has_proxy:
            q_proxy = q

        def body(st, q_l, kn_l, vn_l, qp_l):
            o, st2, info = self.inner.decode(
                st, q_l, kn_l, vn_l, q_proxy=qp_l if has_proxy else None)
            B = q_l.shape[0]
            out_info = {"corrected": info["corrected"],
                        "similarity": info["similarity"]}
            for c in self._COUNTERS:
                val = info.get(c, jnp.zeros((B,), jnp.int32))
                out_info[c] = jax.lax.psum(val, "model")
            return o, st2, out_info

        info_spec = {"corrected": P(None, "model"),
                     "similarity": P(None, "model"),
                     **{c: P(None) for c in self._COUNTERS}}
        o, st2, info = shard_map(
            body, mesh=self.mesh,
            in_specs=(sspec, hq, kn_spec, kn_spec, hq),
            out_specs=(hq, sspec, info_spec),
            check_vma=False)(state, q, k_new, v_new, q_proxy)
        # replicate the per-head-group attention outputs — the ONLY
        # cross-shard tensor transfer of the step. The explicit constraint
        # makes the partitioner all-gather o and run the out-projection as a
        # full replicated matmul; left to itself it may choose a
        # partial-contraction + psum, whose float summation order differs
        # from the single-device program.
        o = jax.lax.with_sharding_constraint(
            o, NamedSharding(self.mesh, P()))
        info["granularity"] = ("token" if getattr(self.inner,
                               "token_wise_recall", False) else "page")
        return o, st2, info

    # -- speculative-decoding rollback (models.serve_step_verify) -------
    # Pure data movement (gathers + elementwise dequant, no float
    # reductions), so it runs OUTSIDE the shard_map on the sharded state:
    # the partitioner keeps the kv-head-aligned gathers shard-local and the
    # restored values are bitwise the unsharded ones.
    def draft_probe(self, state):
        return self._global.draft_probe(state)

    def draft_rewind(self, state, keep_len, probe):
        return self._global.draft_rewind(state, keep_len, probe)


def _partial_attend(cfg, q, k_cat, v_cat, pos, cur_pos):
    """Returns LSE-mergeable partials: num (B,kv,G,d), den (B,kv,G), m."""
    B, H, d = q.shape
    kv = k_cat.shape[1]
    G = H // kv
    qg = q.reshape(B, kv, G, d)
    scale = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / (d ** 0.5)
    s = jnp.einsum("bkgd,bkld->bkgl", qg, k_cat).astype(jnp.float32) * scale
    s = _softcap(s, cfg.attn_logit_softcap)
    ok = (pos >= 0) & (pos <= cur_pos[:, None, None])
    s = jnp.where(ok[:, :, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,kv,G)
    e = jnp.exp(s - m[..., None])
    e = jnp.where(ok[:, :, None, :], e, 0.0)
    num = jnp.einsum("bkgl,bkld->bkgd", e, v_cat.astype(jnp.float32))
    den = jnp.sum(e, axis=-1)
    return num, den, m


def sharded_decode_step(cfg: ArchConfig, fkv: FreeKVConfig, mesh, state, q,
                        k_new, v_new, corr):
    """Shard-local append + select + recall + partial attention + LSE merge.

    Returns (o (B,H,d), updates dict) where updates carries the new pool,
    summ, window buffers and sel_* slices (sel_* sharded over n_sel)."""
    mp = mesh.shape["model"]
    p = fkv.page_size
    Bg, H, d = q.shape
    kv = cfg.n_kv_heads
    n_sel = state["sel_idx"].shape[2]
    assert n_sel % mp == 0, (n_sel, mp)
    k_loc = n_sel // mp
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    import math as _math
    nb = _math.prod(mesh.shape[a] for a in ba) if ba else 1
    b = ba if Bg % max(nb, 1) == 0 else None

    def f(pool, summ, sel_k, sel_v, sel_idx, q, corr, k_new, v_new,
          sink_k, sink_v, win_k, win_v, win_pos, length):
        j = jax.lax.axis_index("model")
        B = pool.shape[0]
        n_loc = pool.shape[1]
        lo = j * n_loc
        n_win = win_k.shape[1]
        dt = win_k.dtype
        bidx = jnp.arange(B)

        # ---- window-ring append (model-replicated; identical on all shards)
        cur_pos = length                                # position of new token
        slot = cur_pos % n_win
        win_k = win_k.at[bidx, slot].set(k_new.astype(dt))
        win_v = win_v.at[bidx, slot].set(v_new.astype(dt))
        win_pos = win_pos.at[bidx, slot].set(cur_pos)
        new_len = cur_pos + 1

        # ---- page offload: only the OWNING shard writes (masked, no comms)
        page_done = (new_len % p) == 0
        page_idx = new_len // p - 1
        tok_pos = page_idx[:, None] * p + jnp.arange(p)[None, :]
        tok_slot = tok_pos % n_win
        pk = jnp.take_along_axis(win_k, tok_slot[:, :, None, None], axis=1)
        pv = jnp.take_along_axis(win_v, tok_slot[:, :, None, None], axis=1)
        hnd = jnp.stack([pk.transpose(0, 2, 1, 3), pv.transpose(0, 2, 1, 3)],
                        axis=2)                         # (B,kv,2,p,d)
        psum_ = jnp.stack([pk.min(axis=1), pk.max(axis=1)], axis=2)  # (B,kv,2,d)
        rel = page_idx - lo
        owned = page_done & (rel >= 0) & (rel < n_loc)
        tgt = jnp.clip(rel, 0, n_loc - 1)
        old_p = pool[bidx, tgt]
        old_s = summ[bidx, tgt]
        selm = owned[:, None, None, None, None]
        pool = pool.at[bidx, tgt].set(
            jnp.where(selm, hnd.astype(pool.dtype), old_p))
        summ = summ.at[bidx, tgt].set(
            jnp.where(selm[..., 0], psum_.astype(summ.dtype), old_s))

        # ---- shard-local selection (global page ids = lo + local index)
        scale = cfg.attn_scale if cfg.attn_scale is not None \
            else 1.0 / (d ** 0.5)
        scores = selection.page_scores_minmax(q, summ, scale)  # (B,H,n_loc)
        pages = lo + jnp.arange(n_loc)
        first = fkv.n_sink // p
        n_done = new_len // p
        last = jnp.maximum(first, (new_len - fkv.n_window) // p)
        valid = (pages[None, :] >= first) & (
            pages[None, :] < jnp.minimum(n_done, last)[:, None])
        pooled = selection.group_consistent_scores(cfg, scores, valid,
                                                   fkv.group_pool)
        kk = min(k_loc, n_loc)
        top_s, top_i = jax.lax.top_k(pooled, kk)
        idx_g = jnp.where(top_s > NEG_INF / 2, top_i + lo, -1).astype(jnp.int32)
        if fkv.sharded_overselect > 1:
            # §Perf opt2 mitigation — global re-rank of the per-shard
            # candidates: all-gather (scores, ids) [tiny: B*kv*kk*8 bytes],
            # keep a local candidate iff its global rank < n_sel_target.
            # Exact global top-k whenever each shard's share of the true
            # top-k is <= kk.
            n_target = (kk * mp) // fkv.sharded_overselect
            all_s = jax.lax.all_gather(top_s, "model")     # (mp,B,kv,kk)
            all_s = all_s.transpose(1, 2, 0, 3).reshape(
                top_s.shape[0], kv, mp * kk)
            # rank = number of strictly-greater scores among all candidates
            rank = jnp.sum(all_s[:, :, None, :] > top_s[..., None], axis=-1)
            survive = (rank < n_target) & (idx_g >= 0)
            idx_g = jnp.where(survive, idx_g, -1)

        # ---- local recall (no collective)
        safe = jnp.clip(idx_g - lo, 0, n_loc - 1)
        bI = bidx[:, None, None]
        kI = jnp.arange(kv)[None, :, None]
        blk = pool[bI, safe, kI]
        blk = jnp.where((idx_g >= 0)[..., None, None, None], blk, 0)
        new_k_pages, new_v_pages = blk[..., 0, :, :], blk[..., 1, :, :]

        # ---- speculative reuse per shard slice
        m = corr[:, :, None, None, None]
        use_k = jnp.where(m, new_k_pages, sel_k.astype(new_k_pages.dtype))
        use_v = jnp.where(m, new_v_pages, sel_v.astype(new_v_pages.dtype))
        use_idx = jnp.where(corr[:, :, None], idx_g, sel_idx)

        # ---- partial attention: local pages (+ sink/window on shard 0)
        wfloor = last * p
        kp = use_k.reshape(B, kv, kk * p, d)
        vp = use_v.reshape(B, kv, kk * p, d)
        pos_p = (use_idx[..., None] * p + jnp.arange(p)[None, None, None])
        pos_p = jnp.where(use_idx[..., None] >= 0, pos_p, -1)
        pos_p = pos_p.reshape(B, kv, kk * p)
        pos_p = jnp.where((pos_p >= fkv.n_sink)
                          & (pos_p < wfloor[:, None, None]), pos_p, -1)
        n_sink = sink_k.shape[1]
        ks = sink_k.transpose(0, 2, 1, 3)
        vs = sink_v.transpose(0, 2, 1, 3)
        pos_s = jnp.broadcast_to(jnp.arange(n_sink)[None, None],
                                 (B, kv, n_sink))
        pos_s = jnp.where((pos_s < new_len[:, None, None]) & (j == 0),
                          pos_s, -1)
        kw = win_k.transpose(0, 2, 1, 3)
        vw = win_v.transpose(0, 2, 1, 3)
        pos_w = jnp.broadcast_to(win_pos[:, None], (B, kv, n_win))
        pos_w = jnp.where((pos_w >= n_sink)
                          & (pos_w >= wfloor[:, None, None]) & (j == 0),
                          pos_w, -1)
        k_cat = jnp.concatenate(
            [ks.astype(kp.dtype), kw.astype(kp.dtype), kp], axis=2)
        v_cat = jnp.concatenate(
            [vs.astype(vp.dtype), vw.astype(vp.dtype), vp], axis=2)
        pos = jnp.concatenate([pos_s, pos_w, pos_p], axis=2).astype(jnp.int32)
        num, den, mx = _partial_attend(cfg, q, k_cat, v_cat, pos, cur_pos)

        # ---- LSE merge across page shards (the only collective)
        mg = jax.lax.pmax(mx, "model")
        w = jnp.exp(mx - mg)
        num = jax.lax.psum(num * w[..., None], "model")
        den = jax.lax.psum(den * w, "model")
        o = (num / jnp.maximum(den, 1e-30)[..., None]).reshape(B, H, d)
        return (o.astype(q.dtype), pool, summ, win_k, win_v, win_pos,
                new_k_pages, new_v_pages, idx_g)

    pool_spec = P(b, "model", None, None, None, None)
    summ_spec = P(b, "model", None, None, None)
    sel_spec = P(b, None, "model", None, None)
    idx_spec = P(b, None, "model")
    rep2 = P(b, None)
    rep3 = P(b, None, None)
    rep4 = P(b, None, None, None)
    out = shard_map(
        f, mesh=mesh,
        in_specs=(pool_spec, summ_spec, sel_spec, sel_spec, idx_spec,
                  rep3, rep2, rep3, rep3, rep4, rep4, rep4, rep4, rep2, P(b)),
        out_specs=(rep3, pool_spec, summ_spec, rep4, rep4, rep2,
                   sel_spec, sel_spec, idx_spec),
        check_vma=False,
    )(state["pool"], state["summ"], state["sel_k"], state["sel_v"],
      state["sel_idx"], q, corr, k_new, v_new, state["sink_k"],
      state["sink_v"], state["win_k"], state["win_v"], state["win_pos"],
      state["length"])
    o, pool, summ, win_k, win_v, win_pos, sel_k, sel_v, sel_idx = out
    updates = dict(pool=pool, summ=summ, win_k=win_k, win_v=win_v,
                   win_pos=win_pos, length=state["length"] + 1)
    return o, updates, sel_k, sel_v, sel_idx
