"""Serving engine: batched prefill + decode with any retrieval method.

Decode dispatch is host-sync-free by default: sampling is fused into the
jitted step (on-device, per-slot PRNG key streams), the decode state and
loop carry are DONATED (the paged KV slot pool updates in place — no
per-step copy), and up to ``FreeKVConfig.sync_interval`` fused steps run
per host round trip (``models.model.decode_window``). Greedy outputs are
bit-identical to the synchronous per-step reference
(``fkv.sample_on_device=False``). See docs/serving.md.

Two schedulers share the jitted model entry points:

* ``scheduler="continuous"`` (default) — the ``serving.scheduler`` /
  ``serving.kv_slots`` subsystem: a fixed pool of physical batch slots, slot
  refill at every step boundary, and an optional radix-trie prefix cache
  (``prefix_cache_tokens > 0``) that skips the transformer forward for a
  previously prefilled shared prompt prefix via ``model.prefill_extend``.
* ``scheduler="static"`` — the original chunked lockstep path, kept as a
  fallback and as the baseline for ``benchmarks/serving_throughput.py``.

Recall transfers ride the overlapped double-buffered pipeline
(``core/recall_pipeline``, on by default via ``FreeKVConfig.recall_overlap``):
each slot carries a staged speculative buffer across continuous-batching
steps, only correction top-ups block the decode step, and the engine-owned
``RecallFlightTracker`` accounts hidden vs exposed transfer per slot —
including buffers abandoned in flight at slot turnover. See
``EngineMetrics.summary()["recall_overlap"]`` and ``docs/architecture.md``.

Prompt lengths can be bucketed (``prefill_bucket``) to bound the number of
compiled prefill shapes under heterogeneous traffic: cold prompts are
left-padded to the bucket (pads become attended context, exactly as the
chunked path treats ragged batches) and the *padded* token sequence keys the
prefix cache — two identically padded prompts dedupe exactly. The default
``prefill_bucket=1`` pads nothing (outputs are unchanged from the chunked
path for equal-length traffic) at the cost of one compile per distinct prompt
length. Cache hits shrink the reused span so the suffix is an exact bucket
multiple — the extension path never pads — but note each distinct
(prefix_len, suffix_len) pair is its own compiled shape.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, FreeKVConfig
from repro.core.recall_pipeline import RecallFlightTracker
from repro.models.model import (DECODE_STAT_KEYS, decode_window,
                                decode_window_spec, prefill, prefill_extend,
                                serve_step, supports_kv_extend,
                                supports_spec_decode)
from repro.obs import Observability
from repro.serving.kv_slots import SlotPool
from repro.serving.metrics import EngineMetrics, RequestMetrics
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.sampling import (SamplerConfig, sample, sample_step,
                                    step_keys)
from repro.serving.scheduler import ContinuousScheduler, _request_stats


@dataclass
class Request:
    uid: int
    tokens: np.ndarray                 # prompt (T,)
    max_new_tokens: int = 32
    frontend: Optional[np.ndarray] = None
    eos_token: Optional[int] = None
    # scheduling priority (higher = more urgent). Admission stays FIFO, but
    # with ``fkv.preempt`` a queued request whose priority STRICTLY exceeds
    # the lowest-priority running request's swaps that victim's paged KV out
    # to host and takes its slot; the victim resumes bit-identically later.
    priority: int = 0
    # per-request SLO tags (ms); None falls back to the engine-level default.
    # Tagged completions feed EngineMetrics.summary()["slo"] (attainment +
    # goodput); tags never influence scheduling decisions.
    slo_ttft_ms: Optional[float] = None
    slo_itl_ms: Optional[float] = None
    # optional reference stream for the speculative drafter (prompt-lookup
    # style: a retrieved document, an earlier draft of the answer, ...).
    # Its bigrams overlay the prompt-seeded table at admission. Hints steer
    # ONLY the proposer — verification guarantees outputs are bit-identical
    # with any hint, a wrong hint just lowers the accept rate.
    draft_hint: Optional[np.ndarray] = None


@dataclass
class Completion:
    uid: int
    tokens: List[int]
    prefill_s: float
    decode_s: float
    steps: int
    stats: dict
    metrics: Optional[RequestMetrics] = None


class PrefillJob:
    """Incremental chunked prefill of one admitted request.

    The prompt is consumed in chunks: the opening chunk runs the ordinary
    prefill forward (capturing its post-RoPE K/V), every later chunk runs
    ``model.prefill_extend`` over the K/V accumulated so far — exactly the
    prefix-cache extension math, so each chunk's attention equals the same
    span of a whole-shot prefill bit-for-bit. Intermediate chunks skip the
    paged-state rebuild (``build_state=False``: their states would be
    discarded at the next chunk); only the FINAL chunk builds the decode
    state, from the full concatenated K/V — the identical construction the
    whole-shot path uses — so the state spliced into the slot pool and the
    first-token logits are bit-identical to un-chunked prefill.

    A prefix-cache hit seeds the accumulated K/V with the cached span
    (shrunk so the remaining suffix is an exact bucket multiple, as in
    ``prefill_one``); on completion the full prompt's K/V is inserted back
    into the trie. The scheduler owns the pacing: it calls ``advance`` with
    its per-window token budget, interleaving chunks with decode windows so
    co-batched decoders stall at most one chunk's compute.

    Note each distinct (prefix_len, suffix_len) pair is its own compiled
    extension shape — steady chunk budgets keep the shape set small.
    """

    def __init__(self, engine: "ServeEngine", req: Request):
        self.engine, self.req = engine, req
        padded = engine._pad_prompt(np.asarray(req.tokens, np.int32))
        assert len(padded) + req.max_new_tokens <= engine.max_len, (
            f"request {req.uid}: padded prompt {len(padded)} + "
            f"{req.max_new_tokens} new tokens exceeds max_len {engine.max_len}")
        self.seq = tuple(int(t) for t in padded)
        self.pos = 0                    # prompt tokens prefilled so far
        self.hit = 0                    # of which served by the prefix cache
        self.chunks = 0
        self._flat: Optional[List[np.ndarray]] = None  # accumulated K/V
        self.result = None  # (logits (1,V), B=1 state, hit, padded) when done
        cache = engine.prefix_cache
        if cache is not None:
            matched, payload = cache.match(self.seq)
            b = engine.prefill_bucket
            suffix = max(b, -(-(len(self.seq) - matched) // b) * b)
            tp = len(self.seq) - suffix
            if tp >= max(b, engine.fkv.page_size):   # at least one page reused
                self._flat = [np.asarray(a[:tp]) for a in payload]
                self.pos = self.hit = tp

    @property
    def remaining(self) -> int:
        return len(self.seq) - self.pos

    @property
    def done(self) -> bool:
        return self.result is not None

    def advance(self, budget: int) -> int:
        """Run ONE chunk of at most ``budget`` prompt tokens; returns the
        tokens consumed. The final chunk sets ``result`` to the same tuple
        ``prefill_one`` returns."""
        assert not self.done and budget > 0
        eng = self.engine
        n = min(int(budget), self.remaining)
        last = n == self.remaining
        if self.pos == 0:
            chunk = np.asarray(self.seq[:n], np.int32)
            fn = eng._prefill_kv if last else eng._prefill_kv_nostate
            logits, state, kv = fn(
                eng.params, {"tokens": jnp.asarray(chunk[None])})
            self._flat = eng._kv_tree_to_flat(kv)
        else:
            ptree = eng._flat_to_prefix_tree(self._flat)
            suf = np.asarray(self.seq[self.pos: self.pos + n], np.int32)
            fn = eng._extend if last else eng._extend_nostate
            logits, state, suf_kv = fn(eng.params,
                                       {"tokens": jnp.asarray(suf[None])},
                                       ptree)
            self._flat = [np.concatenate([p, s], axis=0) for p, s in
                          zip(self._flat, eng._kv_tree_to_flat(suf_kv))]
        self.pos += n
        self.chunks += 1
        if last:
            if eng.prefix_cache is not None:
                eng.prefix_cache.insert(self.seq, self._flat)
            self._flat = None
            self.result = (logits, eng._attach_draft_tab(
                state, self.seq, getattr(self.req, "draft_hint", None)),
                self.hit, len(self.seq))
        return n


class ServeEngine:
    def __init__(self, cfg: ArchConfig, fkv: FreeKVConfig, params,
                 max_len: int, batch_size: int,
                 sampler: SamplerConfig = SamplerConfig(),
                 state_dtype=jnp.float32, mesh=None,
                 scheduler: str = "continuous",
                 prefill_bucket: int = 1,
                 prefix_cache_tokens: int = 0,
                 pad_token: int = 0,
                 tp: int = 1,
                 obs: Optional[Observability] = None,
                 slo_ttft_ms: Optional[float] = None,
                 slo_itl_ms: Optional[float] = None):
        assert scheduler in ("continuous", "static"), scheduler
        if tp > 1:
            # tensor-parallel serving: KV-head-group sharding over a 1-D
            # ('model',) mesh. Every retrieval-side state leaf (pool + quant
            # scales, summaries, rings, selection buffers) is sharded per
            # KV-head group and the per-layer retrieval step runs inside a
            # shard_map; backbone compute stays replicated, so greedy
            # outputs are bit-identical to tp=1 (docs/serving.md).
            assert mesh is None, "pass either mesh= or tp=, not both"
            assert not fkv.sharded_retrieval, \
                "tp serving and the page-sharded fused step are exclusive"
            assert cfg.n_kv_heads % tp == 0 and cfg.n_heads % tp == 0, (
                f"{cfg.name}: tp={tp} must divide both n_heads="
                f"{cfg.n_heads} and n_kv_heads={cfg.n_kv_heads}")
            from repro.launch.mesh import make_tp_mesh
            mesh = make_tp_mesh(tp)
            fkv = dataclasses.replace(fkv, tp_serving=True)
        # speculative decoding (models.serve_step_spec) rides the continuous
        # scheduler's host-sync-free window; configs it cannot serve exactly
        # (static scheduler, synchronous sampling, non-attention stacks, the
        # page-sharded fused step) silently fall back to draft_len=0 — the
        # fallback is exact by construction, it just commits 1 token/step.
        if fkv.draft_len > 0 and not (
                scheduler == "continuous" and fkv.sample_on_device
                and supports_spec_decode(cfg, fkv)):
            fkv = dataclasses.replace(fkv, draft_len=0)
        self.spec_decode = fkv.draft_len > 0
        self.draft_len = fkv.draft_len
        self.tp = tp
        self.mesh = mesh
        self.cfg, self.fkv, self.params = cfg, fkv, params
        self.max_len, self.batch_size = max_len, batch_size
        self.sampler = sampler
        self.state_dtype = state_dtype
        self.scheduler = scheduler
        self.prefill_bucket = max(1, prefill_bucket)
        self.pad_token = pad_token
        self._prefill = jax.jit(
            lambda p, b: prefill(cfg, fkv, p, b, max_len=max_len,
                                 state_dtype=state_dtype, mesh=mesh))
        self._prefill_kv = jax.jit(
            lambda p, b: prefill(cfg, fkv, p, b, max_len=max_len,
                                 state_dtype=state_dtype, mesh=mesh,
                                 return_kv=True))
        # chunked-prefill opening chunk (more chunks follow): capture the
        # chunk's K/V but skip the paged-state build it would discard
        self._prefill_kv_nostate = jax.jit(
            lambda p, b: prefill(cfg, fkv, p, b, max_len=max_len,
                                 state_dtype=state_dtype, mesh=mesh,
                                 return_kv=True, build_state=False))
        self._extend = jax.jit(
            lambda p, b, pkv: prefill_extend(cfg, fkv, p, b, pkv,
                                             max_len=max_len,
                                             state_dtype=state_dtype,
                                             mesh=mesh))
        # chunked-prefill intermediate chunks: same extension math but no
        # paged-state rebuild (the state would be discarded at the next chunk)
        self._extend_nostate = jax.jit(
            lambda p, b, pkv: prefill_extend(cfg, fkv, p, b, pkv,
                                             max_len=max_len,
                                             state_dtype=state_dtype,
                                             mesh=mesh, build_state=False))
        # the decode state (arg 1) is DONATED: XLA updates the paged KV slot
        # pool, host pool, quant scales, rings and selection buffers in
        # place instead of copying the whole pytree every step. Callers
        # (schedulers) reassign their state reference from the output and
        # never read the consumed one.
        self._step = jax.jit(
            lambda p, s, t: serve_step(cfg, fkv, p, s, t, mesh=mesh,
                                       collect_stats=True),
            donate_argnums=(1,))
        # host-sync-free decode: up to sync_interval fused (step + on-device
        # sample) iterations per dispatch, state AND loop carry donated —
        # zero host round trips and zero state copies inside the window.
        self.sync_interval = max(1, fkv.sync_interval)
        self.sample_on_device = bool(fkv.sample_on_device)
        # speculative mode swaps in the drafted-window variant: same carry,
        # same donation, (k, 1 + draft_len, B) token/valid/stat blocks.
        _win = decode_window_spec if self.spec_decode else decode_window
        self._window = jax.jit(
            lambda p, s, lp: _win(cfg, fkv, p, s, lp,
                                  sampler=sampler,
                                  k_max=self.sync_interval,
                                  mesh=mesh),
            donate_argnums=(1, 2))
        self._can_extend = supports_kv_extend(cfg)
        self.prefix_cache = (RadixPrefixCache(prefix_cache_tokens)
                             if prefix_cache_tokens > 0 and self._can_extend
                             else None)
        self._pool: Optional[SlotPool] = None
        self.last_metrics: Optional[EngineMetrics] = None
        # observability plane (repro.obs): per-step latency/speculation
        # histograms + Perfetto trace spans, recorded by the scheduler at
        # sync boundaries only. Default off — the registry-backed counters
        # in EngineMetrics always run; this gates the extra distributions.
        self.obs = obs if obs is not None else Observability.off()
        # engine-level SLO defaults (ms): requests without their own tags
        # inherit these; None leaves the request untagged (see
        # EngineMetrics.slo_check / summary()["slo"]).
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_itl_ms = slo_itl_ms
        # per-slot in-flight staged recall accounting (core/recall_pipeline);
        # the continuous scheduler feeds it each step and invalidates on
        # slot turnover. Reset per generate() run. Under TP it is fed global
        # (psum'ed) counts and carries the per-shard view.
        self.recall_tracker = RecallFlightTracker(shards=self.tp)

    # ------------------------------------------------------------------
    # scheduler backend protocol
    # ------------------------------------------------------------------
    @property
    def page_block_bytes(self) -> int:
        """Bytes of one (kv-head, page) K+V block — the recall transfer unit.
        Under the quantized host tier this is the *packed* unit (int payload
        + fp32 scales); identical to the dense block when kv_quant='none'."""
        from repro.quant import page_block_bytes
        itemsize = jnp.dtype(self.state_dtype).itemsize
        return page_block_bytes(self.fkv, self.cfg.d_head, itemsize)

    def _apply_quant_metrics(self, em: EngineMetrics):
        """Fill the kv_quant section: dense-equivalent block bytes plus the
        slot pool's physical vs dense host-tier footprint."""
        from repro.quant import page_block_bytes_dense, pool_bytes_detail
        itemsize = jnp.dtype(self.state_dtype).itemsize
        em.kv_quant = self.fkv.kv_quant
        em.dense_block_bytes = page_block_bytes_dense(
            self.fkv, self.cfg.d_head, itemsize)
        em.dequant_elems_per_block = 2 * self.fkv.page_size * self.cfg.d_head
        if self._pool is not None:
            detail = pool_bytes_detail(self._pool.state, self.cfg.d_head,
                                       dense_itemsize=itemsize)
            em.pool_bytes_physical = float(detail["physical"])
            em.pool_bytes_dense = float(detail["dense"])

    @property
    def prefill_chunk_tokens(self) -> int:
        """Per-window chunked-prefill token budget; 0 = whole-shot prefill
        at admission. Forced to 0 for stacks the extension path cannot serve
        (recurrent mixers, encoder-decoder, frontends) — the scheduler then
        keeps the inline whole-shot behavior for every request."""
        return self.fkv.prefill_chunk_tokens if self._can_extend else 0

    @property
    def preempt(self) -> bool:
        """Whether the scheduler may swap lower-priority running requests
        out to host to admit strictly higher-priority queued ones."""
        return self.fkv.preempt

    def start_prefill_job(self, req: Request) -> PrefillJob:
        """Open an incremental prefill for ``req`` (chunked-prefill path)."""
        return PrefillJob(self, req)

    def make_slot_pool(self, num_slots: int) -> SlotPool:
        return SlotPool(self.cfg, self.fkv, num_slots, self.max_len,
                        self.state_dtype,
                        mesh=self.mesh if self.tp > 1 else None)

    def step(self, state, tokens):
        # tokens stay device-resident across decode steps; only a cold
        # (host/numpy) vector is ever uploaded
        if not isinstance(tokens, jax.Array):
            tokens = jnp.asarray(tokens)
        return self._step(self.params, state, tokens)

    def decode_window(self, state, loop):
        """Dispatch up to ``sync_interval`` fused decode steps without any
        host synchronization; ``state`` and ``loop`` are donated."""
        if self.mesh is not None:
            # freshly uploaded lanes land single-device; replicate them over
            # the TP mesh once so donation aliases them thereafter
            from repro.sharding.rules import replicated_put
            loop = replicated_put(self.mesh, loop)
        return self._window(self.params, state, loop)

    def sample(self, logits, key):
        return sample(logits, self.sampler, key)

    def sample_lanes(self, logits, keys, counts):
        """Per-slot sampling on the per-request key streams — the same
        sampler the fused device step runs, executed outside it (the
        synchronous reference path and prefill first tokens)."""
        return sample_step(logits, self.sampler, step_keys(keys, counts))

    def sample_slot(self, logits, req_key, count: int):
        """Sample one request's token ``count`` from B=1 logits."""
        keys = jnp.asarray(req_key)[None]
        return self.sample_lanes(logits, keys,
                                 jnp.full((1,), count, jnp.int32))

    def _attach_draft_tab(self, state, seq, hint=None):
        """Seed the B=1 state's bigram drafter table from the (padded)
        prompt before it is spliced into a slot. Host-side and cheap — one
        (1, vocab) scatter per admission; the in-jit drafter then folds the
        generated stream in as tokens commit. ``hint`` (a request's
        ``draft_hint``) overlays its bigrams on top of the prompt's."""
        if not self.spec_decode or state is None:
            return state
        from repro.core import drafter
        tab = drafter.seed_from_prompt(self.cfg.vocab_size,
                                       np.asarray(seq, np.int64))
        if hint is not None and len(hint) >= 2:
            h = drafter.seed_from_prompt(self.cfg.vocab_size,
                                         np.asarray(hint, np.int64))
            tab = np.where(h >= 0, h, tab)
        state = dict(state)
        state["draft_tab"] = jnp.asarray(tab)
        return state

    def _pad_prompt(self, tokens: np.ndarray) -> np.ndarray:
        b = self.prefill_bucket
        padded_len = max(b, -(-len(tokens) // b) * b)
        out = np.full((padded_len,), self.pad_token, np.int32)
        out[padded_len - len(tokens):] = tokens
        return out

    def prefill_one(self, req: Request):
        """Prefill one request (B=1), via the prefix cache when possible.

        Returns (last-token logits (1, V), B=1 decode state,
        prefix_hit_tokens, padded_prompt_tokens)."""
        padded = self._pad_prompt(np.asarray(req.tokens, np.int32))
        assert len(padded) + req.max_new_tokens <= self.max_len, (
            f"request {req.uid}: padded prompt {len(padded)} + "
            f"{req.max_new_tokens} new tokens exceeds max_len {self.max_len}")
        seq = tuple(int(t) for t in padded)
        b = self.prefill_bucket
        if self.prefix_cache is not None:
            matched, payload = self.prefix_cache.match(seq)
            # shrink the reused span so the suffix is an exact bucket multiple
            suffix = max(b, -(-(len(seq) - matched) // b) * b)
            tp = len(seq) - suffix
            if tp >= max(b, self.fkv.page_size):   # at least one page reused
                prefix_flat = [a[:tp] for a in payload]
                ptree = self._flat_to_prefix_tree(prefix_flat)
                suf = jnp.asarray(np.asarray(seq[tp:], np.int32)[None])
                logits, state, suf_kv = self._extend(
                    self.params, {"tokens": suf}, ptree)
                full = [np.concatenate([p, s], axis=0) for p, s in
                        zip(prefix_flat, self._kv_tree_to_flat(suf_kv))]
                self.prefix_cache.insert(seq, full)
                return logits, self._attach_draft_tab(
                    state, seq, getattr(req, "draft_hint", None)), tp, \
                    len(seq)

        batch = {"tokens": jnp.asarray(padded[None])}
        if self.cfg.frontend is not None:
            fe = (req.frontend if req.frontend is not None
                  else np.zeros((self.cfg.n_frontend_tokens, self.cfg.d_model),
                                np.float32))
            batch["frontend"] = jnp.asarray(fe[None])
        if self.prefix_cache is not None:
            logits, state, kv = self._prefill_kv(self.params, batch)
            self.prefix_cache.insert(seq, self._kv_tree_to_flat(kv))
        else:
            logits, state = self._prefill(self.params, batch)
        return logits, self._attach_draft_tab(
            state, seq, getattr(req, "draft_hint", None)), 0, len(seq)

    # -- prefix-cache payload <-> model pytree conversions --------------
    # Flat payload layout: [k, v] per layer, prelude first, then pattern
    # positions period-major; every array (T, n_kv, d_head) with token axis 0
    # (the axis the radix trie slices).
    def _kv_tree_to_flat(self, kvtree) -> List[np.ndarray]:
        flat: List[np.ndarray] = []
        for kvp in kvtree["prelude"]:
            flat += [np.asarray(kvp[0][0]), np.asarray(kvp[1][0])]
        for k, v in kvtree["pattern"]:
            k, v = np.asarray(k), np.asarray(v)     # (n_periods, 1, T, kv, d)
            for j in range(k.shape[0]):
                flat += [k[j, 0], v[j, 0]]
        return flat

    def _flat_to_prefix_tree(self, flat: List[np.ndarray]):
        cfg = self.cfg
        i = 0
        pre = []
        for _ in cfg.prelude:
            pre.append((jnp.asarray(flat[i][None]),
                        jnp.asarray(flat[i + 1][None])))
            i += 2
        pat = []
        for _ in cfg.pattern:
            ks = np.stack(flat[i: i + 2 * cfg.n_periods: 2])
            vs = np.stack(flat[i + 1: i + 2 * cfg.n_periods: 2])
            i += 2 * cfg.n_periods
            pat.append((jnp.asarray(ks[:, None]), jnp.asarray(vs[:, None])))
        return {"prelude": tuple(pre), "pattern": tuple(pat)}

    # ------------------------------------------------------------------
    # generation entry point
    # ------------------------------------------------------------------
    def generate(self, requests: List[Request], seed: int = 0) -> List[Completion]:
        if self.scheduler == "continuous":
            return self._generate_continuous(requests, seed)
        t0 = time.perf_counter()
        out: List[Completion] = []
        for i in range(0, len(requests), self.batch_size):
            out.extend(self._generate_batch(requests[i: i + self.batch_size],
                                            seed + i))
        em = EngineMetrics(num_slots=self.batch_size, scheduler="static",
                           tp=self.tp, sample_on_device=False)
        # the static path never host-places its pool (SlotPool does that
        # for the continuous scheduler), so its transfers are simulated
        em.transfer_is_dma = False
        em.page_block_bytes = self.page_block_bytes
        self._apply_quant_metrics(em)
        em.wall_s = time.perf_counter() - t0
        em.requests = [RequestMetrics(uid=c.uid, prompt_tokens=len(r.tokens),
                                      max_new_tokens=r.max_new_tokens,
                                      new_tokens=len(c.tokens),
                                      prefill_s=c.prefill_s,
                                      decode_s=c.decode_s, finish_t=em.wall_s)
                       for r, c in zip(requests, out)]
        for rm in em.requests:
            em.record_request(rm)
        self.last_metrics = em
        return out

    def _generate_continuous(self, requests, seed, service=None):
        assert self.scheduler == "continuous", \
            "live serving needs scheduler='continuous'"
        if self._pool is None:
            self._pool = self.make_slot_pool(self.batch_size)
        else:
            self._pool.reset_all()
        self.recall_tracker = RecallFlightTracker(shards=self.tp)
        sched = ContinuousScheduler(self, self._pool)
        tracked, em = sched.run(requests, seed, service=service)
        from repro.core.offload import pool_on_host
        em.transfer_is_dma = pool_on_host(self._pool.state)
        self._apply_quant_metrics(em)
        if self.prefix_cache is not None:
            em.prefix_cache = self.prefix_cache.stats()
        self.last_metrics = em
        return [Completion(uid=tr.req.uid, tokens=tr.tokens,
                           prefill_s=tr.prefill_s, decode_s=tr.decode_s,
                           steps=max(len(tr.tokens) - 1, 0),
                           stats=_request_stats(tr.agg), metrics=tr.metrics)
                for tr in tracked]

    def serve_service(self, service, seed: int = 0) -> List[Completion]:
        """Live-serving entry point: drive the continuous scheduler off a
        ``serving/frontend.EngineService`` inbox (dynamic admission,
        streaming per-token events, client-disconnect cancellation) until
        the service closes and drains. Blocking — the front-end runs it on
        a dedicated worker thread. Returns all completions (including
        cancelled requests' partial records) in admission order."""
        return self._generate_continuous([], seed, service=service)

    # -- static chunked fallback ---------------------------------------
    def _generate_batch(self, reqs: List[Request], seed: int) -> List[Completion]:
        cfg = self.cfg
        B = len(reqs)
        T = max(len(r.tokens) for r in reqs)
        toks = np.zeros((B, T), np.int32)
        for i, r in enumerate(reqs):            # left-pad to align last token
            toks[i, T - len(r.tokens):] = r.tokens
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.frontend is not None:
            fe = np.stack([
                r.frontend if r.frontend is not None
                else np.zeros((cfg.n_frontend_tokens, cfg.d_model), np.float32)
                for r in reqs])
            batch["frontend"] = jnp.asarray(fe)

        t0 = time.perf_counter()
        logits, state = self._prefill(self.params, batch)
        logits.block_until_ready()
        prefill_s = time.perf_counter() - t0

        key = jax.random.PRNGKey(seed)
        max_new = max(r.max_new_tokens for r in reqs)
        gen = [[] for _ in reqs]
        # per-request stats: finished rows are masked out of the aggregation
        # (they still ride the lockstep batch — that cost is what the
        # continuous scheduler removes — but they no longer pollute stats)
        aggs = [{k: 0.0 for k in DECODE_STAT_KEYS} for _ in reqs]
        decode_ss = [0.0 for _ in reqs]
        cur = sample(logits, self.sampler, key)
        done = [r.max_new_tokens <= 0 for r in reqs]
        for step in range(max_new):
            for i, r in enumerate(reqs):
                if done[i]:
                    continue
                tok = int(cur[i])
                gen[i].append(tok)
                if len(gen[i]) >= r.max_new_tokens or \
                        (r.eos_token is not None and tok == r.eos_token):
                    done[i] = True
            if all(done):
                break                # no row needs another step: stop
            ts = time.perf_counter()
            logits, state, stats = self._step(self.params, state, cur[:, None])
            stats_np = {k: np.asarray(v) for k, v in stats.items()
                        if k in aggs[0]}
            dt = time.perf_counter() - ts
            for i in range(B):
                # row i needs this step iff it still appends a token next
                # iteration; a finished row's decode cost and retrieval
                # traffic are excluded from its completion record
                if not done[i]:
                    decode_ss[i] += dt
                    for k in aggs[i]:
                        aggs[i][k] += float(stats_np[k][i])
            key = jax.random.fold_in(key, step)
            cur = sample(logits, self.sampler, key)
        jax.block_until_ready(logits)

        return [Completion(uid=r.uid, tokens=gen[i], prefill_s=prefill_s,
                           decode_s=decode_ss[i],
                           steps=max(len(gen[i]) - 1, 0),
                           stats=_request_stats(aggs[i]))
                for i, r in enumerate(reqs)]
