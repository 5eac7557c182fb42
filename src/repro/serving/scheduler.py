"""Continuous-batching scheduler: admission queue + per-slot request lifecycle.

Requests move QUEUED -> PREFILL -> DECODE -> DONE. Slots are refilled at every
host boundary, so a short request's completion immediately frees capacity for
the next queued request instead of idling until the longest co-scheduled
request drains (the static chunked engine's behavior). Finished slots stop
contributing tokens or statistics the moment they drain.

Decode dispatch is HOST-SYNC-FREE (``fkv.sample_on_device``, the default):
the scheduler ships a device-resident loop carry — current tokens, per-slot
PRNG key streams, generated counts, limits, eos ids, finished mask — into
``backend.decode_window``, which runs up to ``fkv.sync_interval`` fused
(decode + on-device sample) steps with the decode state *donated* (updated
in place, never copied) and zero host round trips. The device loop exits
early when every lane finishes or, when admissions are queued, at the first
slot turnover. At each sync the host pulls the (k, B) token / valid / stat
blocks once, appends tokens, detokenizes, frees + refills slots, and only
re-uploads the tiny per-slot lanes that changed. Between syncs nothing
crosses the host boundary (``EngineMetrics.summary()["dispatch"]``).

``fkv.sample_on_device = False`` keeps the synchronous reference path: one
host synchronization per decode step (sampled on the same per-request key
streams, so outputs are identical — and greedy is bit-identical across both
paths and every ``sync_interval``).

CHUNKED PREFILL (``backend.prefill_chunk_tokens > 0``): admission no longer
runs the whole prompt's prefill inline. The request takes a slot and opens a
``backend.start_prefill_job`` state machine; each scheduler round spends at
most ``prefill_chunk_tokens`` prompt tokens across the open jobs (oldest
first) before dispatching the next decode window, so co-batched decoders
stall for at most ~one chunk's compute instead of the whole prefill. The
final chunk builds the decode state from the full accumulated K/V — the
prefix-cache extension math — so outputs are bit-identical to whole-shot.

PREEMPTION (``backend.preempt``): admission stays FIFO, but when the pool is
full and a queued request's priority STRICTLY exceeds the lowest-priority
running (decode-state) request's, that victim's entire slot state — paged
pool at its packed quantized width, scales, rings, selection buffers — is
swapped to host (``SlotPool.swap_out``), the slot handed to the candidate,
and the victim re-queued as SWAPPED; on re-admission ``swap_in`` restores
the slot bit-exactly and its lane (current token, key stream position,
count) is rebuilt from host bookkeeping, so the victim's remaining tokens
are bit-identical to an uninterrupted run. Strict priority inequality means
equal-priority traffic never preempts (liveness: no swap cycles).

The scheduler is backend-agnostic: it drives any object exposing

    prefill_one(request) -> (logits (1, V), B=1 decode state, prefix_hit_tokens,
                             padded_prompt_tokens)
    step(state, tokens (B, 1)) -> (logits (B, V), state, stats)
    sample_slot(logits, req_key, count) -> tokens (1,)
    sample_lanes(logits, keys (B,2), counts (B,)) -> tokens (B,)
    decode_window(state, loop) -> (state, loop, toks, valid, stats, n)
    make_slot_pool(num_slots) -> kv_slots.SlotPool
    page_block_bytes -> int
    prefill_chunk_tokens -> int        (optional; 0 = whole-shot prefill)
    start_prefill_job(request) -> job  (optional; .advance/.done/.result)
    preempt -> bool                    (optional; pool needs swap_out/swap_in)

(``ServeEngine`` is the production backend; tests inject lightweight fakes.
A backend without ``decode_window`` falls back to the synchronous path.)
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.recall_pipeline import RecallFlightTracker
from repro.models.model import DECODE_STAT_KEYS as _STAT_KEYS
from repro.obs import Observability
from repro.obs.trace import (COUNTER_RECALL_PAGES, COUNTER_SPECULATION,
                             SPAN_APPLY, SPAN_DECODE_WINDOW, SPAN_DISPATCH,
                             SPAN_FIRST_TOKEN, SPAN_FLUSH_RESETS,
                             SPAN_FRONTEND_POLL, SPAN_IDLE_WAIT, SPAN_LANES,
                             SPAN_PREFILL, SPAN_PREFILL_CHUNK, SPAN_PULL,
                             SPAN_SCHED_ADMIT, SPAN_SCHED_CANCEL,
                             SPAN_SCHED_PREEMPT, SPAN_SCHED_RESUME,
                             SPAN_SPEC_VERIFY, SPAN_SPLICE, SPAN_STEP,
                             SPAN_SYNC_WAIT, span)
from repro.serving.metrics import EngineMetrics, RequestMetrics
from repro.serving.sampling import request_key

# stat keys the engine-level counters accumulate (a subset of _STAT_KEYS;
# per-request aggregation keeps the full tuple)
_PAGE_KEYS = ("sync_pages", "async_pages", "reused_pages", "sel_pages",
              "spec_hit_pages", "churn_pages")

# request lifecycle states (SWAPPED = preempted, paged KV parked on host;
# CANCELLED = terminal, client abandoned the request mid-flight)
QUEUED, PREFILL, DECODE, DONE = "queued", "prefill", "decode", "done"
SWAPPED = "swapped"
CANCELLED = "cancelled"


def _prio(tr: "_Tracked") -> int:
    return getattr(tr.req, "priority", 0)


def _state_nbytes(host_state) -> float:
    return float(sum(leaf.nbytes for leaf in jax.tree.leaves(host_state)
                     if hasattr(leaf, "nbytes")))


@dataclass
class _Tracked:
    req: object                       # engine.Request (duck-typed)
    order: int                        # position in the submitted batch
    metrics: RequestMetrics
    state: str = QUEUED
    slot: int = -1
    tokens: List[int] = field(default_factory=list)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    job: object = None                # open PrefillJob (chunked prefill)
    host_state: object = None         # swapped-out B=1 decode state (numpy)
    flight_pages: float = 0.0         # staged recall suspended with the swap
    last_tok_t: Optional[float] = None  # run-relative time of last token
    agg: Dict[str, float] = field(
        default_factory=lambda: {k: 0.0 for k in _STAT_KEYS})

    def finished(self) -> bool:
        if len(self.tokens) >= self.req.max_new_tokens:
            return True
        eos = getattr(self.req, "eos_token", None)
        return bool(self.tokens) and eos is not None and self.tokens[-1] == eos


def _request_stats(agg: Dict[str, float]) -> dict:
    stats = dict(agg)
    if agg["kv_heads"] > 0:
        stats["correction_rate"] = agg["corrected"] / agg["kv_heads"]
        stats["mean_similarity"] = (agg["sim_sum"] / agg["sim_cnt"]
                                    if agg["sim_cnt"] else 0.0)
    if agg.get("sel_pages", 0) > 0:
        stats["spec_hit_rate"] = agg["spec_hit_pages"] / agg["sel_pages"]
    return stats


class _Lanes:
    """Host mirror of the device decode-loop carry: one lane per slot.

    The device copy is rebuilt (one tiny (B,)-vector upload) only when a
    lane changed at a sync boundary — admission, turnover — so steady-state
    decode re-uploads nothing, not even the token vector."""

    FIELDS = ("cur", "key", "count", "limit", "eos", "fin")

    def __init__(self, num_slots: int):
        self.cur = np.zeros(num_slots, np.int32)
        self.key = np.zeros((num_slots, 2), np.uint32)
        self.count = np.zeros(num_slots, np.int32)
        self.limit = np.ones(num_slots, np.int32)
        self.eos = np.full(num_slots, -1, np.int32)
        self.fin = np.ones(num_slots, bool)      # empty lanes are "finished"
        self.dirty = True
        self._dev = None

    def admit(self, slot: int, tok: int, key_np, count: int, limit: int,
              eos: Optional[int]):
        self.cur[slot] = tok
        self.key[slot] = key_np
        self.count[slot] = count
        self.limit[slot] = limit
        self.eos[slot] = -1 if eos is None else eos
        self.fin[slot] = False
        self.dirty = True

    def retire(self, slot: int):
        self.fin[slot] = True
        self.dirty = True

    def device_loop(self, stop_turnover: bool, em: EngineMetrics):
        """The loop carry to ship; uploads lanes only when dirty."""
        if self.dirty or self._dev is None:
            self._dev = {f: jnp.asarray(getattr(self, f)) for f in self.FIELDS}
            em.sync_bytes_to_device += sum(
                getattr(self, f).nbytes for f in self.FIELDS)
            self.dirty = False
        loop = dict(self._dev)
        loop["stop_turnover"] = jnp.asarray(stop_turnover)
        return loop

    def carry_back(self, loop):
        """Keep the donated device carry for the next window (the host
        mirrors are updated from the pulled blocks as tokens are applied)."""
        self._dev = {f: loop[f] for f in self.FIELDS}


class ContinuousScheduler:
    """Drives one run of requests to completion over a fixed slot pool."""

    def __init__(self, backend, pool):
        self.backend = backend
        self.pool = pool

    def run(self, requests, seed: int = 0, service=None):
        """Returns (tracked records in submission order, EngineMetrics).

        ``service`` (optional) switches the scheduler into live-serving
        mode: each host round drains ``service.poll()`` into the admission
        queue and ``service.drain_cancels()`` into the cancellation pass,
        per-token/terminal events stream back via ``service.emit_token`` /
        ``service.emit_finish``, and the run ends only once the service is
        ``closed`` and drained (see ``serving/frontend.EngineService``).
        """
        backend, pool = self.backend, self.pool
        on_device = (bool(getattr(backend, "sample_on_device", False))
                     and hasattr(backend, "decode_window"))
        obs = getattr(backend, "obs", None) or Observability.off()
        self._obs, self._trace = obs, obs.trace
        rec = self._trace
        board = obs.timeseries          # None -> no windowed aggregation
        t0 = time.perf_counter()
        self._t0 = t0
        rec.set_origin(t0)
        now = lambda: time.perf_counter() - t0  # noqa: E731
        abst = lambda rel: t0 + rel             # noqa: E731  (board clock)

        queue: deque = deque()
        by_uid: Dict[int, _Tracked] = {}
        next_order = 0

        def track(r) -> _Tracked:
            nonlocal next_order
            rm = RequestMetrics(uid=r.uid, prompt_tokens=len(r.tokens),
                                max_new_tokens=r.max_new_tokens,
                                priority=getattr(r, "priority", 0),
                                enqueue_t=now(),
                                slo_ttft_ms=getattr(r, "slo_ttft_ms", None),
                                slo_itl_ms=getattr(r, "slo_itl_ms", None))
            tr = _Tracked(req=r, order=next_order, metrics=rm)
            next_order += 1
            by_uid[r.uid] = tr
            return tr

        for r in requests:
            queue.append(track(r))

        em = EngineMetrics(num_slots=pool.num_slots, scheduler="continuous",
                           page_block_bytes=backend.page_block_bytes,
                           tp=getattr(backend, "tp", 1),
                           sync_interval=(getattr(backend, "sync_interval", 1)
                                          if on_device else 1),
                           sample_on_device=on_device,
                           draft_len=int(getattr(backend, "draft_len", 0)),
                           slo_ttft_ms=getattr(backend, "slo_ttft_ms", None),
                           slo_itl_ms=getattr(backend, "slo_itl_ms", None))
        svc = service
        if svc is not None:
            svc.attach(em, t0)
        # per-slot in-flight staged recall: the double buffer a slot carries
        # out of step t is consumed by step t+1 unless the slot turns over
        flight = getattr(backend, "recall_tracker", None) \
            or RecallFlightTracker()
        active: Dict[int, _Tracked] = {}
        prefilling: Dict[int, _Tracked] = {}   # slot -> open chunked prefill
        lanes = _Lanes(pool.num_slots)
        done: List[_Tracked] = []
        self._step_idx = 0
        chunk = int(getattr(backend, "prefill_chunk_tokens", 0) or 0)
        if chunk > 0 and not hasattr(backend, "start_prefill_job"):
            chunk = 0
        preempt_on = bool(getattr(backend, "preempt", False))

        def finish(tr: _Tracked, slot: Optional[int]):
            tr.state = DONE
            tr.metrics.finish_t = now()
            tr.metrics.finish_step = self._step_idx
            tr.metrics.new_tokens = len(tr.tokens)
            tr.metrics.prefill_s = tr.prefill_s
            tr.metrics.decode_s = tr.decode_s
            em.record_request(tr.metrics)       # latency histograms
            self._trace.request_lifecycle(tr.metrics)
            done.append(tr)
            if slot is not None:
                flight.invalidate(slot)   # staged buffer abandoned in flight
                pool.free(slot)
                lanes.retire(slot)
            if board is not None:
                board.event("completions", 1.0, abst(tr.metrics.finish_t))
            if svc is not None:
                svc.emit_finish(tr.req.uid, tr)

        def cancel_pass(uids):
            """Terminal CANCELLED path (client disconnect): release the
            slot, drop in-flight staged recall, park nothing — surviving
            requests never observe the cancellation (their lanes, key
            streams and paged KV are untouched, so outputs stay
            bit-identical). Cancelled requests are excluded from
            ``completed`` / latency / SLO accounting."""
            for uid in uids:
                tr = by_uid.get(uid)
                if tr is None or tr.state in (DONE, CANCELLED):
                    continue
                slot = tr.slot if tr.slot >= 0 else None
                if tr.state in (QUEUED, SWAPPED):
                    try:
                        queue.remove(tr)
                    except ValueError:      # pragma: no cover - defensive
                        pass
                    tr.host_state = None    # parked KV dropped with the req
                    tr.flight_pages = 0.0
                elif tr.state == PREFILL and slot is not None \
                        and slot in prefilling:
                    del prefilling[slot]
                    tr.job = None
                    pool.free(slot)
                    lanes.retire(slot)
                elif tr.state == DECODE and slot is not None \
                        and slot in active:
                    del active[slot]
                    flight.invalidate(slot)
                    pool.free(slot)
                    lanes.retire(slot)
                tr.state = CANCELLED
                tr.slot = -1
                tr.metrics.cancelled = True
                tr.metrics.finish_t = now()
                tr.metrics.finish_step = self._step_idx
                tr.metrics.new_tokens = len(tr.tokens)
                tr.metrics.prefill_s = tr.prefill_s
                tr.metrics.decode_s = tr.decode_s
                em.cancellations += 1
                self._trace.instant(
                    SPAN_SCHED_CANCEL, tr.metrics.finish_t,
                    args={"uid": uid, "slot": -1 if slot is None else slot,
                          "tokens": len(tr.tokens)})
                if board is not None:
                    board.event("cancellations", 1.0,
                                abst(tr.metrics.finish_t))
                done.append(tr)
                if svc is not None:
                    svc.emit_finish(uid, tr)

        def apply_step(stats_np, toks_np, live_slots, dt, ts=None,
                       interpolated=False):
            """Host bookkeeping for ONE decode step: telemetry, token
            append, finish detection. Shared by both dispatch modes.
            ``ts`` (run-relative seconds) is the step's start, from which
            its tokens are stamped; everything recorded here came out of
            the sync-boundary stat pull — no extra host traffic.
            ``interpolated`` marks per-token timestamps subdivided out of
            one dispatch (window mode and speculative verify rows) for
            downstream event consumers."""
            em.record_step(len(live_slots))
            for k in _PAGE_KEYS + ("corrected_heads", "kv_head_steps"):
                src = {"corrected_heads": "corrected",
                       "kv_head_steps": "kv_heads"}.get(k, k)
                setattr(em, k, getattr(em, k)
                        + float(sum(stats_np[src][s] for s in live_slots)))
            for s in live_slots:
                flight.note_step(s, float(stats_np["async_pages"][s]),
                                 float(stats_np["sync_pages"][s]),
                                 float(stats_np["reused_pages"][s]))
            if obs.enabled:
                em.observe_decode_step(dt)
                for s in live_slots:
                    em.observe_speculation(
                        float(stats_np["sel_pages"][s]),
                        float(stats_np["spec_hit_pages"][s]),
                        float(stats_np["churn_pages"][s]),
                        float(stats_np["corrected"][s]),
                        float(stats_np["kv_heads"][s]))
            tok_t = (ts + dt) if ts is not None else now()
            if board is not None:
                board.observe("decode_step_s", dt, abst(tok_t))
                board.observe("slot_occupancy",
                              len(live_slots) / max(pool.num_slots, 1),
                              abst(tok_t))
                sel = float(sum(stats_np["sel_pages"][s]
                                for s in live_slots))
                if sel > 0:
                    board.observe(
                        "spec_hit_rate",
                        float(sum(stats_np["spec_hit_pages"][s]
                                  for s in live_slots)) / sel,
                        abst(tok_t))
            for s in live_slots:
                tr = active[s]
                tr.decode_s += dt
                for k in _STAT_KEYS:
                    tr.agg[k] += float(stats_np[k][s])
                tok = int(toks_np[s])
                tr.tokens.append(tok)
                lanes.cur[s] = tok
                lanes.count[s] += 1
                if tr.last_tok_t is not None:
                    gap = max(tok_t - tr.last_tok_t, 0.0)
                    em.observe_token_gap(gap)
                    if gap > tr.metrics.max_token_gap_s:
                        tr.metrics.max_token_gap_s = gap
                    if board is not None:
                        board.observe("itl_s", gap, abst(tok_t))
                tr.last_tok_t = tok_t
                if board is not None:
                    board.event("tokens", 1.0, abst(tok_t))
                if svc is not None:
                    svc.emit_token(tr.req.uid, len(tr.tokens) - 1, tok,
                                   tok_t, interpolated=interpolated)
                if tr.finished():
                    del active[s]
                    finish(tr, s)
            self._step_idx += 1

        def begin_decode(tr, slot, logits1, rkey):
            """First token out of a completed prefill -> decode lane."""
            with span(SPAN_FIRST_TOKEN, rec):
                tok = int(np.asarray(backend.sample_slot(logits1, rkey,
                                                         0))[0])
            tr.metrics.first_token_t = now()
            tr.last_tok_t = tr.metrics.first_token_t
            tr.tokens.append(tok)
            tr.state = DECODE
            tr.slot = slot
            if board is not None:
                t_abs = abst(tr.metrics.first_token_t)
                board.observe("ttft_s", tr.metrics.first_token_t
                              - tr.metrics.enqueue_t, t_abs)
                board.event("tokens", 1.0, t_abs)
            if svc is not None:
                svc.emit_token(tr.req.uid, 0, tok,
                               tr.metrics.first_token_t)
            if tr.finished():           # max_new_tokens == 1 or instant EOS
                finish(tr, slot)
            else:
                active[slot] = tr
                lanes.admit(slot, tok, np.asarray(rkey), 1,
                            tr.req.max_new_tokens,
                            getattr(tr.req, "eos_token", None))

        def resume(tr):
            """Swap a preempted request's parked KV back into a fresh slot;
            its lane (current token, key stream, count) rebuilds from host
            bookkeeping, so generation continues bit-identically."""
            with span(SPAN_SCHED_RESUME, rec, uid=tr.req.uid) as sp:
                slot = pool.alloc(tr.req.uid)
                nbytes = _state_nbytes(tr.host_state)
                pool.swap_in(tr.host_state, slot)
                sp.set(slot=slot, bytes=nbytes)
            tr.host_state = None
            flight.restore(slot, tr.flight_pages)
            tr.flight_pages = 0.0
            rkey = request_key(seed, tr.req.uid)
            lanes.admit(slot, tr.tokens[-1], np.asarray(rkey),
                        len(tr.tokens), tr.req.max_new_tokens,
                        getattr(tr.req, "eos_token", None))
            tr.state = DECODE
            tr.slot = slot
            active[slot] = tr
            em.resumes += 1
            em.swap_in_bytes += nbytes
            if board is not None:
                board.event("swap_bytes", nbytes, abst(now()))

        def admit_one(tr):
            """Give the request a slot (caller guarantees one is free)."""
            with span(SPAN_SCHED_ADMIT, rec, uid=tr.req.uid):
                admit(tr)

        def admit(tr):
            if tr.state == SWAPPED:
                resume(tr)
                return
            if tr.req.max_new_tokens <= 0:
                finish(tr, None)
                return
            tr.state = PREFILL
            tr.metrics.prefill_start_t = now()
            if board is not None:
                board.observe("queue_wait_s", tr.metrics.prefill_start_t
                              - tr.metrics.enqueue_t,
                              abst(tr.metrics.prefill_start_t))
            slot = pool.alloc(tr.req.uid)
            if chunk > 0:
                # chunked path: the slot is held while the job advances one
                # budgeted chunk per scheduler round (advance_prefill)
                tr.job = backend.start_prefill_job(tr.req)
                tr.slot = slot
                prefilling[slot] = tr
                return
            tp = time.perf_counter()
            with span(SPAN_PREFILL, rec):
                logits1, state1, hit, padded = backend.prefill_one(tr.req)
            with span(SPAN_SPLICE, rec):
                pool.insert(state1, slot)
            # per-request sample stream: token i <- fold_in(rkey, i),
            # independent of slot placement and co-scheduling
            rkey = request_key(seed, tr.req.uid)
            tr.prefill_s = time.perf_counter() - tp
            tr.metrics.prefix_hit_tokens = hit
            tr.metrics.padded_prompt_tokens = padded
            begin_decode(tr, slot, logits1, rkey)

        def preempt_pass():
            """Swap the lowest-priority running request out to host whenever
            a STRICTLY higher-priority request waits for a slot. Terminates:
            each admission removes one queue entry and re-queues only a
            strictly lower-priority victim."""
            while queue and active:
                cand = max(queue, key=lambda t: (_prio(t), -t.order))
                victim = min(active.values(),
                             key=lambda t: (_prio(t), -t.order))
                if _prio(cand) <= _prio(victim):
                    return
                slot = victim.slot
                with span(SPAN_SCHED_PREEMPT, rec, uid=victim.req.uid,
                          slot=slot, by_uid=cand.req.uid) as sp:
                    host = pool.swap_out(slot)
                    nbytes = _state_nbytes(host)
                    sp.set(bytes=nbytes)
                victim.host_state = host
                victim.flight_pages = flight.suspend(slot)
                del active[slot]
                pool.free(slot)
                lanes.retire(slot)
                victim.state = SWAPPED
                victim.slot = -1
                victim.metrics.preemptions += 1
                em.preemptions += 1
                em.swap_out_bytes += nbytes
                if board is not None:
                    t_abs = abst(now())
                    board.event("preemptions", 1.0, t_abs)
                    board.event("swap_bytes", nbytes, t_abs)
                queue.append(victim)
                queue.remove(cand)
                admit_one(cand)

        def advance_prefill():
            """Spend at most one ``chunk`` token budget across the open
            prefill jobs (oldest first); completed jobs splice their decode
            state into the slot and join the decode lanes."""
            budget = chunk
            for tr in sorted(prefilling.values(), key=lambda t: t.order):
                while budget > 0 and not tr.job.done:
                    with span(SPAN_PREFILL_CHUNK, rec,
                              uid=tr.req.uid) as sp:
                        tc = time.perf_counter()
                        n = tr.job.advance(budget)
                        tr.prefill_s += time.perf_counter() - tc
                        sp.set(tokens=n, pos=tr.job.pos,
                               total=len(tr.job.seq))
                    budget -= n
                    em.prefill_chunks += 1
                    em.prefill_chunk_tokens += n
                if tr.job.done:
                    slot = tr.slot
                    del prefilling[slot]
                    logits1, state1, hit, padded = tr.job.result
                    tr.job = None
                    with span(SPAN_SPLICE, rec):
                        pool.insert(state1, slot)
                    tr.metrics.prefix_hit_tokens = hit
                    tr.metrics.padded_prompt_tokens = padded
                    begin_decode(tr, slot, logits1,
                                 request_key(seed, tr.req.uid))
                if budget <= 0:
                    break

        while queue or active or prefilling \
                or (svc is not None and not svc.closed):
            # -- live serving: drain arrivals + disconnects ---------------
            if svc is not None:
                with span(SPAN_FRONTEND_POLL, rec):
                    for r in svc.poll():
                        queue.append(track(r))
                    cancels = svc.drain_cancels()
                    if cancels:
                        cancel_pass(cancels)
                em.wall_s = now()       # keep live tokens/s meaningful
            # -- admission: refill freed slots at the host boundary (FIFO) -
            while queue and pool.free_count:
                admit_one(queue.popleft())
            # -- preemption: priority seizes slots from lower-priority work -
            if preempt_on and queue:
                preempt_pass()
            # -- chunked prefill: one token budget per round ---------------
            if prefilling:
                advance_prefill()
            if not active:
                if svc is not None and not (queue or prefilling):
                    with span(SPAN_IDLE_WAIT, rec):
                        svc.wait(0.002)  # idle: park until work arrives
                continue

            with span(SPAN_FLUSH_RESETS, rec):
                pool.flush_resets()      # lazily reset freed-but-idle slots
            if on_device:
                self._window_steps(backend, pool, em, lanes, apply_step,
                                   stop_turnover=bool(queue)
                                   or (svc is not None and svc.pending),
                                   flight=flight)
            else:
                self._sync_step(backend, pool, em, lanes, apply_step)

        em.wall_s = now()
        em.dropped_pages = flight.dropped_pages
        done.sort(key=lambda tr: tr.order)
        em.requests = [tr.metrics for tr in done]
        return done, em

    # ------------------------------------------------------------------
    # decode dispatch modes
    # ------------------------------------------------------------------
    def _trace_counts(self, stats_np, mask, verify=()):
        """The counts of one sync (``mask`` marks the committed steps of
        the leading rows of ``stats_np``), stamped at the window's end: the
        recall-page and speculation counter tracks, and one instant per
        drafted verify iteration."""
        rec = self._trace
        if not rec.enabled:
            return
        t = time.perf_counter() - rec.origin
        mask = np.asarray(mask, bool)
        tot = {k: float(stats_np[k][:len(mask)][mask].sum())
               for k in ("sync_pages", "async_pages", "reused_pages",
                         "sel_pages", "spec_hit_pages", "corrected",
                         "kv_heads")}
        rec.counter(COUNTER_RECALL_PAGES, t, {
            k: tot[k] for k in ("sync_pages", "async_pages", "reused_pages")})
        rec.counter(COUNTER_SPECULATION, t, {
            "hit_rate": (tot["spec_hit_pages"] / tot["sel_pages"]
                         if tot["sel_pages"] else 0.0),
            "correction_rate": (tot["corrected"] / tot["kv_heads"]
                                if tot["kv_heads"] else 0.0)})
        for args in verify:
            rec.instant(SPAN_SPEC_VERIFY, t, args=args)

    def _window_steps(self, backend, pool, em, lanes, apply_step,
                      stop_turnover: bool, flight=None):
        """Host-sync-free mode: dispatch up to sync_interval fused steps,
        then sync once — pull the token/valid/stat blocks, apply them."""
        rec = self._trace
        with span(SPAN_DECODE_WINDOW, rec) as win:
            with span(SPAN_LANES, rec):
                loop = lanes.device_loop(stop_turnover, em)
            ts = time.perf_counter()
            ts_rel = ts - self._t0
            with span(SPAN_DISPATCH, rec):
                state, loop, toks, valid, stats, n = backend.decode_window(
                    pool.state, loop)
            pool.state = state
            lanes.carry_back(loop)
            with span(SPAN_SYNC_WAIT, rec):
                n = int(n)                          # the one host sync
            with span(SPAN_PULL, rec):
                toks_np = np.asarray(toks)
                valid_np = np.asarray(valid)
                stats_np = {k: (np.asarray(stats[k]) if k in stats
                                else np.zeros(toks_np.shape, np.float32))
                            for k in _STAT_KEYS}
            dt = time.perf_counter() - ts
            em.host_syncs += 1
            pulled = (4 + toks_np.nbytes + valid_np.nbytes
                      + sum(v.nbytes for v in stats_np.values()))
            em.sync_bytes_to_host += pulled
            win.set(steps=n, bytes_to_host=pulled)
            per_dt = dt / max(n, 1)
            verify = []
            with span(SPAN_APPLY, rec):
                if toks_np.ndim == 3:
                    self._apply_spec(em, apply_step, flight, toks_np,
                                     valid_np, stats_np, n, ts_rel, per_dt,
                                     verify)
                else:
                    for j in range(n):
                        live = [s for s in np.nonzero(valid_np[j])[0]]
                        apply_step({k: stats_np[k][j] for k in _STAT_KEYS},
                                   toks_np[j], live, per_dt,
                                   ts=ts_rel + j * per_dt, interpolated=True)
            self._trace_counts(stats_np, valid_np[:n], verify)

    def _apply_spec(self, em, apply_step, flight, toks_np, valid_np,
                    stats_np, n, ts_rel, per_dt, verify):
        """Speculative blocks (n, S, B): iteration j committed, per slot,
        the rows r with valid[j, r, slot] — an accept-longest prefix, so
        row 0's live set is the iteration's live set. Each row is applied
        as one logical decode step (per-token bookkeeping is row-exact);
        timestamps subdivide the iteration's wall share. Each iteration's
        counts are appended to ``verify``."""
        dl = toks_np.shape[1] - 1
        for j in range(n):
            rows = []
            for r in range(dl + 1):
                live = [s for s in np.nonzero(valid_np[j, r])[0]]
                if live:
                    rows.append((r, live))
            if not rows:
                continue
            base = rows[0][1]
            committed = sum(len(live) for _, live in rows)
            em.spec_verify_steps += 1
            em.spec_slot_steps += len(base)
            em.spec_proposed_tokens += dl * len(base)
            em.spec_accepted_tokens += committed - len(base)
            em.spec_committed_tokens += committed
            ts_j = ts_rel + j * per_dt
            if self._obs.enabled:
                em.observe_spec_step(committed / len(base))
            verify.append({"live_slots": len(base),
                           "proposed": dl * len(base),
                           "accepted": committed - len(base),
                           "committed": committed})
            # rejected rows' recall traffic was streamed for a
            # continuation that never commits: dropped in flight (the
            # rollback recall re-stages from the last committed row)
            if flight is not None and dl:
                rej = float(sum(
                    stats_np[k][j, r, s]
                    for k in ("async_pages", "sync_pages")
                    for r in range(1, dl + 1)
                    for s in base if not valid_np[j, r, s]))
                if rej:
                    flight.drop(rej)
            sub = per_dt / len(rows)
            for i, (r, live) in enumerate(rows):
                apply_step({k: stats_np[k][j, r] for k in _STAT_KEYS},
                           toks_np[j, r], live, sub, ts=ts_j + i * sub,
                           interpolated=True)

    def _sync_step(self, backend, pool, em, lanes, apply_step):
        """Synchronous reference mode: one decode step, one host sync —
        tokens sampled outside the jitted step, stats pulled every step."""
        rec = self._trace
        with span(SPAN_LANES, rec):
            loop = lanes.device_loop(False, em)
        ts = time.perf_counter()
        ts_rel = ts - self._t0
        with span(SPAN_STEP, rec):
            logits, state, stats = backend.step(pool.state,
                                                loop["cur"][:, None])
            toks = backend.sample_lanes(logits, loop["key"], loop["count"])
        with span(SPAN_SYNC_WAIT, rec):
            toks_np = np.asarray(toks)
            stats_np = {k: (np.asarray(stats[k]) if k in stats
                            else np.zeros(pool.num_slots)) for k in _STAT_KEYS}
        dt = time.perf_counter() - ts
        pool.state = state
        em.host_syncs += 1
        em.nonsync_host_bytes += 0.0     # the sync IS the step boundary
        em.sync_bytes_to_host += toks_np.nbytes + sum(
            v.nbytes for v in stats_np.values())
        # lanes (cur/count) change every step on this path: mark dirty so
        # the next step re-uploads them — the per-step round trip the
        # host-sync-free loop exists to remove
        lanes.dirty = True
        live = ~lanes.fin
        with span(SPAN_APPLY, rec):
            apply_step(stats_np, toks_np, [s for s in np.nonzero(live)[0]],
                       dt, ts=ts_rel)
        self._trace_counts(stats_np, live)
