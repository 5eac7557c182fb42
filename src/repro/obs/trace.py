"""Trace spans: Chrome-trace / Perfetto JSON for the serving pipeline.

``TraceRecorder`` buffers Trace Event Format events (the JSON Perfetto and
``chrome://tracing`` load natively) and writes them with
:meth:`TraceRecorder.write`:

* request-lifecycle spans — one Perfetto *thread* per request uid with
  ``request/queued`` -> ``request/prefill`` -> ``request/decode`` spans
  and a ``request/done`` instant (scheduler emits these at finish time
  from the ``RequestMetrics`` timestamps, so tracing adds no bookkeeping
  to the hot path);
* engine spans — ``engine/decode_window`` per host sync with the fused
  step count / pulled bytes in ``args``, split into per-step
  ``engine/decode_step`` spans on the engine track;
* recall-pipeline spans — per-step ``recall/topup`` (blocking correction
  top-up) on the engine track and ``recall/staged`` (overlapped
  speculative stage) on a separate DMA track, so the hidden-fraction
  claim is visually auditable as overlap. In simulation the DMA span
  durations are **modeled** from block counts at ``MODEL_LINK_BW``
  (mirrors ``benchmarks/_common.HwModel.host_link_bw``) — the event
  ``args`` carry the exact byte counts;
* counter tracks — ``speculation/hit_rate`` and
  ``speculation/correction_rate`` sampled once per sync boundary, giving
  the paper's accuracy-side signal as a timeline.

The same span names are exported as ``jax.named_scope`` annotations via
:func:`annotate` (used inside the jitted retrieval path), so a real
``jax.profiler`` trace lines up with the host-side spans by name.
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

import jax

# modeled host<->device link bandwidth for simulated DMA span durations;
# keep in sync with benchmarks/_common.HwModel.host_link_bw
MODEL_LINK_BW = 20e9

# --- span taxonomy (docs/observability.md) -----------------------------
SPAN_REQUEST_QUEUED = "request/queued"
SPAN_REQUEST_PREFILL = "request/prefill"
SPAN_REQUEST_DECODE = "request/decode"
SPAN_REQUEST_DONE = "request/done"
SPAN_DECODE_WINDOW = "engine/decode_window"
SPAN_DECODE_STEP = "engine/decode_step"
# one drafted-block verify iteration (speculative decoding): args carry the
# live-slot count plus proposed/accepted/committed token counts, so the
# accepted-tokens-per-target-step distribution is readable off the trace
SPAN_SPEC_VERIFY = "engine/spec_verify"
SPAN_PREFILL_CHUNK = "engine/prefill_chunk"
SPAN_SCHED_PREEMPT = "sched/preempt"
SPAN_SCHED_RESUME = "sched/resume"
SPAN_SCHED_CANCEL = "sched/cancel"
SPAN_RECALL_SELECT = "recall/select"
SPAN_RECALL_CORRECTION = "recall/correction"
SPAN_RECALL_TOPUP = "recall/topup"
SPAN_RECALL_STAGED = "recall/staged"
SPAN_RECALL_REUSE = "recall/reuse"
SPAN_ATTN_COMPUTE = "attn/compute"

# Perfetto pid/tid layout: one process for the engine, one for requests
PID_ENGINE = 1
PID_REQUESTS = 2
TID_ENGINE = 1
TID_DMA = 2


def annotate(name: str):
    """``jax.named_scope`` on the shared span names — free at runtime
    (HLO metadata only), and it makes ``jax.profiler`` traces line up
    with the host-side Perfetto spans."""
    return jax.named_scope(name)


class TraceRecorder:
    """Buffers Chrome-trace events; ``enabled=False`` makes every method
    a cheap no-op so the recorder can be threaded unconditionally."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.events: List[dict] = []
        self._origin: Optional[float] = None
        self._names: Dict[tuple, str] = {}
        if enabled:
            self._meta(PID_ENGINE, None, "process_name", "serve-engine")
            self._meta(PID_ENGINE, TID_ENGINE, "thread_name", "decode")
            self._meta(PID_ENGINE, TID_DMA, "thread_name", "recall-dma")
            self._meta(PID_REQUESTS, None, "process_name", "requests")

    # -- clock ---------------------------------------------------------
    def set_origin(self, t: Optional[float] = None) -> None:
        """Anchor ts=0; scheduler calls this with its run-start time so
        span timestamps equal the RequestMetrics timeline."""
        self._origin = time.perf_counter() if t is None else t

    def _us(self, t_s: float) -> float:
        return t_s * 1e6

    # -- event emitters (ts/dur in seconds, run-relative) ---------------
    def _meta(self, pid: int, tid: Optional[int], what: str, name: str):
        ev = {"ph": "M", "pid": pid, "name": what, "args": {"name": name}}
        if tid is not None:
            ev["tid"] = tid
        self.events.append(ev)

    def name_request_track(self, uid: int) -> None:
        if not self.enabled or (PID_REQUESTS, uid) in self._names:
            return
        self._names[(PID_REQUESTS, uid)] = f"req {uid}"
        self._meta(PID_REQUESTS, uid, "thread_name", f"req {uid}")

    def complete(self, name: str, ts_s: float, dur_s: float, *,
                 pid: int = PID_ENGINE, tid: int = TID_ENGINE,
                 args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "ts": self._us(ts_s),
              "dur": max(self._us(dur_s), 0.0), "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, ts_s: float, *, pid: int = PID_ENGINE,
                tid: int = TID_ENGINE,
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "ts": self._us(ts_s), "pid": pid,
              "tid": tid, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, ts_s: float, values: Dict[str, float], *,
                pid: int = PID_ENGINE) -> None:
        if not self.enabled:
            return
        self.events.append({"name": name, "ph": "C", "ts": self._us(ts_s),
                            "pid": pid, "args": dict(values)})

    # -- high-level helpers ---------------------------------------------
    def request_lifecycle(self, rm) -> None:
        """Emit queued/prefill/decode spans + done instant for a finished
        request from its RequestMetrics timestamps."""
        if not self.enabled:
            return
        uid = rm.uid
        self.name_request_track(uid)
        q = {"uid": uid, "prompt_tokens": rm.prompt_tokens}
        if rm.prefill_start_t is not None:
            self.complete(SPAN_REQUEST_QUEUED, rm.enqueue_t,
                          rm.prefill_start_t - rm.enqueue_t,
                          pid=PID_REQUESTS, tid=uid, args=q)
        if rm.prefill_start_t is not None and rm.first_token_t is not None:
            self.complete(SPAN_REQUEST_PREFILL, rm.prefill_start_t,
                          rm.first_token_t - rm.prefill_start_t,
                          pid=PID_REQUESTS, tid=uid,
                          args={"prefix_hit_tokens": rm.prefix_hit_tokens,
                                "padded": rm.padded_prompt_tokens})
        if rm.first_token_t is not None and rm.finish_t is not None:
            self.complete(SPAN_REQUEST_DECODE, rm.first_token_t,
                          rm.finish_t - rm.first_token_t,
                          pid=PID_REQUESTS, tid=uid,
                          args={"new_tokens": rm.new_tokens})
        if rm.finish_t is not None:
            self.instant(SPAN_REQUEST_DONE, rm.finish_t, pid=PID_REQUESTS,
                         tid=uid, args={"uid": uid})

    def recall_step(self, ts_s: float, dur_s: float, *, sync_pages: float,
                    async_pages: float, reused_pages: float,
                    page_block_bytes: float) -> None:
        """Per-step recall stage spans: the blocking top-up lives on the
        decode track (it is on the critical path); the speculative stage
        for the *next* step runs on the DMA track in parallel with the
        step's compute. Durations are modeled (bytes / MODEL_LINK_BW) in
        simulation; args carry the exact page/byte counts."""
        if not self.enabled:
            return
        if sync_pages > 0:
            b = sync_pages * page_block_bytes
            self.complete(SPAN_RECALL_TOPUP, ts_s,
                          min(b / MODEL_LINK_BW, dur_s),
                          tid=TID_ENGINE,
                          args={"pages": sync_pages, "bytes": b,
                                "modeled": True})
        if async_pages > 0:
            b = async_pages * page_block_bytes
            self.complete(SPAN_RECALL_STAGED, ts_s,
                          min(b / MODEL_LINK_BW, dur_s),
                          tid=TID_DMA,
                          args={"pages": async_pages, "bytes": b,
                                "modeled": True, "hidden": True})
        if reused_pages > 0:
            self.instant(SPAN_RECALL_REUSE, ts_s, tid=TID_DMA,
                         args={"pages": reused_pages})

    # -- export ----------------------------------------------------------
    def chrome_trace(self) -> dict:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs.trace"},
        }

    def write(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(), f)


def validate_chrome_trace(doc: dict) -> List[str]:
    """Well-formedness check shared by tests and tools/check_obs.py.
    Returns a list of problems (empty = valid)."""
    errors: List[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["missing traceEvents key"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        for key in ("ph", "pid", "name"):
            if key not in ev:
                errors.append(f"event {i}: missing {key!r}")
        ph = ev.get("ph")
        if ph in ("X", "i", "C") and "ts" not in ev:
            errors.append(f"event {i}: {ph!r} event missing ts")
        if ph == "X":
            if "dur" not in ev or not isinstance(ev["dur"], (int, float)) \
                    or ev["dur"] < 0:
                errors.append(f"event {i}: X event needs dur >= 0")
            if "tid" not in ev:
                errors.append(f"event {i}: X event missing tid")
    return errors
