"""Trace spans: one vocabulary for the profiler's trace and Perfetto JSON.

:class:`span` is the one way the program opens a span. It always opens a
``jax.profiler.TraceAnnotation`` (a TraceMe): while a ``jax.profiler``
trace runs, the span lands in it on the host thread that opened it, on the
same clock as the device's operations; with no profile running it costs
a few microseconds of host time. When a :class:`TraceRecorder` is enabled the span is
also buffered as a Chrome ``X`` event timed by ``time.perf_counter`` at
the same entry and exit, so ``--trace-out`` exports exactly the spans the
profiler sees. Spans are opened where the work happens, on the engine
thread (``serving/scheduler.py``), a fixed handful per decode window: none
per token, slot or layer.

Besides spans, :class:`TraceRecorder` writes (Trace Event Format, which
Perfetto and ``chrome://tracing`` load):

* request-lifecycle spans — one Perfetto *thread* per request uid with
  ``request/queued`` -> ``request/prefill`` -> ``request/decode`` spans
  and a ``request/done`` instant, emitted at finish from the
  ``RequestMetrics`` timestamps (no bookkeeping on the hot path);
* counts pulled at each sync boundary, stamped at the window's end — the
  ``recall/pages`` counter track (sync / async / reused pages), the
  ``speculation`` counter track (hit and correction rates) and one
  ``engine/spec_verify`` instant per drafted verify iteration
  (proposed / accepted / committed tokens). They are counts, not times.

Inside the jitted step, :func:`annotate` puts the recall and attention
names on the HLO as ``jax.named_scope`` metadata (free at run time).
"""
from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

import jax

# --- span taxonomy (docs/observability.md) -----------------------------
SPAN_REQUEST_QUEUED = "request/queued"
SPAN_REQUEST_PREFILL = "request/prefill"
SPAN_REQUEST_DECODE = "request/decode"
SPAN_REQUEST_DONE = "request/done"
# engine host loop (scheduler.py), host-sync-free path: one window is
# lanes -> dispatch -> sync_wait -> pull -> apply
SPAN_DECODE_WINDOW = "engine/decode_window"
SPAN_LANES = "engine/lanes"
SPAN_DISPATCH = "engine/dispatch"
SPAN_SYNC_WAIT = "engine/sync_wait"
SPAN_PULL = "engine/pull"
SPAN_APPLY = "engine/apply"
# synchronous reference path: one step, then engine/sync_wait
SPAN_STEP = "engine/step"
SPAN_FRONTEND_POLL = "frontend/poll"
SPAN_FLUSH_RESETS = "pool/flush_resets"
SPAN_IDLE_WAIT = "engine/idle_wait"
# admission: sched/admit > engine/prefill, pool/splice, engine/first_token
SPAN_SCHED_ADMIT = "sched/admit"
SPAN_PREFILL = "engine/prefill"
SPAN_SPLICE = "pool/splice"
SPAN_FIRST_TOKEN = "engine/first_token"
SPAN_PREFILL_CHUNK = "engine/prefill_chunk"
SPAN_SCHED_PREEMPT = "sched/preempt"
SPAN_SCHED_RESUME = "sched/resume"
SPAN_SCHED_CANCEL = "sched/cancel"
# instant per drafted verify iteration: live-slot count plus proposed /
# accepted / committed token counts
SPAN_SPEC_VERIFY = "engine/spec_verify"
# counter tracks, sampled once per sync boundary
COUNTER_RECALL_PAGES = "recall/pages"
COUNTER_SPECULATION = "speculation"
# named scopes inside the jitted step (annotate)
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


def annotate(name: str):
    """``jax.named_scope`` on the shared span names — free at runtime
    (HLO metadata only); the profiler shows it on the device's ops."""
    return jax.named_scope(name)


class span:
    """A host span around real work: ``with span(name, recorder, **args)``.

    Always a ``jax.profiler.TraceAnnotation`` (recorded only while a
    profile runs); also a Chrome ``X`` event when ``recorder`` is enabled.
    ``set(**args)`` attaches values known only at the end (counts, bytes)
    to both."""

    __slots__ = ("_name", "_rec", "_args", "_me", "_t")

    def __init__(self, name: str, recorder: Optional["TraceRecorder"] = None,
                 **args):
        self._name = name
        self._rec = recorder if recorder is not None and recorder.enabled \
            else None
        self._args = args
        self._me = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        self._me.__enter__()
        if self._rec is not None:
            self._t = time.perf_counter()
        return self

    def set(self, **args) -> None:
        self._me.set_metadata(**args)
        if self._rec is not None:
            self._args.update(args)

    def __exit__(self, *exc) -> None:
        if self._rec is not None:
            t = self._t
            self._rec.complete(self._name, t - self._rec.origin,
                               time.perf_counter() - t,
                               args=self._args or None)
        self._me.__exit__(*exc)


class TraceRecorder:
    """Buffers Chrome-trace events; ``enabled=False`` makes every method
    a cheap no-op so the recorder can be threaded unconditionally."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.events: List[dict] = []
        self.origin = time.perf_counter()
        self._names: Dict[tuple, str] = {}
        if enabled:
            self._meta(PID_ENGINE, None, "process_name", "serve-engine")
            self._meta(PID_ENGINE, TID_ENGINE, "thread_name", "decode")
            self._meta(PID_REQUESTS, None, "process_name", "requests")

    # -- clock ---------------------------------------------------------
    def set_origin(self, t: Optional[float] = None) -> None:
        """Anchor ts=0; the scheduler calls this with its run-start time so
        span timestamps equal the RequestMetrics timeline."""
        self.origin = time.perf_counter() if t is None else t

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
