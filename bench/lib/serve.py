"""The system under test and the measured window.

The engine is the program's continuous-batching ``ServeEngine`` with the
cell's serving settings, driven in process through its live entry point:
``serving/frontend.EngineService.submit`` with a per-token callback, which
feeds ``ServeEngine.serve_service`` on the service's worker thread. Every
time here is the host's ``time.perf_counter``; the window's records are
raw per-request timestamps, reduced by the metric readers.
"""
from __future__ import annotations

import dataclasses
import queue
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import traffic

GRACE_S = 60.0            # wait for first tokens due in the window
WARMUP_UID = 1 << 30      # warm-up requests' uids, clear of the planned ones

# Events that mean an executable was built or loaded in this process: a
# compile by the backend, or a program read back from the persistent cache.
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts executables built or loaded while ``active``. JAX's listeners
    cannot be removed, so the process installs one counter (``get``)."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.active = False
        self.count = 0
        self._lock = threading.Lock()

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed._on_event)
        return cls._installed

    def _on_event(self, event, secs, **kw):
        if self.active and event in _COMPILE_EVENTS:
            with self._lock:
                self.count += 1


def program_config(model: dict):
    """The program's registry architecture, cut to the file's depth; every
    width must agree with the configuration file."""
    from repro.configs import get_config
    cfg = get_config(model["arch"])
    L = model["num_hidden_layers"]
    cfg = dataclasses.replace(cfg, n_layers=L, n_periods=L)
    have = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.d_head,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings}
    bad = {k: (v, model[k]) for k, v in have.items() if v != model[k]}
    if bad:
        raise ValueError(f"registry {model['arch']} differs from the "
                         f"configuration file (program, file): {bad}")
    return cfg


def build_engine(model: dict, mix: dict, params, *, offload: str = "host",
                 kernels: bool = True, dtype=jnp.bfloat16):
    """``ServeEngine`` as ``launch/serve.build_engine`` assembles it, with
    the benchmark's seeded weights and the mix's serving settings."""
    from repro.configs.base import FreeKVConfig
    from repro.obs import Observability
    from repro.serving.engine import ServeEngine
    from repro.serving.sampling import SamplerConfig

    s, f = mix["serving"], mix["serving"]["freekv"]
    fkv = FreeKVConfig(method="freekv", page_size=f["page_size"],
                       budget=f["budget"], n_sink=f["n_sink"],
                       n_window=f["n_window"], tau=f["tau"],
                       sync_interval=f["sync_interval"], offload=offload,
                       use_kernels=kernels)
    return ServeEngine(program_config(model), fkv, params,
                       max_len=traffic.max_len(mix),
                       batch_size=s["slots"], sampler=SamplerConfig(0.0),
                       scheduler="continuous",
                       prefill_bucket=s["prefill_bucket"],
                       state_dtype=dtype, obs=Observability.off())


@dataclass
class Req:
    """One request's host-clock record (seconds, ``perf_counter``)."""
    uid: int
    prompt_len: int
    max_new_tokens: int
    due: float = 0.0
    submit_t: float = 0.0
    first_t: Optional[float] = None
    finish_t: Optional[float] = None
    token_t: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    error: Optional[str] = None
    cancelled: bool = False
    queue_wait_s: Optional[float] = None    # program's RequestMetrics from
    prefill_s: Optional[float] = None       # submission, after the run


@dataclass
class Window:
    records: Dict[int, Req]
    setup_s: float
    t_start: float
    t_end: float
    compiles: int
    late_s: float                 # open loop: how late the sender ran, worst
    corrected: float              # program counters over the window
    kv_head_steps: float
    trace_dir: Optional[str] = None


class _Client:
    """Per-request callbacks (run on the engine's thread)."""

    def __init__(self):
        self.records: Dict[int, Req] = {}
        self.finished: "queue.Queue[int]" = queue.Queue()

    def submit(self, svc, uid, prompt, max_new, due):
        r = Req(uid, len(prompt), max_new, due=due)
        self.records[uid] = r
        r.submit_t = time.perf_counter()
        svc.submit(prompt, max_new, lambda k, p: self._on(r, k, p), uid=uid)
        return r

    def _on(self, r: Req, kind: str, payload: dict):
        now = time.perf_counter()
        if kind == "token":
            if r.first_t is None:
                r.first_t = now
            r.token_t.append(now)
            r.tokens.append(int(payload["token"]))
        else:
            if kind == "error":
                r.error = payload.get("error", "error")
            r.cancelled = bool(payload.get("cancelled", False))
            r.finish_t = now
            self.finished.put(r.uid)


class _Tracer:
    """JAX's profiler over the last part of the window, from ``start_at``
    (when the open loop's slots have filled most) to its close, marked by
    the host span ``bench.traced_window``; a no-op without a directory.
    Starting the profiler takes seconds, so the span opens when it is
    running."""

    def __init__(self, trace_dir: Optional[str], start_at: float):
        self.trace_dir, self.start_at, self.span = trace_dir, start_at, None

    def poll(self, now: float):
        if self.trace_dir and self.span is None and now >= self.start_at:
            jax.profiler.start_trace(self.trace_dir)
            self.span = jax.profiler.TraceAnnotation("bench.traced_window")
            self.span.__enter__()
            self.trace_dir = None          # once

    def stop(self):
        if self.span is not None:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.span = None


def _wait(pred, timeout: float) -> bool:
    end = time.perf_counter() + timeout
    while not pred():
        if time.perf_counter() > end:
            return False
        time.sleep(0.002)
    return True


def _warm_up(svc, client: _Client, mix: dict, vocab: int):
    """One request per prefill bucket the mix can produce, long enough to
    run decode windows with and without slot turnover."""
    rng = np.random.default_rng(0)
    new = 2 * mix["serving"]["freekv"]["sync_interval"] + 2
    reqs = [client.submit(svc, WARMUP_UID + i,
                          rng.integers(1, vocab, b, dtype=np.int32), new,
                          time.perf_counter())
            for i, b in enumerate(traffic.buckets(mix))]
    if not _wait(lambda: all(r.finish_t for r in reqs), 1200.0):
        raise RuntimeError("warm-up requests did not finish")
    bad = [r for r in reqs if r.error or len(r.tokens) != new]
    if bad:
        raise RuntimeError(f"warm-up failed: {bad[0].error or bad[0]}")


def _send_due(svc, client: _Client, todo: List[traffic.Planned],
              t_send: float, until: float, tracer: Optional[_Tracer] = None
              ) -> float:
    """Open loop: send each planned request at ``t_send`` plus its due
    offset, until one falls due at ``until``; the worst lateness."""
    late = 0.0
    while todo:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now)
        due = t_send + todo[0].due_s
        if due >= until:
            break
        if now < due:
            time.sleep(min(due - now, 0.005))
            continue
        p = todo.pop(0)
        r = client.submit(svc, p.index, p.prompt, p.max_new_tokens, due)
        late = max(late, r.submit_t - due)
    return late


def run(engine, mix: dict, plan: List[traffic.Planned], seconds: float,
        t_setup0: float, trace_seconds: float = 0.0,
        trace_dir: Optional[str] = None) -> Window:
    """Warm up, start the cell's load, measure ``seconds``, drain.

    Closed loop: the first ``clients`` requests are prefilled during set-up;
    in the window each finish sends the next planned request, due at that
    finish. Open loop: planned requests are sent at their due offsets from
    the end of warm-up, and the window opens the mix's ``lead_s`` later, so
    that the requests sent in set-up have filled the slots as steady load
    does. After the window no request is sent; requests due in
    it are waited for until their first token, and requests running at its
    close until their next delivery (at most ``GRACE_S``); then everything
    still running is cancelled and the service drained."""
    from repro.serving.frontend import EngineService

    vocab = engine.cfg.vocab_size
    counter = CompileCounter.get()
    client = _Client()
    svc = EngineService(engine, seed=0).start()
    try:
        t = time.perf_counter()
        _warm_up(svc, client, mix, vocab)
        print(f"set-up: warm-up {time.perf_counter() - t:.3f} s",
              file=sys.stderr)
        for uid in list(client.records):
            del client.records[uid]
        while not client.finished.empty():
            client.finished.get()
        closed = mix["loop"] == "closed"
        todo = list(plan)
        if closed:
            first = [client.submit(svc, p.index, p.prompt, p.max_new_tokens,
                                   time.perf_counter())
                     for p in todo[: mix["clients"]]]
            del todo[: mix["clients"]]
            t = time.perf_counter()
            if not _wait(lambda: all(r.first_t or r.error for r in first),
                         1200.0):
                raise RuntimeError("set-up prefills did not finish")
            print(f"set-up: first {len(first)} prefills "
                  f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
        t_send = time.perf_counter()
        if not closed:                  # the open loop's lead-in
            lead_end = t_send + float(mix.get("lead_s", 0.0))
            _send_due(svc, client, todo, t_send, lead_end)
            time.sleep(max(lead_end - time.perf_counter(), 0.0))
        t_start = time.perf_counter()
        setup_s = t_start - t_setup0
        em = svc.em
        c0, k0 = em.corrected_heads, em.kv_head_steps
        counter.count, counter.active = 0, True
        t_end = t_start + seconds
        late = 0.0
        tracer = _Tracer(trace_dir, t_end - trace_seconds)
        while todo and closed:
            now = time.perf_counter()
            tracer.poll(now)
            if now >= t_end:
                break
            try:
                client.finished.get(timeout=min(0.01, t_end - now))
            except queue.Empty:
                continue
            p = todo.pop(0)
            client.submit(svc, p.index, p.prompt, p.max_new_tokens,
                          time.perf_counter())
        if not closed:
            late = _send_due(svc, client, todo, t_send, t_end, tracer)
        for end in (tracer.start_at, t_end):    # nothing left to send
            time.sleep(max(end - time.perf_counter(), 0.0))
            tracer.poll(time.perf_counter())
        tracer.stop()
        counter.active = False
        c1, k1 = em.corrected_heads, em.kv_head_steps
        due_in = [r for r in client.records.values() if r.due < t_end]
        running = [r for r in due_in if r.first_t and r.finish_t is None]
        # every request due in the window reaches its first token, and every
        # one running at the close its next delivery (whose tokens the
        # window shares)
        _wait(lambda: all(r.first_t or r.error for r in due_in) and all(
            r.finish_t or r.token_t[-1] >= t_end for r in running), GRACE_S)
    finally:
        for r in list(client.records.values()):
            if r.finish_t is None:
                svc.cancel(r.uid)
        done = svc.stop()
    for c in done or ():
        r = client.records.get(c.uid)
        if r is not None and c.metrics is not None:
            m = c.metrics
            if m.prefill_start_t is not None:
                r.queue_wait_s = svc.t0 + m.prefill_start_t - r.submit_t
                if m.first_token_t is not None:
                    r.prefill_s = m.first_token_t - m.prefill_start_t
    return Window(client.records, setup_s, t_start, t_end, counter.count,
                  late, c1 - c0, k1 - k0, trace_dir)
