"""Observability plane: registry/histogram correctness, exporter schema
stability, trace well-formedness, and the zero-interference contract
(obs on vs off: bit-identical tokens, zero added host syncs/bytes).

Serving-stack fixtures reuse the tiny smoke arch; the engine runs are the
slowest part so they are shared per-module via fixtures.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FreeKVConfig
from repro.models.model import init_params
from repro.obs import (COUNT_BUCKETS, LATENCY_BUCKETS, RATE_BUCKETS,
                       Observability, TraceRecorder, validate_chrome_trace,
                       validate_snapshot)
from repro.obs.registry import (MetricsRegistry, SNAPSHOT_SCHEMA_VERSION,
                                exponential_buckets, linear_buckets)
from repro.obs.trace import (COUNTER_RECALL_PAGES, SPAN_APPLY,
                             SPAN_DECODE_WINDOW, SPAN_DISPATCH, SPAN_LANES,
                             SPAN_PULL, SPAN_REQUEST_DECODE,
                             SPAN_REQUEST_PREFILL, SPAN_REQUEST_QUEUED,
                             SPAN_SYNC_WAIT, annotate, span)
from repro.serving.engine import Request, ServeEngine
from repro.serving.metrics import EngineMetrics
from repro.serving.sampling import SamplerConfig


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("c_total") is c          # get-or-create is idempotent
    g = reg.gauge("g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5


def test_histogram_bucket_assignment():
    h = MetricsRegistry().histogram("h", [1.0, 2.0, 4.0])
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # le-semantics: 0.5,1.0 -> bucket0; 1.5 -> bucket1; 3.0 -> bucket2;
    # 100 -> overflow
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(106.0)
    assert h.min == 0.5 and h.max == 100.0


def test_histogram_percentiles_against_numpy():
    rng = np.random.default_rng(0)
    xs = rng.exponential(0.01, size=5000)
    h = MetricsRegistry().histogram("lat", LATENCY_BUCKETS)
    for x in xs:
        h.observe(x)
    for q in (0.50, 0.90, 0.99):
        est = h.percentile(q)
        exact = float(np.quantile(xs, q))
        # bucketed estimate must land within one bucket boundary (2x) of
        # the exact quantile
        assert exact / 2 <= est <= exact * 2, (q, est, exact)
    # percentiles are clamped to the observed max (no bucket-edge overshoot)
    assert h.percentile(0.999) <= h.max


def test_histogram_summary_and_empty():
    h = MetricsRegistry().histogram("x", [1.0, 2.0])
    s = h.summary()
    assert s["count"] == 0 and s["p50"] == 0.0
    h.observe(1.5)
    s = h.summary()
    assert s["count"] == 1
    assert s["mean"] == pytest.approx(1.5)
    assert 1.0 <= s["p50"] <= 2.0                # inside containing bucket


def test_bucket_helpers():
    assert linear_buckets(0.0, 1.0, 5) == [0.0, 1.0, 2.0, 3.0, 4.0]
    e = exponential_buckets(1.0, 2.0, 4)
    assert e == [1.0, 2.0, 4.0, 8.0]
    for buckets in (LATENCY_BUCKETS, RATE_BUCKETS, COUNT_BUCKETS):
        assert buckets == sorted(buckets)


def test_snapshot_schema_and_validator():
    reg = MetricsRegistry()
    reg.counter("a_total").inc(3)
    reg.gauge("b").set(1.5)
    reg.histogram("c", [1.0, 2.0]).observe(0.5)
    snap = reg.snapshot()
    assert snap["schema_version"] == SNAPSHOT_SCHEMA_VERSION
    assert validate_snapshot(snap) == []
    # round-trips through JSON unchanged
    assert validate_snapshot(json.loads(json.dumps(snap))) == []
    # validator actually catches corruption
    bad = json.loads(json.dumps(snap))
    bad["histograms"]["c"]["bucket_counts"].append(9)
    assert validate_snapshot(bad)
    assert validate_snapshot({"schema_version": 999}) != []


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(2)
    reg.histogram("lat_seconds", [0.1, 1.0], "latency").observe(0.05)
    reg.histogram("lat_seconds", [0.1, 1.0]).observe(5.0)
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert "req_total 2" in text
    assert "# TYPE lat_seconds histogram" in text
    # cumulative buckets + +Inf terminal
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text


def test_write_jsonl_appends(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n_total").inc()
    path = tmp_path / "m.jsonl"
    reg.write_jsonl(str(path), extra={"run": 1})
    reg.counter("n_total").inc()
    reg.write_jsonl(str(path), extra={"run": 2})
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 2
    assert [ln["extra"]["run"] for ln in lines] == [1, 2]
    assert lines[1]["counters"]["n_total"] == 2
    assert all(validate_snapshot(ln) == [] for ln in lines)


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------
def test_trace_recorder_events_and_validation():
    tr = TraceRecorder(enabled=True)
    tr.complete("engine/decode_window", 1.0, 0.002, args={"steps": 1})
    tr.instant("recall/reuse", 1.001)
    tr.counter("speculation", 1.0, {"hit_rate": 0.5})
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    x = next(e for e in evs if e["ph"] == "X")
    assert x["ts"] == pytest.approx(1.0e6)      # seconds -> microseconds
    assert x["dur"] == pytest.approx(2000.0)
    # disabled recorder drops everything
    off = TraceRecorder(enabled=False)
    off.complete("x", 0.0, 1.0)
    with span("engine/apply", off, uid=3) as sp:
        sp.set(steps=2)
    assert off.events == []


def test_span_records_its_own_interval():
    """A span's X event is timed at its entry and exit on the recorder's
    clock, and carries the args given at entry and those set before its
    end; nested spans nest."""
    import time
    tr = TraceRecorder(enabled=True)
    tr.set_origin(time.perf_counter())
    with span("engine/decode_window", tr, slots=2) as win:
        with span("engine/sync_wait", tr):
            time.sleep(0.01)
        win.set(steps=4)
    assert validate_chrome_trace(tr.chrome_trace()) == []
    inner, outer = [e for e in tr.events if e["ph"] == "X"]
    assert inner["name"] == "engine/sync_wait"
    assert outer["args"] == {"slots": 2, "steps": 4}
    assert inner["dur"] >= 1e4                  # the 10 ms sleep, in us
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_trace_validator_catches_malformed():
    assert validate_chrome_trace({"no": "events"})
    assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    bad_dur = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": -5}]}
    assert validate_chrome_trace(bad_dur)


def test_annotate_composes_with_jit():
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with annotate("attn/compute"):
            return x * 2
    assert float(f(jnp.float32(1.0))) == 2.0


# ---------------------------------------------------------------------------
# engine integration: zero interference + exporter contents
# ---------------------------------------------------------------------------
ARCH = "smollm-360m-smoke"


def _requests(cfg, new_tokens=6, requests=3, context=64):
    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        context).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(requests)]


def _run_engine(obs, new_tokens=6, requests=3, context=64):
    cfg = get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(0))
    fkv = FreeKVConfig(method="freekv", page_size=8, budget=48, n_sink=8,
                       n_window=8, tau=0.8, sync_interval=4)
    eng = ServeEngine(cfg, fkv, params, max_len=context + new_tokens + 8,
                      batch_size=2, sampler=SamplerConfig(temperature=0.0),
                      scheduler="continuous", obs=obs)
    outs = eng.generate(_requests(cfg, new_tokens, requests, context))
    return [c.tokens for c in outs], eng


@pytest.fixture(scope="module")
def obs_on_off_runs():
    tok_off, eng_off = _run_engine(Observability.off())
    tok_on, eng_on = _run_engine(
        Observability(enabled=True, trace=TraceRecorder(enabled=True)))
    return tok_off, eng_off, tok_on, eng_on


def test_obs_zero_interference(obs_on_off_runs):
    tok_off, eng_off, tok_on, eng_on = obs_on_off_runs
    assert tok_on == tok_off                     # bit-identical greedy tokens
    off, on = eng_off.last_metrics, eng_on.last_metrics
    assert on.host_syncs == off.host_syncs       # zero added syncs
    assert on.nonsync_host_bytes == 0.0          # nothing moved between syncs
    assert on.sync_bytes_to_host == off.sync_bytes_to_host
    # counter totals identical: they run with obs on or off
    assert on.steps == off.steps
    assert on.sel_pages == off.sel_pages
    assert on.spec_hit_pages == off.spec_hit_pages


def test_speculation_telemetry_sane(obs_on_off_runs):
    _, _, _, eng_on = obs_on_off_runs
    em = eng_on.last_metrics
    s = em.summary()["speculation"]
    assert s["sel_pages"] > 0
    assert 0 <= s["spec_hit_pages"] <= s["sel_pages"]
    assert s["churn_pages"] == pytest.approx(s["sel_pages"]
                                             - s["spec_hit_pages"])
    assert 0.0 <= s["hit_rate_mean"] <= 1.0
    assert 0.0 <= s["correction_rate_mean"] <= 1.0
    # speculative hits == resident-buffer reuse hits (same mask, by
    # construction: match_resident against the previous selection)
    assert em.spec_hit_pages == pytest.approx(em.reused_pages)
    # per-step histograms populated, values inside the rate range
    assert s["hit_rate"]["count"] > 0
    assert 0.0 <= s["hit_rate"]["min"] <= s["hit_rate"]["max"] <= 1.0


def test_obs_off_skips_histograms(obs_on_off_runs):
    _, eng_off, _, eng_on = obs_on_off_runs
    off = eng_off.last_metrics.summary()
    on = eng_on.last_metrics.summary()
    assert off["speculation"]["hit_rate"]["count"] == 0
    assert on["speculation"]["hit_rate"]["count"] > 0
    assert off["latency"]["decode_step_s"]["count"] == 0
    assert on["latency"]["decode_step_s"]["count"] > 0
    # request-latency histograms record regardless (finish-time accounting)
    assert on["latency"]["ttft_s"]["count"] == on["completed"]


def test_engine_snapshot_valid_and_exports(obs_on_off_runs, tmp_path):
    _, _, _, eng_on = obs_on_off_runs
    reg = eng_on.last_metrics.registry
    assert validate_snapshot(reg.snapshot()) == []
    text = reg.to_prometheus()
    assert "engine_steps_total" in text
    assert "spec_hit_rate_bucket" in text
    path = tmp_path / "m.jsonl"
    reg.write_jsonl(str(path))
    assert validate_snapshot(json.loads(path.read_text())) == []


def test_engine_trace_perfetto_wellformed(obs_on_off_runs, tmp_path):
    _, _, _, eng_on = obs_on_off_runs
    tr = eng_on.obs.trace
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    em = eng_on.last_metrics
    for required in (SPAN_REQUEST_QUEUED, SPAN_REQUEST_PREFILL,
                     SPAN_REQUEST_DECODE, SPAN_DECODE_WINDOW, SPAN_LANES,
                     SPAN_DISPATCH, SPAN_SYNC_WAIT, SPAN_PULL, SPAN_APPLY,
                     COUNTER_RECALL_PAGES):
        assert required in names, required
    # nothing is modelled or split out of a window any more
    assert not names & {"engine/decode_step", "recall/topup",
                        "recall/staged"}
    # the page counter track sums to the engine's exact page counts
    pages = [e["args"] for e in doc["traceEvents"]
             if e["name"] == COUNTER_RECALL_PAGES and e["ph"] == "C"]
    for k in ("sync_pages", "async_pages", "reused_pages"):
        assert sum(p[k] for p in pages) == pytest.approx(getattr(em, k))
    # one window span per host sync, each holding its children
    ev = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    wins = [e for e in ev if e["name"] == SPAN_DECODE_WINDOW]
    assert len(wins) == em.host_syncs == len(pages)
    assert sum(w["args"]["steps"] for w in wins) == em.steps
    for child in (SPAN_LANES, SPAN_DISPATCH, SPAN_SYNC_WAIT, SPAN_PULL,
                  SPAN_APPLY):
        kids = [e for e in ev if e["name"] == child]
        assert len(kids) == len(wins), child
        for w, k in zip(wins, kids):
            assert w["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= w["ts"] + w["dur"]
    out = tmp_path / "t.json"
    tr.write(str(out))
    assert validate_chrome_trace(json.loads(out.read_text())) == []


def test_engine_spans_land_in_the_profiler_trace(obs_on_off_runs, tmp_path):
    """With observability off, a ``jax.profiler`` trace still holds the
    engine loop's spans: every window's spans on one host line (the
    engine thread), its children nested inside it, one sync wait per host
    sync — and the run's tokens unchanged by the profiler."""
    import glob
    import os
    tok_off, eng_off, _, _ = obs_on_off_runs
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # the TraceMe spans alone
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        outs = eng_off.generate(_requests(eng_off.cfg))
    finally:
        jax.profiler.stop_trace()
    em = eng_off.last_metrics
    assert [c.tokens for c in outs] == tok_off
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    window_spans = (SPAN_DECODE_WINDOW, SPAN_LANES, SPAN_DISPATCH,
                    SPAN_SYNC_WAIT, SPAN_PULL, SPAN_APPLY)
    found = {}                      # span name -> [(line, start, end)]
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in window_spans:
                    found.setdefault(e.name, []).append(
                        ((plane.name, i), e.start_ns, e.end_ns))
    lines = {ln for evs in found.values() for ln, _, _ in evs}
    assert len(lines) == 1, lines
    assert len(found[SPAN_SYNC_WAIT]) == em.host_syncs > 0
    wins = sorted(found[SPAN_DECODE_WINDOW], key=lambda w: w[1])
    assert len(wins) == em.host_syncs
    for child in (SPAN_DISPATCH, SPAN_SYNC_WAIT, SPAN_PULL, SPAN_APPLY):
        kids = sorted(found[child], key=lambda k: k[1])
        assert len(kids) == len(wins), child
        for (_, ws, we), (_, ks, ke) in zip(wins, kids):
            assert ws <= ks and ke <= we, child


def test_engine_metrics_summary_dedup():
    em = EngineMetrics(num_slots=2)
    s = em.summary()
    # satellite: the duplicated top-level byte counters are gone — the
    # recall_overlap section is the single source of truth
    assert "recall_bytes_sync" not in s
    assert "recall_bytes_async" not in s
    assert "exposed_bytes" in s["recall_overlap"]
    assert "hidden_bytes" in s["recall_overlap"]
    # legacy attribute style still works (registry-backed properties)
    em.steps += 3
    em.sync_pages += 1.5
    assert em.steps == 3 and isinstance(em.steps, int)
    assert em.sync_pages == 1.5
