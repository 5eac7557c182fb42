"""Observability overhead + exporter-validity benchmark.

Runs the same decode-dominated continuous-batching workload twice on the
host-sync-free loop (``sync_interval=8``):

* **obs off** — ``Observability.off()``: registry counters only (they are
  the engine's bookkeeping and always run), no histograms, no trace.
* **obs on (full)** — per-step latency + speculation-quality histograms
  AND the Chrome-trace/Perfetto recorder capturing the request lifecycle,
  the engine loop's spans and the per-window page / speculation counts.

Gated results (``tools/check_bench.py``):

* **bit_identical** — greedy token streams must match exactly: telemetry
  is pulled from ``decode_window``'s device-side stat blocks at sync
  boundaries and never touches the math.
* **overhead_ok** — full observability costs <= 5% tokens/s (best-of-N
  walls; the raw fraction is recorded but never gated — runners differ).
* **nonsync_bytes_per_step == 0** and **host_syncs_equal** — turning
  observability on adds ZERO host syncs and zero bytes between sync
  points: speculation telemetry rides the existing (k, B) stat blocks.
* **trace_valid / snapshot_valid** — the emitted trace JSON is
  well-formed Chrome-trace (loads in Perfetto) and the metrics snapshot
  matches the schema in docs/observability.md; both are also written to
  ``--artifacts`` for CI upload.

    PYTHONPATH=src python benchmarks/obs_overhead.py [--smoke]
        [--artifacts DIR]

Writes the ``BENCH_obs.json`` trajectory file (schema: _common.bench_json).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import FreeKVConfig  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.obs import (Observability, TraceRecorder,  # noqa: E402
                       validate_chrome_trace, validate_snapshot)
from repro.serving.engine import Request, ServeEngine  # noqa: E402
from repro.serving.sampling import SamplerConfig  # noqa: E402

SMOKE = dict(arch="granite-3-8b-smoke", context=64, requests=4, slots=2,
             new_tokens=48, page_size=8, budget=48, repeats=5)
FULL = dict(arch="granite-3-8b-smoke", context=256, requests=8, slots=4,
            new_tokens=96, page_size=16, budget=96, repeats=5)

OVERHEAD_BUDGET = 0.05


def make_requests(cfg, context, n, new_tokens, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        context).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n)]


def run(arch, context, requests, slots, new_tokens, page_size, budget,
        repeats, artifacts=None, quiet=False):
    cfg = get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    fkv = FreeKVConfig(method="freekv", page_size=page_size, budget=budget,
                       n_sink=page_size, n_window=page_size, tau=0.8,
                       sync_interval=8)
    max_len = context + new_tokens + page_size
    mk = lambda: make_requests(cfg, context, requests, new_tokens)  # noqa: E731

    best, tokens, engines = {}, {}, {}
    for mode in ("off", "on"):
        obs = (Observability.off() if mode == "off" else
               Observability(enabled=True, trace=TraceRecorder(enabled=True)))
        engines[mode] = ServeEngine(cfg, fkv, params, max_len=max_len,
                                    batch_size=slots,
                                    sampler=SamplerConfig(temperature=0.0),
                                    scheduler="continuous", obs=obs)
        engines[mode].generate(mk())            # warmup: compile all shapes
    # interleave the timed repeats (off, on, off, on, ...) and take the
    # best wall per mode: drifting background load on shared CI runners
    # then hits both modes alike instead of biasing one phase
    for _ in range(repeats):
        for mode in ("off", "on"):
            eng = engines[mode]
            if mode == "on":
                # fresh recorder so the artifact trace covers one run
                eng.obs.trace = TraceRecorder(enabled=True)
            outs = eng.generate(mk())
            s = eng.last_metrics.summary()
            if mode not in best or s["wall_s"] < best[mode]["wall_s"]:
                best[mode] = s
            tokens[mode] = [c.tokens for c in outs]
    if not quiet:
        for mode in ("off", "on"):
            print(f"  obs={mode:3s} tok/s={best[mode]['tokens_per_s']:8.2f} "
                  f"wall={best[mode]['wall_s']:6.3f}s "
                  f"host_syncs={best[mode]['dispatch']['host_syncs']}")

    on, off = best["on"], best["off"]
    overhead = on["wall_s"] / max(off["wall_s"], 1e-9) - 1.0
    em_on = engines["on"].last_metrics
    obs_on = engines["on"].obs

    snap = em_on.registry.snapshot()
    snap_errs = validate_snapshot(snap)
    trace_doc = obs_on.trace.chrome_trace()
    trace_errs = validate_chrome_trace(trace_doc)
    if artifacts:
        os.makedirs(artifacts, exist_ok=True)
        em_on.registry.write_jsonl(os.path.join(artifacts,
                                                "obs_metrics.jsonl"),
                                   extra={"arch": arch, "bench": "obs"})
        with open(os.path.join(artifacts, "obs_metrics.prom"), "w",
                  encoding="utf-8") as f:
            f.write(em_on.registry.to_prometheus())
        obs_on.trace.write(os.path.join(artifacts, "obs_trace.json"))
        if not quiet:
            print(f"  artifacts -> {artifacts}/ (obs_metrics.jsonl, "
                  "obs_metrics.prom, obs_trace.json)")

    spec = on["speculation"]
    metrics = {
        "bit_identical": tokens["on"] == tokens["off"],
        "tokens_per_s_off": off["tokens_per_s"],
        "tokens_per_s_on": on["tokens_per_s"],
        "overhead_frac": overhead,
        "overhead_ok": overhead <= OVERHEAD_BUDGET,
        "host_syncs_off": off["dispatch"]["host_syncs"],
        "host_syncs_on": on["dispatch"]["host_syncs"],
        "host_syncs_equal": (on["dispatch"]["host_syncs"]
                             == off["dispatch"]["host_syncs"]),
        "nonsync_bytes_per_step": on["dispatch"]["nonsync_bytes_per_step"],
        "trace_valid": not trace_errs,
        "trace_events": len(trace_doc["traceEvents"]),
        "snapshot_valid": not snap_errs,
        "spec_hit_rate_count": spec["hit_rate"]["count"],
        "spec_hit_rate_mean": spec["hit_rate_mean"],
        "correction_rate_count": spec["correction_rate"]["count"],
        "decode_step_count": on["latency"]["decode_step_s"]["count"],
    }
    if trace_errs and not quiet:
        print(f"  trace errors: {trace_errs[:5]}")
    if snap_errs and not quiet:
        print(f"  snapshot errors: {snap_errs[:5]}")
    return metrics


def main():
    from _common import bench_json
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run — still writes BENCH_obs.json")
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="write metrics snapshot (JSONL + Prometheus) and "
                         "trace JSON here for CI artifact upload")
    ap.add_argument("--no-json", action="store_true")
    args = ap.parse_args()
    config = dict(SMOKE) if args.smoke else dict(FULL)
    print("== observability overhead: obs off vs full (hist + trace) ==")
    res = run(**config, artifacts=args.artifacts)
    ok = (res["bit_identical"] and res["overhead_ok"]
          and res["host_syncs_equal"] and res["nonsync_bytes_per_step"] == 0
          and res["trace_valid"] and res["snapshot_valid"])
    print(f"bit_identical={res['bit_identical']} "
          f"overhead={res['overhead_frac']*100:+.1f}% "
          f"(budget {OVERHEAD_BUDGET*100:.0f}%) "
          f"host_syncs_equal={res['host_syncs_equal']} "
          f"nonsync_B/step={res['nonsync_bytes_per_step']:.1f} "
          f"trace_valid={res['trace_valid']} "
          f"snapshot_valid={res['snapshot_valid']} "
          f"[{'PASS' if ok else 'FAIL'}]")
    if not args.no_json:
        bench_json("obs", config, res)
    if not ok:
        sys.exit(1)
    return res


if __name__ == "__main__":
    main()
