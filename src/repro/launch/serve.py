"""Serving launcher CLI: drive the continuous-batching engine (admission
queue, per-slot lifecycle, optional radix-trie prefix cache) — or the static
chunked fallback — against any arch + retrieval method, with the overlapped
double-buffered recall pipeline on by default (``--no-overlap`` for the
synchronous reference; outputs are bit-identical either way).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b-smoke \
        --method freekv --context 512 --new-tokens 16 --batch 2 \
        --scheduler continuous --prefix-cache-tokens 4096 --tp 2

``--tp N`` serves tensor-parallel over a 1-D ('model',) mesh: the paged KV
slot pool, host pool (+ quant scales), summaries and selection state shard
per KV-head group, the whole retrieval step runs shard-local, and greedy
outputs are bit-identical to ``--tp 1`` (docs/serving.md).

Prints per-request completions plus ``EngineMetrics.summary()`` (tokens/s,
slot occupancy, TTFT, hidden vs exposed recall transfer). Observability
exporters (docs/observability.md): ``--metrics-out`` appends one JSONL
metrics-registry snapshot per run, ``--prom-out`` writes the Prometheus
text exposition, ``--trace-out`` writes a Chrome-trace/Perfetto JSON of
the request lifecycle + recall-pipeline spans. See ``docs/serving.md``
and ``docs/architecture.md``.
"""
import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import FreeKVConfig
from repro.data.synthetic import needle_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.obs import Observability, TraceRecorder
from repro.serving.engine import Request, ServeEngine
from repro.serving.sampling import SamplerConfig


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b-smoke")
    ap.add_argument("--method", default="freekv")
    ap.add_argument("--context", type=int, default=512)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests (default: one per batch slot)")
    ap.add_argument("--budget", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-sink", type=int, default=0,
                    help="attention-sink tokens kept on device "
                         "(0 = two pages)")
    ap.add_argument("--n-window", type=int, default=0,
                    help="recent-token window kept on device (0 = two pages)")
    ap.add_argument("--tau", type=float, default=0.8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduler", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--prefill-bucket", type=int, default=64)
    ap.add_argument("--prefix-cache-tokens", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: at most N prompt tokens per "
                         "scheduler round, interleaved with decode windows "
                         "(0 = whole-shot; greedy outputs bit-identical)")
    ap.add_argument("--preempt", action="store_true",
                    help="priority preemption: swap the lowest-priority "
                         "running request's KV to host when a strictly "
                         "higher-priority request waits for a slot")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the overlapped recall pipeline (use the "
                         "synchronous blocking-recall reference path)")
    ap.add_argument("--kv-quant", choices=("none", "int8", "int4"),
                    default="none",
                    help="quantized host KV tier: store the offloaded pool "
                         "packed with fused dequant-on-recall")
    ap.add_argument("--quant-group-size", type=int, default=0,
                    help="channels per fp32 scale group (0 = per page half)")
    ap.add_argument("--sync-interval", type=int, default=8,
                    help="decode steps dispatched per host synchronization "
                         "(host-sync-free loop; 1 = sync every step)")
    ap.add_argument("--draft-len", type=int, default=0,
                    help="speculative decoding: tokens the on-device bigram "
                         "drafter proposes per verify step (0 = off). One "
                         "batched target pass verifies the drafted block and "
                         "commits the longest greedy-consistent prefix — "
                         "outputs stay bit-identical, steps get wider. "
                         "Requires the continuous scheduler + on-device "
                         "sampling; the engine falls back to 0 otherwise.")
    ap.add_argument("--no-spec-decode", action="store_true",
                    help="force draft_len=0 regardless of --draft-len")
    ap.add_argument("--host-sampling", action="store_true",
                    help="disable on-device sampling (synchronous reference "
                         "path: one host round trip per decode step; greedy "
                         "outputs bit-identical either way)")
    ap.add_argument("--offload", choices=("sim", "host"), default="sim",
                    help="KV pool placement: host = pinned_host memory with "
                         "DMA recall (TPU only; fails where the backend has "
                         "no pinned_host memory), sim = device memory with "
                         "cost-model transfer accounting")
    ap.add_argument("--kernels", action="store_true",
                    help="run decode through the Pallas kernels (paged "
                         "attention, page scoring, recall gather)")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="weight and decode-state dtype")
    ap.add_argument("--kernel-interpret",
                    choices=("auto", "interpret", "compiled"), default="auto",
                    help="Pallas kernel mode: auto = compiled on TPU, "
                         "interpret elsewhere")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards (KV-head-group sharding "
                         "over a 1-D mesh; bit-identical greedy outputs vs "
                         "--tp 1). Under JAX_PLATFORMS=cpu, forces that "
                         "many XLA host devices.")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append a JSONL metrics-registry snapshot "
                         "(counters/gauges/histograms) after the run")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write the Prometheus text exposition after the run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON (request "
                         "lifecycle + recall-pipeline spans)")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable per-step observability histograms/spans "
                         "(registry counters always run)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="engine-level TTFT SLO (ms): completed requests "
                         "are tagged and summary()['slo'] reports "
                         "attainment + goodput (tokens/s from SLO-meeting "
                         "requests)")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="engine-level mean inter-token-latency SLO (ms)")
    ap.add_argument("--serve-http", action="store_true",
                    help="serve the async streaming HTTP front-end instead "
                         "of running a fixed batch: POST /generate (chunked "
                         "NDJSON token stream), GET /metrics (Prometheus), "
                         "GET /stats (sliding-window time series), "
                         "GET /healthz. Ctrl-C to stop.")
    ap.add_argument("--host", default="127.0.0.1",
                    help="HTTP front-end bind address (--serve-http)")
    ap.add_argument("--port", type=int, default=8008,
                    help="HTTP front-end port (--serve-http; 0 = ephemeral)")
    return ap


def build_engine(args, cfg=None):
    """The engine a parsed command line describes: ``cfg`` overrides
    ``--arch`` (e.g. a depth-cut config), weights are random from
    ``PRNGKey(0)``. Returns (engine, observability plane)."""
    cfg = cfg or get_config(args.arch)
    dtype = jnp.dtype(args.dtype)
    # jitted, so only the weights in ``dtype`` ever reach device memory (run
    # eagerly, each f32 draw is a buffer of its own); under --tp they are
    # created replicated on every chip, as tp serving holds them
    on = None
    if args.tp > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_tp_mesh
        on = NamedSharding(make_tp_mesh(args.tp), PartitionSpec())
    params = jax.jit(lambda key: init_params(cfg, key, dtype),
                     out_shardings=on)(jax.random.PRNGKey(0))
    fkv = FreeKVConfig(method=args.method, page_size=args.page_size,
                       budget=args.budget,
                       n_sink=args.n_sink or args.page_size * 2,
                       n_window=args.n_window or args.page_size * 2,
                       tau=args.tau, offload=args.offload,
                       use_kernels=args.kernels,
                       recall_overlap=not args.no_overlap,
                       kv_quant=args.kv_quant,
                       quant_group_size=args.quant_group_size,
                       sync_interval=args.sync_interval,
                       sample_on_device=not args.host_sampling,
                       prefill_chunk_tokens=args.prefill_chunk,
                       preempt=args.preempt,
                       kernel_interpret=args.kernel_interpret,
                       draft_len=0 if args.no_spec_decode else args.draft_len)
    if args.no_obs:
        obs = Observability.off()
    else:
        from repro.obs import TimeSeriesBoard
        obs = Observability(
            enabled=True,
            trace=TraceRecorder(enabled=bool(args.trace_out)),
            # the HTTP front-end serves the windowed series at /stats
            timeseries=TimeSeriesBoard() if args.serve_http else None)
    eng = ServeEngine(cfg, fkv, params,
                      max_len=args.context + args.new_tokens + args.page_size
                      + args.prefill_bucket,
                      batch_size=args.batch,
                      sampler=SamplerConfig(temperature=args.temperature),
                      scheduler=args.scheduler,
                      prefill_bucket=args.prefill_bucket,
                      prefix_cache_tokens=args.prefix_cache_tokens,
                      state_dtype=dtype, tp=args.tp, obs=obs,
                      slo_ttft_ms=args.slo_ttft_ms,
                      slo_itl_ms=args.slo_itl_ms)
    return eng, obs


def make_requests(args, cfg):
    """The fixed batch the command line asks for: ``--requests`` needle
    prompts of ``--context`` tokens, ``--new-tokens`` each."""
    stream = needle_stream(cfg.vocab_size, args.context, args.page_size)
    return [Request(uid=i, tokens=next(stream).tokens,
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests or args.batch)]


def main():
    args = make_parser().parse_args()
    if args.tp > 1 and os.environ.get("JAX_PLATFORMS", "") == "cpu" and \
            "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # CPU only: give the host platform one device per shard; must
        # happen before jax initializes its backends
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.tp}")
    enable_compile_cache()
    eng, obs = build_engine(args)

    if args.serve_http:
        from repro.serving.frontend import (EngineService, HttpFrontend,
                                            run_http_frontend)
        svc = EngineService(eng, seed=0).start()
        fe = HttpFrontend(svc, args.host, args.port)
        print(f"serving {args.arch}/{args.method} on "
              f"http://{args.host}:{args.port} "
              "(POST /generate, GET /metrics /stats /healthz)")
        try:
            run_http_frontend(svc, args.host, args.port, frontend=fe)
        finally:
            svc.stop()
            em = eng.last_metrics
            if em is not None:
                _finish_run(args, em, obs)
        return

    for out in eng.generate(make_requests(args, eng.cfg)):
        steps = max(out.steps, 1)
        print(f"req {out.uid}: {out.tokens}")
        print(f"  prefill {out.prefill_s*1e3:.1f} ms | "
              f"decode {out.decode_s/steps*1e3:.1f} ms/step | "
              f"corr_rate {out.stats.get('correction_rate', 0):.3f}")
    em = eng.last_metrics
    if em is not None:
        _finish_run(args, em, obs)


def _finish_run(args, em, obs):
    """End-of-run reporting shared by batch mode and --serve-http."""
    print(json.dumps(em.summary(), indent=2, default=str))
    sd = em.specdec_summary()
    if sd["draft_len"] > 0:
        print(f"spec-decode (draft_len={sd['draft_len']}): accept rate "
              f"{sd['accept_rate']:.3f} | {sd['tokens_per_step']:.2f} tokens "
              f"per target step over {sd['verify_steps']} verify steps")
    slo = em.slo_summary()
    if slo["tagged"]:
        print(f"SLO (ttft<={slo['ttft_ms']}ms, itl<={slo['itl_ms']}ms): "
              f"{slo['attained']}/{slo['tagged']} attained "
              f"({slo['attainment']:.1%}) | goodput "
              f"{slo['goodput_tokens_per_s']:.1f} tok/s "
              f"(total {em.tokens_per_s:.1f} tok/s)")
    if args.metrics_out:
        em.registry.write_jsonl(args.metrics_out,
                                extra={"arch": args.arch,
                                       "method": args.method,
                                       "tp": args.tp})
        print(f"metrics snapshot appended to {args.metrics_out}")
    if args.prom_out:
        with open(args.prom_out, "w", encoding="utf-8") as f:
            f.write(em.registry.to_prometheus())
        print(f"prometheus exposition written to {args.prom_out}")
    if args.trace_out and obs.trace.enabled:
        obs.trace.write(args.trace_out)
        print(f"trace written to {args.trace_out} "
              f"({len(obs.trace.events)} events)")


if __name__ == "__main__":
    main()
