"""One run of one cell: set up, measure the window, check, report.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic mix
(``bench/traffic/<traffic>.json``), its limits (``bench/limits/<cell>.json``)
and one reader per metric (``bench/metrics/<metric>.py``).
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[2]
SPEC = ROOT / "BENCHMARK.json"


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def cell_spec(name: str, spec_path: Path = SPEC):
    """(cell, configuration entry, end-to-end metrics, per-layer metrics)
    of the named cell, each metric list holding the entries it reports."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return cell, conf, mine(spec["end_to_end"]), mine(spec["per_layer"])


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        fail("no accelerator: JAX sees CPU devices only")
    if len(devs) < n:
        fail(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs


def enable_compile_cache():
    """JAX's persistent cache at a fixed path: ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``.jax_cache`` in the checkout.
    Every program is cached, however fast it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@dataclass
class Run:
    """What a metric reader sees."""
    model: dict
    mix: dict
    window: object                    # bench.lib.serve.Window
    trace: object                     # bench.lib.trace.Trace or None
    peaks: dict


def measure(model: dict, mix: dict, seed: int, seconds: float,
            traced: bool, t0: float, offload: str = "host",
            kernels: bool = True):
    """Set up and measure one window; returns (Window, params, prompts,
    engine). The caller frees the engine before the reference runs."""
    import jax

    from bench.lib import serve, traffic, weights
    t = time.perf_counter()
    params = weights.make_params(model, seed)
    jax.block_until_ready(params)
    print(f"set-up: weights {time.perf_counter() - t:.3f} s (process "
          f"start to here {time.perf_counter() - t0:.3f} s)", file=sys.stderr)
    engine = serve.build_engine(model, mix, params, offload=offload,
                                kernels=kernels)
    plan = traffic.plan(mix, seed, model["vocab_size"],
                        traffic.window_requests(mix, seconds))
    prompts = {p.index: p.prompt for p in plan}
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    window = serve.run(engine, mix, plan, seconds, t0,
                       trace_seconds=float(mix.get("trace_seconds", 0)),
                       trace_dir=tdir)
    return window, params, prompts, engine


def judge(window, params, prompts, model, mix, limits: dict, seed: int):
    """(correct, attempted, failed, checks) of a measured window."""
    import numpy as np

    from bench.lib import check, stats
    due = stats.due_in_window(window)
    first = [r for r in window.records.values() if r.due < window.t_start]
    attempted = due + first
    failed = sum(1 for r in attempted if r.error or r.first_t is None)
    reqs = check.sample(window.records, int(mix["sample_requests"]), seed)
    gaps = check.logit_gaps(params, model, mix, reqs, prompts)
    gap = float(max((float(np.max(g)) for g in gaps), default=np.inf))
    checks = {"failed": {"value": failed, "limit": 0},
              "logit_gap_max": {"value": gap,
                                "limit": limits["logit_gap_max"]},
              "sampled_tokens": {"value": sum(len(r.tokens) for r in reqs),
                                 "limit": 1}}
    ok = (failed == 0 and gap <= limits["logit_gap_max"]
          and checks["sampled_tokens"]["value"] >= 1)
    return ok, len(attempted), failed, checks


def report(run: Run, metrics: list) -> dict:
    """Each metric's reader applied to the run; those that find nothing to
    read are left out."""
    out = {}
    for m in metrics:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None, t0: Optional[float] = None):
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0:
        fail("--seed must be a whole number >= 0")
    cell, conf, e2e, per_layer = cell_spec(a.workload)
    devs = require_chips(cell["chips"])
    enable_compile_cache()

    from bench.lib import check, peaks, traffic
    from bench.lib import trace as trace_mod
    model = json.loads((ROOT / conf["file"]).read_text())
    mix = traffic.load(cell["traffic"])
    pk = peaks.peaks(devs[0].device_kind)
    limits = check.limits(cell["name"])

    window, params, prompts, engine = measure(
        model, mix, a.seed, a.seconds, bool(a.trace), t0)
    print(f"compiles in window: {window.compiles}", file=sys.stderr)
    if mix["loop"] == "open":
        print(f"sender lateness, worst: {window.late_s:.6f} s",
              file=sys.stderr)
    mem = (devs[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    engine._pool = None              # the slot pool's device and host state
    del engine
    gc.collect()

    tr = None
    if window.trace_dir:
        tr = trace_mod.load(window.trace_dir)
        shutil.rmtree(window.trace_dir, ignore_errors=True)
    run = Run(model, mix, window, tr, pk)
    metrics = report(run, per_layer if a.trace else e2e)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    breakdown = None
    if a.trace and tr is not None:
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": trace_mod.top_ops(tr),
                     "idle_gaps": trace_mod.top_gaps(tr)}
    del tr, run

    correct, attempted, failed, checks = judge(
        window, params, prompts, model, mix, limits, a.seed)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compiles_in_window"] = window.compiles
    line["checks"] = checks
    print(json.dumps(line))
