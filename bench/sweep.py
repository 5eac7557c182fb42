"""Sweep an open-loop cell's arrival rate on the chip, to find the highest
rate the system sustains (the knee), once, when the cell is defined.

    python3 bench/sweep.py --workload <cell> --rates 0.5 1 2 --seconds 30

One engine, one window per rate (same seed, the mix with ``rate_per_s``
replaced). Each window opens after the mix's ``lead_s`` of arrivals, so it
measures steady occupancy when ``lead_s`` is about one request's life. Per
rate it prints the tokens per second delivered, the time to first token
(median and 95th percentile over requests due in the window), and the
backlog: requests due in the window that had not started their prefill
when it closed. The knee is the highest rate whose backlog stays near zero
and whose tails do not grow with the window. Not part of a benchmark run.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    from bench.lib import harness
    cell, conf, _, _ = harness.cell_spec(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    from bench.lib import serve, stats, traffic, weights
    model = json.loads((ROOT / conf["file"]).read_text())
    base = traffic.load(cell["traffic"])
    params = weights.make_params(model, a.seed)
    engine = serve.build_engine(model, base, params)
    for rate in a.rates:
        mix = dict(base, rate_per_s=rate)
        plan = traffic.plan(mix, a.seed, model["vocab_size"],
                            traffic.window_requests(mix, a.seconds))
        w = serve.run(engine, mix, plan, a.seconds, time.perf_counter())
        due = stats.due_in_window(w)
        started = [r for r in due if r.queue_wait_s is not None
                   and r.due + r.queue_wait_s < w.t_end]
        tt = stats.ttfts(w)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "backlog_at_close": len(due) - len(started),
            "output_tok_s": stats.tokens_in_window(w) / a.seconds,
            "ttft_ms_p50": 1e3 * stats.percentile(tt, 50),
            "ttft_ms_p95": 1e3 * stats.percentile(tt, 95),
            "finished": len(stats.finished_in_window(w)),
            "late_s": w.late_s, "compiles": w.compiles}), flush=True)


if __name__ == "__main__":
    main()
