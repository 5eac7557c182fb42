"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --seconds 50

For each seed, in one process and with one engine (new weights per seed,
the same compiled programs): the cell's load for ``--seconds``, then the
check's sample of served requests scored by the float32 reference (the
program's reading) and by the reference in float8 (the control's reading:
the gap of the token the float8 reference puts first, at the same positions
of the same prompts and tokens). One JSON line per seed. Not part of a
benchmark run.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    from bench.lib import harness
    cell, conf, _, _ = harness.cell_spec(a.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    import numpy as np

    from bench.lib import check, serve, traffic, weights
    model = json.loads((ROOT / conf["file"]).read_text())
    mix = traffic.load(cell["traffic"])
    engine = None
    for seed in a.seeds:
        t0 = time.perf_counter()
        params = weights.make_params(model, seed)
        if engine is None:
            engine = serve.build_engine(model, mix, params)
        engine.params = params
        plan = traffic.plan(mix, seed, model["vocab_size"],
                            traffic.window_requests(mix, a.seconds))
        prompts = {p.index: p.prompt for p in plan}
        w = serve.run(engine, mix, plan, a.seconds, t0)
        engine._pool = None          # free the slots before the reference
        reqs = check.sample(w.records, int(mix["sample_requests"]), seed)
        prog = check.logit_gaps(params, model, mix, reqs, prompts)
        ctl = check.logit_gaps(params, model, mix, reqs, prompts,
                               quant="fp8")
        print(json.dumps({
            "seed": seed, "compiles": w.compiles,
            "requests": [[r.uid, r.prompt_len, len(r.tokens)] for r in reqs],
            "program": max(float(np.max(g)) for g in prog),
            "control": max(float(np.max(g)) for g in ctl),
            "program_per_request": [float(np.max(g)) for g in prog],
            "control_per_request": [float(np.max(g)) for g in ctl],
            "seconds": time.perf_counter() - t0}), flush=True)
        del params
        engine.params = None


if __name__ == "__main__":
    main()
