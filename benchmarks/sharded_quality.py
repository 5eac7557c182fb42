import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Quality impact of sharded speculative retrieval (beyond-paper §Perf opt2).

Runs REAL multi-device execution on 8 forced host devices (mesh 1x8 data x
model): shard-local top-(k/8) selection vs global top-k, on the structured
attention process — reports attention-output error vs the full-cache oracle
and the page-selection overlap between the two schemes.

    PYTHONPATH=src python benchmarks/sharded_quality.py
"""
import dataclasses
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import numpy as np


def run(B=8, T=512, steps=32, quiet=False):
    from _common import attention_process
    from repro.configs import get_config
    from repro.configs.base import FreeKVConfig
    from repro.core.retrieval import make_retriever

    cfg = get_config("granite-3-8b-smoke")
    mesh = jax.make_mesh((1, 8), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    p = 16
    # pool pages must divide the model axis: pad via pool_pad_pages
    base = dict(method="freekv", page_size=p, budget=128 + 2 * p,
                n_sink=p, n_window=p, tau=0.8, pool_pad_pages=8)
    key = jax.random.PRNGKey(0)
    k, v, query_walk = attention_process(key, cfg, B, T)
    qs = query_walk(steps)
    rf = make_retriever(cfg, FreeKVConfig(method="full"))
    results = {}
    with mesh:
        for name, shard, os_ in (("global", False, 1), ("sharded", True, 1),
                                 ("sharded+rerank", True, 2)):
            fkv = FreeKVConfig(**base, sharded_retrieval=shard,
                               sharded_overselect=os_)
            r = make_retriever(cfg, fkv, mesh=mesh if shard else None)
            st = r.init_state(B, T + steps + p, jnp.float32)
            st = r.prefill(st, k, v, qs[:, 0])
            stf = rf.init_state(B, T + steps + p, jnp.float32)
            stf = rf.prefill(stf, k, v, qs[:, 0])
            errs, idxs = [], []
            for i in range(1, steps):
                q = qs[:, i]
                kn, vn = k[:, i % T], v[:, i % T]
                o, st, _ = r.decode(st, q, kn, vn)
                of, stf, _ = rf.decode(stf, q, kn, vn)
                err = (jnp.linalg.norm(o - of, axis=-1)
                       / jnp.maximum(jnp.linalg.norm(of, axis=-1), 1e-6))
                errs.append(float(err.mean()))
                idxs.append(np.asarray(st["sel_idx"]))
            results[name] = {"err": float(np.mean(errs)), "idx": idxs[-1]}
    def _overlap(name):
        a, b = results[name]["idx"], results["global"]["idx"]
        ov = []
        for bi in range(B):
            for h in range(cfg.n_kv_heads):
                sa = set(a[bi, h][a[bi, h] >= 0].tolist())
                sb = set(b[bi, h][b[bi, h] >= 0].tolist())
                ov.append(len(sa & sb) / max(len(sb), 1))
        return float(np.mean(ov))
    if not quiet:
        print("name,us_per_call,derived")
        print(f"sharded_quality/global,0.0,out_err={results['global']['err']:.4f}")
        for name in ("sharded", "sharded+rerank"):
            print(f"sharded_quality/{name},0.0,out_err={results[name]['err']:.4f};"
                  f"selection_overlap_vs_global={_overlap(name):.3f}")
    return results, _overlap("sharded")


if __name__ == "__main__":
    run()
