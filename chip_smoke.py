"""Chip smoke test: FreeKV serving on one TPU (or four) through the normal
entry points, at granite-3-8b's published widths.

    python chip_smoke.py              # one chip: kernel parity + serving run
    python chip_smoke.py --chips 4    # four chips: tp=4 vs tp=1 greedy tokens
    python chip_smoke.py --chips 4 --pool-witness  # + tp=4, pool in HBM

Phases (one process; it holds the chip throughout):
  1. persistent compile cache (``repro.launch.compile_cache``);
  2. kernel parity on the chip: each decode-path Pallas kernel against its
     ``kernels/ref.py`` oracle;
  3. serving: ``launch/serve.build_engine`` with the pool in pinned_host
     memory and compiled kernels, 6 requests of 8192-token prompts and 32 new
     tokens over 4 continuous-batching slots.

Weights are random from a seed, in bf16. The model is cut in depth only
(``LAYERS`` of 40, whole layers) so it fits one 16 GB chip: at 24 layers the
rehearsal compile for a described v5e reports 9.97 GB of weights and decode
state arguments of 10.89 GB with 2.24 GB of temporaries.

Without a TPU the script exits non-zero before printing a result. Its last
line on success is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import hashlib
import json
import sys
import time
from pathlib import Path

LAYERS = 24                  # of granite-3-8b's 40; see the module docstring
ARCH = "granite-3-8b"
SERVE_ARGS = [
    "--arch", ARCH, "--method", "freekv", "--page-size", "32",
    "--budget", "2048", "--n-sink", "128", "--n-window", "128",
    "--context", "8192", "--new-tokens", "32", "--batch", "4",
    "--requests", "6", "--scheduler", "continuous", "--dtype", "bfloat16",
    "--offload", "host", "--no-obs",
]
KERNELS_IN_STEP = ("paged_attention", "page_scores", "recall_gather_host",
                   "write_host")


def fail(msg: str) -> None:
    print(f"FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX sees {devs[0].platform} devices only")
    if len(devs) < n_chips:
        fail(f"{n_chips} chips asked for, {len(devs)} present")
    return devs


def check_close(name, got, want, atol, why):
    import numpy as np
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32))))
    print(f"parity {name}: max_abs_diff={err:.3g} (tol {atol:g}: {why})")
    if not err <= atol:
        fail(f"{name} differs from its reference by {err}")


def kernel_parity():
    """Each decode-path kernel, compiled for this chip, against ref.py at the
    smoke's widths (8 KV heads, group 4, d_head 128, 32-token pages)."""
    import jax
    import jax.numpy as jnp
    from repro.core import offload, paging
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 8)
    B, kv, G, d, p, N, n_pool, n_sel = 2, 8, 4, 128, 32, 65, 40, 12
    bf = jnp.bfloat16
    q = jax.random.normal(ks[0], (B, kv, G, d), bf)
    kp = jax.random.normal(ks[1], (B, kv, N, p, d), bf)
    vp = jax.random.normal(ks[2], (B, kv, N, p, d), bf)
    pos = jax.random.randint(ks[3], (B, kv, N, p), -1, N * p)
    cur = jnp.full((B,), N * p - 7, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(q.astype(jnp.float32),
                                       kp.astype(jnp.float32),
                                       vp.astype(jnp.float32), pos, cur,
                                       d ** -0.5)
    check_close("paged_attention",
                ops.paged_attention(q, kp, vp, pos, cur, scale=d ** -0.5,
                                    interpret=False), want, 2e-2,
                "bf16 output: one bf16 step at |o| <= 4 is 1.6e-2; the "
                "softmax runs in f32 in both")

    raw = jax.random.normal(ks[4], (B, n_pool, kv, 2, d), bf)
    summ = jnp.stack([raw.min(axis=3), raw.max(axis=3)], axis=3)
    with jax.default_matmul_precision("highest"):
        want = ref.page_scores_ref(q, summ, d ** -0.5)
    check_close("page_scores",
                ops.page_scores(q, summ, scale=d ** -0.5, interpret=False),
                want, 1e-3, "bf16 products are exact in f32; only the f32 "
                "summation order differs")

    pool = jax.random.normal(ks[5], (B, n_pool, kv, 2, p, d), bf)
    idx = jax.random.randint(ks[6], (B, kv, n_sel), -1, n_pool)
    kr, vr = ref.recall_gather_ref(pool, idx)
    k, v = ops.recall_gather(pool, idx, interpret=False)
    check_close("recall_gather(hbm pool)", jnp.stack([k, v]),
                jnp.stack([kr, vr]), 0.0, "a copy")

    words = offload.to_host_format(pool)
    host = jax.device_put(words, offload.host_sharding_for(words))
    if not offload.is_host(host):
        fail("pinned_host placement did not take")
    view = offload.HostPool(host, None, jnp.dtype(bf), p, d, False)
    k, v = jax.jit(lambda w, i: offload.recall_host(view._replace(words=w),
                                                     i))(host, idx)
    check_close("recall_gather_host(pinned_host pool)", jnp.stack([k, v]),
                jnp.stack([kr, vr]), 0.0, "a DMA copy")

    blk = jax.random.normal(ks[7], (B, kv, 2, p, d), bf)
    tgt = jnp.array([3, 5], jnp.int32)
    done = jnp.array([True, False])
    host = jax.jit(offload.write_page, static_argnums=(4, 5),
                   donate_argnums=(0,))(host, blk, tgt, done, None, False)
    want = pool.at[0, 3].set(blk[0])
    k, v = paging.words_to_kv(jax.device_get(host), bf, p, d)
    check_close("write_host(pinned_host pool)", jnp.stack([k, v]),
                jnp.stack(paging.hnd_to_nhd_kv(want)), 0.0, "a DMA copy")


def serve_args(extra=()):
    from repro.launch import serve
    return serve.make_parser().parse_args(SERVE_ARGS + list(extra))


def cut_config():
    from repro.configs import get_config
    cfg = get_config(ARCH)
    return dataclasses.replace(cfg, n_layers=LAYERS, n_periods=LAYERS)


def decode_step_text(eng) -> str:
    """StableHLO of the engine's own jitted decode window at its live
    shapes (lowering only: nothing runs, nothing is donated)."""
    import jax.numpy as jnp
    B = eng.batch_size
    loop = {"cur": jnp.zeros((B,), jnp.int32),
            "key": jnp.zeros((B, 2), jnp.uint32),
            "count": jnp.zeros((B,), jnp.int32),
            "limit": jnp.ones((B,), jnp.int32),
            "eos": jnp.full((B,), -1, jnp.int32),
            "fin": jnp.ones((B,), bool), "stop_turnover": jnp.asarray(False)}
    return eng._window.lower(eng.params, eng._pool.state, loop).as_text()


def serving_run():
    import jax
    from repro.core.offload import pool_on_host
    from repro.launch import serve

    args = serve_args(["--kernels"])
    cfg = cut_config()
    print(f"depth cut: {ARCH} {LAYERS} of 40 layers (d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size} padded to {cfg.padded_vocab()})")
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compile_s.append(secs)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    eng, _ = serve.build_engine(args, cfg=cfg)
    reqs = serve.make_requests(args, cfg)
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    wall = time.perf_counter() - t0
    on_host = pool_on_host(eng._pool.state)
    print(f"pool_on_host={on_host}")
    if not on_host:
        fail("the KV pool is not in pinned_host memory")
    text = decode_step_text(eng)
    found = {k: k in text for k in KERNELS_IN_STEP}
    calls = text.count("tpu_custom_call")
    print(f"decode step: {calls} tpu_custom_call ops; kernels {found}")
    if calls == 0 or not all(found.values()):
        fail(f"decode step lacks Mosaic kernels: {found}")
    lens = [len(o.tokens) for o in outs]
    corr = [o.stats.get("correction_rate", float("nan")) for o in outs]
    print(f"requests answered: {len(outs)}; tokens per request: {lens}")
    print(f"corr_rate per request: {[round(c, 4) for c in corr]}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"compile_s={sum(compile_s):.1f} wall_s={wall:.1f} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    if len(outs) != len(reqs) or any(n != args.new_tokens for n in lens):
        fail(f"expected {len(reqs)} requests x {args.new_tokens} tokens")
    vocab = cfg.vocab_size
    if any(not 0 <= t < vocab for o in outs for t in o.tokens):
        fail("a generated token is outside the vocabulary")


def tensor_parallel_run(n_chips: int, pool_witness: bool):
    """Greedy tokens of tp=n_chips against tp=1, same config and prompts,
    kernels off (the tp path runs retrieval inside shard_map).

    ``pool_witness`` adds a tp=n_chips run with the pool in HBM (one chip
    cannot hold it there at this depth) and compares it with the host-pool
    tp=n_chips run: the recall copies the same bytes either way, so a
    difference is a fault of the sharded host tier, and none clears it."""
    from repro.launch import serve
    cfg = cut_config()
    print(f"depth cut: {ARCH} {LAYERS} of 40 layers; tp={n_chips} vs tp=1")
    runs = [(1, "host"), (n_chips, "host")]
    if pool_witness:
        runs.append((n_chips, "sim"))
    tokens = {}
    for tp, pool in runs:
        args = serve_args(["--tp", str(tp), "--offload", pool])
        eng, _ = serve.build_engine(args, cfg=cfg)
        outs = eng.generate(serve.make_requests(args, cfg))
        toks = tokens[tp, pool] = [list(o.tokens) for o in outs]
        digest = hashlib.sha1(json.dumps(toks).encode()).hexdigest()
        print(f"tp={tp} offload={pool}: {len(outs)} requests, tokens per "
              f"request {[len(t) for t in toks]}, digest {digest[:12]}")
        del eng, outs
        gc.collect()          # free one run's weights before the next
    same = compare(f"tp={n_chips} vs tp=1", tokens[1, "host"],
                   tokens[n_chips, "host"])
    if pool_witness:
        compare(f"tp={n_chips}: host pool vs HBM pool",
                tokens[n_chips, "host"], tokens[n_chips, "sim"])
    if not same:
        fail("tensor-parallel tokens differ from tp=1")


def compare(what, a, b) -> bool:
    first = [next((i for i, (x, y) in enumerate(zip(ra, rb)) if x != y),
                  None) for ra, rb in zip(a, b)]
    print(f"greedy tokens bit-identical {what}: {a == b}; first differing "
          f"step per request (None = same): {first}")
    return a == b


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--pool-witness", action="store_true",
                    help="four-chip phase: also run tp=4 with the pool in "
                         "HBM and compare it with the host-pool run")
    opts = ap.parse_args()
    chips = opts.chips
    if chips == 1 and opts.pool_witness:
        ap.error("--pool-witness belongs to the four-chip phase")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    devs = require_tpu(chips)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script: {e}")
    print(f"compile cache: {enable_compile_cache()}")
    if chips == 1:
        kernel_parity()
        serving_run()
    else:
        tensor_parallel_run(chips, opts.pool_witness)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
