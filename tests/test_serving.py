"""Serving engine end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import FreeKVConfig
from repro.models.model import init_params
from repro.serving.engine import Request, ServeEngine
from repro.serving.sampling import SamplerConfig


def test_engine_generates_batched():
    cfg = get_config("smollm-360m-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    fkv = FreeKVConfig(method="freekv", page_size=8, budget=64, n_sink=8,
                       n_window=8, tau=0.8)
    eng = ServeEngine(cfg, fkv, params, max_len=256, batch_size=2)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 64).astype(np.int32)
    reqs = [Request(uid=i, tokens=prompt, max_new_tokens=8) for i in range(3)]
    outs = eng.generate(reqs)
    assert len(outs) == 3
    for o in outs:
        assert len(o.tokens) == 8
        assert all(0 <= t < cfg.padded_vocab() for t in o.tokens)
        assert o.decode_s > 0 and o.prefill_s > 0
        assert 0.0 <= o.stats["correction_rate"] <= 1.0


def test_engine_deterministic_greedy():
    cfg = get_config("smollm-360m-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    fkv = FreeKVConfig(method="full", page_size=8, budget=64, n_sink=8, n_window=8)
    eng = ServeEngine(cfg, fkv, params, max_len=128, batch_size=1,
                      sampler=SamplerConfig(temperature=0.0))
    prompt = np.arange(40, dtype=np.int32) % cfg.vocab_size
    a = eng.generate([Request(uid=0, tokens=prompt, max_new_tokens=6)])[0]
    b = eng.generate([Request(uid=1, tokens=prompt, max_new_tokens=6)])[0]
    assert a.tokens == b.tokens


def test_method_consistency_full_vs_freekv_bigbudget():
    """Greedy decode with FreeKV at full budget must match the full cache."""
    cfg = get_config("smollm-360m-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 72).astype(np.int32)
    outs = {}
    for method, budget in [("full", 0), ("freekv", 4096)]:
        fkv = FreeKVConfig(method=method, page_size=8, budget=max(budget, 64),
                           n_sink=8, n_window=8, tau=0.8)
        eng = ServeEngine(cfg, fkv, params, max_len=256, batch_size=1)
        outs[method] = eng.generate(
            [Request(uid=0, tokens=prompt, max_new_tokens=8)])[0].tokens
    assert outs["full"] == outs["freekv"]


def test_serve_cli_builds_kernel_bf16_engine():
    """``launch/serve``'s shared factory (the path ``chip_smoke.py`` drives)
    turns on the Pallas kernels and a bf16 model/state from the CLI."""
    from repro.launch import serve
    args = serve.make_parser().parse_args(
        ["--arch", "smollm-360m-smoke", "--context", "64", "--new-tokens",
         "4", "--batch", "2", "--requests", "3", "--page-size", "8",
         "--budget", "48", "--kernels", "--dtype", "bfloat16", "--no-obs"])
    eng, _ = serve.build_engine(args)
    assert eng.fkv.use_kernels and eng.fkv.offload == "sim"
    assert {leaf.dtype for leaf in jax.tree.leaves(eng.params)} == {
        jnp.dtype(jnp.bfloat16)}
    outs = eng.generate(serve.make_requests(args, eng.cfg))
    assert [len(o.tokens) for o in outs] == [4, 4, 4]


def test_compile_cache_dir(monkeypatch):
    """The environment's cache directory wins and nothing else is set;
    otherwise the cache sits at one fixed, git-ignored path in the
    checkout."""
    from pathlib import Path
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
