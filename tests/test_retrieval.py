"""Core FreeKV invariants + baseline retriever behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FreeKVConfig
from repro.core.retrieval import make_retriever, METHODS

KEY = jax.random.PRNGKey(0)


def _setup(cfg, fkv, B=2, T=96, max_len=128, dtype=jnp.float32):
    kv, d, H = cfg.n_kv_heads, cfg.d_head, cfg.n_heads
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, kv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, kv, d), dtype)
    q_last = jax.random.normal(jax.random.fold_in(KEY, 3), (B, H, d), dtype)
    r = make_retriever(cfg, fkv)
    st = r.init_state(B, max_len, dtype)
    st = r.prefill(st, k, v, q_last)
    return r, st, (k, v, q_last)


def _decode_inputs(cfg, B, t):
    kq = jax.random.fold_in(KEY, 100 + t)
    q = jax.random.normal(kq, (B, cfg.n_heads, cfg.d_head))
    kn = jax.random.normal(jax.random.fold_in(kq, 1), (B, cfg.n_kv_heads, cfg.d_head))
    vn = jax.random.normal(jax.random.fold_in(kq, 2), (B, cfg.n_kv_heads, cfg.d_head))
    return q, kn, vn


def test_freekv_full_budget_exact(smoke_cfg):
    """With budget >= context, FreeKV attention == exact full attention.

    This is THE correctness invariant: the sink/window/selected regions
    partition the context exactly (no double counting, no gaps)."""
    cfg = smoke_cfg
    T = 96
    fkv = FreeKVConfig(method="freekv", page_size=8, budget=T + 64, n_sink=16,
                       n_window=16, tau=0.8)
    r, st, _ = _setup(cfg, fkv, T=T)
    rf, stf, _ = _setup(cfg, FreeKVConfig(method="full"), T=T)
    for t in range(20):
        q, kn, vn = _decode_inputs(cfg, 2, t)
        o, st, _ = r.decode(st, q, kn, vn)
        of, stf, _ = rf.decode(stf, q, kn, vn)
        np.testing.assert_allclose(np.asarray(o), np.asarray(of), atol=2e-5)


def test_freekv_budget_subset_finite(smoke_cfg, small_fkv):
    r, st, _ = _setup(smoke_cfg, small_fkv)
    for t in range(10):
        q, kn, vn = _decode_inputs(smoke_cfg, 2, t)
        o, st, info = r.decode(st, q, kn, vn)
        assert jnp.isfinite(o).all()
        assert info["corrected"].shape == (2, smoke_cfg.n_kv_heads)
    # lengths advance
    assert int(st["length"][0]) == 96 + 10


@pytest.mark.parametrize("method", METHODS)
def test_all_methods_run(smoke_cfg, method):
    fkv = FreeKVConfig(method=method, page_size=8, budget=48, n_sink=8,
                       n_window=8, svd_rank=32)
    r, st, _ = _setup(smoke_cfg, fkv)
    for t in range(4):
        q, kn, vn = _decode_inputs(smoke_cfg, 2, t)
        o, st, info = r.decode(st, q, kn, vn, q_proxy=q)
        assert o.shape == (2, smoke_cfg.n_heads, smoke_cfg.d_head)
        assert jnp.isfinite(o).all(), method


def test_kernel_path_matches_jnp(smoke_cfg):
    outs = {}
    for use_k in (False, True):
        fkv = FreeKVConfig(method="freekv", page_size=8, budget=48, n_sink=8,
                           n_window=8, tau=0.8, use_kernels=use_k)
        r, st, _ = _setup(smoke_cfg, fkv)
        q, kn, vn = _decode_inputs(smoke_cfg, 2, 0)
        o, st, _ = r.decode(st, q, kn, vn)
        outs[use_k] = np.asarray(o)
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-5)


def test_correction_uses_fresh_pages_when_query_jumps(smoke_cfg):
    """A step whose query is orthogonal to the previous one must correct
    (C_i ~ 0 < tau) and therefore attend with freshly selected pages."""
    cfg = smoke_cfg
    fkv = FreeKVConfig(method="freekv", page_size=8, budget=48, n_sink=8,
                       n_window=8, tau=0.8)
    r, st, (k, v, q_last) = _setup(cfg, fkv)
    q, kn, vn = _decode_inputs(cfg, 2, 0)
    o, st, info = r.decode(st, q, kn, vn)
    assert bool(info["corrected"].all())  # random qprev -> corrected
    # identical query next step -> similarity 1 -> no correction
    o2, st2, info2 = r.decode(st, q, kn, vn)
    assert not bool(info2["corrected"].any())


def test_speculative_reuse_matches_arkvale_when_similar(smoke_cfg):
    """If q_i == q_{i-1}, FreeKV's stale pages equal fresh selection, so
    speculative reuse loses nothing vs blocking (ArkVale-style) retrieval."""
    cfg = smoke_cfg
    base = dict(page_size=8, budget=48, n_sink=8, n_window=8, tau=0.8)
    rf, stf, _ = _setup(cfg, FreeKVConfig(method="freekv", **base))
    ra, sta, _ = _setup(cfg, FreeKVConfig(method="arkvale", **base))
    q, kn, vn = _decode_inputs(cfg, 2, 0)
    # step 1 (both correct/recall fresh)
    of1, stf, _ = rf.decode(stf, q, kn, vn)
    oa1, sta, _ = ra.decode(sta, q, kn, vn)
    np.testing.assert_allclose(np.asarray(of1), np.asarray(oa1), atol=2e-5)
    # step 2 with the SAME query: FreeKV reuses, ArkVale re-selects; the
    # selection changed by at most the newly completed pages
    q2 = q + 1e-4 * jax.random.normal(jax.random.fold_in(KEY, 7), q.shape)
    of2, stf, i2 = rf.decode(stf, q2, kn, vn)
    oa2, sta, _ = ra.decode(sta, q2, kn, vn)
    assert not bool(i2["corrected"].any())
    np.testing.assert_allclose(np.asarray(of2), np.asarray(oa2), atol=2e-4)


def test_shadowkv_full_rank_close_to_full(smoke_cfg):
    """ShadowKV with rank == d_head reconstructs keys exactly; with a large
    budget it must match the full-cache oracle."""
    cfg = smoke_cfg
    T = 96
    fkv = FreeKVConfig(method="shadowkv", page_size=8, budget=T + 64,
                       n_sink=16, n_window=16, svd_rank=cfg.d_head)
    r, st, _ = _setup(cfg, fkv, T=T)
    rf, stf, _ = _setup(cfg, FreeKVConfig(method="full"), T=T)
    q, kn, vn = _decode_inputs(cfg, 2, 0)
    o, st, _ = r.decode(st, q, kn, vn)
    of, stf, _ = rf.decode(stf, q, kn, vn)
    np.testing.assert_allclose(np.asarray(o), np.asarray(of), atol=5e-4)


def test_streaming_ignores_middle(smoke_cfg):
    """Streaming output is invariant to middle-context K/V (by construction)."""
    cfg = smoke_cfg
    fkv = FreeKVConfig(method="streaming", page_size=8, budget=32, n_sink=8,
                       n_window=8)
    B, T = 2, 96
    kv, d = cfg.n_kv_heads, cfg.d_head
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, kv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, kv, d))
    k2 = k.at[:, 20:60].set(jax.random.normal(jax.random.fold_in(KEY, 9),
                                              (B, 40, kv, d)))
    q_last = jax.random.normal(jax.random.fold_in(KEY, 3), (B, cfg.n_heads, d))
    r = make_retriever(cfg, fkv)
    outs = []
    for kk in (k, k2):
        st = r.init_state(B, 128, jnp.float32)
        st = r.prefill(st, kk, v, q_last)
        q, kn, vn = _decode_inputs(cfg, B, 0)
        o, st, _ = r.decode(st, q, kn, vn)
        outs.append(np.asarray(o))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


def test_top_p_dynamic_budget(smoke_cfg):
    """top_p=1 ~ static top-k; small top_p selects fewer pages, never zero."""
    import jax
    import jax.numpy as jnp
    from repro.core import selection
    cfg = smoke_cfg
    key = jax.random.PRNGKey(3)
    B, H, d, n_pages = 2, cfg.n_heads, cfg.d_head, 16
    q = jax.random.normal(key, (B, H, d)) * 3
    summ = jax.random.normal(jax.random.fold_in(key, 1),
                             (B, n_pages, cfg.n_kv_heads, 2, d))
    length = jnp.array([16 * 8, 16 * 8])
    base = dict(method="freekv", page_size=8, budget=10 ** 5, n_sink=8,
                n_window=8)
    idx_full, _ = selection.select_pages(
        cfg, FreeKVConfig(**base), q, summ, length, 8)
    idx_p, _ = selection.select_pages(
        cfg, FreeKVConfig(**base, select_top_p=0.5), q, summ, length, 8)
    n_full = int((idx_full >= 0).sum())
    n_p = int((idx_p >= 0).sum())
    assert 0 < n_p <= n_full
    # kept pages are a prefix of the full top-k ranking
    import numpy as np
    a, b = np.asarray(idx_p), np.asarray(idx_full)
    for bi in range(B):
        for h in range(cfg.n_kv_heads):
            kept = a[bi, h][a[bi, h] >= 0]
            np.testing.assert_array_equal(kept, b[bi, h][: len(kept)])


def test_host_offload_placement(smoke_cfg, small_fkv):
    """offload='host' places the pool in pinned_host memory, in the host word
    format, and decode still runs where the backend can compute on it."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core.offload import place_decode_state, pool_bytes, pool_on_host
    # 32-token pages: the host pool's DMA unit is a whole 16 KiB block
    fkv = dataclasses.replace(small_fkv, offload="host", page_size=32,
                              budget=128, n_sink=32, n_window=32)
    r = make_retriever(smoke_cfg, fkv)
    st = r.init_state(2, 128, jnp.float32)
    k = jax.random.normal(KEY, (2, 96, smoke_cfg.n_kv_heads, smoke_cfg.d_head))
    v = jax.random.normal(jax.random.fold_in(KEY, 1), k.shape)
    q_last = jax.random.normal(jax.random.fold_in(KEY, 2),
                               (2, smoke_cfg.n_heads, smoke_cfg.d_head))
    st = r.prefill(st, k, v, q_last)
    st = place_decode_state(st, fkv)
    assert st["pool"].sharding.memory_kind == "pinned_host"
    assert pool_on_host(st)
    assert pool_bytes(st) > 0
    q, kn, vn = _decode_inputs(smoke_cfg, 2, 0)
    try:
        o, st2, _ = r.decode(st, q, kn, vn)
    except ValueError as e:          # backend rejects compute on host buffers
        if "memor" in str(e).lower():
            pytest.skip("host-memory compute unsupported on this backend")
        raise
    assert jnp.isfinite(o).all()
