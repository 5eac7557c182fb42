"""Per-kernel shape/dtype sweeps, assert_allclose vs the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(atol=5e-2, rtol=5e-2) if dt == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,kv,G,N,p,d", [
    (1, 1, 1, 2, 8, 128), (2, 3, 4, 6, 32, 128), (1, 2, 8, 4, 16, 64),
    (3, 4, 2, 5, 32, 256),
])
def test_paged_attention_sweep(B, kv, G, N, p, d, dtype):
    q = jax.random.normal(KEY, (B, kv, G, d), dtype)
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (B, kv, N, p, d), dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), (B, kv, N, p, d), dtype)
    pos = jax.random.randint(jax.random.fold_in(KEY, 3), (B, kv, N, p), -1,
                             N * p)
    cur = jnp.full((B,), N * p, jnp.int32)
    scale = 1.0 / d ** 0.5
    o = ops.paged_attention(q, kp, vp, pos, cur, scale=scale)
    oref = ref.paged_attention_ref(q, kp, vp, pos, cur, scale)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), **_tol(dtype))


def test_paged_attention_softcap():
    B, kv, G, N, p, d = 2, 2, 2, 4, 16, 128
    q = jax.random.normal(KEY, (B, kv, G, d))
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (B, kv, N, p, d))
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), (B, kv, N, p, d))
    pos = jax.random.randint(jax.random.fold_in(KEY, 3), (B, kv, N, p), -1, 60)
    cur = jnp.full((B,), 64, jnp.int32)
    o = ops.paged_attention(q, kp, vp, pos, cur, scale=0.1, softcap=20.0)
    oref = ref.paged_attention_ref(q, kp, vp, pos, cur, 0.1, softcap=20.0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), atol=2e-5)


@pytest.mark.parametrize("N", [8, 5])
def test_paged_attention_blocked(N):
    """A resident set longer than ``max_block`` streams in blocks with an
    online softmax; N=5 leaves a ragged last block, masked by token index."""
    from repro.kernels.paged_attention import paged_attention
    B, kv, G, p, d = 2, 2, 4, 32, 128
    q = jax.random.normal(KEY, (B, kv, G, d))
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), (B, kv, N, p, d))
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), (B, kv, N, p, d))
    pos = jax.random.randint(jax.random.fold_in(KEY, 3), (B, kv, N, p), -1,
                             N * p)
    cur = jnp.array([N * p, N * p // 2], jnp.int32)
    o = paged_attention(q, kp, vp, pos, cur, scale=d ** -0.5,
                        interpret=True, max_block=128)
    oref = ref.paged_attention_ref(q, kp, vp, pos, cur, d ** -0.5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), **_tol(
        jnp.float32))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,T,kv,d,p", [
    (1, 64, 1, 128, 8), (2, 128, 3, 128, 32), (2, 96, 2, 64, 16),
])
def test_page_summary_sweep(B, T, kv, d, p, dtype):
    k = jax.random.normal(KEY, (B, T, kv, d), dtype)
    s = ops.page_summary(k, page_size=p)
    sref = ref.page_summary_ref(k.reshape(B, T // p, p, kv, d))
    np.testing.assert_allclose(np.asarray(s, np.float32),
                               np.asarray(sref, np.float32), atol=0)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,kv,G,d,N", [
    (1, 1, 1, 128, 4), (2, 3, 4, 128, 8), (2, 2, 5, 64, 256),
])
def test_page_scores_sweep(B, kv, G, d, N, dtype):
    q = jax.random.normal(KEY, (B, kv, G, d), dtype)
    raw = jax.random.normal(jax.random.fold_in(KEY, 1), (B, N, kv, 2, d), dtype)
    summ = jnp.stack([jnp.minimum(raw[..., 0, :], raw[..., 1, :]),
                      jnp.maximum(raw[..., 0, :], raw[..., 1, :])], axis=3)
    s = ops.page_scores(q, summ, scale=0.088)
    sref = ref.page_scores_ref(q, summ, 0.088)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sref, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,n_pages,kv,p,d,n_sel", [
    (1, 4, 1, 8, 128, 2), (2, 16, 3, 32, 128, 5), (2, 8, 2, 16, 64, 8),
])
def test_recall_gather_sweep(B, n_pages, kv, p, d, n_sel, dtype):
    pool = jax.random.normal(KEY, (B, n_pages, kv, 2, p, d), dtype)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (B, kv, n_sel), -1,
                             n_pages)
    k, v = ops.recall_gather(pool, idx)
    kr, vr = ref.recall_gather_ref(pool, idx)
    np.testing.assert_allclose(np.asarray(k, np.float32),
                               np.asarray(kr, np.float32), atol=0)
    np.testing.assert_allclose(np.asarray(v, np.float32),
                               np.asarray(vr, np.float32), atol=0)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("B,H,kv,T,d,blk", [
    (1, 2, 1, 128, 128, 64), (2, 6, 3, 256, 64, 128), (1, 4, 4, 128, 128, 128),
])
def test_flash_prefill_sweep(B, H, kv, T, d, blk, dtype):
    q = jax.random.normal(KEY, (B, H, T, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, kv, T, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, kv, T, d), dtype)
    scale = 1.0 / d ** 0.5
    o = ops.flash_prefill(q, k, v, scale=scale, blq=blk, blk=blk)
    oref = ref.flash_prefill_ref(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), **_tol(dtype))


def test_flash_prefill_window():
    B, H, kv, T, d = 1, 2, 2, 256, 64
    q = jax.random.normal(KEY, (B, H, T, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, kv, T, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, kv, T, d))
    o = ops.flash_prefill(q, k, v, scale=0.125, window=64, blq=64, blk=64)
    oref = ref.flash_prefill_ref(q, k, v, 0.125, window=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), atol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("stacked", [False, True])
def test_recall_host_matches_ref(dtype, stacked):
    """The host-pool DMA recall (interpret mode, device-resident words)
    returns exactly ``recall_gather_ref``'s pages, zeros for -1 lanes."""
    from repro.core import offload, paging
    B, n_pages, kv, p, d, n_sel, L = 2, 6, 2, 32, 128, 5, 3
    pools = jax.random.normal(KEY, (L, B, n_pages, kv, 2, p, d), dtype)
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (B, kv, n_sel), -1,
                             n_pages)
    layer = 1
    words = paging.hnd_to_words(pools if stacked else pools[layer])
    view = offload.HostPool(words, jnp.int32(layer) if stacked else None,
                            jnp.dtype(dtype), p, d, True)
    k, v = offload.recall_host(view, idx)
    kr, vr = ref.recall_gather_ref(pools[layer], idx)
    np.testing.assert_array_equal(np.asarray(k, np.float32),
                                  np.asarray(kr, np.float32))
    np.testing.assert_array_equal(np.asarray(v, np.float32),
                                  np.asarray(vr, np.float32))


@pytest.mark.parametrize("stacked", [False, True])
def test_write_host_matches_masked_scatter(stacked):
    """Page offload by DMA writes exactly the rows whose page completed."""
    from repro.core import offload, paging
    from repro.kernels.host_pages import write_slot
    B, n_pages, kv, p, d, L = 3, 5, 2, 32, 128, 2
    pools = jax.random.normal(KEY, (L, B, n_pages, kv, 2, p, d), jnp.bfloat16)
    blk = jax.random.normal(jax.random.fold_in(KEY, 1), (B, kv, 2, p, d),
                            jnp.bfloat16)
    tgt = jnp.array([4, 0, 2], jnp.int32)
    done = jnp.array([True, False, True])
    layer = 1
    words = paging.hnd_to_words(pools if stacked else pools[layer])
    out = offload.write_page(words, blk, tgt, done,
                             layer=jnp.int32(layer) if stacked else None,
                             interpret=True)
    want = pools[layer].at[jnp.arange(B), tgt].set(jnp.where(
        done[:, None, None, None, None], blk,
        pools[layer][jnp.arange(B), tgt]))
    got = out[layer] if stacked else out
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(paging.hnd_to_words(want)))
    # slot splice: a batch-1 row lands in row 1 of every layer
    src = jax.random.normal(jax.random.fold_in(KEY, 2),
                            (L, 1, n_pages, kv, 2, p, d), jnp.bfloat16)
    spliced = write_slot(paging.hnd_to_words(pools), paging.hnd_to_words(src),
                         jnp.int32(1), axis=1, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(spliced),
        np.asarray(paging.hnd_to_words(pools.at[:, 1].set(src[:, 0]))))
