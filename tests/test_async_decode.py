"""Host-sync-free decode loop: donation (no per-step state copies),
sync-interval bit-identity vs the synchronous path, the decode window's
layer scan vs an unrolled per-layer reference, per-slot RNG stream
stability across slot turnover, and host-transfer accounting."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FreeKVConfig
from repro.models import layers as L
from repro.models import model
from repro.models.model import init_params
from repro.serving.engine import Request, ServeEngine
from repro.serving.sampling import SamplerConfig, request_key


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    fkv = FreeKVConfig(method="freekv", page_size=8, budget=64, n_sink=8,
                       n_window=8, tau=0.8)
    return cfg, fkv, params


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _turnover_reqs(cfg, n=5):
    """Mixed lengths over few slots -> slot reuse mid-run."""
    return [Request(uid=i, tokens=_prompt(cfg, 48 + 8 * (i % 2), seed=i),
                    max_new_tokens=3 if i % 2 else 7) for i in range(n)]


def _run(cfg, fkv, params, reqs, batch_size=2, temperature=0.0):
    eng = ServeEngine(cfg, fkv, params, max_len=256, batch_size=batch_size,
                      sampler=SamplerConfig(temperature=temperature),
                      prefill_bucket=64)
    outs = eng.generate(reqs)
    return outs, eng.last_metrics


def _donation_supported():
    f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.zeros((8,))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f(x)
    return x.is_deleted()


# ---------------------------------------------------------------------------
# bit-identity across dispatch modes
# ---------------------------------------------------------------------------
def test_sync_interval_bit_identity(setup):
    """Greedy token streams (and per-request retrieval stats) are identical
    for the synchronous reference path and sync_interval in {1, 4, 8}."""
    cfg, fkv, params = setup
    results = {}
    for name, f in [
            ("sync", dataclasses.replace(fkv, sample_on_device=False)),
            ("k1", dataclasses.replace(fkv, sync_interval=1)),
            ("k4", dataclasses.replace(fkv, sync_interval=4)),
            ("k8", dataclasses.replace(fkv, sync_interval=8))]:
        outs, em = _run(cfg, f, params, _turnover_reqs(cfg))
        results[name] = ([o.tokens for o in outs],
                         [o.stats.get("correction_rate", 0.0) for o in outs])
        assert em.slot_occupancy > 0
    ref_tokens, ref_stats = results["sync"]
    for name, (tokens, stats) in results.items():
        assert tokens == ref_tokens, f"{name} diverged from sync path"
        assert np.allclose(stats, ref_stats), f"{name} stats diverged"


def test_eos_stops_mid_window(setup):
    """An eos sampled mid-window truncates exactly as the per-step path."""
    cfg, fkv, params = setup
    prompt = _prompt(cfg, 64, seed=5)
    full, _ = _run(cfg, dataclasses.replace(fkv, sample_on_device=False),
                   params, [Request(uid=0, tokens=prompt, max_new_tokens=8)],
                   batch_size=1)
    eos = full[0].tokens[2]
    cut = full[0].tokens.index(eos) + 1
    outs, _ = _run(cfg, dataclasses.replace(fkv, sync_interval=8), params,
                   [Request(uid=0, tokens=prompt, max_new_tokens=8,
                            eos_token=eos)], batch_size=1)
    assert outs[0].tokens == full[0].tokens[:cut]
    assert outs[0].tokens[-1] == eos


# ---------------------------------------------------------------------------
# the layer scan's in-place carry vs an unrolled per-layer reference
# ---------------------------------------------------------------------------
def _per_layer_step(cfg, fkv, params, state, tok):
    """One greedy decode step with the layer loop unrolled: each layer's
    state is a plain slice of the stack and the new stack is rebuilt by
    ``jnp.stack`` (no scan carry, no ``offload.layer_state``)."""
    assert not cfg.prelude
    x = L.embed_tokens(cfg, params["embed"], tok[:, None])
    pos = state["pos"]
    _, pat_r = model._retrievers(cfg, fkv)
    q_proxy = jnp.zeros((x.shape[0], cfg.n_heads, cfg.d_head), x.dtype)
    layers = [[] for _ in cfg.pattern]
    for i in range(cfg.n_periods):
        for pos_i, lk in enumerate(cfg.pattern):
            lp = jax.tree.map(lambda a: a[i], params["pattern"][pos_i])
            st = jax.tree.map(lambda a: a[i], state["pattern"][pos_i])
            x, st, q_proxy, _ = model._apply_layer_decode(
                cfg, fkv, lk, pat_r[pos_i], lp, x, pos, st, None, q_proxy)
            layers[pos_i].append(st)
    pattern = tuple(
        jax.tree.map(lambda a, *ls: jnp.stack(ls).astype(a.dtype), old, *new)
        for old, new in zip(state["pattern"], layers))
    x = L.apply_norm(cfg, params["final_norm"], x)
    tok = jnp.argmax(L.lm_logits(cfg, params["embed"], x[:, -1]), -1)
    return tok.astype(jnp.int32), dict(state, pattern=pattern, pos=pos + 1)


@pytest.mark.parametrize("arch,method,overlap", [
    ("smollm-360m-smoke", "freekv", True),
    ("smollm-360m-smoke", "freekv", False),
    ("smollm-360m-smoke", "arkvale", True),
    ("smollm-360m-smoke", "quest", True),
    ("xlstm-350m-smoke", "freekv", True)])
def test_decode_window_matches_per_layer_reference(arch, method, overlap):
    """Greedy tokens and the whole returned state of a decode window over
    three layers are bit-identical to the unrolled per-layer reference: the
    scan's in-place carry, and how the retriever reads its old layer, change
    no value."""
    base = get_config(arch)
    cfg = dataclasses.replace(base, n_periods=3,
                              n_layers=3 * len(base.pattern))
    fkv = FreeKVConfig(method=method, page_size=8, budget=64, n_sink=8,
                       n_window=8, tau=0.8, recall_overlap=overlap)
    params = init_params(cfg, jax.random.PRNGKey(1))
    B, steps = 2, 6
    prompt = np.stack([_prompt(cfg, 96, seed=s) for s in range(B)])
    logits, state = model.prefill(cfg, fkv, params,
                                  {"tokens": jnp.asarray(prompt)}, 256)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    loop = {"cur": tok, "key": jnp.zeros((B, 2), jnp.uint32),
            "count": jnp.zeros(B, jnp.int32),
            "limit": jnp.full((B,), steps, jnp.int32),
            "eos": jnp.full((B,), -1, jnp.int32),
            "fin": jnp.zeros(B, bool), "stop_turnover": jnp.asarray(False)}
    got_state, _, toks, valid, _, n = jax.jit(
        lambda s, lp: model.decode_window(cfg, fkv, params, s, lp,
                                          SamplerConfig(), k_max=steps))(
        state, loop)
    assert int(n) == steps and bool(jnp.all(valid))

    ref_step = jax.jit(lambda s, t: _per_layer_step(cfg, fkv, params, s, t))
    ref_toks = []
    for _ in range(steps):
        tok, state = ref_step(state, tok)
        ref_toks.append(tok)
    np.testing.assert_array_equal(np.asarray(toks), np.stack(ref_toks))
    jax.tree_util.tree_map_with_path(
        lambda path, a, b: np.testing.assert_array_equal(
            a, b, err_msg=jax.tree_util.keystr(path)), got_state, state)


# ---------------------------------------------------------------------------
# donation: the slot pool is updated in place, never copied
# ---------------------------------------------------------------------------
def test_no_per_step_copy_of_slot_pool(setup):
    """The decode window DONATES state + loop carry: the previous step's
    pool buffers are consumed (deleted), and the live-buffer census stays
    flat across windows — no shadow copy of the slot pool anywhere."""
    cfg, fkv, params = setup
    if not _donation_supported():
        pytest.skip("backend does not implement buffer donation")
    eng = ServeEngine(cfg, fkv, params, max_len=256, batch_size=2,
                      sampler=SamplerConfig(temperature=0.0))
    pool = eng.make_slot_pool(2)
    req = Request(uid=0, tokens=_prompt(cfg, 64), max_new_tokens=32)
    logits1, s1, _, _ = eng.prefill_one(req)
    slot = pool.alloc(0)
    pre_splice = jax.tree.leaves(pool.state)
    pool.insert(s1, slot)
    # SlotPool splice donated the old full-batch state (in-place update)
    assert all(leaf.is_deleted() for leaf in pre_splice)

    tok = int(np.asarray(eng.sample_slot(logits1, request_key(0, 0), 0))[0])
    loop = {"cur": jnp.asarray(np.array([tok, 0], np.int32)),
            "key": jnp.tile(jnp.asarray(request_key(0, 0))[None], (2, 1)),
            "count": jnp.ones(2, jnp.int32),
            "limit": jnp.asarray(np.array([32, 1], np.int32)),
            "eos": jnp.full((2,), -1, jnp.int32),
            "fin": jnp.asarray(np.array([False, True])),
            "stop_turnover": jnp.asarray(False)}
    old_leaves = jax.tree.leaves(pool.state)
    pool.state, loop, *rest = eng.decode_window(pool.state, loop)
    # every donated input buffer was consumed — no copy survived
    assert all(leaf.is_deleted() for leaf in old_leaves)
    del rest
    baseline = len(jax.live_arrays())
    deltas = []
    for _ in range(3):
        old_leaves = jax.tree.leaves(pool.state)
        pool.state, loop, *rest = eng.decode_window(pool.state, loop)
        assert all(leaf.is_deleted() for leaf in old_leaves)
        del rest
        deltas.append(len(jax.live_arrays()) - baseline)
    # live-buffer census flat across windows (block outputs are freed as
    # `rest` is dropped; the pool itself is aliased in place)
    assert max(deltas) - min(deltas) <= 2, deltas


# ---------------------------------------------------------------------------
# per-slot RNG streams
# ---------------------------------------------------------------------------
def test_rng_stream_stable_across_turnover(setup):
    """A request's sampled tokens depend only on (seed, uid, token index):
    identical whether it runs alone, co-scheduled through slot turnover,
    under any sync_interval, or on the synchronous path."""
    cfg, fkv, params = setup
    prompt = _prompt(cfg, 64, seed=3)
    mk = lambda uids: [Request(uid=u, tokens=prompt, max_new_tokens=5)
                       for u in uids]
    crowded, _ = _run(cfg, fkv, params, mk([7, 8, 9]), batch_size=1,
                      temperature=0.8)
    crowded = {o.uid: o.tokens for o in crowded}
    for u in (7, 8, 9):
        alone, _ = _run(cfg, fkv, params, mk([u]), batch_size=2,
                        temperature=0.8)
        assert alone[0].tokens == crowded[u]
    for f in (dataclasses.replace(fkv, sync_interval=1),
              dataclasses.replace(fkv, sample_on_device=False)):
        outs, _ = _run(cfg, f, params, mk([7, 8, 9]), batch_size=2,
                       temperature=0.8)
        assert {o.uid: o.tokens for o in outs} == crowded


# ---------------------------------------------------------------------------
# host-transfer accounting
# ---------------------------------------------------------------------------
def test_zero_host_bytes_between_syncs(setup):
    """With on-device sampling nothing crosses the host boundary between
    syncs, and a long request amortizes many steps per sync."""
    cfg, fkv, params = setup
    reqs = [Request(uid=0, tokens=_prompt(cfg, 64), max_new_tokens=16)]
    _, em = _run(cfg, dataclasses.replace(fkv, sync_interval=8), params, reqs)
    d = em.summary()["dispatch"]
    assert d["nonsync_host_bytes"] == 0.0
    assert d["host_syncs"] == 2 and em.steps == 15      # 8 + 7 (early exit)
    assert d["steps_per_sync"] > 4
    # synchronous reference: one sync per step, strictly more traffic
    _, em_sync = _run(cfg, dataclasses.replace(fkv, sample_on_device=False),
                      params, reqs)
    ds = em_sync.summary()["dispatch"]
    assert ds["host_syncs"] == em_sync.steps == 15
    assert ds["host_bytes_per_step"] > d["host_bytes_per_step"]


def test_sync_path_metrics_match(setup):
    """Engine step/occupancy accounting is identical across dispatch modes
    (the window's valid masks reproduce per-step bookkeeping exactly)."""
    cfg, fkv, params = setup
    reqs = lambda: [Request(uid=0, tokens=_prompt(cfg, 64), max_new_tokens=16),
                    Request(uid=1, tokens=_prompt(cfg, 64), max_new_tokens=2)]
    _, em_a = _run(cfg, dataclasses.replace(fkv, sync_interval=8), params,
                   reqs())
    _, em_b = _run(cfg, dataclasses.replace(fkv, sample_on_device=False),
                   params, reqs())
    assert em_a.steps == em_b.steps == 15
    assert em_a.active_slot_steps == em_b.active_slot_steps == 16
    assert em_a.sync_pages == em_b.sync_pages
    assert em_a.async_pages == em_b.async_pages
