"""Sharding rules (AbstractMesh — no devices needed) + 1-device pjit
integration + loop-aware HLO analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, ASSIGNED
from repro.configs.base import FreeKVConfig, SHAPES
from repro.models.model import init_decode_state, init_params
from repro.sharding import rules

MESHES = [AbstractMesh((16, 16), ("data", "model")),
          AbstractMesh((2, 16, 16), ("pod", "data", "model"))]
FKV = FreeKVConfig(method="freekv", page_size=32, budget=2048, n_sink=512,
                   n_window=512, pool_pad_pages=512)


def _check_divisible(mesh, spec, shape, where):
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        assert shape[dim] % n == 0, (where, shape, spec)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("mesh", MESHES, ids=["single", "multi"])
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))

    def f(path, leaf):
        spec = rules.param_spec(mesh, rules._path_str(path), leaf)
        _check_divisible(mesh, spec, leaf.shape, rules._path_str(path))
    jax.tree_util.tree_map_with_path(f, shapes)


@pytest.mark.parametrize("arch", ["granite-3-8b", "deepseek-moe-16b",
                                  "jamba-1.5-large-398b", "whisper-tiny",
                                  "xlstm-350m"])
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_decode_state_specs_divisible(arch, shape_name):
    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    mesh = MESHES[0]
    st = jax.eval_shape(lambda: init_decode_state(
        cfg, FKV, shp.global_batch, shp.seq_len + 64, jnp.bfloat16))

    def f(path, leaf):
        spec = rules.decode_state_spec(cfg, mesh, rules._path_str(path), leaf)
        _check_divisible(mesh, spec, leaf.shape, rules._path_str(path))
    jax.tree_util.tree_map_with_path(f, st)


def test_pjit_one_device_end_to_end(small_fkv):
    """The full sharded pipeline on the real 1-device mesh: values must match
    the unsharded path exactly (mesh plumbing is semantically a no-op)."""
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import forward_train, prefill, serve_step
    cfg = get_config("deepseek-moe-16b-smoke")   # exercises MoE shard_map
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                          cfg.vocab_size)}
    mesh = make_host_mesh(1)
    loss_plain, _ = jax.jit(lambda p, b: forward_train(cfg, p, b))(params, batch)
    with mesh:
        loss_mesh, _ = jax.jit(
            lambda p, b: forward_train(cfg, p, b, mesh=mesh))(params, batch)
        logits, st = jax.jit(lambda p, b: prefill(
            cfg, small_fkv, p, b, max_len=96, mesh=mesh,
            state_dtype=jnp.float32))(params, batch)
        tok = jnp.argmax(logits, -1)[:, None]
        logits2, st = jax.jit(lambda p, s, t: serve_step(
            cfg, small_fkv, p, s, t, mesh=mesh))(params, st, tok)
    np.testing.assert_allclose(float(loss_plain), float(loss_mesh), rtol=2e-4)
    assert jnp.isfinite(logits2).all()


def test_hlo_cost_analyzer_loops():
    from repro.launch import hlo_cost
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y.sum()
    comp = jax.jit(f).lower(jnp.ones((64, 128)), jnp.ones((128, 128))).compile()
    r = hlo_cost.analyze(comp.as_text())
    expected = 7 * 2 * 64 * 128 * 128
    assert abs(r["flops"] - expected) / expected < 0.01
    # grad-of-scan: fwd 7 dots + bwd 14 dots
    comp2 = jax.jit(jax.grad(f, argnums=1)).lower(
        jnp.ones((64, 128)), jnp.ones((128, 128))).compile()
    r2 = hlo_cost.analyze(comp2.as_text())
    assert abs(r2["flops"] - 3 * expected) / (3 * expected) < 0.05


def test_collective_parse():
    from repro.launch import roofline as rl
    hlo = """
  %ag = bf16[128,256] all-gather(%x), replica_groups={}
  %ar = f32[64] all-reduce(%y), to_apply=%sum
  %a2a.1 = f32[32,32] all-to-all(%z)
"""
    c = rl.collective_bytes_per_device(hlo)
    assert c["per_op"]["all-gather"] == 128 * 256 * 2
    assert c["per_op"]["all-reduce"] == 2 * 64 * 4
    assert c["per_op"]["all-to-all"] == 32 * 32 * 4
