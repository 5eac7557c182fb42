"""Compile the decode-path kernels at granite-3-8b widths for a described
TPU v5e (no chip needed): what Mosaic refuses here — unaligned blocks, a
memory space it cannot DMA, VMEM overflow — it would refuse on the chip.

Widths: 8 KV heads, GQA group 4, d_head 128, 32-token pages, 4 slots of an
8K-token context (260 pool pages), a 2048-token budget (56 selected pages),
24 layers in the host pool stack. The topology is described inside a module
fixture, so only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import host_pages, page_scores, paged_attention
from repro.kernels import recall_gather

B, KV, G, D, PAGE, N_POOL, N_SEL, LAYERS = 4, 8, 4, 128, 32, 260, 56, 24
N_ATTN = 65            # sink 4 + window ring 5 + 56 selected pages
ROWS, LANES = 8, 512   # one bf16 (2, 32, 128) K+V block as host words


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def shapes(topo):
    """Shape factories on one described chip, device and pinned_host."""
    mesh = Mesh(np.array(topo.devices[:1]), ("x",))
    dev = NamedSharding(mesh, P())
    host = NamedSharding(mesh, P(), memory_kind="pinned_host")

    def make(shape, dtype, on_host=False):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=host if on_host else dev)
    # a described chip's executables cannot be read back from the persistent
    # cache (each read warns), so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield make
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_attn", [N_ATTN, 8 * N_ATTN])
def test_paged_attention_compiles(shapes, n_attn):
    """The smoke's 2080-token resident set (one block per head) and a
    16640-token one (streamed blocks, ragged last block), where one
    whole-axis block per head runs out of VMEM."""
    bf = jnp.bfloat16
    _compile(lambda q, k, v, pos, cur: paged_attention.paged_attention(
        q, k, v, pos, cur, scale=D ** -0.5, interpret=False),
        shapes((B, KV, G, D), bf), shapes((B, KV, n_attn, PAGE, D), bf),
        shapes((B, KV, n_attn, PAGE, D), bf),
        shapes((B, KV, n_attn, PAGE), jnp.int32), shapes((B,), jnp.int32))


def test_page_scores_compiles(shapes):
    """Reads the summaries in their state layout: no per-step transpose or
    relayout of the (B, pages, kv, 2, d) array before the kernel."""
    bf = jnp.bfloat16
    text = _compile(lambda q, s: page_scores.page_scores(
        q, s, scale=D ** -0.5, interpret=False),
        shapes((B, KV, G, D), bf),
        shapes((B, N_POOL, KV, 2, D), bf)).as_text()
    for op in (" transpose(", " reshape(", " copy(", " fusion("):
        assert op not in text, op


def test_recall_gather_host_pool_compiles(shapes):
    """The streamed recall and the page offload against a layer-stacked
    pinned_host pool: host->HBM and HBM->host DMAs only."""
    pool = shapes((LAYERS, B, N_POOL, KV, ROWS, LANES), jnp.uint32, True)
    idx = shapes((B, KV, N_SEL), jnp.int32)
    layer = shapes((), jnp.int32)
    _compile(lambda p, i, l: host_pages.recall_gather_host(p, i, layer=l),
             pool, idx, layer)
    compiled = jax.jit(
        lambda p, blk, t, dn, l: host_pages.write_host(p, blk, t, dn,
                                                       layer=l),
        donate_argnums=(0,)).lower(
        pool, shapes((B, KV, ROWS, LANES), jnp.uint32),
        shapes((B,), jnp.int32), shapes((B,), jnp.bool_), layer).compile()
    kinds = {s.memory_kind for s in jax.tree.leaves(compiled.output_shardings)}
    assert kinds == {"pinned_host"}


def test_recall_gather_device_pool_compiles(shapes):
    _compile(lambda p, i: recall_gather.recall_gather(p, i, interpret=False),
             shapes((B, N_POOL, KV, 2, PAGE, D), jnp.bfloat16),
             shapes((B, KV, N_SEL), jnp.int32))


def test_recall_gather_quant_int8_compiles(shapes):
    _compile(lambda p, s, i: recall_gather.recall_gather_quant(
        p, s, i, bits=8, out_dtype=jnp.bfloat16, interpret=False),
        shapes((B, N_POOL, KV, 2, PAGE, D), jnp.int8),
        shapes((B, N_POOL, KV, 2, 1), jnp.float32),
        shapes((B, KV, N_SEL), jnp.int32))


@pytest.mark.parametrize("draft_len", [0, 2])
def test_decode_window_host_pool_compiles(shapes, draft_len):
    """The engine's decode window (draft_len 0) and its drafted variant
    (verify scan + rewind scan) over a two-layer pinned_host pool, compiled
    kernels: every layer scan must reach the host pool through the DMA
    kernels only. A scan that slices the host stack makes XLA copy the pool
    into a host temporary, so none may exist."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import FreeKVConfig
    from repro.core import offload
    from repro.models import model
    from repro.serving.sampling import SamplerConfig

    cfg = dataclasses.replace(get_config("granite-3-8b-smoke"), d_model=512,
                              d_head=D, n_layers=2, n_periods=2)
    fkv = FreeKVConfig(method="freekv", page_size=PAGE, budget=256,
                       n_sink=64, n_window=64, offload="host",
                       use_kernels=True, kernel_interpret="compiled",
                       draft_len=draft_len)
    batch, max_len = 2, 1024
    params = jax.tree.map(lambda a: shapes(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))

    def place(path, a):
        if str(getattr(path[-1], "key", path[-1])) == "pool":
            a = jax.eval_shape(offload.to_host_format, a)
            return shapes(a.shape, a.dtype, True)
        return shapes(a.shape, a.dtype)
    state = jax.tree_util.tree_map_with_path(place, jax.eval_shape(
        lambda: model.init_decode_state(cfg, fkv, batch, max_len,
                                        jnp.bfloat16)))
    i32 = jnp.int32
    loop = {"cur": shapes((batch,), i32), "key": shapes((batch, 2), jnp.uint32),
            "count": shapes((batch,), i32), "limit": shapes((batch,), i32),
            "eos": shapes((batch,), i32), "fin": shapes((batch,), jnp.bool_),
            "stop_turnover": shapes((), jnp.bool_)}
    win = model.decode_window_spec if draft_len else model.decode_window
    compiled = _compile(
        lambda p, s, lp: win(cfg, fkv, p, s, lp, sampler=SamplerConfig(),
                             k_max=2), params, state, loop)
    text = compiled.as_text()
    assert "recall_gather_host" in text and "write_host" in text
    assert compiled.memory_analysis().host_temp_size_in_bytes == 0
    kinds = {s.memory_kind for s in jax.tree.leaves(compiled.output_shardings)}
    assert "pinned_host" in kinds
