"""Host offload of the KV pool via JAX memory kinds (the paper's CPU-DRAM
offload, TPU-native).

``fkv.offload == "host"`` places the per-layer pool in ``pinned_host``
memory; page summaries, rings and selection buffers stay in HBM (they are
read every step). ``"sim"`` keeps the pool in device memory and accounts
transfer costs analytically (benchmarks/_common.py) — the only mode the CPU
backend can run, since it cannot move data between memory kinds inside a
compiled program.

XLA cannot gather from or scatter into host memory, so the host pool is
reached only by the DMA kernels of ``kernels/host_pages.py``: the streamed
recall (host->HBM, one DMA per selected block) and the page offload
(HBM->host, one DMA per completed page, into the pool aliased in place).
Their block format is ``core/paging.hnd_to_words``; ``place_decode_state``
converts the pool to it as it moves the pool to the host, and the slot pool
(``serving/kv_slots``) converts spliced-in prefill states the same way.
Inside every scan over layers the layer-stacked host pool is never sliced:
``layer_state`` gives the per-layer state the whole stack plus its layer
index (``pool_layer``), and the kernels index the layer themselves.

With the overlapped recall pipeline (``core/recall_pipeline``), the host
pool is the *source* of both transfer classes: the correction top-up (the
only host→device DMA the decode step waits on) and the staged speculative
stream that fills the alternate double buffer. ``pool_on_host`` tells the
executor/telemetry whether transfers are real DMAs or simulated
(cost-model) ones.

Usage:
    state = place_decode_state(state, fkv)            # after init/prefill
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FreeKVConfig
from repro.core import paging

# pool payload + its quant scales form the host tier (``pool_bytes``); only
# the fp ``pool`` payload is ever placed in host memory.
HOST_KEYS = ("pool", "pool_scale")
HOST_MEMORY_KIND = "pinned_host"


def is_host(x) -> bool:
    """Whether ``x`` (an array, or a tracer inside jit) lives in host memory."""
    return jax.typeof(x).memory_space == jax.memory.Space.Host


def layer_state(states, i):
    """Layer ``i``'s decode state out of a period-stacked scan carry — the
    one way every layer scan (decode, drafted verify, rewind) reads it.

    ``layer_writeback`` updates the carry in place only where every read of
    the old layer is ordered before the update by dataflow. A reader whose
    result the new state does not depend on (FreeKV's old selection buffers,
    read by attention) must read a materialised slice, or XLA copies the
    whole stack, and back, once per layer: the retriever materialises that
    read itself (``FreeKVRetriever.decode``).

    Host-resident leaves (the ``pinned_host`` pool) are not sliced: a slice
    of a host buffer is a copy of the layer's whole pool. The per-layer state
    carries the stack itself plus ``pool_layer``, and the host DMA kernels
    index the layer."""
    st = jax.tree.map(
        lambda a: a if is_host(a)
        else jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), states)
    if isinstance(st, dict) and any(is_host(a) for a in st.values()):
        st = dict(st, pool_layer=i)
    return st


def layer_writeback(states, st, i):
    """Inverse of ``layer_state``: write layer ``i``'s new state back into
    the stacked carry; host leaves come back as the updated stack."""
    if isinstance(st, dict):
        st = {k: v for k, v in st.items() if k != "pool_layer"}
    return jax.tree.map(
        lambda a, n: n if is_host(a)
        else jax.lax.dynamic_update_index_in_dim(a, n.astype(a.dtype), i, 0),
        states, st)


def host_sharding_for(leaf, mesh=None, spec=None):
    """A sharding equivalent to the leaf's current one but in pinned host
    memory. Raises when the backend has no ``pinned_host`` memory: asking for
    the host tier and silently keeping the pool in HBM would hide the
    transfers this mode exists to make real."""
    dev = jax.devices()[0]
    kinds = [m.kind for m in dev.addressable_memories()]
    if HOST_MEMORY_KIND not in kinds:
        raise RuntimeError(
            f"offload='host' needs {HOST_MEMORY_KIND!r} memory; "
            f"{dev.platform} device offers {kinds}")
    if mesh is not None and spec is not None:
        return jax.sharding.NamedSharding(mesh, spec,
                                          memory_kind=HOST_MEMORY_KIND)
    return jax.sharding.SingleDeviceSharding(dev, memory_kind=HOST_MEMORY_KIND)


def to_host_format(pool):
    """A device pool leaf (..., 2, p, d) in the host word format (idempotent
    on a leaf already in it)."""
    if pool.dtype == jnp.uint32:
        return pool
    return paging.hnd_to_words(pool)


class HostPool(NamedTuple):
    """Recall-side view of a host-resident pool: the word-format array (its
    whole layer stack inside the decode scan, with ``layer`` set), the
    logical block format the words decode to, and the kernel mode."""
    words: jax.Array
    layer: Optional[jax.Array]
    dtype: jnp.dtype
    page_size: int
    d_head: int
    interpret: bool


def host_view(state, interpret: bool) -> Optional[HostPool]:
    """The ``HostPool`` view of a per-layer state, or None for a device pool."""
    if not is_host(state["pool"]):
        return None
    *_, p, d = state["sel_k"].shape
    return HostPool(state["pool"], state.get("pool_layer"),
                    state["sel_k"].dtype, p, d, interpret)


def recall_host(view: HostPool, idx):
    """(k, v) each (B, kv, n_sel, p, d) of the selected pages; ``idx < 0``
    lanes are zero (the ``recall_gather_ref`` contract)."""
    from repro.kernels.host_pages import recall_gather_host as _recall
    words = _recall(view.words, idx, layer=view.layer,
                    interpret=view.interpret)
    ok = (idx >= 0)[..., None, None]
    return tuple(jnp.where(ok, x, 0) for x in paging.words_to_kv(
        words, view.dtype, view.page_size, view.d_head))


def write_page(pool, hnd, tgt, done, layer=None, interpret=None):
    """Write each row's completed (kv, 2, p, d) page into the host pool at
    page ``tgt[b]`` where ``done[b]``; other rows write nothing.
    ``interpret=None`` runs the compiled kernel on TPU, interpret elsewhere."""
    from repro.kernels.host_pages import write_host
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return write_host(pool, paging.hnd_to_words(hnd), tgt, done, layer=layer,
                      interpret=interpret)


def write_slot(dst, src, slot, axis=0, interpret=None, sharding=None):
    """Splice a batch-1 pool leaf (device, either format) into row ``slot``
    of the host-resident pool ``dst`` (batch on ``axis``). Under a mesh
    (``sharding``: dst's NamedSharding) each shard writes its own slice."""
    from repro.kernels.host_pages import write_slot as _write_slot
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def write(d, s, i):
        return _write_slot(d, s, i, axis=axis, interpret=interpret)

    src = to_host_format(src)
    if sharding is None:
        return write(dst, src, slot)
    spec = sharding.spec
    return jax.shard_map(write, mesh=sharding.mesh,
                         in_specs=(spec, spec, jax.sharding.PartitionSpec()),
                         out_specs=spec, check_vma=False)(dst, src, slot)


def init_decode_state(init, fkv: FreeKVConfig, mesh=None, cfg=None):
    """Run the jitted zero-state constructor ``init``, creating the pool
    leaves directly in pinned_host memory (word format) under
    ``offload == 'host'`` so they never pass through HBM — the slot pool's
    host tier is several times the chip's free memory (3.3 GB at 4 slots of
    an 8K context over 24 layers). ``mesh``/``cfg`` shard them as
    ``place_decode_state`` does."""
    if fkv.offload != "host":
        return init()
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(init))
    is_pool = [str(getattr(path[-1], "key", path[-1])) == "pool"
               for path, _ in leaves]
    dev = iter(jax.jit(lambda: [a for a, pool in zip(jax.tree.leaves(init()),
                                                     is_pool) if not pool])())

    def host_zeros(path, leaf):
        words = jax.eval_shape(to_host_format, leaf)
        spec = None
        if mesh is not None and cfg is not None:
            from repro.sharding import rules
            spec = rules.decode_state_spec(cfg, mesh, rules._path_str(path),
                                           words, fkv)
        return jax.device_put(np.zeros(words.shape, words.dtype),
                              host_sharding_for(words, mesh, spec))

    return jax.tree_util.tree_unflatten(treedef, [
        host_zeros(path, leaf) if pool else next(dev)
        for (path, leaf), pool in zip(leaves, is_pool)])


def place_decode_state(state, fkv: FreeKVConfig, mesh=None, specs=None,
                       cfg=None):
    """Move the pool leaves of a (possibly nested, layer-stacked) decode state
    to pinned_host memory, in the host word format. No-op for offload !=
    'host'; raises when the backend cannot hold them there.

    Sharding-aware: with a ``mesh``, each pool leaf keeps its partitioning
    while moving memory kinds — pass ``specs`` (a single PartitionSpec for
    every pool leaf) or ``cfg`` (per-leaf specs derived from
    ``sharding/rules.decode_state_spec``, e.g. KV-head-group sharding under
    tensor-parallel serving, where each shard's pool slice is host-resident
    on its own device)."""
    if fkv.offload != "host":
        return state
    if fkv.kv_quant != "none":
        raise NotImplementedError(
            "offload='host' holds fp pools only; the quantized tier "
            f"(kv_quant={fkv.kv_quant!r}) runs with offload='sim'")

    def _spec_for(path, leaf):
        if specs is not None:
            return specs
        if mesh is not None and cfg is not None:
            from repro.sharding import rules
            return rules.decode_state_spec(cfg, mesh, rules._path_str(path),
                                           leaf, fkv)
        return None

    def move(path, leaf):
        key = str(getattr(path[-1], "key", path[-1]))
        if key == "pool" and hasattr(leaf, "shape"):
            leaf = to_host_format(leaf)
            sh = _spec_for(path, leaf)
            return jax.device_put(leaf, host_sharding_for(leaf, mesh, sh))
        return leaf

    return jax.tree_util.tree_map_with_path(move, state)


def pool_on_host(state) -> bool:
    """True when the state's pool leaves live in ``pinned_host`` memory —
    i.e. recall transfers are genuine host→device DMAs rather than the
    ``offload='sim'`` cost-model simulation."""
    found = False

    def check(path, leaf):
        nonlocal found
        key = str(getattr(path[-1], "key", path[-1]))
        if key in HOST_KEYS:
            kind = getattr(getattr(leaf, "sharding", None), "memory_kind",
                           None)
            found = found or kind == HOST_MEMORY_KIND
        return leaf

    jax.tree_util.tree_map_with_path(check, state)
    return found


def swap_state_to_host(state):
    """Pull an extracted (B=1) decode state fully to host numpy — the
    serving preemption swap-out tier.

    Unlike ``place_decode_state`` (which keeps pool leaves device-addressable
    in pinned host memory for DMA recall), a swapped-out victim's state
    leaves the device entirely: every leaf — packed int8/int4 pool payload,
    fp32 quant scales, sink/window rings, selection buffers, summaries,
    ``pos`` — is materialized as a host numpy array at its stored dtype, so
    the round trip back through ``SlotPool.swap_in`` is exact (bit-identical
    for fp leaves, the identical packed representation for quantized pools).
    """
    return jax.tree.map(np.asarray, jax.device_get(state))


def pool_bytes(state) -> int:
    """Total bytes resident in the (host) pool across layers (telemetry).

    Quant-aware by construction: packed int8/int4 pool leaves report their
    physical ``nbytes`` and the fp32 ``pool_scale`` leaves are included, so
    this is the true host-tier footprint. For the dense-equivalent
    comparison (capacity multiplier), see
    ``repro.quant.accounting.pool_bytes_detail``."""
    total = 0

    def acc(path, leaf):
        nonlocal total
        key = str(getattr(path[-1], "key", path[-1]))
        if key in HOST_KEYS and hasattr(leaf, "nbytes"):
            total += leaf.nbytes
        return leaf

    jax.tree_util.tree_map_with_path(acc, state)
    return total
