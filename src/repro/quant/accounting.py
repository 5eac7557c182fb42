"""Byte accounting for the quantized host KV tier.

All recall-traffic telemetry in the framework counts (kv-head, page) blocks
(``core/recall_pipeline``, ``serving/metrics``); these helpers convert block
counts to bytes under a given ``kv_quant`` mode so the serving engine, the
slot pool, and the benchmarks agree on one definition of the transfer unit:

  dense:  2 * p * d * itemsize                      (K+V halves, fp)
  int8:   2 * p * d * 1      + 2 * n_groups * 4     (payload + fp32 scales)
  int4:   2 * p * (d/2) * 1  + 2 * n_groups * 4

The fp32 scales ride the same DMA as the packed page (they are gathered
per-page alongside the payload), so they count as transferred bytes — the
compression ratios reported by ``benchmarks/quant_quality.py`` include them.
"""
from __future__ import annotations

import numpy as np

from repro.quant.quantizers import effective_group, quant_bits

# Nominal dequant throughput (elements/s) for the cost-model estimate of
# dequant overhead in EngineMetrics.summary()["kv_quant"]. Dequant is one
# int->f32 convert + one multiply per element, streaming at HBM-ish rates on
# the target accelerator; the *measured* per-step overhead on this container
# comes from benchmarks/quant_quality.py.
DEQUANT_ELEMS_PER_S = 2.0e10


def scale_bytes_per_block(fkv, d_head: int) -> int:
    """fp32 scale bytes transferred with one (kv-head, page) K+V block."""
    if fkv.kv_quant == "none":
        return 0
    g = effective_group(fkv.quant_group_size, d_head)
    return 2 * (d_head // g) * 4


def page_block_bytes_dense(fkv, d_head: int, itemsize: int = 2) -> int:
    """Unquantized (kv-head, page) K+V block bytes at ``itemsize``/element."""
    return 2 * fkv.page_size * d_head * itemsize


def page_block_bytes(fkv, d_head: int, itemsize: int = 2) -> int:
    """Transferred bytes of one (kv-head, page) block under ``fkv.kv_quant``
    (packed payload + scales; == dense when quantization is off)."""
    bits = quant_bits(fkv.kv_quant)
    if bits == 0:
        return page_block_bytes_dense(fkv, d_head, itemsize)
    payload = 2 * fkv.page_size * (d_head * bits // 8)
    return payload + scale_bytes_per_block(fkv, d_head)


def pool_bytes_detail(state, d_head: int, dense_itemsize: int = 2) -> dict:
    """Physical vs dense-equivalent pool bytes for a decode-state pytree.

    Returns {"payload", "scales", "physical", "dense", "ratio"}: ``payload``
    sums the (possibly packed) pool leaves, ``scales`` the fp32 scale leaves,
    ``dense`` what the same page capacity would occupy unquantized at
    ``dense_itemsize`` bytes/element. Works on any nesting (per-layer dicts,
    the serving slot pool's full state tree)."""
    import jax

    acc = {"payload": 0, "scales": 0, "dense": 0}

    def visit(path, leaf):
        key = str(getattr(path[-1], "key", path[-1]))
        if key == "pool" and hasattr(leaf, "nbytes"):
            acc["payload"] += leaf.nbytes
            if leaf.dtype == np.uint32:       # host word format: fp, unpacked
                acc["dense"] += leaf.nbytes
                return leaf
            n_elems = leaf.size // leaf.shape[-1] * d_head
            acc["dense"] += n_elems * dense_itemsize
        elif key == "pool_scale" and hasattr(leaf, "nbytes"):
            acc["scales"] += leaf.nbytes
        return leaf

    jax.tree_util.tree_map_with_path(visit, state)
    physical = acc["payload"] + acc["scales"]
    return {"payload": acc["payload"], "scales": acc["scales"],
            "physical": physical, "dense": acc["dense"],
            "ratio": acc["dense"] / physical if physical else 1.0}
