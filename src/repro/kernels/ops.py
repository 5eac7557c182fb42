"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (this container) and False on TPU, where
the kernels lower through Mosaic. The wrappers are the only entry points the
rest of the framework uses.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.centroid_scores import centroid_scores as _centroid
from repro.kernels.flash_prefill import flash_prefill as _flash
from repro.kernels.page_scores import default_interpret as _default_interpret
from repro.kernels.page_scores import page_scores as _scores
from repro.kernels.page_summary import page_summary as _summary
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.recall_gather import recall_gather as _recall
from repro.kernels.recall_gather import recall_gather_quant as _recall_quant


def resolve_interpret(fkv=None, interpret=None):
    """Resolve the kernel execution mode: an explicit ``interpret`` wins,
    then ``FreeKVConfig.kernel_interpret`` ("interpret" / "compiled"), then
    the backend default ("auto": compiled on TPU, interpret elsewhere)."""
    if interpret is not None:
        return interpret
    mode = getattr(fkv, "kernel_interpret", "auto") if fkv is not None \
        else "auto"
    if mode == "auto":
        return _default_interpret()
    assert mode in ("interpret", "compiled"), mode
    return mode == "interpret"


def paged_attention(q, k_pages, v_pages, page_pos, cur_pos, *, scale,
                    softcap=None, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _paged(q, k_pages, v_pages, page_pos, cur_pos, scale=scale,
                  softcap=softcap, interpret=interpret)


def page_summary(k, *, page_size, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _summary(k, page_size=page_size, interpret=interpret)


def page_scores(q, summ, *, scale, block_pages=128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _scores(q, summ, scale=scale, block_pages=block_pages,
                   interpret=interpret)


def centroid_scores(q, cent, count, *, scale, interpret=None):
    """Stage-1 centroid-box scoring for the centroid retriever: q vs the
    C cluster bounding boxes (C << n_pages); empty clusters -> NEG_INF."""
    interpret = _default_interpret() if interpret is None else interpret
    return _centroid(q, cent, count, scale=scale, interpret=interpret)


def recall_gather(pool, idx, *, chunk=None, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _recall(pool, idx, chunk=chunk, interpret=interpret)


def recall_values(pool, idx, *, chunk=None, interpret=None):
    """ShadowKV-style V-only recall: half the transfer, K output unused."""
    interpret = _default_interpret() if interpret is None else interpret
    _, v = _recall(pool, idx, values_only=True, chunk=chunk,
                   interpret=interpret)
    return v


def recall_gather_quant(pool, scales, idx, *, bits, out_dtype=jnp.float32,
                        chunk=None, interpret=None):
    """Fused dequant-on-recall from the packed int8/int4 host pool
    (src/repro/quant): page payload + fp32 scales stream through the same
    2-deep VMEM ring; dequant to ``out_dtype`` happens in-kernel."""
    interpret = _default_interpret() if interpret is None else interpret
    return _recall_quant(pool, scales, idx, bits=bits, out_dtype=out_dtype,
                         chunk=chunk, interpret=interpret)


def recall_values_quant(pool, scales, idx, *, bits, out_dtype=jnp.float32,
                        chunk=None, interpret=None):
    """V-only fused dequant recall (ShadowKV x quantized pool)."""
    interpret = _default_interpret() if interpret is None else interpret
    _, v = _recall_quant(pool, scales, idx, bits=bits, out_dtype=out_dtype,
                         values_only=True, chunk=chunk, interpret=interpret)
    return v


def flash_prefill(q, k, v, *, scale, causal=True, window=None, softcap=None,
                  interpret=None, blq=128, blk=128):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, scale=scale, causal=causal, window=window,
                  softcap=softcap, blq=blq, blk=blk, interpret=interpret)


REFS = {
    "paged_attention": ref.paged_attention_ref,
    "page_summary": ref.page_summary_ref,
    "page_scores": ref.page_scores_ref,
    "centroid_scores": ref.centroid_scores_ref,
    "recall_gather": ref.recall_gather_ref,
    "flash_prefill": ref.flash_prefill_ref,
}
