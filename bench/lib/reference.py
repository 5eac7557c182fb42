"""Plain reference of what a served request's logits should be.

A straightforward ``jax.numpy`` forward pass in float32 (matmuls at
``precision=HIGHEST``) of a dense GQA decoder, teacher-forced over one
request: its padded prompt followed by the tokens the system served. It
implements the semantics the configuration states, written from the FreeKV
description and not from the code under test (nothing of ``repro`` is
imported):

* prompt positions attend causally to every earlier position (whole-prompt
  prefill);
* every served position ``P`` attends to the attention sink (positions below
  ``n_sink``), the recent window ``[max(n_sink, wfloor), P]`` with
  ``wfloor = max(n_sink // p, (P + 1 - n_window) // p) * p``, and the
  selected pages inside ``[n_sink, wfloor)``;
* the pages are chosen by Quest's min/max upper bound, softmax over the
  selectable pages per query head, averaged over the GQA group, top
  ``(budget - n_sink - n_window) // page`` per KV head. Selectable are the
  completed pages from ``n_sink // p`` up to the window boundary;
* speculative retrieval: position ``P`` uses the pages chosen with the query
  of ``P - 1`` (the prompt's last query for the first served token), unless
  the group-mean cosine similarity of the queries at ``P`` and ``P - 1`` is
  below ``tau``; that KV head is then corrected with the pages chosen with
  the query of ``P`` itself.

``quant="fp8"`` runs the same mathematics with every matmul operand and the
keys and values rounded to float8 e4m3 (per-row / per-output-column scales):
the control, one precision below the bfloat16 the configuration serves.

The weights come in the serving layout the benchmark itself generates
(``bench/lib/weights.py``), one layer at a time upcast to float32, so the
pass fits beside the bfloat16 weights on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0          # largest finite float8_e4m3fn
ROW_BLOCK = 1024        # positions are padded to a multiple of this
Q_CHUNK = 256           # query rows per attention block


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head. x (N, h, d), pos (N,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _selection(qs, lo, hi, L, m, scale):
    """Pages chosen with queries ``qs`` (C, kv, G, d) at lengths ``L`` (C,):
    a (C, kv, n_pages) bool mask of the top ``n_sel`` selectable pages."""
    n_pages = lo.shape[0]
    s = (jnp.einsum("ckgd,nkd->ckgn", jnp.maximum(qs, 0), hi, precision=HI)
         + jnp.einsum("ckgd,nkd->ckgn", jnp.minimum(qs, 0), lo,
                      precision=HI)) * scale
    first = m["n_sink"] // m["page"]
    last = jnp.minimum(L // m["page"], jnp.maximum(
        first, (L - m["n_window"]) // m["page"]))
    j = jnp.arange(n_pages)
    ok = (j[None] >= first) & (j[None] < last[:, None])        # (C, n)
    s = jnp.where(ok[:, None, None], s, NEG)
    pooled = jax.nn.softmax(s, -1).mean(2)                      # (C, kv, n)
    pooled = jnp.where(ok[:, None], pooled, NEG)
    top_s, top_i = jax.lax.top_k(pooled, min(m["n_sel"], n_pages))
    hit = jax.nn.one_hot(top_i, n_pages, dtype=jnp.bool_)
    return jnp.any(hit & (top_s > NEG / 2)[..., None], axis=2)


def _attention(q, k, v, T0, m, scale, quant):
    """q (N, kv, G, d), k/v (N, kv, d) -> (N, kv, G, d)."""
    N, kv, G, d = q.shape
    p = m["page"]
    if quant == "fp8":
        k, v = _fp8(k, -1), _fp8(v, -1)
    n_pages = N // p
    kp = k.reshape(n_pages, p, kv, d)
    lo, hi = kp.min(1), kp.max(1)                               # (n, kv, d)
    q_prev = jnp.concatenate([q[:1], q[:-1]], 0)
    keys = jnp.arange(N)

    def block(c0):
        P = c0 + jnp.arange(Q_CHUNK)
        qc = jax.lax.dynamic_slice_in_dim(q, c0, Q_CHUNK)
        qp = jax.lax.dynamic_slice_in_dim(q_prev, c0, Q_CHUNK)
        L = P + 1
        spec = _selection(qp, lo, hi, L - 1, m, scale)          # chosen at P-1
        fresh = _selection(qc, lo, hi, L, m, scale)             # chosen at P
        cos = jnp.sum(qc * qp, -1) / jnp.maximum(
            jnp.linalg.norm(qc, axis=-1) * jnp.linalg.norm(qp, axis=-1),
            1e-6)
        corr = cos.mean(-1) < m["tau"]                          # (C, kv)
        pages = jnp.where(corr[..., None], fresh, spec)         # (C, kv, n)
        wfloor = jnp.maximum(m["n_sink"] // p,
                             (L - m["n_window"]) // p) * p      # (C,)
        kk, PP, WF = keys[None], P[:, None], wfloor[:, None]
        causal = kk <= PP
        sink = kk < m["n_sink"]
        window = kk >= jnp.maximum(m["n_sink"], WF)
        mid = (kk >= m["n_sink"]) & (kk < WF)
        in_page = jnp.take(pages, keys // p, axis=2)            # (C, kv, N)
        served = ((sink | window)[:, None] | (mid[:, None] & in_page))
        ok = causal[:, None] & jnp.where((P < T0)[:, None, None], True,
                                         served)
        s = jnp.einsum("ckgd,nkd->ckgn", qc, k, precision=HI) * scale
        s = jnp.where(ok[:, :, None], s, NEG)
        w = jax.nn.softmax(s, -1)
        return jnp.einsum("ckgn,nkd->ckgd", w, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(0, N, Q_CHUNK))
    return out.reshape(N, kv, G, d)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, lp, T0, m, quant):
    N = x.shape[0]
    H, kv, d = m["heads"], m["kv_heads"], m["head_dim"]
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    pos = jnp.arange(N)
    h = _rms(x, lp["norm1"]["w"], m["eps"])
    at = lp["mixer"]
    q = _rope(_mm(h, at["wq"], quant).reshape(N, H, d), pos, m["theta"])
    k = _rope(_mm(h, at["wk"], quant).reshape(N, kv, d), pos, m["theta"])
    v = _mm(h, at["wv"], quant).reshape(N, kv, d)
    o = _attention(q.reshape(N, kv, H // kv, d), k, v, T0, m,
                   d ** -0.5, quant)
    x = x + _mm(o.reshape(N, H * d), at["wo"], quant)
    ff = lp["ffn"]

    def mlp(xb):
        hb = _rms(xb, lp["norm2"]["w"], m["eps"])
        g = _mm(hb, ff["gate"], quant)
        return xb + _mm(jax.nn.silu(g) * _mm(hb, ff["up"], quant),
                        ff["down"], quant)

    return jax.lax.map(mlp, x.reshape(-1, ROW_BLOCK, x.shape[1])
                       ).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, emb, norm_w, rows, picks, m, quant):
    """Final norm and LM head at ``rows``; returns the best logit, the
    logit of each token in ``picks`` (k, R), and the argmax, per row."""
    xr = _rms(x[rows], norm_w.astype(jnp.float32), m["eps"])
    w = (emb["tok"].T if m["tied"] else emb["head"]).astype(jnp.float32)
    logits = _mm(xr, w, quant)[:, : m["vocab"]]
    at = logits[jnp.arange(logits.shape[0])[None], picks]
    return logits.max(-1), at, jnp.argmax(logits, -1)


class Meta(dict):
    """The static description the reference needs; hashable, so that it
    can be a ``jax.jit`` static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def as_run(model: dict, key: str):
    """A configuration value as the program runs it: the file's
    ``departures`` entry where there is one, else the published value."""
    dep = model.get("departures", {})
    return dep[key]["runs"] if key in dep else model[key]


def meta(model: dict, serving: dict) -> Meta:
    """The reference's view of a configuration and its serving settings:
    the published model with the program's stated departures (the
    reference has no muP multipliers either)."""
    fkv = serving["freekv"]
    n_sel = (fkv["budget"] - fkv["n_sink"] - fkv["n_window"]) \
        // fkv["page_size"]
    return Meta({
        "heads": model["num_attention_heads"],
        "kv_heads": model["num_key_value_heads"],
        "head_dim": model["head_dim"],
        "eps": float(as_run(model, "rms_norm_eps")),
        "theta": float(as_run(model, "rope_theta")),
        "tied": bool(model["tie_word_embeddings"]),
        "vocab": model["vocab_size"],
        "layers": model["num_hidden_layers"],
        "page": fkv["page_size"], "n_sink": fkv["n_sink"],
        "n_window": fkv["n_window"], "tau": float(fkv["tau"]),
        "n_sel": n_sel,
    })


def score(params, m: Meta, tokens, T0: int, picks, quant=None):
    """Teacher-forced reference over ``tokens`` (N,), prompt length ``T0``.

    Returns numpy arrays over the positions ``T0 - 1 .. N - 1`` (whose
    logits choose tokens ``tokens[T0:]`` and one more): the best logit,
    the logit of each token row of ``picks`` (k, N - T0 + 1), and the
    argmax token."""
    tokens = np.asarray(tokens, np.int32)
    N = len(tokens)
    Np = -(-N // ROW_BLOCK) * ROW_BLOCK
    tok = np.zeros(Np, np.int32)
    tok[:N] = tokens
    x = jnp.take(params["embed"]["tok"], jnp.asarray(tok), axis=0
                 ).astype(jnp.float32)
    stack = params["pattern"][0]
    for i in range(m["layers"]):
        lp = jax.tree.map(lambda a: a[i], stack)
        x = _layer(x, lp, jnp.int32(T0), m, quant)
    R = N - T0 + 1                    # rows padded: one head program per
    Rp = -(-R // 512) * 512           # 512 rows, not one per length
    rows = np.full(Rp, N - 1, np.int32)
    rows[:R] = np.arange(T0 - 1, N)
    picks = np.asarray(picks, np.int32).reshape(-1, R)
    pk = np.zeros((picks.shape[0], Rp), np.int32)
    pk[:, :R] = picks
    best, at, arg = _head(x, params["embed"], params["final_norm"]["w"],
                          jnp.asarray(rows), jnp.asarray(pk), m, quant)
    return (np.asarray(best)[:R], np.asarray(at)[:, :R],
            np.asarray(arg)[:R])
