"""Random weights from the run's seed, drawn on the device in one jitted
call and in the type they are served in.

The tree is the serving layout of a dense GQA decoder whose layers are
stacked on a leading axis (the program's ``prelude + pattern`` form with an
empty prelude and one ``(attention, dense MLP)`` pattern position):

    embed: tok (V_pad, d)  [+ head (d, V_pad) when untied]
    final_norm: w (d,)
    pattern[0]: norm1.w, mixer.{wq, wk, wv, wo}, norm2.w, ffn.{up, gate, down}

Each matrix is normal with standard deviation 1/sqrt(fan_in); norms are
ones. The vocabulary is padded to a multiple of 512 rows (the padded logits
are masked by the program and cut off by the reference).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_PAD = 512


def padded_vocab(model: dict) -> int:
    v = model["vocab_size"]
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def _shapes(model: dict) -> dict:
    """{path: (shape, fan_in or None for ones)} of every leaf."""
    d, L = model["hidden_size"], model["num_hidden_layers"]
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    dh, ff, V = model["head_dim"], model["intermediate_size"], \
        padded_vocab(model)
    out = {("embed", "tok"): ((V, d), d),
           ("final_norm", "w"): ((d,), None),
           ("norm1", "w"): ((L, d), None), ("norm2", "w"): ((L, d), None),
           ("mixer", "wq"): ((L, d, H * dh), d),
           ("mixer", "wk"): ((L, d, kv * dh), d),
           ("mixer", "wv"): ((L, d, kv * dh), d),
           ("mixer", "wo"): ((L, H * dh, d), H * dh),
           ("ffn", "up"): ((L, d, ff), d), ("ffn", "gate"): ((L, d, ff), d),
           ("ffn", "down"): ((L, ff, d), ff)}
    if not model["tie_word_embeddings"]:
        out[("embed", "head")] = ((d, V), d)
    return out


def make_params(model: dict, seed: int, dtype=jnp.bfloat16):
    """The weights of ``model`` (a configuration file's dict) for ``seed``."""
    shapes = _shapes(model)

    def draw(key):
        leaves = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(shapes.items())):
            if fan_in is None:
                leaves[path] = jnp.ones(shape, dtype)
            else:
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * fan_in ** -0.5
                leaves[path] = w.astype(dtype)
        layer = {}
        for (grp, leaf), a in leaves.items():
            if grp not in ("embed", "final_norm"):
                layer.setdefault(grp, {})[leaf] = a
        embed = {k: a for (g, k), a in leaves.items() if g == "embed"}
        return {"embed": embed,
                "final_norm": {"w": leaves[("final_norm", "w")]},
                "prelude": (), "pattern": (layer,)}

    return jax.jit(draw)(seed_key(seed))
