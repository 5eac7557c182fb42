"""``paged_attention``: one decode step's attention over the resident set,
for every slot and KV head at once.

Per slot and KV head the kernel reads the resident keys and values (the
attention sink, the recent-token ring, which holds ``n_window + page``
rows, and the ``n_sel`` selected pages) with one int32 position per token,
and the group's queries; it writes the group's outputs. Operations: a
query-key product and a weight-value product per query head and token.
"""
from __future__ import annotations


def resident_tokens(mix: dict) -> int:
    f = mix["serving"]["freekv"]
    p = f["page_size"]
    n_sel = (f["budget"] - f["n_sink"] - f["n_window"]) // p
    return f["n_sink"] + f["n_window"] + p + n_sel * p


def counts(model: dict, mix: dict, itemsize: int = 2):
    B = mix["serving"]["slots"]
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    L = resident_tokens(mix)
    flops = 2 * 2 * B * H * L * d
    nbytes = (2 * B * kv * L * d * itemsize      # keys and values
              + B * kv * L * 4                   # positions
              + 2 * B * H * d * itemsize)        # queries in, outputs out
    return flops, nbytes
