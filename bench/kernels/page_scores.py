"""``page_scores``: Quest upper-bound scores of every pool page for every
query head, from the pages' (min, max) key summaries.

Per slot it reads the summaries ``(n_pages, kv, 2, d)`` and the queries,
and writes one float32 score per query head and page. Operations: two
products (positive part against the max, negative part against the min)
per query head, page and channel.
"""
from __future__ import annotations

from bench.lib import traffic


def counts(model: dict, mix: dict, itemsize: int = 2):
    B = mix["serving"]["slots"]
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    n_pages = -(-traffic.max_len(mix) // mix["serving"]["freekv"]["page_size"])
    flops = 2 * 2 * B * H * n_pages * d
    nbytes = (B * n_pages * kv * 2 * d * itemsize   # summaries
              + B * H * d * itemsize                 # queries
              + B * H * n_pages * 4)                 # float32 scores
    return flops, nbytes
