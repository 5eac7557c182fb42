"""One generator for every traffic mix: a mix is a data file of parameters
(``bench/traffic/<name>.json``), and the generator turns it and a seed into
requests.

Keys of a mix file:

    loop            "closed" (``clients`` callers, each sends its next request
                    when the previous one finishes) or "open" (Poisson
                    arrivals at ``rate_per_s``, sent whether or not earlier
                    ones finished)
    clients         closed loop: concurrent callers
    rate_per_s      open loop: mean arrival rate
    lead_s          open loop: seconds of arrivals sent in set-up, before the
                    window opens (about one request's life, so that the
                    window starts at steady occupancy); default 0
    prompt_tokens   [lo, hi] prompt lengths, uniform
    output_tokens   [lo, hi] tokens generated per request, uniform
    block           requests per stratified block (below)
    requests        how many requests to generate (enough for the window)
    serving         the deployment's settings: slots, prefill_bucket, freekv
    sample_requests requests the correctness check compares after the window
    trace_seconds   length of the profiled slice in a ``--trace 1`` run

Every seed sees the same sizes and arrival times: each block of ``block``
consecutive requests holds the block's evenly spaced quantiles of the
length ranges and of the exponential gap distribution, in one shuffled
order that is the mix's own, not the seed's. A window shorter than a
request's life is a transient whose tokens depend on which request comes
when, so the seed changes only what the prompts say. Prompt tokens are
uniform over the vocabulary, excluding the pad token 0.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


@dataclass
class Planned:
    index: int
    prompt: np.ndarray         # (T,) int32, unpadded
    max_new_tokens: int
    due_s: float               # open loop: offset from the end of warm-up


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def _quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    return np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int)


def plan(mix: dict, seed: int, vocab: int, n: int = 0) -> List[Planned]:
    """The mix's first ``n`` requests (default: its ``requests``)."""
    order, rng = np.random.default_rng(0), np.random.default_rng(seed)
    n, block = int(n or mix["requests"]), int(mix["block"])
    p_lo, p_hi = mix["prompt_tokens"]
    o_lo, o_hi = mix["output_tokens"]
    rate = float(mix.get("rate_per_s", 0.0))
    gaps_q = -np.log(1.0 - (np.arange(block) + 0.5) / block)   # Exp(1)
    out, t = [], 0.0
    for b0 in range(0, n, block):
        plens = order.permutation(_quantiles(p_lo, p_hi, block))
        olens = order.permutation(_quantiles(o_lo, o_hi, block))
        gaps = order.permutation(gaps_q)
        for j in range(min(block, n - b0)):
            if rate > 0:
                t += gaps[j] / rate
            prompt = rng.integers(1, vocab, size=int(plens[j]),
                                  dtype=np.int32)
            out.append(Planned(b0 + j, prompt, int(olens[j]), t))
    return out


def padded_len(n: int, bucket: int) -> int:
    """The prompt length after the engine's left padding to its bucket."""
    return max(bucket, -(-n // bucket) * bucket)


def buckets(mix: dict) -> List[int]:
    """Every padded prompt length the mix's length range can produce."""
    b = mix["serving"]["prefill_bucket"]
    lo, hi = mix["prompt_tokens"]
    return list(range(padded_len(lo, b), padded_len(hi, b) + 1, b))


def max_len(mix: dict) -> int:
    """Engine positions: the longest padded prompt, the longest answer, a
    page and a bucket of slack (the serving launcher's rule)."""
    s = mix["serving"]
    return (buckets(mix)[-1] + mix["output_tokens"][1]
            + s["freekv"]["page_size"] + s["prefill_bucket"])


def window_requests(mix: dict, seconds: float) -> int:
    """How many requests to plan: a closed loop's ``requests``; an open
    loop's arrivals in its lead-in and ``seconds``, and one block more."""
    if mix["loop"] == "closed":
        return int(mix["requests"])
    span = float(mix.get("lead_s", 0.0)) + seconds
    return int(math.ceil(float(mix["rate_per_s"]) * span)) \
        + int(mix["block"])
