"""Whether what the timed path served is correct.

After the window, a sample of the requests that delivered tokens in it,
drawn from the seed and always holding the one that delivered the most,
is run once through the plain reference (``bench/lib/reference.py``),
teacher-forced over the request's padded prompt and the tokens it was
served. The number compared is the widest gap, over every served token of
the sample, by which the served token's reference logit lies below the
reference's best logit at that position. Greedy decoding serves the
program's own best token, so a sound program reads only its rounding (and
the rare page selection that rounding flips); a lower precision, or a
token altered where it is produced, reads more.

Limits live in ``bench/limits/<cell>.json``, each with the readings it was
set from.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.lib import reference, traffic

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"


def limits(cell: str, directory: Path = LIMITS_DIR) -> Dict[str, float]:
    data = json.loads((Path(directory) / f"{cell}.json").read_text())
    return {k: float(v["limit"]) for k, v in data.items()
            if not k.startswith("_")}


def sample(records, n: int, seed: int) -> list:
    """``n`` served requests: the longest, and the rest drawn from the
    seed."""
    served = sorted((r for r in records.values() if r.tokens),
                    key=lambda r: r.uid)
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(seed ^ 0x5EED)
    pick = rng.permutation(len(rest))[: max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def padded_prompt(prompt: np.ndarray, bucket: int) -> np.ndarray:
    """The engine's input: the prompt left-padded with token 0 to a whole
    number of prefill buckets."""
    out = np.zeros(traffic.padded_len(len(prompt), bucket), np.int32)
    out[len(out) - len(prompt):] = prompt
    return out


def logit_gaps(params, model: dict, mix: dict, reqs, prompts,
               quant=None) -> List[np.ndarray]:
    """Per request, the gap at each served position: the reference's best
    logit minus its logit of the served token. With ``quant`` the first
    token of that lower-precision reference is judged instead of the served
    one (the control), against the float32 reference."""
    m = reference.meta(model, mix["serving"])
    out = []
    for r in reqs:
        ids = padded_prompt(prompts[r.uid], mix["serving"]["prefill_bucket"])
        served = np.asarray(r.tokens, np.int32)
        if np.any((served < 0) | (served >= model["vocab_size"])):
            out.append(np.array([np.inf]))
            continue
        seq = np.concatenate([ids, served[:-1]])
        picks = served[None]
        if quant is not None:
            _, _, arg = reference.score(params, m, seq, len(ids), picks,
                                        quant=quant)
            picks = np.stack([served, arg])
        best, at, _ = reference.score(params, m, seq, len(ids), picks)
        out.append(best - at[-1])
    return out
