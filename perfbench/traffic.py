"""The one traffic generator: open-loop arrivals and request sizes, read from
a mix's parameter file (``traffic/<mix>.json``).

Every block of ``block`` consecutive requests holds the same sizes, tiers
and gaps between arrivals, in another order: lengths sit at evenly spaced
quantiles of their distribution, gaps at evenly spaced quantiles of the
exponential (Poisson arrivals at the cell's rate, stratified), and the seed
shuffles each list of each block on its own and draws the prompt tokens.
So the seed changes which request comes when, not how much work a run's
window holds.

A mix file holds::

    {"tiers": {"8/8": 0.3, "2/2": 0.7},
     "prompt_tokens": {"median": 512, "sigma": 0.7, "min": 128, "max": 2048},
     "output_tokens": {"median": 128, "sigma": 0.7, "min": 32, "max": 512},
     "engine": {"max_batch": 16, "max_len": 2560, "prompt_bucket": 512,
                "decode_chunk": 8},
     "block": 20,
     "backlog": 20,
     "lead_in_s": 30}

Lengths are lognormal (``median``, ``sigma`` of the log), clipped to
``[min, max]``.  ``backlog`` (default 0) requests are due at once when the
traffic starts, a service that opens with a queue; the rest follow at the
rate.  A backlog of whole blocks leaves every seed the same gaps.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of a run: when it is due (seconds after the run's
    traffic starts), its prompt, how many tokens it asks for, its tier."""

    uid: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    tier: str


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the spec's clipped
    lognormal (ascending)."""
    nd = statistics.NormalDist()
    z = np.asarray([nd.inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def tier_list(shares: Dict[str, float], n: int) -> List[str]:
    """``n`` tier names in the mix's proportions (largest remainder)."""
    raw = {t: s * n for t, s in shares.items()}
    count = {t: int(math.floor(v)) for t, v in raw.items()}
    rest = n - sum(count.values())
    for t in sorted(raw, key=lambda t: raw[t] - count[t], reverse=True)[:rest]:
        count[t] += 1
    return [t for t in shares for _ in range(count[t])]


def generate(mix: Dict[str, Any], rate: float, seed: int, horizon_s: float,
             vocab_size: int) -> List[Planned]:
    """The mix's backlog, due at 0, then at least ``rate * horizon_s``
    requests of an open loop at ``rate`` requests per second, in blocks of
    ``mix["block"]``: each block holds the same gaps, prompt and output
    lengths and tiers, at evenly spaced quantiles, in its own seed-drawn
    order (the backlog takes the first requests, so their gaps)."""
    k = int(mix["block"])
    backlog = int(mix.get("backlog", 0))
    n_blocks = max(1, int(math.ceil((rate * horizon_s + backlog) / k)))
    rng = np.random.default_rng(int(seed))
    gaps0 = -np.log1p(-_quantiles(k)) / rate
    prompts0 = lengths(mix["prompt_tokens"], k)
    outputs0 = lengths(mix["output_tokens"], k)
    tiers0 = tier_list(mix["tiers"], k)
    out: List[Planned] = []
    due = 0.0
    for _ in range(n_blocks):
        gaps, prompts, outputs = (rng.permutation(a) for a in
                                  (gaps0, prompts0, outputs0))
        tiers = [tiers0[i] for i in rng.permutation(k)]
        for i in range(k):
            if len(out) >= backlog:
                due += float(gaps[i])
            out.append(Planned(
                uid=len(out), due_s=due,
                prompt=rng.integers(0, vocab_size, size=int(prompts[i]),
                                    dtype=np.int32),
                max_new=int(outputs[i]), tier=tiers[i]))
    return out


def prefill_buckets(mix: Dict[str, Any]) -> List[int]:
    """Every padded prompt length the mix can produce."""
    eng = mix["engine"]
    b, spec = eng["prompt_bucket"], mix["prompt_tokens"]
    lo, hi = -(-spec["min"] // b) * b, -(-spec["max"] // b) * b
    return sorted({min(x, eng["max_len"]) for x in range(lo, hi + 1, b)})

