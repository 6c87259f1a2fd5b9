"""Arithmetic of the end-to-end metrics, on host timestamps of one run.

A run's record holds, per request: when it was due, when each of its tokens
came back (the wall time after the ``step()`` that emitted it), and whether
it failed.  The window is ``[start, end]``.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class Served:
    uid: int
    tier: str
    due: float
    prompt_len: int
    max_new: int
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return len(self.tokens) == self.max_new

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default); ``inf`` entries
    stand for requests that never got an answer."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or xs[lo] == xs[hi]:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts_ms(reqs: Sequence[Served], start: float, end: float
             ) -> List[float]:
    """Due time to first token of every request due in the window (``inf``
    where it never came)."""
    return [((r.first - r.due) * 1e3 if r.first is not None else math.inf)
            for r in reqs if start <= r.due < end]


def tpots_ms(reqs: Sequence[Served], start: float, end: float
             ) -> List[float]:
    """(last - first token time) / (tokens - 1) of every request whose last
    token came in the window."""
    out = []
    for r in reqs:
        if r.finished and len(r.tokens) > 1 \
                and start < r.token_times[-1] <= end:
            out.append((r.token_times[-1] - r.token_times[0]) * 1e3
                       / (len(r.tokens) - 1))
    return out


def output_tok_s(reqs: Sequence[Served], start: float, end: float) -> float:
    n = sum(1 for r in reqs for t in r.token_times if start < t <= end)
    return n / (end - start)


def end_to_end(reqs: Sequence[Served], start: float, end: float
               ) -> Dict[str, float]:
    tt, tp = ttfts_ms(reqs, start, end), tpots_ms(reqs, start, end)
    return {"ttft_p95_ms": percentile(tt, 95), "ttft_p50_ms": percentile(tt, 50),
            "ttft_n": len(tt),
            "tpot_p95_ms": percentile(tp, 95) if tp else math.inf,
            "tpot_p50_ms": percentile(tp, 50) if tp else math.inf,
            "tpot_n": len(tp),
            "output_tok_s": output_tok_s(reqs, start, end)}


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (Python's ``statistics.quantiles``, n=4)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
