"""End-to-end metric arithmetic over one run's requests.

Times are seconds on the run's host clock.  A request's latency counts from
its due time, so a stalled loop that submits late is charged for the stall.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], pct: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a non-empty set."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), pct))


def ttfts(due: Sequence[float], first: Sequence[Optional[float]],
          window: Tuple[float, float], t_end: float) -> List[float]:
    """Time to first token of every request due inside ``window``.  A
    request that has no first token by ``t_end`` counts as missing: its
    TTFT is at least ``t_end - due``, and that is what it gets."""
    w0, w1 = window
    return [(f if f is not None else t_end) - d
            for d, f in zip(due, first) if w0 <= d < w1]


def inter_token_gaps(token_times: Iterable[Sequence[float]],
                     window: Tuple[float, float]) -> List[float]:
    """Every gap between two consecutive tokens of one request that were
    both delivered inside ``window``."""
    w0, w1 = window
    gaps: List[float] = []
    for times in token_times:
        t = np.asarray(times, np.float64)
        if len(t) < 2:
            continue
        inside = (t[:-1] >= w0) & (t[1:] <= w1)
        gaps.extend((t[1:] - t[:-1])[inside].tolist())
    return gaps


def tokens_per_s(token_times: Iterable[Sequence[float]],
                 window: Tuple[float, float]) -> float:
    """Output tokens delivered inside ``window``, over its length."""
    w0, w1 = window
    n = sum(int(np.count_nonzero((np.asarray(t) >= w0) & (np.asarray(t) <= w1)))
            for t in token_times)
    return n / (w1 - w0)


def end_to_end(due, first, token_times, window, t_end) -> Dict[str, float]:
    """Every end-to-end metric the harness knows, by name.  A cell reports
    the ones ``BENCHMARK.json`` lists for it."""
    out: Dict[str, float] = {"out_tok_s": tokens_per_s(token_times, window)}
    tt = ttfts(due, first, window, t_end)
    if tt:
        out["ttft_p50_s"] = percentile(tt, 50)
        out["ttft_p90_s"] = percentile(tt, 90)
    gaps = inter_token_gaps(token_times, window)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    return out
