"""Metric arithmetic over a run's requests: percentiles over all requests
and all gaps, and the tokens that reached the host inside a window.  No
chunking and no medians of medians: a tail is the tail of every sample."""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) with linear interpolation between
    order statistics (numpy's default)."""
    if len(values) == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(np.asarray(values, np.float64), q))


def inter_token_gaps(token_times: Sequence[Sequence[float]]) -> List[float]:
    """Every gap between consecutive output tokens of every request."""
    out: List[float] = []
    for times in token_times:
        out.extend(float(b - a) for a, b in zip(times[:-1], times[1:]))
    return out


def tokens_in_window(token_times: Sequence[Sequence[float]], start: float,
                     end: float) -> int:
    """Output tokens that reached the host inside ``[start, end)``."""
    return sum(1 for times in token_times for t in times if start <= t < end)


def end_to_end(token_times: Sequence[Sequence[float]]) -> Dict[str, float]:
    """The serving cell's end-to-end latency tail (times on the host
    clock).  Requests with no token are left to the caller, which counts
    them as failed."""
    gaps = inter_token_gaps(token_times)
    return {"itl_p90_ms": 1e3 * percentile(gaps, 90)}
