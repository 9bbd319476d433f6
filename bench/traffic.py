"""One general open-loop traffic generator, read from a mix's data file.

A mix (``traffic/<name>.json``) states distributions; the cell states
the rate.  A run of ``seconds`` at ``rate`` offers ``n = round(rate *
seconds)`` requests.  Their sizes and gaps are the distributions'
quantiles at ``(i + 0.5) / n``, so every seed gets the same set of sizes
and arrivals and the seed only shuffles them (and draws the prompt
tokens): the work of a run does not change with its seed, only its
order.

Mix keys:

``arrivals``  ``{"kind": "poisson"}`` (exponential gaps).
``prompt_len`` / ``max_new``  ``{"dist": "lognormal", "median", "sigma",
              "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``
              (both ends included).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_set(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The ``n`` lengths of a run: the distribution's stratified
    quantiles, rounded and clipped to ``[min, max]``."""
    u = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        vals = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def gap_set(arrivals: Dict[str, Any], n: int, seconds: float) -> np.ndarray:
    """The ``n`` gaps between arrivals, scaled to sum to ``seconds``, so
    the offered rate is exactly ``n / seconds``."""
    u = _quantiles(n)
    kind = arrivals["kind"]
    if kind != "poisson":
        raise ValueError(f"unknown arrival kind {kind!r}")
    g = -np.log1p(-u)
    return g * (seconds / g.sum())


def schedule(mix: Dict[str, Any], *, rate: float, seconds: float,
             seed: int, vocab: int) -> List[Arrival]:
    """The run's arrivals in due order, all due inside ``[0, seconds)``."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed % 2**64)
    prompt_lens = rng.permutation(length_set(mix["prompt_len"], n))
    max_news = rng.permutation(length_set(mix["max_new"], n))
    gaps = rng.permutation(gap_set(mix["arrivals"], n, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(due_s=float(due[i]),
                    prompt=rng.integers(0, vocab, size=int(prompt_lens[i]),
                                        dtype=np.int32),
                    max_new=int(max_news[i]))
            for i in range(n)]
