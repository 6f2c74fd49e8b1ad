"""Arithmetic over one measured window: rates, gaps and percentiles.

Every end-to-end number is taken over the whole window: a rate is all the
tokens over all the seconds, and a percentile ranks every sample, never a
median of chunks.  A percentile is the nearest rank: the smallest sample
with at least ``p`` of all samples at or below it.
"""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank ``p``-quantile (``0 < p <= 1``) of ``values``."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    return vals[max(0, math.ceil(p * len(vals)) - 1)]


def tokens_in(stamps: dict, t0: float, t1: float) -> int:
    """Tokens stamped in ``(t0, t1]``, over every request."""
    return sum(1 for ts in stamps.values() for t in ts if t0 < t <= t1)


def inter_token_gaps(stamps: dict, t0: float, t1: float) -> list[float]:
    """Every gap between consecutive tokens of one request whose later
    token falls in ``(t0, t1]``; a request's first token opens no gap."""
    return [b - a for ts in stamps.values()
            for a, b in zip(ts, ts[1:]) if t0 < b <= t1]


def ttfts(due: dict, first: dict, t1: float) -> list[float]:
    """Due-to-first-token seconds of every request due before ``t1``.

    A request with no first token by ``t1`` ranks as the slowest.  Its
    value is the larger of its wait so far and the slowest first token
    seen: a lower bound of its latency that no served request outranks.
    """
    done = [first[u] - d for u, d in due.items()
            if d < t1 and u in first and first[u] <= t1]
    top = max(done, default=0.0)
    waiting = [max(t1 - d, top) for u, d in due.items()
               if d < t1 and not (u in first and first[u] <= t1)]
    return done + waiting
