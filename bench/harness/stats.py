"""Tail and rate arithmetic over every request and the whole window."""
from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """The p-th percentile (linear interpolation) of all values; None when
    there are none."""
    a = np.asarray(list(values), float)
    if a.size == 0:
        return None
    return float(np.percentile(a, p))


def tpot(first_seen: float, last_seen: float, n_tokens: int
         ) -> Optional[float]:
    """Mean gap between a request's tokens as the client saw them: tokens
    that arrive together at one drain average out over the request."""
    if n_tokens < 2:
        return None
    return (last_seen - first_seen) / (n_tokens - 1)


def t_cut(out) -> float:
    """When the client stopped waiting: the window's end plus the drain."""
    return out.window[1] + out.drain_s


def ttft_samples(counted, cut: float) -> List[float]:
    """Due time to first token, seconds, for every request due in the
    window: one that never saw a token counts as if it came at ``cut``, so
    a request pushed past the drain limit raises the tail, not lowers it."""
    return [(r.first_seen if r.first_seen is not None else cut) - r.due
            for r in counted]


def tpot_samples(counted, cut: float) -> List[float]:
    """Time per output token, seconds, for every request due in the window
    that asks for two tokens or more. One that did not finish counts as if
    its last token came at ``cut``: its stream runs from its first token
    (its due time, if none came) over all the tokens it asked for."""
    v = []
    for r in counted:
        if r.done:
            t = tpot(r.first_seen, r.last_seen, r.seen)
        elif r.max_new >= 2:
            start = r.first_seen if r.first_seen is not None else r.due
            t = tpot(start, cut, r.max_new)
        else:
            t = None
        if t is not None:
            v.append(t)
    return v


def rate(count: float, window_s: float) -> float:
    """A count over the whole window, per second."""
    if window_s <= 0:
        raise ValueError("empty window")
    return count / window_s


def spread(values: Iterable[float]) -> float:
    """Interquartile distance as a share of the median (the bound rule)."""
    import statistics
    v = list(values)
    q1, med, q3 = statistics.quantiles(v, n=4)
    return (q3 - q1) / statistics.median(v)
