"""Work the engine did inside the traced stretch, counted from the client's
records: decode tokens by the client's stamps (a token's query position is
its prompt length plus its index, minus one), and prefill tokens spread
evenly over each admission, from its first chunk to its first token."""
from __future__ import annotations

from typing import Iterator, Tuple


def decode_positions(records, t0: float, t1: float) -> Iterator[int]:
    """Query position of every decode token the client saw in [t0, t1)."""
    for r in records:
        for j, t in enumerate(r.stamps):
            if j and t0 <= t < t1:
                yield r.prompt_len + j - 1


def prefill_spans(records, t0: float, t1: float) -> Iterator[Tuple[int, int,
                                                                   float]]:
    """(start, end, share): the prompt positions an admission prefilled and
    the share of its admission time that fell inside [t0, t1)."""
    for r in records:
        a, b = r.admit_start, r.admit_done or r.first_seen
        if a is None or b is None:
            continue
        start = int(round(r.shared))
        if b <= a:
            share = 1.0 if t0 <= a < t1 else 0.0
        else:
            share = max(0.0, min(b, t1) - max(a, t0)) / (b - a)
        if share > 0:
            yield start, r.prompt_len, share
