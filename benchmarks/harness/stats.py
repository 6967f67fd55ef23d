"""Client-side statistics over token stamps. Every function takes plain
lists of floats (seconds on one clock) and the window's two ends."""
from __future__ import annotations

from bisect import bisect_left
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    r = (len(xs) - 1) * p / 100.0
    lo = int(r)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)


def in_window(stamps: Sequence[float], t0: float, t1: float) -> List[float]:
    """The stamps with t0 <= t < t1 (stamps are increasing)."""
    return list(stamps[bisect_left(stamps, t0):bisect_left(stamps, t1)])


def tokens_in_window(streams: Iterable[Sequence[float]], t0, t1) -> int:
    return sum(
        bisect_left(s, t1) - bisect_left(s, t0) for s in streams
    )


def window_gaps(streams: Iterable[Sequence[float]], t0, t1) -> List[float]:
    """Every gap between consecutive tokens of one stream, both inside the
    window, all streams pooled."""
    out: List[float] = []
    for s in streams:
        w = in_window(s, t0, t1)
        out.extend(b - a for a, b in zip(w, w[1:]))
    return out


def gap_mean(streams: Iterable[Sequence[float]], t0, t1) -> Optional[float]:
    """Sum over streams of (last stamp - first stamp) over the sum of
    (tokens - 1): the mean of all gaps, a stalled stream's stall included."""
    span, n = 0.0, 0
    for s in streams:
        w = in_window(s, t0, t1)
        if len(w) >= 2:
            span += w[-1] - w[0]
            n += len(w) - 1
    return span / n if n else None


def clump_tokens(streams, t0, t1, delivery_gap_s: float = 1e-3):
    """Tokens per delivery: a delivery starts at a stream's first token in
    the window and after every gap longer than ``delivery_gap_s``."""
    tokens, deliveries = 0, 0
    for s in streams:
        w = in_window(s, t0, t1)
        if not w:
            continue
        tokens += len(w)
        deliveries += 1 + sum(
            1 for a, b in zip(w, w[1:]) if b - a > delivery_gap_s
        )
    return tokens / deliveries if deliveries else None
