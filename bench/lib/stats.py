"""Percentile and window arithmetic on host timestamps.

All times are seconds on one monotonic clock (``time.perf_counter``).
The window is ``[t0, t1)``.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """``q``-th percentile (0..100), linear between order statistics
    (numpy's default rule).  Raises on an empty sequence."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_from_due(requests: Iterable[tuple[float, float | None]],
                  t0: float, t1: float) -> list[float]:
    """Time to first token of every request due inside the window,
    measured from its due time.  ``requests`` yields ``(due,
    first_token_time)``; a request with no first token by ``t1`` counts
    with the wait it reached by then."""
    out = []
    for due, first in requests:
        if not t0 <= due < t1:
            continue
        if first is None or first >= t1:
            out.append(t1 - due)
        else:
            out.append(first - due)
    return out


def inter_token_gaps(token_times: Iterable[Sequence[float]],
                     t0: float, t1: float) -> list[float]:
    """Gaps between consecutive output tokens of each request whose
    later token was emitted inside the window."""
    out = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def tokens_in_window(token_times: Iterable[Sequence[float]],
                     t0: float, t1: float) -> int:
    return sum(1 for times in token_times for t in times if t0 <= t < t1)


def train_window(step_ends: Sequence[float], t0: float, t1: float,
                 tokens_per_step: int) -> tuple[float, int]:
    """Tokens per second of the steps that ended inside the window,
    over the time from ``t0`` to the end of the last of them; and the
    number of those steps.  Raises when no step ended in the window."""
    done = [t for t in step_ends if t0 < t <= t1]
    if not done:
        raise ValueError("no train step completed inside the window")
    return tokens_per_step * len(done) / (max(done) - t0), len(done)
