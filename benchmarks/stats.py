"""Metric arithmetic of the benchmark: every end-to-end number is computed
here, from times the benchmark's own client or train loop took."""

from __future__ import annotations

import math
import statistics
from typing import List, Optional, Sequence, Tuple

FIRST_DELIVERY_S = 0.001   # tokens within 1 ms of the first came with it


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100): the smallest value with at
    least q% of the samples at or below it. A sample that failed is passed
    in as ``math.inf`` and so sits in the tail it belongs to."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def with_failures(values: Sequence[Optional[float]]) -> List[float]:
    """A failed or refused request (``None``) counts as the worst: it is
    given the largest value seen, times ten, so that it can never improve a
    tail and the line stays finite JSON."""
    good = [v for v in values if v is not None]
    worst = 10.0 * max(good) if good else 1e9
    return [worst if v is None else v for v in values]


def ttft_ms(due: float, first_arrival: Optional[float]) -> Optional[float]:
    """From the instant the request was DUE to the first line's arrival."""
    return None if first_arrival is None else (first_arrival - due) * 1e3


def tpot_ms(arrivals: Sequence[float]) -> Optional[float]:
    """``(t_last - t_first) / (n - k_first)`` over one request's token
    arrival times: ``k_first`` tokens came with the first delivery (within
    1 ms of it). It is the step time a user sees after the first delivery,
    whether that delivery holds sixteen tokens or one. ``None`` when every
    token came with the first (``n <= k_first``)."""
    n = len(arrivals)
    if n == 0:
        return None
    t_first = arrivals[0]
    k_first = sum(1 for t in arrivals if t - t_first <= FIRST_DELIVERY_S)
    if n <= k_first:
        return None
    return (arrivals[-1] - t_first) / (n - k_first) * 1e3


def request_latencies(outcomes: Sequence, never_sent: int = 0
                      ) -> Tuple[List[float], List[float], int]:
    """TTFT and per-token gap (ms) of the measured requests, failures as the
    worst, and how many requests were left out of the gap because all their
    tokens came with the first delivery. ``outcomes`` have ``ok``, ``due``
    and ``arrivals``; ``never_sent`` requests failed before they were sent."""
    bad = sum(1 for o in outcomes if not o.ok) + never_sent
    ttft = [ttft_ms(o.due, o.arrivals[0]) for o in outcomes if o.ok]
    gaps = [tpot_ms(o.arrivals) for o in outcomes if o.ok]
    tpot = [g for g in gaps if g is not None]
    return (with_failures(ttft + [None] * bad),
            with_failures(tpot + [None] * bad), len(gaps) - len(tpot))


def tokens_per_s(credits: Sequence[Tuple[float, int]], t0: float,
                 t1: float) -> float:
    """Tokens credited at times inside ``[t0, t1)`` over its length."""
    return sum(n for t, n in credits if t0 <= t < t1) / (t1 - t0)


def whole_steps(steps: Sequence[Tuple[float, float]], t0: float, t1: float
                ) -> List[Tuple[float, float]]:
    """The steps that both start and end inside ``[t0, t1]``."""
    return [(a, b) for a, b in steps if a >= t0 and b <= t1]


def whole_steps_rate(steps: Sequence[Tuple[float, float]], t0: float,
                     t1: float, units_per_step: float, chips: int) -> float:
    """Units per second per chip over whole steps: the units of the steps
    inside the window over the time from the first such start to the last
    such end, so a step more or less at the window's edges moves nothing."""
    inside = whole_steps(steps, t0, t1)
    if not inside:
        raise ValueError("no whole step inside the window")
    span = inside[-1][1] - inside[0][0]
    return len(inside) * units_per_step / span / chips


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median (the contract's)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
