"""Metric arithmetic on timestamps: percentiles, token gaps, rates. Pure
Python on lists, so it can be checked by hand (tests/bench)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default method), of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def token_gaps(stamps) -> list:
    """Gaps between consecutive token arrival times of one request."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def ttft_values(requests, miss_s: float) -> list:
    """Seconds from `t_ref` (due time in an open loop, send time in a closed
    one) to the first token, one per attempted request. A request that
    failed counts as a miss: it waits `miss_s`, the whole allowance."""
    return [miss_s if r.failed or not r.stamps else r.stamps[0] - r.t_ref
            for r in requests]


def ttft_percentile_ms(run, q: float):
    """The q-th percentile of TTFT over a run's requests, in ms; a failed
    request waits the window plus the drain allowance."""
    if not run.requests:
        return None
    miss = (run.t1 - run.t0) + run.extra.get("drain_allowance_s", 0.0)
    return percentile(ttft_values(run.requests, miss), q) * 1e3


def pooled_gaps(requests) -> list:
    out = []
    for r in requests:
        out.extend(token_gaps(r.stamps))
    return out


def tokens_in_window(requests, t0: float, t1: float) -> int:
    """Output tokens delivered inside [t0, t1); a failed request's tokens
    were delivered all the same and are not taken back."""
    return sum(1 for r in requests for s in r.stamps if t0 <= s < t1)


def interval_union(intervals) -> list:
    """Merge (start, end) intervals; returns disjoint sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]
