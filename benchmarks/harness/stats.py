"""Percentiles with their sample counts, failure accounting and window
accounting. Pure Python + numpy; imports nothing of the program.

Arithmetic copied from bench_serving.py's client-side percentiles (linear
interpolation between order statistics), with two changes this benchmark
needs: a failed request is placed at +inf BEFORE the percentile is taken
(so it misses every percentile it can reach), and every percentile comes
with the number of samples beyond it."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

INF = float("inf")


def percentile(values: Sequence[float], q: float) -> float:
    """q in [0, 100]; linear interpolation between order statistics
    (numpy's default). +inf entries sort last; a percentile that lands on
    or next to one is +inf. Empty input is an error, not a zero."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    pos = (len(vals) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    if frac == 0.0 or vals[lo] == vals[hi]:
        return float(vals[lo])
    if vals[hi] == INF:
        return INF
    return float(vals[lo] + (vals[hi] - vals[lo]) * frac)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie strictly beyond the q-th percentile's
    upper order statistic."""
    if n <= 0:
        return 0
    pos = (n - 1) * q / 100.0
    return n - 1 - int(math.ceil(pos))


def with_failures(values: Iterable[Optional[float]]) -> List[float]:
    """None (a request that failed, was refused or never finished) -> +inf."""
    return [INF if v is None else float(v) for v in values]


def iqr_share(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median with statistics.quantiles(n=4), the spread the
    bounds in BENCHMARK.json were set from."""
    import statistics

    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else INF


def tokens_in_window(chunk_times: Sequence[float], weights: Sequence[float],
                     t0: float, t1: float) -> float:
    """Output tokens whose chunk reached the client inside [t0, t1)."""
    return float(sum(w for t, w in zip(chunk_times, weights) if t0 <= t < t1))


def union_length(intervals: Iterable[Sequence[float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def in_flight_thirds(records: Sequence[dict], seconds: float) -> List[float]:
    """Mean number of requests in flight (sent, not yet ended) over each
    third of the window: a backlog that grows from the middle third to the
    last is the sign of a rate above the knee."""
    thirds = [0.0, 0.0, 0.0]
    for r in records:
        if r.get("t_send") is None:
            continue
        end = r["t_end"] if r.get("t_end") is not None else float("inf")
        for k in range(3):
            lo, hi = k * seconds / 3.0, (k + 1) * seconds / 3.0
            thirds[k] += max(0.0, min(end, hi) - max(r["t_send"], lo)) / (hi - lo)
    return thirds
