"""Percentile and attainment arithmetic — the benchmark's own.

Nothing here reads a clock or the program: lists of numbers in, numbers
out, so the rules can be checked on hand-made samples.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

#: the percentiles a tail may be named after, lowest first
TAIL_CANDIDATES = (50, 80, 90, 95, 99)
#: a percentile is reported only with this many samples beyond it
MIN_BEYOND = 10


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear between order statistics;
    None for no values.  A failed request is passed in as ``math.inf``: it
    then ranks last, and a percentile that reaches it is infinite."""
    if not values:
        return None
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    if ordered[lo] == ordered[hi]:  # also keeps inf - inf out of the sum
        return ordered[lo]
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie strictly beyond the ``q``-th
    percentile's rank."""
    return n - 1 - math.floor((n - 1) * q / 100.0)


def highest_supported_percentile(n: int) -> Optional[int]:
    """The highest candidate percentile that ``n`` samples support: at
    least ``MIN_BEYOND`` samples beyond it.  None under 20 samples."""
    best = None
    for q in TAIL_CANDIDATES:
        if samples_beyond(n, q) >= MIN_BEYOND:
            best = q
    return best


def attainment(
    ttft_ms: Sequence[float], gap_ms: Sequence[Optional[float]],
    ttft_limit_ms: float, gap_limit_ms: float,
) -> Optional[float]:
    """Share of attempted requests that met both limits.  One entry per
    attempted request: a failed one carries ``math.inf`` and misses; a
    request too short to have a gap (None) is judged on its TTFT alone."""
    if not ttft_ms:
        return None
    met = sum(
        1 for t, g in zip(ttft_ms, gap_ms)
        if t <= ttft_limit_ms and (g is None or g <= gap_limit_ms)
    )
    return met / len(ttft_ms)


def draw_ints(rng, spec: dict, n: int) -> list[int]:
    """``n`` seeded integers from a traffic file's ``{"dist", "low",
    "high"}``, stratified: one from each ``n``-th of the distribution, in
    seeded order.  Every seed then asks for the same amount of work, which
    a plain draw from a heavy-tailed distribution does not."""
    low, high = float(spec["low"]), float(spec["high"])
    quantiles = [(i + rng.random()) / n for i in range(n)]
    rng.shuffle(quantiles)
    if spec["dist"] == "uniform":
        return [int(round(low + q * (high - low))) for q in quantiles]
    if spec["dist"] == "loguniform":
        span = math.log(high) - math.log(low)
        return [int(round(math.exp(math.log(low) + q * span))) for q in quantiles]
    raise ValueError(f"unknown distribution {spec['dist']!r}")
