"""Tail arithmetic shared by the end-to-end readers.

A tail is taken over every sample of the window, never from medians of
chunks.  A request that failed is a sample of +inf, so it misses any
latency limit and can only raise the tail.
"""

from __future__ import annotations

import math
from typing import Iterable


def percentile(samples: Iterable[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of all samples:
    the smallest sample with at least ``q`` percent of samples at or
    below it.  NaN (missing) counts as +inf.  Raises on no samples."""
    xs = sorted(math.inf if (x is None or math.isnan(x)) else float(x)
                for x in samples)
    if not xs:
        raise ValueError("no samples")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    rank = math.ceil(q / 100.0 * len(xs))
    return xs[max(rank, 1) - 1]


def in_window(t: float, t_open: float, t_close: float) -> bool:
    """Half-open window membership: ``t_open <= t < t_close``."""
    return t_open <= t < t_close
