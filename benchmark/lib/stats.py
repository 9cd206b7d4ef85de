"""Exact order statistics over the run's own samples (no reservoir)."""

from __future__ import annotations

from typing import Optional, Sequence

# a percentile is reported only where at least this many samples lie
# beyond it (choosing-metrics guide, section 1)
MIN_BEYOND = 10


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics, as ``numpy.percentile``'s default does."""
    if not samples:
        raise ValueError("percentile of no samples")
    s = sorted(samples)
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return percentile(samples, 0.5)


def tail(samples: Sequence[float], q: float) -> Optional[float]:
    """``percentile(samples, q)``, or None where fewer than MIN_BEYOND
    samples lie beyond it."""
    if len(samples) * (1.0 - q) < MIN_BEYOND:
        return None
    return percentile(samples, q)
