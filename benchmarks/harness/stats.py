"""Percentiles and the sample-count rule for the end-to-end metrics."""

from __future__ import annotations

import math
from typing import Optional, Sequence

# A percentile is reported only with at least this many samples beyond
# it (choosing-metrics guide, section 1): p95 therefore needs 200.
SAMPLES_BEYOND = 10


def percentile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0 <= q <= 1) by linear interpolation between
    order statistics: the (n-1)*q-th point of the sorted sample. One
    sample is its own every percentile."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must lie in [0, 1], got {q}")
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def samples_needed(q: float, beyond: int = SAMPLES_BEYOND) -> int:
    """Fewest samples from which the q-quantile may be reported: the
    sample must hold `beyond` values past the percentile."""
    if not 0.0 <= q < 1.0:
        raise ValueError(f"q must lie in [0, 1), got {q}")
    # rounded first: 10 / (1 - 0.9) is 100.00000000000001 in floats
    return math.ceil(round(beyond / (1.0 - q), 6))


def supported_percentile(values: Sequence[float], q: float,
                         beyond: int = SAMPLES_BEYOND
                         ) -> Optional[float]:
    """percentile(values, q), or None where the sample is too small to
    support it — the run is then too short, and says so by leaving the
    metric out."""
    if len(values) < samples_needed(q, beyond):
        return None
    return percentile(values, q)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's measure of run-to-run spread."""
    import statistics
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
