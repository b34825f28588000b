"""Latency arithmetic, copied from the program's telemetry so that the
yardstick cannot move with it."""
from __future__ import annotations


def percentile(sorted_values, q: float) -> float:
    """Linear-interpolated percentile of an ascending-sorted sequence,
    ``q`` in [0, 1]."""
    if not sorted_values:
        raise ValueError("percentile of no values")
    if len(sorted_values) == 1:
        return float(sorted_values[0])
    pos = q * (len(sorted_values) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_values) - 1)
    frac = pos - lo
    return float(sorted_values[lo] * (1 - frac) + sorted_values[hi] * frac)
