"""The benchmark's arithmetic on samples: interpolated percentiles."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linearly interpolated between
    order statistics: position ``q/100 * (n - 1)`` in the sorted sample.
    A nearest-rank percentile of a hundred clustered values jumps from
    cluster to cluster; this one moves with the values around it."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = q / 100.0 * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
