"""Sample statistics in plain Python (the load generator imports this and
must not import numpy or JAX)."""


def percentile(values, p):
    """``p`` in [0, 100], linear interpolation between closest ranks (what
    ``numpy.percentile`` does by default); None for no samples."""
    s = sorted(values)
    if not s:
        return None
    k = (len(s) - 1) * (p / 100.0)
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def summary(values):
    """Count, median, p95 and largest value of a sample, for the earlier
    lines (the largest shows a single stall that no percentile does)."""
    return {"n": len(values), "p50": percentile(values, 50),
            "p95": percentile(values, 95),
            "max": max(values) if values else None}
