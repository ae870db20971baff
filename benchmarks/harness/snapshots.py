"""Differences of two registry snapshots (the registry is cumulative from
process start, set-up included; counters and histograms' sum and count are
sound as differences, the histograms' percentiles are not)."""

from typing import Optional


def counter(a: dict, b: dict, name: str) -> int:
    return b["counters"].get(name, 0) - a["counters"].get(name, 0)


def histogram_mean(a: dict, b: dict, name: str) -> Optional[float]:
    """Mean of the samples observed between the two snapshots."""
    hb = b["histograms"].get(name)
    if hb is None:
        return None
    ha = a["histograms"].get(name, {"sum": 0, "count": 0})
    n = hb["count"] - ha["count"]
    return (hb["sum"] - ha["sum"]) / n if n > 0 else None
