"""Percentiles and spreads, one definition for every metric.

``percentile`` is the nearest-rank percentile on the sorted sample (the value
at or above the requested share of the sample), so a p95 is always a value
that was observed.
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float) -> float | None:
    """Nearest-rank percentile, ``q`` in (0, 100]. None for an empty sample."""
    data = sorted(values)
    if not data:
        return None
    if not 0 < q <= 100:
        raise ValueError(f"percentile wants 0 < q <= 100, got {q}")
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return float(data[rank - 1])


def mean(values: Iterable[float]) -> float | None:
    data = list(values)
    return float(sum(data) / len(data)) if data else None


def stat(values: Sequence[float], name: str) -> float | None:
    """A statistic by name, as the layer-metric files spell it:
    ``p50``/``p95``/... , ``mean``, ``max``, ``min``, ``sum``, ``count``."""
    data = [float(v) for v in values if v is not None]
    if name == "count":
        return float(len(data))
    if not data:
        return None
    if name == "mean":
        return mean(data)
    if name in ("max", "min", "sum"):
        return float({"max": max, "min": min, "sum": sum}[name](data))
    if name.startswith("p") and name[1:].replace(".", "", 1).isdigit():
        return percentile(data, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")


def iqr_share(values: Sequence[float]) -> float | None:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)`` —
    the spread the bounds are set from."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return abs(q3 - q1) / abs(med) if med else None
