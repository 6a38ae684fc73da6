"""A statistic of the values the driver recorded under one span name."""

from __future__ import annotations

import statistics
from typing import Any

from benchmark.stats import quantile


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    values = run.spans.get(spec["span"])
    if not values:
        return None
    stat = spec["stat"]
    if stat == "sum":
        return float(sum(values))
    if stat == "mean":
        return statistics.fmean(values)
    return quantile(values, float(stat.lstrip("p")) / 100.0)
