"""A statistic of the device time of the traced executions of some programs."""

from __future__ import annotations

from typing import Any

from benchmark.stats import quantile


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    durations = trace.module_durations(spec["programs"])
    if not durations:
        return None
    return spec.get("scale", 1.0) * quantile(durations, float(spec["stat"].lstrip("p")) / 100.0)
