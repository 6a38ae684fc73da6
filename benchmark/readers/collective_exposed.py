"""Collective time that no compute overlaps, as a share of the step programs'
device time."""

from __future__ import annotations

from typing import Any


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    steps = trace.module_durations(spec["programs"])
    exposed = trace.exposed_collective_seconds()  # a device's mean
    if not steps or not exposed:
        return None
    return 100.0 * exposed / (sum(steps) / len(trace.devices))
