"""One of the driver's counters as a percentage of another."""

from __future__ import annotations

from typing import Any


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    part, whole = run.counters.get(spec["counter"]), run.counters.get(spec["over"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
