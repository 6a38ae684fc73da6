"""Share of the traced slice in which no operation ran on the device."""

from __future__ import annotations

from typing import Any


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    return 100.0 * trace.idle_share
