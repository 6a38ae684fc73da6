"""Model operations of one training step (nothing recomputed is counted) over
the median traced step's device time, the chips and the chip's peak."""

from __future__ import annotations

from typing import Any

from benchmark import costs
from benchmark.stats import quantile


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    steps = trace.module_durations(spec["programs"])
    if not steps:
        return None
    rows = run.traffic["rows_per_chip"] * run.chips
    flops = costs.train_step_flops(run.config, rows, run.traffic["seq_len"])
    return 100.0 * flops / (quantile(steps, 0.5) * run.chips * costs.peak(kind)["bf16_flops_per_s"])
