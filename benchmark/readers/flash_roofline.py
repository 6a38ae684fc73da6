"""Attention forward + dq + dkv: the least time the chip could take for the
cell's windowed causal attention, over the kernels' device time a step."""

from __future__ import annotations

from typing import Any

from benchmark import costs


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    steps = trace.module_durations(spec["programs"])
    kernel_s = trace.op_seconds(spec["kernels"])  # a device's mean over the slice
    if not steps or not kernel_s:
        return None
    steps_per_device = len(steps) / len(trace.devices)
    flops, nbytes = costs.flash_train_cost(run.config, run.traffic["rows_per_chip"], run.traffic["seq_len"])
    return 100.0 * costs.roofline_seconds(flops, nbytes, kind) / (kernel_s / steps_per_device)
