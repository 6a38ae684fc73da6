"""A traced decode step of Mellum 2's decoder against the work the driver saw
it do (``benchmark/mellum/costs.py``): the rows' contexts from the scheduler,
the experts touched from the engine's counter over the slice, the tables'
shapes and fill from the labels of the engine's launch spans.

``what: mfu`` -- the step's operations over the decode programs' device time
and the chip's peak. ``what: decode_roofline`` -- the least time for the
step's operations and needed bytes (every live row of the full layers, at most
a window's rows of the window layers, the touched experts), over the decode
programs' mean time. ``what: moe_roofline`` -- the same for the grouped
products' own work, over the device time a step of the operations whose short
name matches ``ops``. ``what: label_share`` -- over the whole steps' ``span``
events in the trace, the sum of the product of the labels ``part`` as a share
of the sum of the product of the labels ``whole`` (blocks live over blocks
gathered; blocks the window group holds over what whole tables would). A
program without the labels, the counters or the operations gives None.
Recorded steps and traced executions are matched by their means, since the
slice's edges can cut either.
"""

from __future__ import annotations

import math
from typing import Any

from benchmark import costs as peaks
from benchmark.mellum import costs
from benchmark.readers import serve_spans


def label_share(run: Any, spec: dict[str, Any]) -> float | None:
    part = whole = 0.0
    for step in serve_spans.host_side(run.trace_dir).steps:
        for name, _, _, labels in step.children:
            if name != spec["span"]:
                continue
            try:
                part += math.prod(float(labels[k]) for k in spec["part"])
                whole += math.prod(float(labels[k]) for k in spec["whole"])
            except (KeyError, ValueError):
                return None  # a program whose spans lack the labels
    return 100.0 * part / whole if whole else None


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    what, cfg = spec["what"], run.config
    if what == "label_share":
        return label_share(run, spec)
    decode_t = trace.module_durations(spec["decode_programs"])
    rows = [w["decode"] for w in run.work if w["decode"]]
    steps = run.counters.get("serve_decode_steps")
    if not decode_t or not rows or not steps or "serve_moe_experts_touched" not in run.counters:
        return None
    touched = run.counters["serve_moe_experts_touched"] / steps  # (layer, expert) pairs a step
    mean = lambda pairs: [sum(v) / len(v) for v in zip(*pairs)]  # noqa: E731
    if what in ("mfu", "decode_roofline"):
        flops, nbytes = mean([costs.decode_step_cost(cfg, r, touched) for r in rows])
        step_s = sum(decode_t) / len(decode_t)
        if what == "mfu":
            return 100.0 * flops / (step_s * peaks.peak(kind)["bf16_flops_per_s"])
        return 100.0 * peaks.roofline_seconds(flops, nbytes, kind) / step_s
    op_s = trace.op_seconds(spec["ops"]) / len(decode_t)
    if not op_s:
        return None
    flops, nbytes = mean([costs.moe_cost(cfg, len(r), touched) for r in rows])
    return 100.0 * peaks.roofline_seconds(flops, nbytes, kind) / op_s
