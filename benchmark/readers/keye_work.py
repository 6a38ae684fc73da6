"""A traced decode step of Keye-VL-2.0's language model against the work the
driver saw it do (``benchmark/keye/costs.py``): the rows' contexts from the
scheduler, the experts touched from the engine's counter over the slice.

``what: mfu`` -- the step's operations over the decode programs' device time
and the chip's peak. ``what: decode_roofline`` -- the least time for the
step's operations and needed bytes, over the decode programs' mean time.
``what: moe_roofline`` -- the same for the grouped products' own work, over
the device time a step of the operations whose short name matches ``ops``.
``what: op_share`` -- the share of the decode programs' device time that the
operations matching ``ops`` take (the top-k's sorts: a device event carries
its HLO instruction, not its scope, so the selection's other operations,
anonymous fusions, cannot be told from the rest of the step). Recorded steps
and traced executions are matched by their means, since the slice's edges can
cut either.
"""

from __future__ import annotations

from typing import Any

from benchmark import costs as peaks
from benchmark.keye import costs


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    decode_t = trace.module_durations(spec["decode_programs"])
    rows = [w["decode"] for w in run.work if w["decode"]]
    steps = run.counters.get("serve_decode_steps")
    if not decode_t or not rows or not steps or "serve_moe_experts_touched" not in run.counters:
        return None
    touched = run.counters["serve_moe_experts_touched"] / steps  # (layer, expert) pairs a step
    what, cfg = spec["what"], run.config
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
    if what == "op_share":
        return 100.0 * op_s * len(decode_t) / sum(decode_t)
    flops, nbytes = mean([costs.moe_cost(cfg, len(r), touched) for r in rows])
    return 100.0 * peaks.roofline_seconds(flops, nbytes, kind) / op_s
