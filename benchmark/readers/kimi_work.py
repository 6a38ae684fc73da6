"""A traced decode step of Kimi-K2.7-Code's language model against the work
the driver saw it do (``benchmark/kimi/costs.py``): the rows' contexts from
the scheduler, the experts touched from the engine's counter over the slice,
the table's fill from the labels of the engine's launch spans.

``what: mfu`` -- the step's operations over the decode programs' device time
and the chip's peak. ``what: decode_roofline`` -- the least time for the
step's operations and needed bytes (every live latent row once, the weights
outside the routed experts, the touched held experts), over the decode
programs' mean time. ``what: latent_attn_roofline`` -- the same for the
latent attention's own work (the live latent rows once; the absorbed
scores and weighted sum), over the device time a step of the operations
whose short name matches ``ops``: the page gathers and the core, found by
their shapes, which carry the latent's 512-wide and 64-wide parts. ``what:
label_share`` -- ``mellum_work``'s: the labels ``part`` over ``whole`` of
the whole steps' ``span`` events (live latent positions over those the
table's rectangle gathers). A program without the labels, the counters or
the operations gives None.
"""

from __future__ import annotations

from typing import Any

from benchmark import costs as peaks
from benchmark.kimi import costs
from benchmark.readers.mellum_work import label_share


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
    flops, nbytes = mean([costs.latent_attention_cost(cfg, r) for r in rows])
    return 100.0 * peaks.roofline_seconds(flops, nbytes, kind) / op_s
