"""The engine's traced steps against the work the driver saw them do.

``what: mfu`` -- model operations of the decode rows and prefill chunks over the
device time of the decode and prefill programs and the chip's peak.
``what: decode_roofline`` -- the bytes a decode step has to read (bf16 weights
once, the batch's live KV rows once) and its operations, as the least time the
chip could take, over the decode programs' device time.
Recorded steps and traced executions are matched by their means, since the
slice's edges can cut one or the other.
"""

from __future__ import annotations

from typing import Any

from benchmark import costs


def read(run: Any, trace: Any, spec: dict[str, Any], kind: str) -> float | None:
    decode_t = trace.module_durations(spec["decode_programs"])
    decode_w = [w["decode"] for w in run.work if w["decode"]]
    if not decode_t or not decode_w:
        return None
    mean_t = sum(decode_t) / len(decode_t)
    if spec["what"] == "decode_roofline":
        least = [costs.roofline_seconds(*costs.decode_step_cost(run.config, rows), kind) for rows in decode_w]
        return 100.0 * (sum(least) / len(least)) / mean_t
    flops = sum(costs.decode_step_cost(run.config, rows)[0] for rows in decode_w) / len(decode_w) * len(decode_t)
    seconds = sum(decode_t)
    prefill_t = trace.module_durations(spec["prefill_programs"])
    prefill_w = [c for w in run.work for c in w["prefill"]]
    if prefill_t and prefill_w:
        flops += sum(costs.prefill_chunk_flops(run.config, s, n) for s, n in prefill_w) / len(prefill_w) * len(prefill_t)
        seconds += sum(prefill_t)
    return 100.0 * flops / (seconds * costs.peak(kind)["bf16_flops_per_s"])
