"""Profile the ResNet-50 train step on the real TPU and attribute step time.

Captures a ``jax.profiler`` trace of a few hot steps (the instrumentation
the reference lacks entirely — SURVEY.md §5.1), then parses the emitted
Perfetto ``trace.json.gz`` directly so the analysis works on a headless box
with no TensorBoard: aggregates device-lane event durations by op name and
prints the top-K, plus the derived MFU.

Usage:
    python tools/profile_resnet.py --image_size 224 --batch_size 128
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def run_traced_steps(image_size: int, batch_size: int, trace_dir: str,
                     steps: int = 6) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.models import resnet50
    from deeplearning_mpi_tpu.train import create_train_state, make_train_step
    from deeplearning_mpi_tpu.train.trainer import build_optimizer
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    model = resnet50(num_classes=10, dtype=jnp.bfloat16)
    tx = build_optimizer("sgd", 0.1, momentum=0.9, weight_decay=1e-5)
    state = create_train_state(
        model, jax.random.key(0), jnp.zeros((1, image_size, image_size, 3)), tx
    )
    step = make_train_step("classification")

    rng = jax.random.key(1)
    images = jax.random.normal(rng, (batch_size, image_size, image_size, 3), jnp.float32)
    labels = jax.random.randint(rng, (batch_size,), 0, 10)
    batch = {"image": images, "label": labels}

    # Grab the optimized HLO from the compiled executable (no XLA_FLAGS dump
    # directory to set up or clean).
    compiled = step.lower(state, batch).compile()
    Path("/tmp/resnet_optimized_hlo.txt").write_text(compiled.as_text())

    for _ in range(3):  # compile + warm
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])

    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])
    jax.profiler.stop_trace()

    import time
    t0 = time.perf_counter()
    for _ in range(20):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])
    dt = time.perf_counter() - t0
    return {"step_time_ms": dt / 20 * 1e3,
            "images_per_s": batch_size * 20 / dt,
            "steps_traced": steps}


def categorize_with_hlo(trace_dir: str, hlo_dump: str, steps: int) -> None:
    """Split device time into conv / reduce / elementwise using the dumped
    optimized HLO: each trace event name is an HLO instruction; look up its
    fusion body in the dump and classify by what it computes."""
    p = Path(hlo_dump)
    if p.is_file():
        text = p.read_text()
    else:
        dumps = sorted(p.glob("*after_optimizations.txt"),
                       key=lambda q: q.stat().st_size)
        if not dumps:
            print("no HLO dump found under", hlo_dump)
            return
        text = dumps[-1].read_text()  # biggest module = the train step
    # Map instruction name -> jax-level op_name metadata (e.g.
    # "jit(step)/transpose(jvp(ResNet))/Bottleneck_3/Conv_0/conv_general_dilated").
    import re
    inst_opname: dict[str, str] = {}
    for m in re.finditer(
        r"%([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]+)\"", text
    ):
        inst_opname[m.group(1)] = m.group(2)

    def classify(event_name: str) -> str:
        op = inst_opname.get(event_name)
        if op is None:
            return "(no metadata: copies/infeed/etc)"
        bwd = "transpose(jvp" in op
        tail = op.rsplit("/", 1)[-1]
        if "conv_general_dilated" in tail:
            return "conv bwd" if bwd else "conv fwd"
        if "dot_general" in tail:
            return "matmul bwd" if bwd else "matmul fwd"
        if "reduce_window" in tail or "select_and_scatter" in tail:
            return "maxpool"
        if "BatchNorm" in op:
            return "batchnorm bwd" if bwd else "batchnorm fwd"
        if "reduce" in tail:
            return "reduce bwd" if bwd else "reduce fwd"
        return "other bwd" if bwd else "other"

    traces = sorted(Path(trace_dir).rglob("*.trace.json.gz"))
    with gzip.open(traces[-1], "rt") as f:
        events = json.load(f).get("traceEvents", [])
    pid_name = {e["pid"]: e["args"].get("name", "") for e in events
                if e.get("ph") == "M" and e.get("name") == "process_name"}
    tid_name = {(e["pid"], e["tid"]): e["args"].get("name", "") for e in events
                if e.get("ph") == "M" and e.get("name") == "thread_name"}
    device_pids = {p for p, n in pid_name.items()
                   if "TPU" in n or "/device:" in n or "Device" in n}
    cat_ms: dict[str, float] = defaultdict(float)
    unmatched_ms = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = tid_name.get((e["pid"], e["tid"]), "")
        if "Steps" in lane or "XLA Modules" in lane:
            continue
        name = e.get("name", "?")
        cat = classify(name)
        if cat == "elementwise/other" and name not in inst_to_comp and \
                name not in inst_op:
            unmatched_ms += e.get("dur", 0) / 1e3
        cat_ms[cat] += e.get("dur", 0) / 1e3
    total = sum(cat_ms.values())
    print(f"\n== category breakdown ({total/steps:.2f} ms/step) ==")
    for cat, ms in sorted(cat_ms.items(), key=lambda kv: -kv[1]):
        print(f"{ms/steps:8.3f} ms/step  {100*ms/total:5.1f}%  {cat}")
    if unmatched_ms:
        print(f"(unmatched against HLO dump: {unmatched_ms/steps:.3f} ms/step)")


def analyze_trace(trace_dir: str, steps: int, top_k: int = 30) -> None:
    traces = sorted(Path(trace_dir).rglob("*.trace.json.gz"))
    if not traces:
        print("no trace.json.gz found under", trace_dir)
        return
    with gzip.open(traces[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])

    # Identify device lanes: process names containing "TPU" / "/device:".
    pid_name = {}
    tid_name = {}
    for e in events:
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                pid_name[e["pid"]] = e["args"].get("name", "")
            elif e.get("name") == "thread_name":
                tid_name[(e["pid"], e["tid"])] = e["args"].get("name", "")

    device_pids = {p for p, n in pid_name.items()
                   if "TPU" in n or "/device:" in n or "Device" in n}
    by_op: dict[str, float] = defaultdict(float)
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = tid_name.get((e["pid"], e["tid"]), "")
        # Only count the XLA op lanes (skip step/scope summary lanes).
        if "Steps" in lane or "XLA Modules" in lane:
            continue
        dur = e.get("dur", 0) / 1e3  # us -> ms
        by_op[e.get("name", "?")] += dur
        total += dur
    print(f"\n== device op time over {steps} traced steps: {total:.2f} ms "
          f"({total/steps:.2f} ms/step) ==")
    for name, ms in sorted(by_op.items(), key=lambda kv: -kv[1])[:top_k]:
        print(f"{ms/steps:8.3f} ms/step  {100*ms/total:5.1f}%  {name[:110]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--image_size", type=int, default=224)
    ap.add_argument("--batch_size", type=int, default=128)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--trace_dir", default="/tmp/resnet_trace")
    ap.add_argument("--top_k", type=int, default=30)
    ap.add_argument("--hlo_dump", default=None,
                    help="dir passed to --xla_dump_to; enables the conv-vs-"
                    "reduce-vs-elementwise category breakdown")
    args = ap.parse_args()
    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform()

    res = run_traced_steps(args.image_size, args.batch_size, args.trace_dir,
                           args.steps)
    # ResNet-50 @224 fwd ≈ 4.1 GFLOPs/image; train ≈ 3× fwd.
    flops_per_image = 12.3e9 * (args.image_size / 224) ** 2
    tflops = res["images_per_s"] * flops_per_image / 1e12
    print(json.dumps(res | {
        "achieved_tflops": round(tflops, 1),
        "mfu_vs_197tflops_v5e": round(tflops / 197.0, 3),
    }))
    analyze_trace(args.trace_dir, args.steps, args.top_k)
    if args.hlo_dump:
        categorize_with_hlo(args.trace_dir, args.hlo_dump, args.steps)


if __name__ == "__main__":
    main()
