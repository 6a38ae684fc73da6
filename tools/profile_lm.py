"""Profile the TransformerLM train step on the real TPU and attribute step time.

Same harness as ``tools/profile_resnet.py`` (jax.profiler trace parsed
headlessly, optimized HLO captured through the compiled executable)
pointed at the LM benchmark workload
(``bench.py::bench_lm``): 110M-param 768d x 12L, bf16, compiled Pallas flash
attention. The attribution is what found the RoPE f32 round-trip (~2.4
GB/step of layout copies) and sizes the logits/loss traffic that motivates
chunked cross-entropy experiments.

Usage:
    python tools/profile_lm.py --seq_len 2048 --batch_size 8
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from tools.profile_resnet import analyze_trace  # noqa: E402


def run_traced_steps(seq_len: int, batch_size: int, trace_dir: str,
                     steps: int = 6, layout: str = "bhsd") -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
        flash_attention,
        flash_attention_bhsd,
    )
    from deeplearning_mpi_tpu.train import create_train_state, make_train_step
    from deeplearning_mpi_tpu.train.trainer import build_optimizer
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    config = TransformerConfig()
    # Default = the BHSD-kernel-native path bench_lm ships (projections
    # emit the kernel layout, no transposes) — the attribution must profile
    # the flagship configuration, not the older BSHD entry.
    attn = flash_attention_bhsd if layout == "bhsd" else flash_attention
    model = TransformerLM(
        config=config, dtype=jnp.bfloat16, attention_fn=attn
    )
    tx = build_optimizer("adam", 3e-4, clip_norm=1.0)
    state = create_train_state(
        model, jax.random.key(0), jnp.zeros((1, seq_len), jnp.int32), tx
    )
    step = make_train_step("lm", donate=False)
    tokens = jax.random.randint(
        jax.random.key(1), (batch_size, seq_len), 0, config.vocab_size
    )
    batch = {"tokens": tokens}

    compiled = step.lower(state, batch).compile()
    Path("/tmp/lm_optimized_hlo.txt").write_text(compiled.as_text())

    for _ in range(3):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])

    jax.profiler.start_trace(trace_dir)
    for _ in range(steps):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])
    jax.profiler.stop_trace()

    t0 = time.perf_counter()
    for _ in range(10):
        state, metrics = step(state, batch)
    host_sync(metrics["loss"])
    dt = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    return {
        "step_time_ms": dt / 10 * 1e3,
        "tokens_per_s": batch_size * seq_len * 10 / dt,
        "n_params": n_params,
        "steps_traced": steps,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq_len", type=int, default=2048)
    ap.add_argument("--batch_size", type=int, default=8)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--trace_dir", default="/tmp/lm_trace")
    ap.add_argument("--top_k", type=int, default=40)
    ap.add_argument("--layout", default="bhsd", choices=("bhsd", "bshd"),
                    help="attention entry: bhsd = the kernel-native "
                    "flagship path bench_lm ships (default)")
    args = ap.parse_args()
    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform()

    res = run_traced_steps(args.seq_len, args.batch_size, args.trace_dir,
                           args.steps, layout=args.layout)
    print(f"step {res['step_time_ms']:.2f} ms, "
          f"{res['tokens_per_s']:.0f} tokens/s, {res['n_params']:,} params")
    analyze_trace(args.trace_dir, args.steps, args.top_k)


if __name__ == "__main__":
    main()
