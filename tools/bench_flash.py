"""Flash-attention micro-bench on the real TPU: compiled Mosaic vs dense.

Round-3 evidence for the Pallas kernel (`ops/pallas/flash_attention.py`):
compiled (non-interpret) execution, correctness vs the dense oracle, and
fwd timing at 2k/4k/8k — plus the sequence where dense stops fitting and
flash keeps going. Device-time honest: timings sync via a device→host fetch
(see utils.profiling.host_sync).

Usage: python tools/bench_flash.py [--seqs 2048 4096 8192] [--bwd]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _clock(fn, args, steps: int) -> float:
    """Shared timing harness: one warmup/compile call, device-honest sync
    via a device→host fetch, mean over ``steps``. Both bench modes MUST use
    this — divergent sync discipline would make their numbers incomparable.
    """
    from deeplearning_mpi_tpu.utils.profiling import host_sync

    out = fn(*args)
    host_sync(out.ravel()[:1])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    host_sync(out.ravel()[:1])
    return (time.perf_counter() - t0) / steps


def bench_one(seq: int, *, batch: int, heads: int, head_dim: int,
              causal: bool, bwd: bool, steps: int = 10,
              window: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.ops.attention import dense_attention
    from deeplearning_mpi_tpu.ops.pallas.flash_attention import flash_attention

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    shape = (batch, seq, heads, head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                                    window=window,
                                                    interpret=False))
    dense = jax.jit(lambda q, k, v: dense_attention(q, k, v, causal=causal,
                                                    window=window))

    def time_fn(fn):
        return _clock(fn, (q, k, v), steps)

    result: dict = {"seq": seq, "batch": batch, "heads": heads,
                    "head_dim": head_dim, "causal": causal}
    if window is not None:
        result["window"] = window
    t_flash = time_fn(flash)
    result["flash_fwd_ms"] = round(t_flash * 1e3, 3)
    if window is not None:
        # The sliding-window claim is vs FULL flash (dense rarely compiles
        # at the seqs where a window matters): O(S·W) vs O(S²/2) tiles.
        full = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            interpret=False)
        )
        t_full = time_fn(full)
        result["full_flash_fwd_ms"] = round(t_full * 1e3, 3)
        result["window_fwd_speedup"] = round(t_full / t_flash, 2)
    # Attention fwd FLOPs: 2 matmuls of [S,D]x[D,S] and [S,S]x[S,D] per
    # head, halved for the causal triangle.
    flops = 2 * 2 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1)
    result["flash_fwd_tflops"] = round(flops / t_flash / 1e12, 1)
    try:
        t_dense = time_fn(dense)
        result["dense_fwd_ms"] = round(t_dense * 1e3, 3)
        result["speedup_vs_dense"] = round(t_dense / t_flash, 2)
        of, od = flash(q, k, v), dense(q, k, v)
        result["max_abs_err_vs_dense"] = float(
            jnp.max(jnp.abs(of.astype(jnp.float32) - od.astype(jnp.float32)))
        )
    except Exception as e:  # noqa: BLE001 — dense OOMs first at long seq
        result["dense_error"] = repr(e)[:120]

    if bwd:
        from deeplearning_mpi_tpu.utils.profiling import host_sync

        def make_grad(win):
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal=causal, window=win,
                                    interpret=False)
                    .astype(jnp.float32) ** 2
                )
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def time_g(g):
            out = g(q, k, v)
            host_sync(out[0].ravel()[:1])
            t0 = time.perf_counter()
            for _ in range(steps):
                out = g(q, k, v)
            host_sync(out[0].ravel()[:1])
            return (time.perf_counter() - t0) / steps

        t_g = time_g(make_grad(window))
        result["flash_fwd_bwd_ms"] = round(t_g * 1e3, 3)
        if window is not None:
            t_g_full = time_g(make_grad(None))
            result["full_flash_fwd_bwd_ms"] = round(t_g_full * 1e3, 3)
            result["window_fwd_bwd_speedup"] = round(t_g_full / t_g, 2)
    return result


def bench_ring_inner(seq: int, *, batch: int, heads: int, head_dim: int,
                     steps: int = 10) -> dict:
    """Per-rotation inner comparison: the ring-flash schedule's Pallas block
    pass vs the XLA ring's dense block pass, one device.

    A real ring needs >=2 chips (chip_smoke.py runs it there), but the two ring
    schedules differ ONLY in their inner per-rotation computation — the
    ppermute pattern, rotation count, and ICI bytes are identical
    (`parallel/ring_flash.py` vs `parallel/ring_attention.py`). So the
    per-rotation inner is the measurable single-chip quantity that decides
    between them: resident-Q flash kernel against a visiting K/V block
    (scores stay in VMEM) vs blockwise dense attention (an
    [S_local, S_local] f32 score matrix in HBM per rotation). Multiply by
    (ring size - 1) + diagonal for a whole-forward estimate.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning_mpi_tpu.ops.pallas.flash_attention import (
        fit_block,
        flash_fwd_block,
        usable_blocks,
    )

    # Same tiling guard as every production caller (ring_flash.py applies
    # it before driving these kernels): a non-dividing seq would silently
    # compute only the first grid's rows and time a fraction of the work.
    bq, bk = fit_block(1024, seq), fit_block(1024, seq)
    if not usable_blocks(bq, bk, seq):
        return {"mode": "ring_inner", "s_local": seq,
                "error": f"seq {seq} not tileable (blocks {bq}x{bk}); "
                "production ring_flash falls back to the XLA ring here"}

    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    shape = (batch, seq, heads, head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k_blk = jax.random.normal(kk, shape, jnp.bfloat16)
    v_blk = jax.random.normal(kv, shape, jnp.bfloat16)

    # Ring-flash inner: full non-causal kernel + the lse the merge consumes
    # (the off-diagonal "visiting block fully in the past" case — the
    # dominant one at ring size n: n-1 of n rotations).
    interpret = jax.default_backend() != "tpu"  # CPU smoke runs the interpreter
    flash_inner = jax.jit(lambda q, k, v: flash_fwd_block(
        q, k, v, False, bq, bk, interpret, with_lse=True,
        out_dtype=jnp.float32,
    )[0])
    # XLA-ring inner: the PRODUCTION per-rotation update
    # (ring_attention._block_update — online-softmax merge into f32 running
    # accumulators), not a plain dense_attention: the decision number must
    # time exactly what the schedule being decided against executes.
    from deeplearning_mpi_tpu.parallel.ring_attention import _block_update

    def _xla_inner(q, k, v):
        acc0 = (
            jnp.zeros(q.shape, jnp.float32),
            jnp.zeros(q.shape[:2] + (q.shape[2],), jnp.float32),
            jnp.full(q.shape[:2] + (q.shape[2],), -1e30, jnp.float32),
        )
        o, l, m = _block_update(
            q, k, v, acc0, causal=False, q_offset=seq, kv_offset=0
        )
        return o

    dense_inner = jax.jit(_xla_inner)

    def time_fn(fn):
        return _clock(fn, (q, k_blk, v_blk), steps)

    result = {"mode": "ring_inner", "s_local": seq, "batch": batch,
              "heads": heads, "head_dim": head_dim,
              "block_q": bq, "block_k": bk}
    t_flash = time_fn(flash_inner)
    result["ring_flash_inner_ms"] = round(t_flash * 1e3, 3)
    try:
        t_dense = time_fn(dense_inner)
        result["xla_ring_inner_ms"] = round(t_dense * 1e3, 3)
        result["speedup"] = round(t_dense / t_flash, 2)
    except Exception as e:  # noqa: BLE001 — the [S,S] scores OOM first
        result["xla_ring_inner_error"] = repr(e)[:120]
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="+", default=[2048, 4096, 8192])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head_dim", type=int, default=64)
    ap.add_argument("--non_causal", action="store_true")
    ap.add_argument("--bwd", action="store_true")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window size: times windowed flash AND "
                    "full flash in one run, reporting the speedup (the "
                    "O(S*W) vs O(S^2/2) block-skip claim)")
    ap.add_argument("--ring_inner", action="store_true",
                    help="compare the two ring schedules' per-rotation inner "
                    "pass (the single-chip-measurable part; see "
                    "bench_ring_inner docstring)")
    args = ap.parse_args()
    if args.ring_inner and (args.bwd or args.non_causal):
        ap.error("--ring_inner measures the fwd per-rotation inner only; "
                 "--bwd/--non_causal do not apply (the off-diagonal ring "
                 "block is non-causal by construction)")
    from deeplearning_mpi_tpu.runtime.bootstrap import select_platform

    select_platform()
    for seq in args.seqs:
        if args.ring_inner:
            print(json.dumps(bench_ring_inner(
                seq, batch=args.batch, heads=args.heads,
                head_dim=args.head_dim,
            )))
        else:
            print(json.dumps(bench_one(
                seq, batch=args.batch, heads=args.heads, head_dim=args.head_dim,
                causal=not args.non_causal, bwd=args.bwd, window=args.window,
            )))


if __name__ == "__main__":
    main()
